"""Compiled GPipe-style split-learning pipeline over a (client, stage) mesh.

This module replaces the reference's entire training data plane — the
queue-driven streaming loop with bounded in-flight batches and activation
recomputation (``/root/reference/src/train/VGG16.py:61-191``) — with ONE
jitted SPMD program:

* the per-batch activation hop ``intermediate_queue_{k}_{c}`` /
  ``gradient_queue_{k}_{id}`` becomes ``jax.lax.ppermute`` along the
  ``stage`` mesh axis (ICI, inside the compiled step — no host round-trip);
* the reference's ``control-count`` in-flight cap becomes the microbatch
  count of a static GPipe schedule (``num_microbatches``);
* backward recomputation (``src/train/VGG16.py:89-92``) becomes a
  PER-STAGE ``jax.checkpoint`` policy (see *Remat policy* below);
* the backward pipeline is not hand-written at all: differentiating through
  the scan-of-ppermute forward yields the reverse schedule automatically;
* "clients" of the same stage are rows of the mesh's ``client`` axis —
  their training is embarrassingly parallel between round barriers, and the
  round-end weighted FedAvg (``src/Utils.py:35-66``) is a ``psum`` over the
  ``client`` axis (:func:`make_fedavg_step`).

Heterogeneous stages (a VGG cut gives stages wildly different programs) are
handled with ``lax.switch`` over per-stage branches; activations cross the
wire flattened and padded to the largest boundary so every device runs the
same collective.

**Streamed loss** (default, ``stream_loss=True``): the last stage's
branch computes the per-microbatch loss INSIDE the stage block, every
pipeline tick, and the scan carries one accumulating scalar.  The
``(M, mb, n_out)`` collect-then-cross-entropy buffer of the
materialized-logits path — ~3.9 GB/chip at the baseline5 TinyLlama
geometry, 40% of one chip's HBM — never exists: an LLM head's logits are
consumed in the tick that produces them.  When the final stage is
rematerialized (which the ``wide`` policy picks automatically for
wide-output heads), no per-tick logits residual survives to the backward
pass either.  ``stream_loss=False`` keeps the materialized path as the
parity oracle (``tests/test_pipeline_streamed.py``).

**Remat policy** (``remat=``): ``"all"`` checkpoints every stage (the
old blanket behavior — maximum recompute, minimum residency), ``"none"``
stores every stage's activations, and ``"wide"`` (default) checkpoints
exactly the stages whose per-sample boundary width (max of input and
output) exceeds ``remat_threshold`` — narrow CIFAR-scale stages skip the
~1.3x recompute tax entirely while transformer-scale stages keep the
memory bound.  Booleans still work (``True`` == ``"all"``,
``False`` == ``"none"``).

**Tick-loop unroll** (``scan_unroll="auto"``): XLA:CPU runs a scan's
while-loop body through its sequential thunk executor, where the
conv/matmul kernels lose intra-op threading (measured ~3x on the VGG
step — most of the round-5 "2.1x split overhead", which taxed the M=1
unsplit baseline hardest).  ``auto`` fully unrolls short tick loops on
CPU meshes and keeps the compact scan on accelerators, where the loop
costs nothing and unrolling an A-branch switch per tick only bloats
compile time.

**Parameter residency**: by default parameters are replicated along
``stage`` (each device holds the full model, uses only its stage's
slice; gradients are psum'd over ``stage`` to keep replicas in sync) —
the fully-general path for arbitrary heterogeneous cuts.
:func:`make_sliced_train_step` instead keeps each device's OWN stage
slice only, as a flat ``(client, stage)``-sharded parameter wire
(:class:`StageParamLayout`): per-device params/grads/opt-state drop to
~1/A of the model and the per-step full-tree gradient psum over
``stage`` (A redundant copies of every gradient, every step)
disappears; the full tree is reassembled only at FedAvg / validation /
checkpoint boundaries.  Big homogeneous transformer models can also
shard parameters along ``model`` (tensor parallelism,
:mod:`split_learning_tpu.parallel.tensor`).

Semantic note: the reference steps the optimizer once per in-flight batch
with stale weights (async pipelining); here microbatch gradients are
accumulated into one synchronous update per step — same data consumed per
round, deterministic, and MXU-friendly.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from split_learning_tpu.models import build_model, shard_params
from split_learning_tpu.models.split import SplitModel
from split_learning_tpu.ops.fedavg import fedavg_psum
from split_learning_tpu.parallel.expert import moe_aux_loss
from split_learning_tpu.parallel.mesh import stage_ranges


def _flat_size(shape: Sequence[int]) -> int:
    return int(np.prod(shape[1:]))  # per-sample, excluding batch dim


def _tree_flat_size(struct_tree) -> int:
    """Total per-sample wire width of a (possibly pytree) boundary."""
    return sum(_flat_size(leaf.shape)
               for leaf in jax.tree_util.tree_leaves(struct_tree))


#: Collections a layer may sow scalar counters into (``self.sow(collection,
#: name, value)``: what it did, never a term of the objective), and how a
#: step folds each: over one leaf, over same-named leaves (the layers of a
#: stage, the stages and microbatches of a step), and over the devices of
#: the ``stage`` axis.  The layer owns its counters' names and, by the
#: collection it picks, their rule; the pipeline knows neither
COUNTER_FOLDS = {
    "counters_sum": (jnp.sum, jnp.add, jax.lax.psum),
    "counters_max": (jnp.max, jnp.maximum, jax.lax.pmax),
}


def sown_counters(mut: dict) -> dict:
    """``{collection: {name: scalar}}`` of what one apply sowed into the
    counter collections, same-named leaves folded by their collection's
    rule; ``{}`` for a model that sows none."""
    out: dict = {}
    for col, (over_leaf, fold, _) in COUNTER_FOLDS.items():
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                mut.get(col, {})):
            name = next(p.key for p in reversed(path)
                        if isinstance(getattr(p, "key", None), str))
            value = over_leaf(leaf).astype(jnp.float32)
            seen = out.setdefault(col, {})
            seen[name] = fold(seen[name], value) if name in seen else value
    return out


def fold_counters(acc: dict, new: dict) -> dict:
    """``acc`` with ``new``'s counters folded in, each by its rule; a name
    ``new`` lacks stays as it is."""
    return {col: {name: (COUNTER_FOLDS[col][1](v, new[col][name])
                         if name in new.get(col, {}) else v)
                  for name, v in names.items()}
            for col, names in acc.items()}


class PipelineModel:
    """Static description + compiled bodies for one pipelined split model.

    Built once per (model, cuts, microbatch geometry); owns no parameters.
    """

    #: per-sample boundary width (flattened elements) above which the
    #: ``wide`` remat policy checkpoints a stage.  Sized so CIFAR-scale
    #: CNN/ViT cuts (<= 2^16 elements/sample) run remat-free while
    #: token-model stages (seq x hidden, millions/sample) keep the
    #: memory-bounding recompute.
    REMAT_WIDE_THRESHOLD = 65536

    def __init__(self, model_name: str, cuts: Sequence[int],
                 example_input: jax.ShapeDtypeStruct | jnp.ndarray,
                 num_microbatches: int = 4,
                 loss: str = "softmax_cross_entropy",
                 remat: bool | str = "wide",
                 remat_threshold: int | None = None,
                 stream_loss: bool = True,
                 scan_unroll: int | str = "auto",
                 model_kwargs: dict | None = None,
                 moe_aux_weight: float = 0.01,
                 seq_axis: str | None = None):
        self.model_name = model_name
        self.moe_aux_weight = moe_aux_weight
        self.model_kwargs = dict(model_kwargs or {})
        # PP x SP (VERDICT r4 item 4): with ``seq_axis`` set the mesh
        # carries a manual ``seq`` axis, ``example_input`` is the
        # PER-DEVICE sequence block, stage models run ring attention
        # over the axis (RoPE offset by the block index), and the wire
        # hop moves each block independently — packing stays purely
        # local, so cuts and sequence sharding compose with no extra
        # boundary collective.  The loss becomes each device's token-
        # block share; psum over ``seq`` rebuilds exact full-sequence
        # gradients (the ring is exact attention).
        self.seq_axis = seq_axis
        self.full_model: SplitModel = build_model(model_name,
                                                  **self.model_kwargs)
        self.specs = self.full_model.specs
        self.n_layers = len(self.specs)
        self.cuts = list(cuts)
        self.ranges = stage_ranges(self.n_layers, self.cuts)
        self.n_stages = len(self.ranges)
        self.num_microbatches = num_microbatches
        # legacy bool spellings map onto the named policies
        remat = {True: "all", False: "none"}.get(remat, remat)
        if remat not in ("all", "wide", "none"):
            raise ValueError(
                f"remat must be 'all', 'wide', 'none' or a bool; got "
                f"{remat!r}")
        self.remat = remat
        self.remat_threshold = int(self.REMAT_WIDE_THRESHOLD
                                   if remat_threshold is None
                                   else remat_threshold)
        self.stream_loss = bool(stream_loss)
        if scan_unroll != "auto" and not isinstance(scan_unroll, int):
            raise ValueError(
                f"scan_unroll must be 'auto' or an int, got "
                f"{scan_unroll!r}")
        self.scan_unroll = scan_unroll
        self.loss_name = loss

        mk_stage = dict(self.model_kwargs)
        if seq_axis is not None:
            mk_stage["seq_axis"] = seq_axis
        self.stage_models = [
            build_model(model_name, start_layer=a, end_layer=b,
                        **mk_stage)
            for a, b in self.ranges
        ]
        # shape twins WITHOUT the seq axis: boundary eval_shape runs
        # outside shard_map (no axis env), and every layer is
        # shape-preserving w.r.t. the local block, so block-sized
        # boundaries come out identical
        shape_models = (self.stage_models if seq_axis is None else [
            build_model(model_name, start_layer=a, end_layer=b,
                        **self.model_kwargs)
            for a, b in self.ranges
        ])
        self.stage_layer_names = [
            [s.name for s in self.specs[a:b]] for a, b in self.ranges
        ]

        # boundary ShapeDtypeStructs per microbatch, chained via eval_shape
        x = (example_input if isinstance(example_input, jax.ShapeDtypeStruct)
             else jax.ShapeDtypeStruct(example_input.shape,
                                       example_input.dtype))
        self.mb_size = x.shape[0]
        self.boundary: list[jax.ShapeDtypeStruct] = [x]
        var_shapes = jax.eval_shape(
            lambda: self.full_model.init(jax.random.key(0), jnp.zeros(
                x.shape, x.dtype), train=False))
        # the counters the model's layers sow (COUNTER_FOLDS), as a tree
        # of zeros: what a step starts its count from.  ``{}`` for a
        # model that sows none, whose step then carries nothing more
        self.counters0: dict = {}

        def shapes(m, sub, x):
            out, mut = m.apply(sub, x, train=False,
                               mutable=list(COUNTER_FOLDS))
            return out, sown_counters(mut)
        for m, (a, b) in zip(shape_models, self.ranges):
            sub = {
                col: shard_params(tree, self.specs, a, b)
                for col, tree in var_shapes.items()
            }
            out, counted = jax.eval_shape(
                functools.partial(shapes, m), sub, self.boundary[-1])
            self.boundary.append(out)
            for col, names in counted.items():
                self.counters0.setdefault(col, {}).update(
                    dict.fromkeys(names, jnp.zeros(())))
        out_leaves = jax.tree_util.tree_leaves(self.boundary[-1])
        if len(out_leaves) != 1:
            raise ValueError(
                "the final stage must output a single logits array, got "
                f"a {len(out_leaves)}-leaf pytree")
        self.out_struct = out_leaves[0]
        self.n_out = _flat_size(self.out_struct.shape)
        # wire width: the widest boundary that actually RIDES the wire —
        # the INPUT of every stage (boundary[0..S-1]).  The final output
        # does not hop: it returns through a separate exact-width switch
        # slot on the last device, so an LLM head's logits (S x vocab,
        # ~16x wider than hidden for TinyLlama-1.1B) no longer inflate
        # every ppermute buffer and scan carry.
        self.max_flat = max(_tree_flat_size(b) for b in self.boundary[:-1])
        # wire dtype: float32 carries every boundary exactly (token ids
        # are < 2^24; bf16/f32 activations upcast losslessly; bool masks
        # ride as 0.0/1.0)
        self.wire_dtype = jnp.float32
        # per-stage remat flags from the policy: 'wide' checkpoints a
        # stage iff its widest per-sample boundary (input or output)
        # exceeds the threshold — the blanket 'all' policy taxed every
        # narrow stage with a full recompute it never needed
        widths = [_tree_flat_size(b) for b in self.boundary]
        if self.remat == "all":
            self.stage_remat = [True] * self.n_stages
        elif self.remat == "none":
            self.stage_remat = [False] * self.n_stages
        else:
            self.stage_remat = [
                max(widths[s], widths[s + 1]) > self.remat_threshold
                for s in range(self.n_stages)
            ]
        # full-model param SHAPES (ShapeDtypeStructs) for the flat
        # stage-sliced layout; owns no memory
        self.param_shapes = var_shapes.get("params", {})
        self._layout_cache: dict = {}

    #: auto-unroll bound: tick loops at most this long are fully
    #: unrolled on CPU backends
    SCAN_UNROLL_MAX_TICKS = 16

    def scan_unroll_for(self, mesh: Mesh) -> int:
        """Tick-loop unroll factor for a step compiled on ``mesh``.

        XLA:CPU executes a ``lax.scan``'s while-loop body through the
        sequential thunk path — convolution/matmul kernels inside it
        lose intra-op threading, which measured ~3x slower than the
        identical straight-line code (the round-5 2.1x "split overhead"
        was mostly this, taxing the M=1 unsplit baseline hardest).
        ``auto`` therefore fully unrolls the tick loop on CPU meshes
        when it is short (<= SCAN_UNROLL_MAX_TICKS ticks) and keeps the
        compact scan elsewhere: on TPU the while loop costs nothing
        and unrolling an A-branch switch per tick only bloats compile
        time.  An int ``scan_unroll`` forces the factor everywhere.
        """
        if self.scan_unroll != "auto":
            return max(1, int(self.scan_unroll))
        A = int(mesh.shape["stage"]) if "stage" in mesh.axis_names else 1
        ticks = self.num_microbatches + A - 1
        on_cpu = next(iter(mesh.devices.flat)).platform == "cpu"
        if on_cpu and ticks <= self.SCAN_UNROLL_MAX_TICKS:
            return ticks
        return 1

    def stage_param_layout(self, stage_axis_size: int) -> "StageParamLayout":
        """Memoized :class:`StageParamLayout` for an ``A``-wide stage
        axis (virtual stages: each device owns ``n_stages/A``
        consecutive stages)."""
        if stage_axis_size not in self._layout_cache:
            self._layout_cache[stage_axis_size] = StageParamLayout(
                self, stage_axis_size)
        return self._layout_cache[stage_axis_size]

    # -- wire packing ------------------------------------------------------
    # A boundary may be any pytree (e.g. BERT's (hidden, attention_mask)
    # — models/bert.py threads the pad mask with the activations): leaves
    # are flattened per sample, concatenated, and padded to the widest
    # INTERIOR boundary so every stage hop moves one (mb, max_flat)
    # buffer; the final output rides its own exact-width slot.

    # (both ends of a hop, and the ppermute in `tick`, carry its name)

    @jax.named_scope("hop")
    def _to_wire(self, x) -> jnp.ndarray:
        leaves = jax.tree_util.tree_leaves(x)
        flat = jnp.concatenate(
            [v.reshape(v.shape[0], -1).astype(self.wire_dtype)
             for v in leaves], axis=1)
        pad = self.max_flat - flat.shape[1]
        return jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat

    @jax.named_scope("hop")
    def _from_wire(self, wire, struct):
        leaves, treedef = jax.tree_util.tree_flatten(struct)
        out, off = [], 0
        for leaf in leaves:
            n = _flat_size(leaf.shape)
            out.append(wire[:, off:off + n].astype(leaf.dtype).reshape(
                (wire.shape[0],) + tuple(leaf.shape[1:])))
            off += n
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- per-device pipeline body -----------------------------------------

    @jax.named_scope("loss")
    def loss_from_logits(self, logits, labels):
        if self.loss_name == "softmax_cross_entropy":
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
        if self.loss_name == "mse":
            return jnp.mean((logits - labels) ** 2)
        raise ValueError(f"unknown loss {self.loss_name!r}")

    def _device_branch(self, d: int, k: int, train: bool,
                       last: bool = False, layout=None):
        """Branch for mesh-axis position ``d`` holding stages
        ``[d*k, (d+1)*k)`` chained locally (virtual pipeline stages).

        ``k == 1`` is the classic one-stage-per-device GPipe mapping; on a
        1-wide ``stage`` axis (single chip) the whole split model chains
        locally — same cut semantics and microbatch accumulation, no
        inter-device hop.  Activations between co-located stages stay in
        their native shape/dtype (no wire round-trip).

        Every branch has identical signature and output shapes
        (lax.switch requirement): ``(params, stats, wire_in, rng_data,
        labels_mb) -> (wire, slot, stats, aux, counters)``.  Under streamed loss
        (default) ``slot`` is a scalar: the ``last`` branch fuses the
        final stage's apply WITH the microbatch's loss in one
        (optionally rematerialized) block — the logits are consumed
        where they are produced and never ride a buffer; interior
        branches return ``0.0``.  With ``stream_loss=False`` ``slot``
        is the exact-width ``(mb, n_out)`` output tail the scan
        collects into the materialized logits buffer (parity oracle).

        ``layout`` switches the parameter source: ``None`` reads the
        stage slice out of the replicated full tree; a
        :class:`StageParamLayout` unpacks it from this device's flat
        stage-sliced segment.

        ``counters`` are the sown counters of its stages, folded into the
        model's tree of them (``counters0``; ``{}`` where none is sown).
        """
        lo, hi = d * k, (d + 1) * k
        in_struct = self.boundary[lo]

        def stage_params_of(params, s):
            if layout is not None:
                return layout.unpack_stage(d, s, params)
            a, b = self.ranges[s]
            return shard_params(params, self.specs, a, b)

        def apply_device(params, stats, wire_in, rng_data, labels_mb):
            x = self._from_wire(wire_in, in_struct)
            new_stats = dict(stats)
            aux = jnp.zeros(())
            count = self.counters0
            loss_mb = jnp.zeros(())
            for s in range(lo, hi):
                model = self.stage_models[s]
                a, b = self.ranges[s]
                fuse_loss = (self.stream_loss and last and s == hi - 1)

                # raw uint32 key data stays raw across the remat/switch
                # boundary: typed PRNG key avals confuse lax.switch's
                # residual unification under autodiff (observed MLIR
                # verifier failure, jax 0.9)
                def apply_one(sp, st_in, x, rng_data, labels,
                              model=model, a=a, b=b, fuse=fuse_loss):
                    rng = jax.random.wrap_key_data(rng_data)
                    variables: dict = {"params": sp}
                    st = shard_params(st_in, self.specs, a, b)
                    if st:
                        variables["batch_stats"] = st
                    out, mut = model.apply(
                        variables, x, train=train,
                        mutable=["batch_stats", "intermediates",
                                 *COUNTER_FOLDS],
                        rngs={"dropout": rng} if train else None)
                    if fuse:
                        # streamed loss: reduce the final output to the
                        # microbatch loss INSIDE this block, so when the
                        # block is rematerialized no logits-sized
                        # residual survives a pipeline tick.  f32
                        # scalar: a bf16 model's loss would otherwise
                        # break lax.switch's identical-type requirement
                        # against the interior branches' f32 zeros
                        out = self.loss_from_logits(
                            jax.tree_util.tree_leaves(out)[0],
                            labels).astype(jnp.float32)
                    # sown MoE load-balance losses (zero for dense
                    # stages) join the objective on THIS device
                    return (out, mut.get("batch_stats", {}),
                            moe_aux_loss(mut.get("intermediates", {})),
                            sown_counters(mut))

                # the stage's name, entered INSIDE what jax.checkpoint
                # wraps, so that the recomputed copy carries it too
                apply_one = jax.named_scope(f"stage{s + 1}")(apply_one)
                if self.stage_remat[s]:
                    apply_one = jax.checkpoint(apply_one)
                out, mut_stats, stage_aux, stage_count = apply_one(
                    stage_params_of(params, s), new_stats, x, rng_data,
                    labels_mb)
                new_stats.update(mut_stats)
                aux = aux + stage_aux
                count = fold_counters(count, stage_count)
                if fuse_loss:
                    loss_mb = out
                else:
                    x = out
            mb = wire_in.shape[0]
            if self.stream_loss:
                if last:
                    return (jnp.zeros((mb, self.max_flat),
                                      self.wire_dtype),
                            loss_mb, new_stats, aux, count)
                return (self._to_wire(x), jnp.zeros(()), new_stats, aux,
                        count)
            if last:
                tail = jnp.concatenate(
                    [v.reshape(mb, -1).astype(self.wire_dtype)
                     for v in jax.tree_util.tree_leaves(x)], axis=1)
                return (jnp.zeros((mb, self.max_flat), self.wire_dtype),
                        tail, new_stats, aux, count)
            return (self._to_wire(x),
                    jnp.zeros((mb, self.n_out), self.wire_dtype),
                    new_stats, aux, count)

        return apply_device

    def device_loss(self, params, stats, x_mb, labels, rng,
                    train: bool = True,
                    mesh_axes: tuple = ("client", "stage"),
                    stage_axis_size: int | None = None,
                    layout=None, scan_unroll: int = 1):
        """Per-device pipelined loss. Must run inside shard_map with a
        ``stage`` axis of size ``stage_axis_size`` (default: one device
        per stage).  When the axis is smaller than ``n_stages`` each
        device chains ``n_stages/axis`` consecutive stages locally.

        Under streamed loss (default) the scan carry holds ONE
        accumulating loss scalar: each tick the last device folds its
        just-finished microbatch's loss in (cross-entropy computed
        inside the final stage block on that tick's logits).  The
        materialized path (``stream_loss=False``) instead collects every
        microbatch's logits into an ``(M, mb, n_out)`` buffer and runs
        one loss over the collapse — identical numerics, plus one
        logits-sized buffer per device.

        ``layout`` (a :class:`StageParamLayout`) makes ``params`` this
        device's flat stage-sliced segment instead of the replicated
        full tree (:func:`make_sliced_train_step`).

        Returns ``(local_loss, (loss, new_stats, counters))``:
        ``local_loss`` is this device's (unsummed) contribution — the
        value to differentiate; ``loss`` is the stage-psum'd scalar for
        reporting, ``new_stats`` the stage-merged batch stats, and
        ``counters`` what the model's layers counted over the step's
        stages and microbatches (``COUNTER_FOLDS``; ``{}`` for a model
        that sows none).
        """
        S, M = self.n_stages, self.num_microbatches
        A = S if stage_axis_size is None else stage_axis_size
        if S % A != 0:
            raise ValueError(
                f"n_stages={S} must be a multiple of the stage axis "
                f"size {A}")
        k = S // A
        dev = jax.lax.axis_index("stage")
        branches = [self._device_branch(d, k, train, last=(d == A - 1),
                                        layout=layout)
                    for d in range(A)]
        stats0 = stats

        def tick(carry, t):
            act_wire, stats, acc, aux_acc, count_acc = carry
            inj_idx = jnp.clip(t, 0, M - 1)
            with jax.named_scope("hop"):
                x_inj = self._to_wire(
                    jax.lax.dynamic_index_in_dim(x_mb, inj_idx, 0,
                                                 keepdims=False))
                act_in = jnp.where(dev == 0, x_inj, act_wire)
            mb_idx = jnp.clip(t - dev, 0, M - 1)
            rng_t = jax.random.fold_in(rng, mb_idx)
            if self.seq_axis is not None:
                # distinct dropout masks per sequence block (a shared
                # rng would repeat one block's pattern along the axis)
                rng_t = jax.random.fold_in(
                    rng_t, jax.lax.axis_index(self.seq_axis))

            # the microbatch the LAST device finishes this tick (bubble
            # ticks clip to a garbage slot that `collect` masks off)
            c_idx = jnp.clip(t - (A - 1), 0, M - 1)
            labels_t = jax.lax.dynamic_index_in_dim(labels, c_idx, 0,
                                                    keepdims=False)
            out_wire, out_slot, new_stats, aux, count = jax.lax.switch(
                dev, branches, params, stats, act_in,
                jax.random.key_data(rng_t), labels_t)

            # bubble ticks compute garbage: keep their stats out
            valid = (t >= dev) & (t < dev + M)
            new_stats = jax.tree_util.tree_map(
                lambda n, o: jnp.where(valid, n, o), new_stats, stats)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
            count_acc = fold_counters(count_acc, jax.tree_util.tree_map(
                lambda c: jnp.where(valid, c, 0.0), count))

            collect = (dev == A - 1) & (t >= A - 1)
            if self.stream_loss:
                # streamed: fold the finished microbatch's loss scalar
                # (zeros on interior devices and bubble ticks)
                acc = acc + jnp.where(collect, out_slot, 0.0)
            else:
                # materialized: collect logits for microbatch t-(A-1)
                # from the exact-width tail slot
                acc = jnp.where(
                    collect,
                    jax.lax.dynamic_update_index_in_dim(
                        acc, out_slot, c_idx, 0),
                    acc)

            perm = [(i, i + 1) for i in range(A - 1)]
            with jax.named_scope("hop"):
                act_next = (jax.lax.ppermute(out_wire, "stage", perm)
                            if perm else out_wire)
            return (act_next, new_stats, acc, aux_acc, count_acc), None

        del mesh_axes  # only relevant under check_vma, which we disable
        act0 = jnp.zeros((self.mb_size, self.max_flat), self.wire_dtype)
        acc0 = (jnp.zeros(()) if self.stream_loss
                else jnp.zeros((M, self.mb_size, self.n_out),
                               self.wire_dtype))
        # full unroll must be requested as an int >= 2: both unroll=1
        # and unroll=True (which lax.scan resolves to unroll=length,
        # i.e. 1 for a single-tick loop) take the while-loop path,
        # keeping the XLA:CPU sequential-thunk tax the unroll exists
        # to remove
        ticks = M + A - 1
        unroll = (max(2, ticks) if scan_unroll >= ticks
                  else max(1, scan_unroll))
        # the tick loop's own name: what it does outside the stages
        # (stacking each tick's residuals for the backward pass and
        # reading them back, the carry's copies, the microbatches'
        # gradient accumulation) carries it without a stage's
        with jax.named_scope("pipeline"):
            (_, stats_f, acc, aux_acc, count_acc), _ = jax.lax.scan(
                tick, (act0, stats0, acc0, jnp.zeros(()), self.counters0),
                jnp.arange(ticks), unroll=unroll)

        if self.stream_loss:
            # equal microbatch sizes: the mean of per-microbatch means
            # IS the flat (M*mb) mean of the materialized path
            ce_local = jnp.where(dev == A - 1, acc / M, 0.0)
        else:
            logits = acc.astype(self.out_struct.dtype).reshape(
                (M * self.mb_size,) + tuple(self.out_struct.shape[1:]))
            # collapse (M, mb, ...) -> (M*mb, ...): int labels stay 1-D
            # for CE, vector targets keep their feature dims for MSE
            labels_flat = labels.reshape((M * self.mb_size,)
                                         + labels.shape[2:])
            ce_local = jnp.where(dev == A - 1,
                                 self.loss_from_logits(logits,
                                                       labels_flat),
                                 0.0)
        # MoE load-balance aux (mean over microbatches, weighted) joins
        # the objective on whichever device computed it; dense models sow
        # nothing and aux_acc is identically 0.  Reported loss stays CE.
        local = ce_local + self.moe_aux_weight * aux_acc / M
        if self.seq_axis is not None:
            # equal static blocks: the local token-block mean / n_seq is
            # this device's share of the GLOBAL token mean; psum of the
            # shares' grads over `seq` (make_train_step) rebuilds exact
            # full-sequence gradients on the seq-replicated params
            local = local / jax.lax.axis_size(self.seq_axis)
            ce_report = ce_local / jax.lax.axis_size(self.seq_axis)
        else:
            ce_report = ce_local
        # NOTE: `local` (CE nonzero only on the last device, aux on the
        # device that owns the MoE stage) is what must be differentiated.
        # Cross-stage gradient flow happens through the ppermute
        # transpose; psum-ing the loss BEFORE grad would seed a cotangent
        # on every stage replica and overcount grads by A.
        loss = jax.lax.psum(jax.lax.stop_gradient(ce_report), "stage")
        if self.seq_axis is not None:
            loss = jax.lax.psum(loss, self.seq_axis)

        # exactly one stage updated each stats leaf; share via delta-psum
        delta = jax.tree_util.tree_map(lambda f, i: f - i, stats_f, stats0)
        if self.seq_axis is not None:
            # seq replicas each normalized their own token block: keep
            # the stage-replicated stats identical by averaging
            delta = jax.tree_util.tree_map(
                lambda d: jax.lax.pmean(d, self.seq_axis), delta)
        stats_out = jax.tree_util.tree_map(
            lambda i, d: i + jax.lax.psum(d, "stage"), stats0, delta)
        counters = {col: {name: COUNTER_FOLDS[col][2](
            jax.lax.stop_gradient(v), "stage") for name, v in names.items()}
            for col, names in count_acc.items()}
        return local, (loss, stats_out, counters)


class StageParamLayout:
    """Static flat layout of per-device stage-parameter segments.

    Device ``d`` of an ``A``-wide stage axis owns stages
    ``[d*k, (d+1)*k)``; its parameters ride as ONE flat fp32 segment —
    the raveled leaves of its stages' subtrees, concatenated
    stage-major, padded to the widest device segment — so a
    ``(client, stage)``-sharded ``(C, A*seg_len)`` array gives every
    device exactly (and only) its own slice of the model.  Compared to
    the replicated layout this cuts per-device parameter, gradient and
    optimizer-state residency by ~(A-1)/A and removes the per-step
    full-tree gradient psum over ``stage``.

    fp32 is a lossless carrier for fp32/bf16/int leaves; leaf dtypes are
    restored on unpack from the recorded shapes.
    """

    def __init__(self, pipe: "PipelineModel", stage_axis_size: int):
        S = pipe.n_stages
        if stage_axis_size <= 0 or S % stage_axis_size:
            raise ValueError(
                f"n_stages={S} must be a multiple of the stage axis "
                f"size {stage_axis_size}")
        self.pipe = pipe
        self.A = stage_axis_size
        self.k = S // stage_axis_size
        self.dtype = jnp.float32
        #: (d, s) -> (treedef, [(shape, dtype, offset, size)])
        self._meta: dict = {}
        seg_lens = []
        for d in range(self.A):
            off = 0
            for s in range(d * self.k, (d + 1) * self.k):
                a, b = pipe.ranges[s]
                sub = shard_params(pipe.param_shapes, pipe.specs, a, b)
                leaves, treedef = jax.tree_util.tree_flatten(sub)
                metas = []
                for leaf in leaves:
                    size = int(np.prod(leaf.shape))
                    metas.append((tuple(leaf.shape), leaf.dtype, off,
                                  size))
                    off += size
                self._meta[(d, s)] = (treedef, metas)
            seg_lens.append(off)
        self.seg_len = max(seg_lens) if seg_lens else 0

    def pack(self, params) -> jnp.ndarray:
        """Full layer-keyed param tree -> ``(A, seg_len)`` flat wire."""
        rows = []
        for d in range(self.A):
            parts = []
            for s in range(d * self.k, (d + 1) * self.k):
                a, b = self.pipe.ranges[s]
                sub = shard_params(params, self.pipe.specs, a, b)
                parts += [jnp.ravel(leaf).astype(self.dtype)
                          for leaf in jax.tree_util.tree_leaves(sub)]
            v = (jnp.concatenate(parts) if parts
                 else jnp.zeros((0,), self.dtype))
            rows.append(jnp.pad(v, (0, self.seg_len - v.shape[0])))
        return jnp.stack(rows)

    def unpack_stage(self, d: int, s: int, seg) -> dict:
        """Device ``d``'s flat segment -> stage ``s``'s param subtree."""
        treedef, metas = self._meta[(d, s)]
        leaves = [seg[off:off + size].reshape(shape).astype(dtype)
                  for shape, dtype, off, size in metas]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def unpack(self, wire) -> dict:
        """``(A, seg_len)`` (or flat ``(A*seg_len,)``) wire -> full
        layer-keyed tree (host-side reassembly at FedAvg / validation /
        checkpoint boundaries)."""
        wire = jnp.asarray(wire).reshape(self.A, self.seg_len)
        out: dict = {}
        for d in range(self.A):
            for s in range(d * self.k, (d + 1) * self.k):
                out.update(self.unpack_stage(d, s, wire[d]))
        return out


def _strip(tree):
    return jax.tree_util.tree_map(lambda a: a[0], tree)


def _restore(tree):
    return jax.tree_util.tree_map(lambda a: a[None], tree)


# The jitted programs are named by the functions they are made from
# (``sl_train_step`` in every train-step factory, ``sl_fedavg``): the
# profiler's ``XLA Modules`` line shows ``jit_sl_train_step(<id>)`` and
# every operation's ``op_name`` starts ``jit(sl_train_step)/``.

@jax.named_scope("optimizer")
def _apply_optimizer(optimizer, grads, opt_state, params):
    """The update, with the leading client axis restored: XLA fuses the
    update of a leaf into that last reshape and names the fusion after
    it, so the reshape has to be under the scope too."""
    updates, new_opt = optimizer.update(grads, opt_state, params)
    return _restore(optax.apply_updates(params, updates)), _restore(new_opt)


def _shmap_kwargs(mesh: Mesh) -> dict:
    """Extra ``jax.shard_map`` kwargs for this mesh.

    On a (client, stage[, seq]) mesh every axis is manual (the
    default).  When the mesh carries a ``model`` tensor-parallel or
    ``expert`` axis, that axis is left to GSPMD — parameters sharded
    under :func:`split_learning_tpu.parallel.tensor.tp_spec` /
    :func:`split_learning_tpu.parallel.expert.ep_spec` get their
    collectives (all-gather after column-parallel, psum after
    row-parallel, dispatch/combine all-to-alls around the expert FFNs)
    derived by XLA *inside* the manual pipeline body.
    """
    auto = {"model", "expert"} & set(mesh.axis_names)
    if auto:
        return {"axis_names": frozenset(set(mesh.axis_names) - auto)}
    return {}


def _make_grad_sync(client_sync: dict | None, mesh: Mesh):
    """Shared grouped-gradient-mean closure for the dense and LoRA steps.

    Returns ``sync(grads_by_layer, c_idx)`` applying the per-layer
    ``axis_index_groups`` psum-mean, or None when no sync is configured.
    """
    if not client_sync:
        return None
    n_client = mesh.shape["client"]
    group_denom = {}
    for name, groups in client_sync.items():
        sizes = np.ones(n_client, np.float32)
        for g in groups:
            for col in g:
                sizes[col] = len(g)
        group_denom[name] = sizes

    @jax.named_scope("grad_sync")
    def sync(grads_part, c_idx):
        synced = dict(grads_part)
        for name, groups in client_sync.items():
            if name not in grads_part:
                continue
            denom = jnp.asarray(group_denom[name])[c_idx]
            synced[name] = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(
                    g, "client", axis_index_groups=groups) / denom,
                grads_part[name])
        return synced

    return sync


def make_train_step(pipe: PipelineModel, optimizer: optax.GradientTransformation,
                    mesh: Mesh, train: bool = True,
                    donate: bool = True,
                    client_sync: dict | None = None) -> Callable:
    """Jitted multi-client pipelined train step.

    Inputs are stacked along a leading ``client`` axis and sharded over the
    mesh's ``client`` dimension:

    * ``params``/``opt_state``/``stats``: leaves of shape (C, ...) —
      per-client model replicas (federated: NO gradient sync across
      clients; they only meet at the FedAvg barrier);
    * ``x``: (C, M, mb, ...), ``labels``: (C, M, mb);
    * ``rngs``: jax typed key array of shape (C,).

    ``client_sync`` maps a top-level param key (layer name) to
    ``axis_index_groups`` partitioning the client axis: gradients for that
    layer are mean-synced within each group every step.  This expresses
    the reference's shared later-stage clients — N stage-1 clients feeding
    one stage-2 client through a shared queue (``src/train/VGG16.py:154``)
    train that stage-2 shard on ALL their activations, which in the
    synchronous mesh regime is exactly a grouped gradient mean.  DCSL's
    server-side data aggregation (``other/DCSL/src/Scheduler.py:152-191``,
    one fwd/bwd over ``sda_size`` concatenated client batches) is the same
    mechanism with a full-axis group.

    The mesh's ``stage`` axis may be smaller than ``pipe.n_stages`` (it
    must divide it): stages are then blocked onto devices as virtual
    pipeline stages — on a 1-wide axis the whole split model runs on one
    device with microbatch gradient accumulation (no collective hops),
    preserving cut semantics on a single chip.

    Returns (params, opt_state, stats, loss[C], counters): ``counters``
    is ``{collection: {name: value[C]}}``, what the model's layers sowed
    into the counter collections (``COUNTER_FOLDS``) over the step, and
    ``{}`` for a model that sows none.
    """
    grad_sync = _make_grad_sync(client_sync, mesh)
    stage_axis = int(mesh.shape["stage"])
    unroll = pipe.scan_unroll_for(mesh)
    # seq-sharded pipelines: grads are per-stage AND per-token-block
    # partial sums; one psum over both axes restores full gradients on
    # the (stage, seq)-replicated params
    sync_axes = (("stage",) if pipe.seq_axis is None
                 else ("stage", pipe.seq_axis))

    def sl_train_step(params, opt_state, stats, x, labels, rngs):
        params, opt_state, stats = map(_strip, (params, opt_state, stats))
        x, labels, rng = x[0], labels[0], rngs[0]

        def loss_fn(p):
            local, aux = pipe.device_loss(p, stats, x, labels, rng,
                                          train=train,
                                          stage_axis_size=stage_axis,
                                          scan_unroll=unroll)
            return local, aux

        (_, (loss, new_stats, counters)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        # each device produced grads for its own stage only; sync replicas
        with jax.named_scope("grad_sync"):
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, sync_axes), grads)
        if grad_sync is not None:
            grads = grad_sync(grads, jax.lax.axis_index("client"))
        new_params, new_opt = _apply_optimizer(optimizer, grads, opt_state,
                                               params)
        return (new_params, new_opt, _restore(new_stats), loss[None],
                jax.tree_util.tree_map(lambda c: c[None], counters))

    spec_c = P("client")
    # x/labels carry the sequence on their last dim (token models):
    # shard it over `seq` so each device sees its block
    spec_x = (spec_c if pipe.seq_axis is None
              else P("client", None, None, pipe.seq_axis))
    # check_vma=False: jax 0.9's varying-axis tracker miscompiles the
    # transpose of the scan-of-ppermute pipeline (observed: heap corruption
    # and garbage gradients on the CPU backend). Replication along `stage`
    # is guaranteed manually by the grad/stats psums in `sl_train_step`.
    mapped = jax.shard_map(
        sl_train_step, mesh=mesh,
        in_specs=(spec_c, spec_c, spec_c, spec_x, spec_x, spec_c),
        out_specs=(spec_c,) * 5,
        check_vma=False,
        **_shmap_kwargs(mesh),
    )
    return jax.jit(mapped, donate_argnums=(0, 1, 2) if donate else ())


def make_sliced_train_step(pipe: PipelineModel,
                           optimizer: optax.GradientTransformation,
                           mesh: Mesh, train: bool = True,
                           donate: bool = True) -> Callable:
    """Stage-sliced parameter residency variant of :func:`make_train_step`.

    Parameters ride as the flat ``(C, A*seg_len)`` fp32 wire of
    :meth:`PipelineModel.stage_param_layout` (build with
    :func:`slice_params_for_mesh`), sharded ``(client, stage)``: each
    device holds ONLY its own stages' parameters (~1/A of the model plus
    padding) instead of a full replica.  Gradients come back for the
    local slice alone, so the per-step full-tree gradient psum over
    ``stage`` — A redundant copies of every gradient, every step —
    disappears, and optimizer state shards identically for free.

    Contract differences vs the replicated step:

    * the optimizer must be elementwise (sgd / momentum / adam / adamw
      families): it sees one flat vector, not the layer tree, so
      per-layer transforms (masking, layerwise lr) don't apply;
    * ``client_sync`` grouped gradient means are not supported (no
      per-layer gradient access) — shared-later-stage plans keep the
      replicated step;
    * the returned params are the updated flat wire; reassemble the
      full tree at round boundaries with
      ``pipe.stage_param_layout(A).unpack(wire[c])``.  FedAvg over
      clients works directly on the wire
      (``make_fedavg_step(mesh, param_spec=P("client", "stage"))``).

    Returns ``step(params_wire, opt_state, stats, x, labels, rngs) ->
    (params_wire, opt_state, stats, loss[C])``.
    """
    stage_axis = int(mesh.shape["stage"])
    layout = pipe.stage_param_layout(stage_axis)
    unroll = pipe.scan_unroll_for(mesh)

    def sl_train_step(params, opt_state, stats, x, labels, rngs):
        p = params[0]                      # (seg_len,) own-stage slice
        opt_state, stats = map(_strip, (opt_state, stats))
        x, labels, rng = x[0], labels[0], rngs[0]

        def loss_fn(pv):
            local, aux = pipe.device_loss(pv, stats, x, labels, rng,
                                          train=train,
                                          stage_axis_size=stage_axis,
                                          layout=layout,
                                          scan_unroll=unroll)
            return local, aux

        (_, (loss, new_stats, _)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p)
        # grads are purely LOCAL (this device's slice): no stage psum.
        # Seq-sharded pipelines still fold token-block partial sums.
        if pipe.seq_axis is not None:
            with jax.named_scope("grad_sync"):
                grads = jax.lax.psum(grads, pipe.seq_axis)
        new_p, new_opt = _apply_optimizer(optimizer, grads, opt_state, p)
        return (new_p, new_opt, _restore(new_stats),
                loss[None])

    # optimizer-state specs mirror the flat param wire: vector leaves
    # (moments) shard (client, stage); scalars (count) stay client-only
    opt_struct = jax.eval_shape(
        optimizer.init,
        jax.ShapeDtypeStruct((stage_axis * layout.seg_len,),
                             layout.dtype))
    spec_opt = jax.tree_util.tree_map(
        lambda leaf: (P("client", "stage") if leaf.ndim >= 1
                      else P("client")),
        opt_struct)
    spec_c = P("client")
    spec_x = (spec_c if pipe.seq_axis is None
              else P("client", None, None, pipe.seq_axis))
    mapped = jax.shard_map(
        sl_train_step, mesh=mesh,
        in_specs=(P("client", "stage"), spec_opt, spec_c, spec_x,
                  spec_x, spec_c),
        out_specs=(P("client", "stage"), spec_opt, spec_c, spec_c),
        check_vma=False,
        **_shmap_kwargs(mesh),
    )
    return jax.jit(mapped, donate_argnums=(0, 1, 2) if donate else ())


def slice_params_for_mesh(pipe: PipelineModel, params, n_clients: int,
                          mesh: Mesh):
    """Pack a full param tree into the client-stacked stage-sliced wire
    and place it: ``(C, A*seg_len)`` sharded ``(client, stage)``."""
    layout = pipe.stage_param_layout(int(mesh.shape["stage"]))
    wire = layout.pack(params).reshape(-1)
    stacked = jnp.broadcast_to(wire[None], (n_clients,) + wire.shape)
    return jax.device_put(
        stacked, NamedSharding(mesh, P("client", "stage")))


def shard_sliced_opt_to_mesh(opt_state, mesh: Mesh):
    """Place client-stacked optimizer state for the sliced step: vector
    leaves (moments over the flat wire) shard ``(client, stage)``;
    scalars (count) stay client-sharded only."""
    def put(leaf):
        spec = (P("client", "stage") if jnp.ndim(leaf) >= 2
                else P("client"))
        return jax.device_put(leaf, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(put, opt_state)


def make_lora_train_step(pipe: PipelineModel,
                         optimizer: optax.GradientTransformation,
                         mesh: Mesh, lora_alpha: float, lora_rank: int,
                         donate: bool = True,
                         client_sync: dict | None = None) -> Callable:
    """LoRA variant of :func:`make_train_step`.

    Parameters ride as ``(frozen, trainable)`` per client —
    ``trainable = {"lora": adapters, "head": unfrozen layers}`` — and the
    pipelined loss differentiates the *merged* model w.r.t. the trainable
    tree only (peft semantics, ``src/RpcClient.py:61-66``).  Both trees
    are client-stacked so FLEX-style per-client bases keep working.

    Returns ``step(frozen_c, t_c, opt_c, stats_c, x, labels, rngs) ->
    (t_c, opt_c, stats_c, loss)``; frozen never changes.
    """
    from split_learning_tpu.ops.lora import lora_merge

    grad_sync = _make_grad_sync(client_sync, mesh)
    stage_axis = int(mesh.shape["stage"])
    unroll = pipe.scan_unroll_for(mesh)

    def sl_train_step(frozen, t, opt_state, stats, x, labels, rngs):
        frozen, t, opt_state, stats = map(_strip,
                                          (frozen, t, opt_state, stats))
        x, labels, rng = x[0], labels[0], rngs[0]

        def loss_fn(tt):
            merged = lora_merge({**frozen, **tt["head"]}, tt["lora"],
                                alpha=lora_alpha, rank=lora_rank)
            local, aux = pipe.device_loss(merged, stats, x, labels, rng,
                                          train=True,
                                          stage_axis_size=stage_axis,
                                          scan_unroll=unroll)
            return local, aux

        (_, (loss, new_stats, _)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(t)
        with jax.named_scope("grad_sync"):
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, "stage"), grads)
        if grad_sync is not None:
            c_idx = jax.lax.axis_index("client")
            grads = {"lora": grad_sync(grads["lora"], c_idx),
                     "head": grad_sync(grads["head"], c_idx)}
        new_t, new_opt = _apply_optimizer(optimizer, grads, opt_state, t)
        return new_t, new_opt, _restore(new_stats), loss[None]

    spec_c = P("client")
    mapped = jax.shard_map(
        sl_train_step, mesh=mesh,
        in_specs=(spec_c,) * 7,
        out_specs=(spec_c,) * 4,
        check_vma=False,
    )
    # frozen (arg 0) is returned unchanged and must NOT be donated; the
    # trainable/opt/stats buffers are dead after the step and reused
    return jax.jit(mapped, donate_argnums=(1, 2, 3) if donate else ())


def make_fedavg_step(mesh: Mesh, param_spec: P | None = None) -> Callable:
    """Jitted round barrier: weighted FedAvg of per-client params over the
    ``client`` mesh axis (weights = samples consumed, the reference's
    ``data_count`` semantics at ``src/Server.py:169-179``).

    ``param_spec`` overrides the parameter placement — pass
    ``P("client", "stage")`` to average the stage-sliced flat wire of
    :func:`make_sliced_train_step` in place (the psum stays over
    ``client`` only; each device folds just its own slice)."""
    param_spec = P("client") if param_spec is None else param_spec

    def sl_fedavg(params, weights):
        p, w = _strip(params), weights[0]
        avg = fedavg_psum(p, w, "client")
        return _restore(avg)

    mapped = jax.shard_map(
        sl_fedavg, mesh=mesh, in_specs=(param_spec, P("client")),
        out_specs=param_spec, check_vma=False,
        **_shmap_kwargs(mesh))
    return jax.jit(mapped)


# --------------------------------------------------------------------------
# host-side helpers
# --------------------------------------------------------------------------

def init_pipeline_variables(pipe: PipelineModel, rng,
                            example_input) -> dict:
    """Initialize FULL-model variables once on host (single device)."""
    x = jnp.zeros(example_input.shape, example_input.dtype)
    return pipe.full_model.init(rng, x, train=False)


def stack_for_clients(tree, n_clients: int):
    """Broadcast a host pytree to a leading client axis."""
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(jnp.asarray(a)[None],
                                   (n_clients,) + jnp.asarray(a).shape),
        tree)


def shard_to_mesh(tree, mesh: Mesh):
    """Place a client-stacked pytree onto the mesh: client-sharded,
    stage-replicated — and, when the mesh carries a ``model`` or
    ``expert`` axis, tensor-/expert-sharded per leaf under the
    path-based rules of
    :func:`split_learning_tpu.parallel.tensor.tp_spec` /
    :func:`split_learning_tpu.parallel.expert.ep_spec` (the rules see
    through opt-state wrappers; non-matching leaves simply
    replicate)."""
    rule = None
    if "model" in mesh.axis_names:
        from split_learning_tpu.parallel.tensor import tp_spec
        rule = tp_spec
    elif "expert" in mesh.axis_names:
        from split_learning_tpu.parallel.expert import ep_spec
        rule = ep_spec
    if rule is not None:
        import types

        def put(path, leaf):
            # the rule sizes its spec to the UNSTACKED leaf; the client
            # axis is dim 0 here
            sub = rule(path, types.SimpleNamespace(
                ndim=jnp.ndim(leaf) - 1))
            sharding = NamedSharding(mesh, P("client", *tuple(sub)))
            return jax.device_put(leaf, sharding)

        return jax.tree_util.tree_map_with_path(put, tree)
    sharding = NamedSharding(mesh, P("client"))
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sharding), tree)
