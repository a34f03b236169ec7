"""Multi-process protocol client: the reference's ``client.py`` +
``RpcClient`` + ``Train_*`` stack as one generic runner.

Lifecycle parity (``/root/reference/src/RpcClient.py:33-135``): REGISTER →
wait on ``reply_{id}`` → START builds the shard model and (stage 1) the
data loader → READY ack (replacing the reference's 25 s settle sleep,
``src/Server.py:289``) → SYN runs the streaming hot loop → NOTIFY/PAUSE →
UPDATE with the trained shard → STOP exits.

The hot loops reproduce the reference's three roles with ONE generic
:class:`ShardRunner` instead of three per-model ``Train_{VGG16,BERT,KWT}``
classes (``src/train/*.py``):

* stage 1 (``train_on_first_layer``, ``src/train/VGG16.py:61-136``):
  event-driven 1F1B with a bounded in-flight window (``control-count``)
  and backward-time activation **recomputation** — here the recompute is a
  jitted VJP that re-runs the forward inside the gradient computation,
  with the SAME dropout rng as the original forward (the reference
  redraws masks on recompute; re-using the rng makes the gradient exact);
* middle stages: trace-routed forward/backward relay
  (``src/train/VGG16.py:40-53``);
* last stage (``train_on_last_layer``, ``:138-191``): loss + backward,
  input-gradient returned along the popped trace; NaN flags the round
  (``:169-171``).  DCSL's server-side data aggregation — concatenate
  ``sda_size`` client batches into one fwd/bwd and split the input
  gradient back per client (``other/DCSL/src/Scheduler.py:152-191``) —
  is the same loop with a collect window.

Unlike the reference there is no 0.5 s sleep-polling: transport ``get``
blocks on a condition variable / socket (``runtime/bus.py``).
"""

from __future__ import annotations

import argparse
import dataclasses
import time
import uuid
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

from split_learning_tpu.config import Config, LearningConfig, from_yaml
from split_learning_tpu.data import make_data_loader, subset_seed
from split_learning_tpu.models import build_model
from split_learning_tpu.ops.lora import lora_init, lora_merge, split_frozen
from split_learning_tpu.runtime.bus import Transport
from split_learning_tpu.runtime.log import Logger
from split_learning_tpu.runtime.memo import bounded_setdefault
from split_learning_tpu.runtime.codec import make_codecs, wire_raw_nbytes
from split_learning_tpu.runtime import blackbox
from split_learning_tpu.runtime.protocol import (
    Activation, BlackboxDump, DigestRoute, EpochEnd, FrameAssembler,
    Gradient, Heartbeat, Notify, Pause, Ready, Register, SparseLeaf,
    Start, Stop, Syn, QuantLeaf, Update, aggregate_queue, encode,
    encode_parts, gradient_queue, intermediate_queue, reply_queue,
    RPC_QUEUE,
)
from split_learning_tpu.runtime.spans import make_tracer, unpack_ctx
from split_learning_tpu.runtime.validation import dataset_for_model

def _wire_np_dtype(name: str):
    from split_learning_tpu.config import TransportConfig
    name = TransportConfig.WIRE_DTYPE_ALIASES.get(name, name)
    if name == "bfloat16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    return np.dtype(name)


def device_wire_dtype(wire_np_dtype):
    """jnp dtype for the ON-DEVICE wire cast, or None when no device
    cast applies: fp32 ships as-is, and int8 quantizes host-side
    (QuantLeaf needs the absmax, which would be a device sync)."""
    dt = np.dtype(wire_np_dtype)
    if dt == np.dtype(np.float16):
        return jnp.float16
    if dt.name == "bfloat16":
        return jnp.bfloat16
    return None


def _cast_for_wire(tree, dtype):
    """Cast float leaves to the wire dtype ON DEVICE, before the
    device->host fetch: the async sender's ``np.asarray`` then moves
    wire-width bytes instead of fp32 (half the PCIe traffic on the
    bf16 default), and the host-side ``_to_wire_tree`` cast becomes a
    no-op.  Bit-identical to casting on host — both round to nearest
    even.  No-op when ``dtype`` is None."""
    if dtype is None:
        return tree

    def conv(leaf):
        ldt = getattr(leaf, "dtype", None)
        if (ldt is None or ldt == jax.dtypes.float0
                or not jnp.issubdtype(ldt, jnp.floating)):
            return leaf
        return leaf if ldt == dtype else leaf.astype(dtype)
    return jax.tree_util.tree_map(conv, tree)


def _start_host_copy(tree) -> None:
    """Kick off the device→host transfer of every leaf WITHOUT blocking
    (jax.Array.copy_to_host_async), so by the time the async sender's
    encode thunk calls np.asarray the bytes are already on host — the
    transfer overlaps the training thread's next microbatch compute."""
    for leaf in jax.tree_util.tree_leaves(tree):
        copy = getattr(leaf, "copy_to_host_async", None)
        if copy is not None:
            try:
                copy()
            except Exception:  # noqa: BLE001 — purely a prefetch hint
                return


def _quant_int8(a: np.ndarray):
    """Absmax int8 quantization of one float payload leaf.

    A non-finite payload ships raw fp32 instead: quantizing NaN/inf is
    undefined, and the diverged values must survive the hop so the
    receiver's NaN sentinel (``src/train/VGG16.py:169-171``) fires."""
    a32 = np.asarray(a, np.float32)
    amax = float(np.max(np.abs(a32))) if a32.size else 0.0
    if not np.isfinite(amax):
        return a32
    scale = (amax / 127.0) or 1.0   # all-zero payload: any scale works
    return QuantLeaf(q=np.round(a32 / scale).astype(np.int8),
                     scale=scale)


def _to_wire_tree(tree, dtype=np.float32):
    """Device pytree -> numpy payload for Activation/Gradient messages.

    Stage boundaries may be pytrees (e.g. BERT's (hidden, mask),
    models/bert.py): float leaves travel as ``dtype``
    (``transport.wire-dtype``; fp16/bf16 halve the hop bytes vs the
    reference's fp32 pickles, int8 absmax-quantizes for ~4x), bool/int
    leaves keep their dtype, and float0 gradient leaves (cotangents of
    non-differentiable inputs) become zeros so they pickle."""
    quantize = dtype == np.int8

    def conv(leaf):
        if getattr(leaf, "dtype", None) == jax.dtypes.float0:
            return np.zeros(np.shape(leaf),
                            np.float32 if quantize else dtype)
        a = np.asarray(leaf)
        # jnp.issubdtype, NOT np.issubdtype: numpy's lattice does not
        # classify ml_dtypes (bfloat16 model activations) as floating,
        # which would silently skip the wire cast
        if jnp.issubdtype(a.dtype, jnp.floating):
            return _quant_int8(a) if quantize else a.astype(dtype,
                                                            copy=False)
        return a
    return jax.tree_util.tree_map(conv, tree)


def _from_wire_tree(tree):
    """Wire payload tree -> device arrays.  Self-describing: QuantLeaf
    (legacy per-tensor OR tiled codec form) and SparseLeaf decode
    without knowing the sender's codec config, so mixed-policy
    deployments interoperate."""
    def conv(leaf):
        if isinstance(leaf, QuantLeaf):
            from split_learning_tpu.runtime.codec.quant import (
                dequantize_leaf,
            )
            return dequantize_leaf(leaf)
        if isinstance(leaf, SparseLeaf):
            from split_learning_tpu.runtime.codec.sparse import (
                densify_leaf,
            )
            return densify_leaf(leaf)
        return jnp.asarray(leaf)
    return jax.tree_util.tree_map(conv, tree)


def _wire_vdot(out_tree, ct_tree):
    """<out, cotangent> over the float leaves of a boundary pytree (the
    scalar whose gradient backpropagates a received cotangent)."""
    tot = jnp.zeros((), jnp.float32)
    for o, c in zip(jax.tree_util.tree_leaves(out_tree),
                    jax.tree_util.tree_leaves(ct_tree)):
        if jnp.issubdtype(o.dtype, jnp.floating):
            tot = tot + jnp.vdot(o.astype(jnp.float32),
                                 c.astype(jnp.float32))
    return tot


#: async drain-on-pause idle grace (seconds): a PAUSEd async consumer
#: keeps eating its in-flight activation stream until every origin
#: feeder's final epoch fence arrived, or the queue has been silent
#: this long — the bounded-staleness tax a delayed stream may cost
#: (frames beyond the grace are dropped, not waited for)
ASYNC_DRAIN_IDLE_S = 0.5


@dataclasses.dataclass
class _AbortPause(Pause):
    """Local sentinel: the round was abandoned (STOP/fresh START arrived
    mid-loop) — unwind WITHOUT publishing any UPDATE.  Distinct from a
    server Pause(send_weights=False), which still expects a weight-less
    UPDATE (FLEX non-aggregation rounds)."""


def make_optimizer_from_dict(learning: dict | None) -> tuple[
        optax.GradientTransformation, LearningConfig]:
    d = dict(learning or {})
    known = {f.name for f in dataclasses.fields(LearningConfig)}
    cfg = LearningConfig(**{k: v for k, v in d.items() if k in known})
    from split_learning_tpu.runtime.context import make_optimizer
    return make_optimizer(cfg), cfg


def _ops_cache_key(model_key, start_layer, end_layer, learning,
                   model_kwargs) -> tuple:
    d = dict(learning or {})
    # loop-behavior-only knobs: the jitted ops are identical with the
    # flag on or off, so sharing the compiled bundle across the A/B is
    # free (and keeps the sync-overlap bench/test legs compile-warm)
    d.pop("sync_overlap", None)
    return (model_key, start_layer, end_layer,
            repr(sorted(d.items())),
            repr(sorted((model_kwargs or {}).items())))


#: jitted-op bundles shared across ShardRunner instances with identical
#: (model, layer range, learning, kwargs) — see runtime/memo.py
_OPS_CACHE: dict = {}
_OPS_CACHE_MAX = 64


class ShardRunner:
    """Jitted forward / recompute-backward / optimizer ops for one shard.

    Parameters are carried as ``(frozen, trainable)``: ``trainable`` is
    ``{"lora": adapters, "head": unfrozen params}``.  Without LoRA the
    whole shard rides in ``head`` and ``frozen``/``lora`` are empty, so
    plain training and adapter training share one code path.  With
    ``learning.lora_rank > 0`` this reproduces the reference's peft wrap:
    adapters on attention kernels, base frozen, classifier head unfrozen
    on the final shard (``src/RpcClient.py:61-66``, ``:99-103``).
    """

    def __init__(self, model_key: str, start_layer: int, end_layer: int,
                 learning: dict | None, model_kwargs: dict | None = None,
                 seed: int = 0):
        self.model = build_model(model_key, start_layer=start_layer,
                                 end_layer=end_layer,
                                 **(model_kwargs or {}))
        self.start_layer = start_layer
        self.learning_dict = dict(learning or {})  # for change detection
        self.optimizer, self.learning = make_optimizer_from_dict(learning)
        self.rng = jax.random.key(seed)
        self._counter = 0
        lrn = self.learning
        self.lora_rank, self.lora_alpha = lrn.lora_rank, lrn.lora_alpha
        # async decoupled mode (learning.mode: async): every non-final
        # stage trains against a local auxiliary head on its cut
        # boundary (ops/auxiliary.py) instead of waiting for a wire
        # cotangent.  The module is deterministic from the cache key
        # (model_key fixes the label space, learning fixes the
        # architecture), so sharing it through _OPS_CACHE is safe.
        self.aux = None
        if lrn.mode == "async":
            from split_learning_tpu.ops.auxiliary import (
                build_aux_head, num_classes_for,
            )
            self.aux = build_aux_head(lrn.aux_head,
                                      num_classes_for(model_key),
                                      hidden=lrn.aux_hidden)

        cache_key = _ops_cache_key(model_key, start_layer, end_layer,
                                   learning, model_kwargs)
        ops = bounded_setdefault(_OPS_CACHE, _OPS_CACHE_MAX, cache_key,
                                 self._build_ops)
        (self.fwd, self.bwd, self.last_step, self.whole_step,
         self.aux_step, self.apply_update, self._merged) = ops

    def init_aux_params(self, boundary_shapes) -> dict:
        """Aux-head params for this shard's boundary shape pytree (the
        ``jax.eval_shape`` of ``fwd``)."""
        from split_learning_tpu.ops.auxiliary import init_aux_params
        return init_aux_params(self.aux, self.next_rng(),
                               boundary_shapes)

    def _build_ops(self) -> tuple:
        """The five jitted ops + merged-params helper.  Closes over the
        (stateless) model/optimizer only — everything instance-specific
        (rng stream, params, stats) is passed per call, which is what
        makes the bundle shareable through ``_OPS_CACHE``."""

        def merged(frozen, t):
            base = {**frozen, **t["head"]}
            if not t["lora"]:
                return base
            return lora_merge(base, t["lora"], alpha=self.lora_alpha,
                              rank=self.lora_rank)

        def _variables(params, stats):
            v = {"params": params}
            if stats:
                v["batch_stats"] = stats
            return v

        @jax.jit
        def fwd(frozen, t, stats, x, rng):
            """Forward in train mode; batch_stats update deferred to the
            backward recompute (single update per consumed batch)."""
            out, _ = self.model.apply(
                _variables(merged(frozen, t), stats), x, train=True,
                mutable=["batch_stats"], rngs={"dropout": rng})
            return out

        @jax.jit
        def bwd(frozen, t, stats, x, ct, rng):
            """Recompute forward, backprop the received cotangent.

            Returns (trainable_grads, input_grad, new_stats)."""
            def f(tt, xx):
                out, mut = self.model.apply(
                    _variables(merged(frozen, tt), stats), xx, train=True,
                    mutable=["batch_stats"], rngs={"dropout": rng})
                return _wire_vdot(out, ct), mut
            # allow_int: stage-1 inputs can be integer token ids; their
            # float0 cotangent is never used (no upstream hop to route to)
            grad_fn = jax.grad(f, argnums=(0, 1), has_aux=True,
                               allow_int=True)
            (gt, gx), mut = grad_fn(t, x)
            new_stats = dict(stats)
            new_stats.update(mut.get("batch_stats", {}))
            return gt, gx, new_stats

        @jax.jit
        def last_step(frozen, t, stats, x, labels, rng):
            """Last stage: CE loss, grads wrt trainables AND input.

            Returns (loss, trainable_grads, input_grad, new_stats)."""
            def f(tt, xx):
                out, mut = self.model.apply(
                    _variables(merged(frozen, tt), stats), xx, train=True,
                    mutable=["batch_stats"], rngs={"dropout": rng})
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    out.astype(jnp.float32), labels).mean()
                return loss, mut
            (loss, mut), (gt, gx) = jax.value_and_grad(
                f, argnums=(0, 1), has_aux=True, allow_int=True)(t, x)
            new_stats = dict(stats)
            new_stats.update(mut.get("batch_stats", {}))
            return loss, gt, gx, new_stats

        @jax.jit
        def whole_step(frozen, t, stats, x, labels, rng):
            """Degenerate whole-model client (``layers == [0, 0]``,
            ``src/Server.py:241-243``): plain local train step."""
            def f(tt):
                out, mut = self.model.apply(
                    _variables(merged(frozen, tt), stats), x, train=True,
                    mutable=["batch_stats"], rngs={"dropout": rng})
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    out.astype(jnp.float32), labels).mean()
                return loss, mut
            (loss, mut), gt = jax.value_and_grad(f, has_aux=True)(t)
            new_stats = dict(stats)
            new_stats.update(mut.get("batch_stats", {}))
            return loss, gt, new_stats

        @jax.jit
        def apply_update(t, opt_state, grads):
            updates, new_opt = self.optimizer.update(grads, opt_state, t)
            return optax.apply_updates(t, updates), new_opt

        aux_step = None
        if self.aux is not None:
            @jax.jit
            def aux_step(frozen, t, aux_p, stats, x, labels, rng):
                """Decoupled forward + local aux loss in ONE program:
                the stage steps on its auxiliary gradient immediately
                after the forward tick — no wire cotangent, no
                gradient_queue park.  Returns the boundary output so
                the activation still streams downstream.

                Returns (loss, out, shard_grads, aux_grads,
                new_stats)."""
                def f(tt, ap):
                    out, mut = self.model.apply(
                        _variables(merged(frozen, tt), stats), x,
                        train=True, mutable=["batch_stats"],
                        rngs={"dropout": rng})
                    logits = self.aux.apply({"params": ap}, out)
                    loss = \
                        optax.softmax_cross_entropy_with_integer_labels(
                            logits.astype(jnp.float32), labels).mean()
                    return loss, (out, mut)
                (loss, (out, mut)), (gt, ga) = jax.value_and_grad(
                    f, argnums=(0, 1), has_aux=True)(t, aux_p)
                new_stats = dict(stats)
                new_stats.update(mut.get("batch_stats", {}))
                return loss, out, gt, ga, new_stats

        return (fwd, bwd, last_step, whole_step, aux_step,
                apply_update, jax.jit(merged))

    def partition_params(self, params, is_final_shard: bool):
        """(frozen, trainable) split of the shard's params.

        LoRA off: everything trainable.  LoRA on: adapters over target
        kernels; the model's final layer (classifier) is unfrozen when
        this shard holds it."""
        self.lora_noop = False
        if self.lora_rank <= 0:
            return {}, {"lora": {}, "head": params}
        unfrozen_names = []
        if is_final_shard:
            unfrozen_names = [self.model.specs[-1].name]
        frozen, head = split_frozen(params, unfrozen_names)
        adapters = lora_init(self.next_rng(), frozen,
                             targets=self.learning.lora_targets,
                             rank=self.lora_rank)
        if not adapters and not head:
            # no target kernels in this shard (conv-only model/slice):
            # freezing everything would silently train nothing — fall
            # back to full training and let the caller warn
            self.lora_noop = True
            return {}, {"lora": {}, "head": params}
        return frozen, {"lora": adapters, "head": head}

    def merge_params(self, frozen, t):
        """Bake adapters back into dense weights (merge_and_unload,
        ``src/RpcClient.py:121-122``) for UPDATE/aggregation."""
        return self._merged(frozen, t)

    def next_rng(self):
        self._counter += 1
        return jax.random.fold_in(self.rng, self._counter)


@dataclasses.dataclass
class _Inflight:
    x: Any
    rng: Any
    trace: list
    labels: Any = None
    n: int = 0   # batch size; counted into num_samples at BACKWARD time


class ProtocolClient:
    """One split-learning client process (reference ``client.py`` +
    ``src/RpcClient.py``)."""

    def __init__(self, cfg: Config, client_id: str, stage: int,
                 transport: Transport | None = None,
                 cluster: int | None = None, profile: dict | None = None,
                 logger: Logger | None = None):
        self.cfg = cfg
        self.client_id = client_id
        self.stage = stage
        self.cluster = cluster
        self.profile = profile
        if transport is None:
            # configured stack: base bus -> chaos injection -> reliable
            # delivery (tests pass a pre-built transport instead)
            from split_learning_tpu.runtime.chaos import (
                make_runtime_transport,
            )
            transport = make_runtime_transport(cfg, client_id)
        self.bus = transport
        from split_learning_tpu.runtime.trace import (
            HistogramSet, default_fault_counters, default_wire_counters,
        )
        self.faults = getattr(self.bus, "faults", None) \
            or default_fault_counters
        self.wire = getattr(self.bus, "wire", None) \
            or default_wire_counters
        # distributed-tracing surface: the transport stack's tracer
        # when make_runtime_transport built one, else this client's own
        self.tracer = getattr(self.bus, "tracer", None) \
            or make_tracer(cfg, client_id)
        self.hists = getattr(self.bus, "hists", None) or HistogramSet()
        # chunked-frame reassembly is per consumer thread; the client is
        # single-threaded over its queues
        self._assembler = FrameAssembler()
        self._chunk_bytes = cfg.transport.chunk_mb << 20
        self.log = logger or Logger.for_run(cfg, client_id,
                                            console=False)
        # live telemetry plane (runtime/telemetry.py): gauges +
        # background heartbeat emitter publishing a TelemetrySnapshot
        # (counters, gauges, histogram digests, EWMA samples/s) on the
        # rpc queue every observability.heartbeat-interval seconds —
        # started at the first START, so the server's FleetMonitor
        # hears this client even through a long first-round compile
        from split_learning_tpu.runtime.telemetry import (
            GaugeSet, TelemetryEmitter,
        )
        self.gauges = GaugeSet()
        obs = getattr(cfg, "observability", None)
        self.telemetry = TelemetryEmitter(
            client_id, self._send_heartbeat,
            interval=(obs.heartbeat_interval if obs is not None else 0),
            faults=self.faults, wire=self.wire, hists=self.hists,
            gauges=self.gauges,
            samples_fn=lambda: self.num_samples, stage=stage)
        # hierarchical heartbeat roll-up: where heartbeats publish —
        # a digest queue (START extra.digest named this client's
        # aggregator node) or None for direct rpc beats; a mid-round
        # DigestRoute frame re-points it (digest-node death fallback)
        self._hb_queue: str | None = None
        # compute performance-attribution plane (runtime/perf.py):
        # sampled step timing (device fence only every
        # perf.sample-every steps), compile/retrace accounting on the
        # runner's jitted ops, HBM watermarks, MFU — emitted as one
        # kind=perf record per round and ridden on heartbeats as gauges
        from split_learning_tpu.runtime.perf import (
            make_perf_plane, process_capture,
        )
        # process_capture() is non-None only when this client shares
        # the server's process (in-proc cells): its hot-loop ticks then
        # close a POST /profile steps=K window after K steps.  Separate
        # client processes get None — the round boundary closes the
        # window there (it profiles the server process).
        self.perf = make_perf_plane(
            cfg, client_id, gauges=self.gauges, hists=self.hists,
            faults=self.faults, tracer=self.tracer, log=self.log,
            capture=process_capture())
        self.runner: ShardRunner | None = None
        self.frozen: dict = {}
        self.trainable: dict = {}
        self.stats: dict = {}
        self.opt_state = None
        self.loader = None
        self.epochs = 1
        self.sda_size = 1
        self.sda_strict = False
        self.sda_feeders = None
        self.sda_fence_quorum = 1
        self.round_ok = True
        self.num_samples = 0
        self.wire_dtype = _wire_np_dtype(cfg.transport.wire_dtype)
        self._dev_cast = device_wire_dtype(self.wire_dtype)
        # per-queue-family wire codecs (transport.codec): quantized
        # activations, EF-sparsified gradients, delta-encoded Updates.
        # Families without a policy fall back to the wire-dtype path.
        self.codecs = make_codecs(cfg, faults=self.faults)
        # scheduler-granted knob retune currently applied (START
        # extra.sched, runtime/scheduler.py): the codec-override map
        # in force, so a repeated grant doesn't rebuild codecs (and
        # reset their EF state) every round
        self._sched_codec_over: dict | None = None
        # delta codec state: (version, base tree) of the last START
        # params, and the shadow version the server advertised — a
        # delta is sent ONLY when these agree (else: full frame)
        self._delta_base = None
        self._delta_advert = None
        self._agg_group = None   # L1 group index (aggregation.fan-in)
        # async decoupled mode (learning.mode: async): client-local
        # auxiliary-head state — params + their own optimizer stream,
        # lazily shaped from the first batch's boundary eval_shape and
        # reset whenever a re-plan moves the cut (the shape signature
        # below is the reset trigger)
        self.aux_params = None
        self.aux_opt_state = None
        self._aux_sig = None
        # pipelined rounds: samples trained by overlap ticks between
        # the round's UPDATE and the next START (counted into the NEXT
        # round's Update), and control frames an overlap loop popped
        # off the reply queue for run() to handle in order
        self._overlap_samples = 0
        self._pending_ctrl: list[bytes] = []
        # sync-mode round-boundary overlap (learning.sync-overlap):
        # the speculative cache built between this round's UPDATE and
        # the next START (_sync_overlap_ticks), the splice the next
        # round's hot loop consumes when the speculation held, and the
        # last START's shape (the hold/re-seed predictor)
        self._sync_cache: dict | None = None
        self._spliced: dict | None = None
        self._last_start_held = False
        self._update_pub_t = 0.0
        if cfg.checkpoint.load:
            self._load_ef_state()
        # device-resident NaN sentinel: hot loops fold jnp.isfinite
        # into this WITHOUT a host sync; _send_update reads it once
        # per round (slcheck JX001)
        self._ok_dev = None

    # -- control plane -----------------------------------------------------

    def _decode(self, raw: bytes, queue: str | None = None):
        """Tolerant decode: a frame that fails a checksum (or ANY guard
        inside decode — a crafted pickle can raise arbitrary exceptions
        from numpy reconstruction) is dropped and counted, never fatal:
        a flipped bit on the wire must cost one message (which the
        reliable layer redelivers), not the process.  Same breadth as
        the server's rpc pump.  Returns None both for dropped frames
        and for a chunk of a still-partial message.

        A decoded message carrying a wire trace context becomes a
        *consume* span parented to the sender's publish span (the
        cross-participant flow edge), and its context send-time feeds
        the ``frame_rtt`` histogram."""
        t_wall = time.time()
        t0 = time.perf_counter()
        try:
            msg = self._assembler.feed(raw)
        except Exception as e:  # noqa: BLE001 — see docstring
            self.faults.inc("corrupt_rejected")
            self.log.warning(f"dropping undecodable frame: {e}")
            self.wire.add_decode(time.perf_counter() - t0)
            return None
        dt = time.perf_counter() - t0
        self.wire.add_decode(dt)
        self.hists.observe("decode", dt)
        if msg is not None:
            ctx = unpack_ctx(getattr(msg, "_ctx", None))
            if ctx is not None:
                _, sender_span, t_send = ctx
                rtt = max(0.0, t_wall - t_send)
                self.hists.observe("frame_rtt", rtt)
                self.tracer.record(
                    "consume", t_wall, t_wall + dt, parent=sender_span,
                    queue=queue, kind=type(msg).__name__,
                    nbytes=len(raw), rtt_ms=round(rtt * 1e3, 3),
                    round=getattr(msg, "round_idx", None))
        if isinstance(msg, BlackboxDump):
            # fleet-snapshot request: absorbed HERE, in the one decode
            # path every reply-queue consumer shares, so the dump fires
            # whatever phase the client is in (idle pump, PAUSE wait,
            # barrier) and no state machine sees an unexpected frame
            blackbox.record("dump_request", reason=msg.reason)
            blackbox.dump(msg.reason or "fleet_snapshot")
            return None
        return msg

    def _publish_parts(self, queue: str, build, kind: str | None = None
                       ) -> None:
        """Data-plane publish: ``build(ctx)`` produces the frame part
        list (device fetch + TENSOR encode + chunking) carrying the
        wire trace context ``ctx``.  On an async bus the thunk is
        enqueued and runs on the background sender — microbatch k's
        transfer/encode/socket-write overlaps microbatch k+1's compute;
        on a plain bus it runs inline.  The *publish* span opens at
        enqueue (queue-time included) and closes when the frame bytes
        exist; its id rides ``ctx`` to the receiver's consume span."""
        span = self.tracer.start("publish", always=False, queue=queue,
                                 kind=kind,
                                 round=getattr(self, "round_idx", None))
        ctx = self.tracer.wire_context(span)
        if getattr(self.bus, "deferred", False):
            def thunk():
                parts = build(ctx)
                span.end(nbytes=sum(len(p) for p in parts))
                return parts
            self.bus.publish(queue, thunk)
            return
        t0 = time.perf_counter()
        parts = build(ctx)
        dt = time.perf_counter() - t0
        self.wire.add_encode(dt)
        self.hists.observe("encode", dt)
        span.end(nbytes=sum(len(p) for p in parts))
        for part in parts:
            self.bus.publish(queue, part)
            self.wire.count_out(queue, len(part))

    # -- wire codec plumbing -----------------------------------------------

    def _wire_out(self, tree, family: str, queue: str):
        """Device-side wire stage, ON the training thread (so stateful
        codecs advance in publish order): codec ``prepare`` when a
        policy covers ``family``, else the plain device wire cast.
        Counts the pre-codec dense-equivalent bytes so the compression
        ratio is measured, not estimated."""
        c = self.codecs.get(family)
        if c is None:
            return _cast_for_wire(tree, self._dev_cast)
        self.wire.count_raw(queue,
                            wire_raw_nbytes(tree, self.wire_dtype))
        return c.prepare(tree, key=queue)

    def _wire_host(self, tree, family: str):
        """Host-side wire stage (runs inside the publish thunk, i.e.
        on the async sender): codec ``encode`` or the plain host cast."""
        c = self.codecs.get(family)
        if c is None:
            return _to_wire_tree(tree, self.wire_dtype)
        return c.encode(tree)

    def _encode_update_wire(self, params_h):
        """(wire params tree, delta_base version) for this round's
        UPDATE: a quantized delta against the START base when the
        version chain is intact, else the full fp32 frame (the resync
        path — restarted client, moved shadow, no rpc codec)."""
        rpc = self.codecs.get("rpc")
        if rpc is None or params_h is None:
            return params_h, None
        base = self._delta_base
        if (base is None or self._delta_advert is None
                or base[0] != self._delta_advert):
            return params_h, None   # server counts the full frame
        ver, base_tree = base
        self.wire.count_raw(
            RPC_QUEUE, wire_raw_nbytes(params_h, np.float32))
        return rpc.encode_update(params_h, base_tree), ver

    def _apply_sched_knobs(self, knobs: dict | None) -> None:
        """Apply a scheduler-granted per-client retune (START
        ``extra.sched``): a codec-override map is merged over the
        config's ``transport.codec`` block and the wire codecs are
        rebuilt.  Idempotent — the same grant repeated every round
        rebuilds nothing (EF-stateful codecs keep their residuals);
        a revoked grant (None) reverts to the config codecs.  A bad
        spec is rejected-and-counted, never fatal: a scheduler bug
        must cost one knob frame, not the client."""
        over = (knobs or {}).get("codec") or None
        if over == self._sched_codec_over:
            return
        import types

        from split_learning_tpu.runtime.codec.specs import (
            CodecSpecError,
        )
        base = dict(getattr(self.cfg.transport, "codec", None) or {})
        merged = {**base, **(over or {})}
        shim = types.SimpleNamespace(transport=types.SimpleNamespace(
            codec=merged or None))
        try:
            codecs = make_codecs(shim, faults=self.faults)
        except CodecSpecError as e:
            self.faults.inc("sched_knob_rejects")
            self.log.warning(
                f"rejecting scheduler codec knob {over!r}: {e}")
            return
        self.codecs = codecs
        self._sched_codec_over = over
        self.log.info(
            "scheduler retune: codec "
            + (f"override {over}" if over else "reverted to config"),
            "cyan")

    def _ef_stateful_codecs(self):
        for family in ("gradient", "rpc"):
            c = self.codecs.get(family)
            if c is not None and hasattr(c, "state_dict"):
                yield family, c

    def _save_ef_state(self):
        """Persist each stateful codec's error-feedback residuals next
        to the model checkpoint (atomic sidecar) so a restarted client
        resumes with its unsent gradient mass instead of dropping it."""
        from split_learning_tpu.runtime.checkpoint import (
            save_sidecar_arrays,
        )
        for family, c in self._ef_stateful_codecs():
            state = c.state_dict()
            if state:
                save_sidecar_arrays(
                    self.cfg.checkpoint.directory,
                    f"ef_{self.client_id}_{family}", state)

    def _load_ef_state(self):
        from split_learning_tpu.runtime.checkpoint import (
            load_sidecar_arrays,
        )
        for family, c in self._ef_stateful_codecs():
            state = load_sidecar_arrays(
                self.cfg.checkpoint.directory,
                f"ef_{self.client_id}_{family}")
            if state:
                c.load_state_dict(state)

    def register(self):
        self.bus.publish(RPC_QUEUE, encode(Register(
            client_id=self.client_id, stage=self.stage,
            cluster=self.cluster, profile=self.profile)))
        self.log.info(f"[>>>] REGISTER stage={self.stage}")

    def _send_heartbeat(self, snapshot: dict) -> None:
        """Publish one HEARTBEAT (called by the emitter's background
        thread): liveness + the full telemetry snapshot, on the rpc
        queue — or on this client's assigned digest queue when the
        server routed its beats through an aggregator node's roll-up
        (``observability.digest-interval``).  Not logged — at one
        frame per interval per client the [>>>] markers would drown
        the protocol trace."""
        # allow-send: the target alternates between the rpc queue and
        # this client's assigned digest queue — both legal for
        # (client, Heartbeat) in the model, unresolvable statically
        self.bus.publish(self._hb_queue or RPC_QUEUE, encode(Heartbeat(  # slcheck: allow-send
            client_id=self.client_id,
            round_idx=getattr(self, "round_idx", 0),
            telemetry=snapshot)))

    def run(self):
        """Lifecycle loop + telemetry guard: however the loop exits —
        STOP, closed transport, or a fault unwinding a hot loop (e.g.
        a scripted ChaosCrash) — the heartbeat thread must die with
        it, or a 'crashed' client would keep reporting healthy."""
        try:
            return self._run()
        finally:
            self.telemetry.stop()

    def _run(self):
        """Blocking lifecycle loop; returns on STOP.

        Until the first START arrives, REGISTER is re-sent every few
        seconds: a client that comes up before the server would otherwise
        lose its registration to the server's startup queue purge
        (``src/Utils.py:8-32`` hygiene — the reference simply requires
        clients to start after the server, README.md:144-171)."""
        from split_learning_tpu.runtime.bus import QueueClosed
        self.register()
        q = reply_queue(self.client_id)
        started = False
        while True:
            try:
                # control frames an async overlap loop already popped
                # from the reply queue come first — same order they
                # arrived on the wire
                if self._pending_ctrl:
                    raw = self._pending_ctrl.pop(0)
                else:
                    raw = self.bus.get(q,
                                       timeout=None if started else 3.0)
            except (QueueClosed, ConnectionError, OSError) as e:
                # Transport gone while idle BETWEEN rounds: after at
                # least one START this is almost always the STOP fan-out
                # racing the broker teardown (server exits right after
                # publishing it) — exit cleanly instead of dying with a
                # traceback.  During registration (no START yet) a dead
                # transport is a real deployment failure: stay loud so
                # the operator sees more than a server-side timeout.
                # Mid-round transport loss surfaces inside the hot loops
                # and still raises.
                if not started:
                    raise
                self.log.warning(f"transport closed ({e}); shutting down")
                self.tracer.close()
                return
            if raw is None:
                if not started:
                    self.register()
                continue
            msg = self._decode(raw, q)
            if msg is None:
                continue
            if isinstance(msg, Start):
                started = True
                # heartbeats begin at the first START (idempotent):
                # the FleetMonitor must hear this client through the
                # shard build + first-round compile that follow
                self.telemetry.start()
                self._on_start(msg)
                self.bus.publish(RPC_QUEUE, encode(Ready(
                    client_id=self.client_id, round_idx=self.fence)))
                self.log.info("[>>>] READY")
            elif isinstance(msg, Syn):
                self._on_syn(msg)
            elif isinstance(msg, DigestRoute):
                # mid-round heartbeat re-route (digest-node death
                # fallback): adopt the new target and beat once NOW so
                # the server's liveness view never gaps
                self._hb_queue = msg.queue
                try:
                    self.telemetry.beat_once()
                except Exception:  # noqa: BLE001 — transport teardown
                    pass           # races the re-route; next beat covers
            elif isinstance(msg, Stop):
                self.log.info(f"[<<<] STOP {msg.reason}")
                # drain the async sender before the process exits: a
                # still-enqueued frame must not die with this client
                flush = getattr(self.bus, "flush", None)
                if flush is not None:
                    flush(timeout=30.0)
                self.tracer.close()
                return
            else:
                self.log.warning(f"unexpected control message {msg}")

    def _on_start(self, msg: Start):
        self.log.info(f"[<<<] START layers=[{msg.start_layer}, "
                      f"{msg.end_layer}] cluster={msg.cluster}")
        self.cluster = msg.cluster
        extra = msg.extra or {}
        # join the server's run-scoped trace: every span this client
        # journals (and every wire context it sends) now carries the
        # same trace id, across processes
        if extra.get("trace_id"):
            self.tracer.adopt_trace_id(extra["trace_id"])
        self.epochs = int(extra.get("epochs", 1))
        self.sda_size = int(extra.get("sda_size", 1))
        self.round_idx = msg.round_idx
        # delta-codec version chain: the server advertises the shadow
        # version it holds for us; _send_update sends a delta only when
        # our local base carries the same tag (else: full-frame resync)
        self._delta_advert = extra.get("delta_base_version")
        # aggregator tree (aggregation.fan-in): the round UPDATE goes
        # to this L1 group's aggregate queue instead of rpc_queue
        # (None = direct-to-root; re-read every START, the tree can
        # re-shape per round).  Tree rounds never advertise a delta
        # base, so the full-frame path follows automatically.
        self._agg_group = extra.get("agg_group")
        # scheduler-granted per-client knob retune (heavier wire codec
        # for a wire-slow straggler; runtime/scheduler.py)
        self._apply_sched_knobs(extra.get("sched"))
        # hierarchical heartbeat roll-up: beats publish to this digest
        # queue (an aggregator node folds them into FleetDigest
        # frames); None = direct rpc heartbeats.  Re-read every START
        # — the route can move with the node topology.
        self._hb_queue = extra.get("digest")
        # server-issued per-invocation generation: stamps every message
        # this client sends so the server/peers can drop strays from an
        # invocation that was already abandoned (round_idx alone can't —
        # sequential strategies reuse it across sub-calls)
        self.fence = int(extra.get("gen", msg.round_idx))
        self.n_stages = int(extra.get("n_stages", self.cfg.num_stages))
        # 2LS fixed edge<->head pairing: route this client's forward
        # data plane through its pair-indexed queue (None = shared)
        self.pair = extra.get("pair")
        # DCSL dispatch topology: next-stage client ids whose per-device
        # queues this client scatters successive batches across,
        # round-robin (other/DCSL/src/Scheduler.py:21-26, :110-133)
        self.sda_peers = extra.get("sda_peers")
        self.sda_fence_quorum = int(extra.get("sda_fence_quorum", 1))
        self.sda_strict = bool(extra.get("sda_strict", False))
        self.sda_feeders = extra.get("sda_feeders")
        # sync-overlap speculation (built between the last UPDATE and
        # this START): consumed below iff it matches the round this
        # START actually opens; every mismatch discards with state
        # restored so the round stays bit-identical to non-overlapped
        sc, self._sync_cache = self._sync_cache, None
        if self._spliced is not None:
            # a previous START spliced but no round ever consumed it
            # (e.g. an elastic re-plan fanned out a second START before
            # SYN): unwind the speculation's state — the rng counter on
            # a kept runner, the kept loader's shuffle (hold mode), or
            # the adopted clone's shuffle (reseed mode) — exactly as a
            # discard would, or the bit-identity contract breaks
            stale, self._spliced = self._spliced, None
            if (stale["mode"] == "reseed"
                    and self.loader is stale["loader"]
                    and stale["loader_rng0"] is not None):
                self.loader._rng.bit_generator.state = \
                    stale["loader_rng0"]
                self.faults.inc("overlap_discards")
            else:
                self._discard_sync_cache(stale, runner_kept=True,
                                         loader_kept=True)
        self._last_start_held = msg.params is None
        if msg.params is None:
            # FLEX non-reseed round (other/FLEX/src/Server.py:220-226):
            # START without weights — keep the locally persisted shard
            # (and its optimizer state) from the previous round
            if (getattr(self, "runner", None) is None
                    or self.runner.start_layer != msg.start_layer):
                raise RuntimeError(
                    "START without params but no matching local shard "
                    f"(layers [{msg.start_layer}, {msg.end_layer}])")
            runner_kept = True
            if dict(msg.learning or {}) != self.runner.learning_dict:
                # hyperparams changed mid-hold (e.g. lr decay): rebuild
                # the jitted ops around the kept weights; optimizer
                # state resets, matching the reference's fresh-optimizer-
                # per-round behavior (src/train/VGG16.py:62)
                self.runner = ShardRunner(
                    self.cfg.model_key, msg.start_layer, msg.end_layer,
                    msg.learning,
                    model_kwargs=dict(self.cfg.model_kwargs or {}),
                    seed=self.cfg.seed
                    + zlib.crc32(self.client_id.encode()) % 100000)
                self.perf.wrap_runner(self.runner)
                self.opt_state = self.runner.optimizer.init(self.trainable)
                self._reset_aux()
                runner_kept = False
                self.log.info("hyperparams changed: rebuilt runner "
                              "(weights kept)")
            else:
                self.log.info("keeping local shard weights (no re-seed)")
            # rebuild the loader on a hold START when (a) refresh
            # re-samples every round (the reference rebuilds its loader
            # on every START when refresh is on, src/RpcClient.py:108)
            # or (b) an elastic re-plan moved this client's data
            # distribution without moving its layer range — otherwise
            # the server's plan and the trained subset silently diverge
            loader_kept = True
            if (self.stage == 1 and msg.label_counts is not None
                    and ((msg.extra or {}).get("refresh")
                         or [int(c) for c in msg.label_counts]
                         != getattr(self, "_loader_counts", None))):
                self._build_loader(msg)
                loader_kept = False
            # hold START: the delta base survives only while it still
            # matches the server's shadow — a drifted advertisement
            # (shadow lost/moved) breaks the chain, so fall back to a
            # full-frame UPDATE rather than a delta nobody can fold
            if (self._delta_base is not None
                    and self._delta_base[0] != self._delta_advert):
                self._delta_base = None
            if sc is not None:
                # the speculation holds iff the round it predicted is
                # the round it got: a hold START with the SAME runner
                # (same params AND rng stream) and the SAME loader —
                # then the cached forwards are bit-exactly the round's
                # first microbatches and the hot loop consumes them
                if (sc["mode"] == "hold" and runner_kept
                        and loader_kept):
                    self._spliced = sc
                    self.faults.inc("overlap_splices")
                    self.log.info(
                        f"sync overlap: splicing {len(sc['items'])} "
                        "precomputed forward(s) into this round")
                else:
                    self._discard_sync_cache(sc, runner_kept,
                                             loader_kept)
            return
        model_kwargs = dict(self.cfg.model_kwargs or {})
        self.runner = ShardRunner(
            self.cfg.model_key, msg.start_layer, msg.end_layer,
            msg.learning, model_kwargs=model_kwargs,
            seed=self.cfg.seed
            + zlib.crc32(self.client_id.encode()) % 100000)
        # compile/retrace accounting on the five jitted ops (instance
        # attributes only; the shared _OPS_CACHE bundle is untouched)
        self.perf.wrap_runner(self.runner)
        # aux-head state deliberately NOT cleared here: it is
        # client-local (like EF residuals) and survives same-shape
        # re-seeds so the local probe keeps converging; _ensure_aux's
        # boundary-shape signature resets it when a re-plan moved the
        # cut (the old head would be probing another tensor)
        if self.codecs.get("rpc") is not None \
                and self._delta_advert is not None:
            # base = the shard EXACTLY as received (the server's shadow
            # holds the same bytes — START params travel fp32 pickled)
            self._delta_base = (
                self._delta_advert,
                jax.tree_util.tree_map(np.asarray, msg.params))
        params = jax.tree_util.tree_map(jnp.asarray, msg.params)
        self.stats = jax.tree_util.tree_map(
            jnp.asarray, msg.batch_stats or {})
        is_final = (msg.end_layer == -1
                    or msg.end_layer >= len(self.runner.model.specs))
        self.frozen, self.trainable = self.runner.partition_params(
            params, is_final)
        if self._overlap_samples:
            # pipelined overlap trained the PREVIOUS seed's shard;
            # this START just re-seeded it, so that shard work never
            # reaches the fold — crediting its samples would inflate
            # this client's FedAvg weight with training the server
            # cannot see.  (The aux head keeps its overlap progress:
            # it is client-local and survives the re-seed.)  A hold
            # START (no params) keeps local weights AND the credit.
            self.log.info(f"overlap: {self._overlap_samples} old-seed "
                          "samples uncounted (shard re-seeded)")
            self._overlap_samples = 0
        if getattr(self.runner, "lora_noop", False):
            self.log.warning(
                "lora_rank set but no target kernels in this shard; "
                "training full shard parameters instead")
        self.opt_state = self.runner.optimizer.init(self.trainable)
        if (sc is not None and sc["mode"] == "reseed"
                and self.stage == 1 and msg.label_counts is not None
                and not (msg.extra or {}).get("refresh")
                and [int(c) for c in msg.label_counts]
                == getattr(self, "_loader_counts", None)
                and sc["batch_size"]
                == self.runner.learning.batch_size):
            # re-seed predicted and got: the overlap's loader clone IS
            # what _build_loader would now rebuild (same subset seed,
            # same counts, same batch geometry) — adopt it, and let
            # the round consume the already-transferred first batches.
            # The speculative stale-seed forwards lose their bet (this
            # START replaced the params): drop the outputs and their
            # rng draws — the runner is fresh-built, so the round's
            # recompute draws from the new stream exactly like a
            # non-overlapped run.
            for ent in sc["items"]:
                ent["rng"] = ent["out"] = None
            self.loader = sc["loader"]
            self._spliced = sc
            self.faults.inc("overlap_splices")
            self.log.info(
                f"sync overlap: {len(sc['items'])} prefetched "
                "batch(es) spliced into this round (loader adopted)")
        else:
            if sc is not None:
                self._discard_sync_cache(sc, runner_kept=False,
                                         loader_kept=False)
            self._build_loader(msg)

    def _build_loader(self, msg: Start):
        """(Re)build the stage-1 data loader from a START's label
        counts: per-client subset seed (clients with identical label
        counts must not train on identical samples), re-salted per
        round under ``distribution.refresh`` — the reference rebuilds
        its loader every START when refresh is on
        (``src/RpcClient.py:108``)."""
        if self.stage == 1 and msg.label_counts is not None:
            from split_learning_tpu.runtime.validation import (
                dataset_kwargs_for_model,
            )
            self.loader = make_data_loader(
                dataset_for_model(self.cfg.model_key),
                self.runner.learning.batch_size,
                distribution=np.asarray(msg.label_counts), train=True,
                seed=subset_seed(self.cfg.seed, self.client_id,
                                 msg.round_idx,
                                 (msg.extra or {}).get("refresh", False)),
                synthetic_size=self.cfg.synthetic_size,
                dataset_kwargs=dataset_kwargs_for_model(
                    self.cfg.model_key, self.cfg.model_kwargs))
            # remembered so a weight-less (hold) START whose plan moved
            # this client's data distribution still rebuilds the loader
            self._loader_counts = [int(c) for c in msg.label_counts]

    def _on_syn(self, msg: Syn):
        self.log.info(f"[<<<] SYN round={msg.round_idx}")
        self.round_ok = True
        self._ok_dev = jnp.asarray(True)
        self.round_idx = msg.round_idx
        # pipelined async rounds: overlap-tick samples survive only a
        # HOLD start (local shard kept — the work is in what the next
        # Update uploads); a re-seeding START zeroed them in
        # _apply_start because the fold never sees that training
        self.num_samples = self._overlap_samples
        self._overlap_samples = 0
        self.gauges.set("round", msg.round_idx)
        # perf plane round window: SYN -> UPDATE published.  The
        # attribution record's components (compute|compile|dispatch|
        # host|wait) sum to this window's wall by construction.
        self.perf.start_round(msg.round_idx)
        # responsive-set overrides (server recomputes after the READY
        # barrier): a dropped previous-stage client must not leave this
        # client waiting on fence copies that will never arrive
        if getattr(msg, "sda_fence_quorum", None) is not None:
            self.sda_fence_quorum = int(msg.sda_fence_quorum)
        if getattr(msg, "sda_feeders", None) is not None:
            self.sda_feeders = list(msg.sda_feeders)
        whole = (self.runner.start_layer == 0
                 and self.runner.model.resolved_end
                 == len(self.runner.model.specs))
        # the round's root span on this participant: hot-loop and
        # publish spans parent under it, so the merged trace's span
        # tree stays connected per round
        with self.tracer.span("client_round", round=msg.round_idx,
                              stage=self.stage):
            if self.stage == 1 and whole:
                pause = self._train_whole()
            elif self._async_mode and self.stage == 1:
                pause = self._train_first_async()
            elif self._async_mode and self.stage == self.n_stages:
                pause = self._train_last_async()
            elif self._async_mode:
                pause = self._train_middle_async()
            elif self.stage == 1:
                pause = self._train_first()
            elif self.stage == self.n_stages:
                pause = self._train_last()
            else:
                pause = self._train_middle()
            if isinstance(pause, _AbortPause):
                # close the perf window (no record emitted mid-abort
                # confusion is avoided by still journaling what ran)
                rec = self.perf.end_round(samples=self.num_samples)
                if rec:
                    self.log.metric(kind="perf", client=self.client_id,
                                    stage=self.stage,
                                    round_idx=msg.round_idx,
                                    aborted=True, **rec)
                self.tracer.flush()
                return   # round abandoned: the server stopped counting us
            if pause is not None and not pause.send_weights:
                # FLEX non-aggregation round (other/FLEX/src/RpcClient
                # .py:110-121): UPDATE still reports samples/result, but
                # carries NO weights — the shard persists locally for
                # the next round
                self._send_update(with_weights=False)
            else:
                self._send_update()
            # close the perf window INSIDE the client_round span so the
            # record's wall matches what the trace shows for this round
            rec = self.perf.end_round(samples=self.num_samples)
            if rec:
                self.log.metric(kind="perf", client=self.client_id,
                                stage=self.stage,
                                round_idx=msg.round_idx, **rec)
        # pipelined rounds: keep ticking locally while the server
        # aggregates/validates and the next START streams in — BEFORE
        # the span flush below, so the overlap window opens while the
        # server's update wall is still running (the flush's file I/O
        # would otherwise eat the head start)
        self._overlap_ticks()
        # a finished round's spans must be durable even if the process
        # dies while idle between rounds
        self.tracer.flush()

    def _send_update(self, with_weights: bool = True):
        # the round's ONE host sync of the NaN sentinel the hot loops
        # accumulated on device (per-batch bool() was a per-tick sync)
        if self._ok_dev is not None and not bool(self._ok_dev):
            self.round_ok = False
        params_h = stats_h = None
        delta_base = None
        if with_weights:
            merged = self.runner.merge_params(self.frozen, self.trainable)
            params_h = jax.tree_util.tree_map(np.asarray, merged)
            stats_h = jax.tree_util.tree_map(np.asarray, self.stats)
            # rpc codec: ship ``trained - base`` against the START's
            # version tag when the chain is intact, full fp32 otherwise
            params_h, delta_base = self._encode_update_wire(params_h)
        # telemetry piggyback: every sync round's UPDATE delivers one
        # fleet sample (counters/gauges/rate) for free, so the server
        # gets end-of-round telemetry even with heartbeats disabled
        tel = self.telemetry.snapshot().as_dict()
        # TENSOR-framed and chunked: a shard UPDATE is the biggest frame
        # a client ever publishes.  Under the aggregator tree the
        # upload lands on this client's L1 group queue; the model
        # allows Update on both rpc and aggregate families.
        dest = RPC_QUEUE
        if getattr(self, "_agg_group", None) is not None:
            dest = aggregate_queue(self.cluster, self._agg_group)
        self._publish_parts(dest, lambda ctx, p=params_h, s=stats_h,
                            n=self.num_samples, ok=self.round_ok,
                            fence=self.fence, cl=self.cluster,
                            db=delta_base, tel=tel:
                            encode_parts(Update(
                                client_id=self.client_id,
                                stage=self.stage, cluster=cl, params=p,
                                batch_stats=s, num_samples=n, ok=ok,
                                round_idx=fence, delta_base=db,
                                # async staleness tag: the generation
                                # these params were seeded from — the
                                # server's admission window reads it
                                version=fence,
                                telemetry=tel),
                                self._chunk_bytes,
                                ctx=ctx), kind="Update")
        # wall-clock anchor for the round-boundary overlap window: the
        # bench intersects [publish, next ctrl] with the server's
        # update/fan-out window on the same host clock
        self._update_pub_t = time.time()
        # error-feedback residuals are part of the client's durable
        # state: checkpoint them with the round (atomic sidecar)
        if self.cfg.checkpoint.save and self.codecs:
            self._save_ef_state()
        self.log.info(f"[>>>] UPDATE samples={self.num_samples} "
                      f"ok={self.round_ok}"
                      + ("" if with_weights else " (no weights)"))
        # failure/recovery counters live per PROCESS: in a multi-process
        # deployment the server can only report its own, so each client
        # surfaces its cumulative stack counters into (its) metrics.jsonl
        # at round end — same diff-successive-records contract
        snap = {k: v for k, v in self.faults.snapshot().items() if v}
        if snap and snap != getattr(self, "_fault_base", None):
            self._fault_base = snap
            self.log.info("round faults (cumulative): " + " ".join(
                f"{k}={v}" for k, v in sorted(snap.items())))
            self.log.metric(kind="faults", client=self.client_id,
                            round_idx=self.round_idx, **snap)
        # wire counters (bytes in/out, encode/decode seconds, sender
        # high-water mark) follow the same contract
        wsnap = {k: v for k, v in self.wire.snapshot().items() if v}
        if wsnap and wsnap != getattr(self, "_wire_base", None):
            self._wire_base = wsnap
            self.log.metric(kind="wire_client", client=self.client_id,
                            round_idx=self.round_idx, **wsnap)
        # fixed-bucket latency percentiles (frame RTT, queue wait, step
        # time, encode/decode) ride metrics.jsonl next to the counters;
        # cumulative like everything above — diff successive records
        hsnap = self.hists.snapshot()
        if hsnap and hsnap != getattr(self, "_hist_base", None):
            self._hist_base = hsnap
            self.log.metric(kind="latency", client=self.client_id,
                            round_idx=self.round_idx, **hsnap)

    def _redeliver_stop(self, msg: Stop) -> Pause:
        """A STOP arriving mid-training: requeue it for the run() loop and
        unwind the hot loop without uploading (the server is shutting
        down; an UPDATE would go nowhere)."""
        self.bus.publish(reply_queue(self.client_id), encode(msg))
        return _AbortPause(send_weights=False)

    def _redeliver_start(self, msg: Start) -> Pause:
        """A START arriving while still in a previous round's loop: the
        server timed this client out of that round and has moved on (its
        barriers no longer count us, so no PAUSE is coming).  Requeue the
        START for the run() loop and unwind without uploading — the
        client then rejoins from the fresh START instead of being lost
        until STOP.

        Async mode instead UPLOADS the round's work before rejoining:
        the Update carries the old seed's version tag, and the server's
        bounded-staleness admission window folds it with a
        staleness-scaled weight — the straggler contributes late
        instead of throwing its round away.  The requeued START is the
        double-buffered next seed, swapped at this tick boundary."""
        self.bus.publish(reply_queue(self.client_id), encode(msg))
        if self._async_mode:
            self.log.info("START mid-round (async): uploading late "
                          "update, swapping seed at tick boundary")
            return Pause(send_weights=True)
        self.log.warning("START while mid-round: rejoining next round")
        return _AbortPause(send_weights=False)

    def _wait_pause(self) -> Pause:
        q = reply_queue(self.client_id)
        while True:
            raw = self.bus.get(q)
            if raw is None:
                continue
            msg = self._decode(raw, q)
            if msg is None:
                continue
            if isinstance(msg, Pause):
                self.log.info("[<<<] PAUSE")
                return msg
            if isinstance(msg, Stop):
                return self._redeliver_stop(msg)
            if isinstance(msg, Start):
                return self._redeliver_start(msg)
            self.log.warning(f"ignoring {type(msg).__name__} while "
                             f"awaiting PAUSE")

    def _check_pause(self) -> Pause | None:
        """Non-blocking-ish control poll from inside a hot loop."""
        raw = self.bus.get(reply_queue(self.client_id), timeout=0.001)
        if raw is None:
            return None
        msg = self._decode(raw, reply_queue(self.client_id))
        if msg is None:
            return None
        if isinstance(msg, Pause):
            return msg
        if isinstance(msg, Stop):
            return self._redeliver_stop(msg)
        if isinstance(msg, Start):
            return self._redeliver_start(msg)
        return None

    # -- async decoupled mode (learning.mode: async) -------------------------

    @property
    def _async_mode(self) -> bool:
        r = getattr(self, "runner", None)
        return r is not None and r.learning.mode == "async"

    def _reset_aux(self) -> None:
        self.aux_params = None
        self.aux_opt_state = None
        self._aux_sig = None
        self._aux_key = None

    def _ensure_aux(self, x) -> None:
        """Shape (or re-shape) the aux head for the current boundary.

        The boundary shape is ``eval_shape`` of this shard's forward on
        the live batch — recomputed only when the shard slice or the
        batch shape moved.  A changed signature means a re-plan moved
        the cut: params AND optimizer state reset (the old moments are
        another tensor's momentum); an unchanged one keeps both, so the
        local probe keeps converging across rounds."""
        r = self.runner
        key = (r.start_layer, r.model.resolved_end,
               tuple(np.shape(leaf)
                     for leaf in jax.tree_util.tree_leaves(x)))
        if self.aux_params is not None \
                and key == getattr(self, "_aux_key", None):
            return
        from split_learning_tpu.ops.auxiliary import aux_shapes_signature
        shapes = jax.eval_shape(r.fwd, self.frozen, self.trainable,
                                self.stats, x, jax.random.key(0))
        sig = aux_shapes_signature(shapes)
        if sig != self._aux_sig:
            if self._aux_sig is not None:
                self.log.info("aux head re-shaped (re-plan moved the "
                              "cut): optimizer state reset")
            self.aux_params = r.init_aux_params(shapes)
            self.aux_opt_state = r.optimizer.init(self.aux_params)
            self._aux_sig = sig
        self._aux_key = key

    def _aux_tick(self, xd, yd, n: int, publish_to: str | None = None):
        """One decoupled training tick: forward + aux loss + immediate
        shard AND head step.  When ``publish_to`` is set the boundary
        output streams downstream as a normal Activation payload (the
        caller wraps it); returns the wire-staged output or None."""
        r = self.runner
        self._ensure_aux(xd)
        rng = r.next_rng()
        sp = self.tracer.start("aux_step", always=False,
                               round=self.round_idx)
        t_sp = time.perf_counter()
        loss, out, gt, ga, self.stats = r.aux_step(
            self.frozen, self.trainable, self.aux_params, self.stats,
            xd, yd, rng)
        self._ok_dev = jnp.logical_and(self._ok_dev,
                                       jnp.isfinite(loss))
        self.trainable, self.opt_state = r.apply_update(
            self.trainable, self.opt_state, gt)
        self.aux_params, self.aux_opt_state = r.apply_update(
            self.aux_params, self.aux_opt_state, ga)
        wire_out = None
        if publish_to is not None:
            wire_out = self._wire_out(out, "intermediate", publish_to)
        sp.end()
        self.hists.observe("step", time.perf_counter() - t_sp)
        self.perf.note_step(t_sp, (loss, self.trainable), n=n)
        self.num_samples += n
        return wire_out

    def _overlap_ticks(self) -> None:
        """Pipelined rounds: after the round's UPDATE leaves, a stage-1
        async client keeps ticking on its CURRENT version (local aux
        steps, nothing published) while the server aggregates/validates
        and the next START streams in — server wall overlaps client
        compute instead of alternating with it.  Bounded to one pass
        over the loader; any control frame ends the overlap and is
        handed back to run() in arrival order.  The extra samples are
        banked for the NEXT round's Update but survive only a hold
        START (shard kept): a re-seed discards the credit along with
        the shard work (_apply_start), while the client-local aux head
        keeps its progress either way."""
        if not self._async_mode:
            # sync twin (learning.sync-overlap): speculative prefetch +
            # stale-seed forward ticks instead of aux training
            self._sync_overlap_ticks()
            return
        if (self.stage != 1
                or self.loader is None or self.aux_params is None):
            return
        from split_learning_tpu.runtime.bus import QueueClosed
        q = reply_queue(self.client_id)
        ticked = 0
        for x, labels in iter(self.loader):
            try:
                raw = self.bus.get(q, timeout=0.0005)
            except (QueueClosed, ConnectionError, OSError):
                # transport gone between rounds: stop ticking and let
                # run()'s own get take the graceful-shutdown path
                # (tracer flush + close), same as a sync client
                return
            if raw is not None:
                self._pending_ctrl.append(raw)
                break
            with self.perf.host():
                xd = jnp.asarray(x)
                yd = jnp.asarray(labels.astype(np.int32))
            self._aux_tick(xd, yd, len(labels))
            # _aux_tick counts into num_samples (already reported in
            # the sent UPDATE) — move the credit to the next round
            self.num_samples -= len(labels)
            self._overlap_samples += len(labels)
            ticked += 1
        if ticked:
            self.log.info(f"async overlap: {ticked} local ticks "
                          f"({self._overlap_samples} samples banked "
                          "for the next round)")

    # -- sync-mode round-boundary overlap (learning.sync-overlap) ------------

    def _overlap_loader_clone(self):
        """The loader a re-seeding next START would build — rebuilt
        HERE, ahead of the START, so the subset draw, epoch shuffle and
        host->device transfers of the next round's first batches all
        run inside the server's update wall.  None when the next
        round's loader is unknowable (refresh re-salts the subset per
        round) or this client has no stage-1 loader."""
        if (self.stage != 1
                or getattr(self, "_loader_counts", None) is None
                or self.cfg.distribution.refresh):
            return None
        from split_learning_tpu.runtime.validation import (
            dataset_kwargs_for_model,
        )
        return make_data_loader(
            dataset_for_model(self.cfg.model_key),
            self.runner.learning.batch_size,
            distribution=np.asarray(self._loader_counts), train=True,
            seed=subset_seed(self.cfg.seed, self.client_id, 0, False),
            synthetic_size=self.cfg.synthetic_size,
            dataset_kwargs=dataset_kwargs_for_model(
                self.cfg.model_key, self.cfg.model_kwargs))

    def _sync_overlap_ticks(self) -> None:
        """Sync-mode pipelined rounds: after the round's UPDATE leaves,
        a stage-1 client keeps working while the server runs its
        round-boundary update (fold finish, FedAvgM, re-shard, START
        fan-out) — the serial bubble that otherwise idles every
        accelerator.

        The client runs the next round's first microbatches — data
        draw, host->device transfer, AND the forward pass — on the
        stale seed, in-flight-window's worth (``control-count``), then
        keeps prefetching further batches.  Two speculative modes,
        predicted from the LAST START's shape:

        * **hold predicted** (FLEX/periodic wire economy): the local
          shard IS the next round's seed and the kept loader IS its
          batch stream — the cached ``(x, rng, out)`` forwards splice
          into the round bit-exactly;
        * **re-seed predicted** (the FedAvg common case): batches come
          from a freshly rebuilt loader clone (the exact sequence a
          re-seeding START's ``_build_loader`` would draw).  The
          forwards are a losing-but-cheap bet (a re-seed replaces the
          params, so their outputs are dropped at the splice and only
          the transferred batches survive), but they are exactly "the
          next round's first microbatches on the stale seed" — the
          compute that fills the server's update wall either way.

        The next ``_on_start`` splices a cache that matches the round
        it actually got and discards anything else — with the rng
        counter and the kept loader's shuffle state restored on
        discard, so an overlapped round stays **bit-identical** to a
        non-overlapped one (tests/test_async.py).  Any control frame
        ends the overlap and is handed back to run() in arrival
        order."""
        r = getattr(self, "runner", None)
        if (r is None or self.stage != 1 or self.loader is None
                or not getattr(r.learning, "sync_overlap", False)):
            return
        whole = (r.start_layer == 0
                 and r.model.resolved_end == len(r.model.specs))
        if whole:
            return   # _train_whole has no splice consumer
        from split_learning_tpu.runtime.bus import QueueClosed
        hold = bool(self._last_start_held)
        cap_fwd = max(1, r.learning.control_count)
        cap = cap_fwd * 4
        counter0 = r._counter
        # the activity window opens HERE: the loader clone build (the
        # next round's subset draw + epoch shuffle) is overlap work too
        t0 = time.time()
        loader_rng0 = None
        if hold:
            src_loader = self.loader
            loader_rng0 = self.loader._rng.bit_generator.state
        else:
            src_loader = self._overlap_loader_clone()
            if src_loader is None:
                return
            # pristine clone state: if an adopted-then-never-trained
            # splice is dropped by a second START, the clone's shuffle
            # stream rewinds to what a fresh _build_loader would hold
            loader_rng0 = src_loader._rng.bit_generator.state
        it = iter(src_loader)
        q = reply_queue(self.client_id)
        items: list[dict] = []
        while len(items) < cap:
            try:
                raw = self.bus.get(q, timeout=0.0005)
            except (QueueClosed, ConnectionError, OSError):
                return   # transport gone between rounds: run() exits
            if raw is not None:
                self._pending_ctrl.append(raw)
                break
            with self.perf.host():
                item = next(it, None)
                if item is not None:
                    x, labels = item
                    xd = jnp.asarray(x)
                    yd = np.asarray(labels, np.int32)
            if item is None:
                break   # epoch exhausted: nothing left to speculate on
            rng = out = None
            if len(items) < cap_fwd:
                # the next round's first microbatch forwards, on the
                # stale seed (both modes — a re-seed drops the outputs
                # at the splice, a hold consumes them bit-exactly)
                rng = r.next_rng()
                sp = self.tracer.start("overlap_fwd", always=False,
                                       round=self.round_idx)
                out = r.fwd(self.frozen, self.trainable, self.stats,
                            xd, rng)
                sp.end()
            items.append({"x": xd, "labels": yd, "rng": rng,
                          "out": out})
        t1 = time.time()
        if not items:
            return
        self._sync_cache = {
            "mode": "hold" if hold else "reseed",
            "loader": None if hold else src_loader,
            "iter": it, "items": items, "counter0": counter0,
            "loader_rng0": loader_rng0,
            "batch_size": r.learning.batch_size,
        }
        # kind=overlap: the activity window the bench intersects with
        # the server's kind=agg/kind=update wall on the shared clock
        self.log.metric(kind="overlap", client=self.client_id,
                        round_idx=self.round_idx,
                        mode=self._sync_cache["mode"],
                        ticks=len(items),
                        t_pub=round(self._update_pub_t, 6),
                        act_t0=round(t0, 6), act_t1=round(t1, 6))
        self.log.info(
            f"sync overlap: {len(items)} speculative "
            f"{'forward' if hold else 'prefetch'} tick(s) while the "
            "server updates")

    def _discard_sync_cache(self, sc: dict, runner_kept: bool,
                            loader_kept: bool) -> None:
        """Unwind a speculation the actual START invalidated: restore
        the rng counter (the kept runner's stream must match a
        non-overlapped round) and the kept loader's shuffle state (the
        overlap consumed an epoch permutation the round now re-draws)."""
        self.faults.inc("overlap_discards")
        if runner_kept and sc["items"] and sc["items"][0]["rng"] \
                is not None:
            self.runner._counter = sc["counter0"]
        if loader_kept and sc["mode"] == "hold" \
                and sc["loader_rng0"] is not None:
            self.loader._rng.bit_generator.state = sc["loader_rng0"]

    def _train_first_async(self) -> Pause:
        """Stage-1 decoupled loop: dispatch + local aux step per batch,
        activations stream downstream, NO gradient wait — the
        gradient queue (and its EF codec) stays dormant."""
        out_qs = self._out_queues()
        n_fwd = 0
        for ep in range(self.epochs):
            self.gauges.set("epoch", ep)
            data_iter = iter(self.loader)
            while True:
                pause = self._check_pause()
                if pause is not None:
                    return pause
                with self.perf.host():
                    item = next(data_iter, None)
                    if item is not None:
                        x, labels = item
                        xd = jnp.asarray(x)
                        yd = jnp.asarray(labels.astype(np.int32))
                if item is None:
                    break
                out_q = out_qs[n_fwd % len(out_qs)]
                out = self._aux_tick(xd, yd, len(labels),
                                     publish_to=out_q)
                _start_host_copy(out)
                labels_np = np.asarray(labels, np.int32)
                data_id = uuid.uuid4().hex
                self._publish_parts(
                    out_q,
                    lambda ctx, out=out, labels_np=labels_np, d=data_id,
                    fence=self.fence, cl=self.cluster:
                        encode_parts(Activation(
                            data_id=d,
                            data=self._wire_host(out, "intermediate"),
                            labels=labels_np, trace=[self.client_id],
                            cluster=cl, round_idx=fence),
                            self._chunk_bytes, ctx=ctx),
                    kind="Activation")
                n_fwd += 1
            # epoch fence, unconditionally in async (not just strict
            # SDA): downstream PAUSE drains exit the moment every
            # feeder's final fence arrives instead of idling out
            # ASYNC_DRAIN_IDLE_S — per-queue FIFO orders it after
            # every activation it covers
            for q in out_qs:
                self.bus.publish(q, encode(EpochEnd(
                    client_id=self.client_id, round_idx=self.fence,
                    epoch=ep)))
        self.bus.publish(RPC_QUEUE, encode(Notify(
            client_id=self.client_id, cluster=self.cluster,
            round_idx=self.fence)))
        self.log.info(f"[>>>] NOTIFY fwd={n_fwd} (async)")
        return self._wait_pause()

    def _drained(self, fenced: set, last_rx: float) -> bool:
        """PAUSE-drain exit test for async consumers: every origin
        feeder's final epoch fence arrived (per-queue FIFO: nothing
        the fences cover is still upstream), or the in-queue idled
        past the grace (a delayed stream's tail beyond it is dropped —
        the bounded-staleness liveness contract)."""
        feeders = set(self.sda_feeders or ())
        if feeders and feeders <= fenced:
            return True
        return time.monotonic() - last_rx > ASYNC_DRAIN_IDLE_S

    def _train_middle_async(self) -> Pause:
        """Middle-stage decoupled loop: consume upstream activations,
        local aux step, forward downstream.  EpochEnd markers relay
        downstream AND fence this stage's PAUSE drain — a Pause does
        not abandon the in-flight stream (the feeders NOTIFY the
        moment they exhaust their data, well before a slow wire has
        delivered everything they sent)."""
        in_q = intermediate_queue(self.stage - 1, self.cluster,
                                  self.pair)
        out_qs = self._out_queues()
        n_fwd = 0
        fenced: set = set()
        paused: Pause | None = None
        last_rx = time.monotonic()
        while True:
            if paused is None:
                pause = self._check_pause()
                if isinstance(pause, _AbortPause):
                    return pause      # round abandoned: nothing to drain
                if pause is not None:
                    paused = pause
                    last_rx = time.monotonic()
            elif self._drained(fenced, last_rx):
                self.log.info("[<<<] PAUSE (stream drained)")
                return paused
            raw = self.bus.get(in_q, timeout=0.001)
            if raw is None:
                continue
            act = self._decode(raw, in_q)
            if act is None or act.round_idx != self.fence:
                continue
            last_rx = time.monotonic()
            if isinstance(act, EpochEnd):
                if act.epoch >= self.epochs - 1:
                    fenced.add(act.client_id)
                for q in out_qs:
                    self.bus.publish(q, raw)  # slcheck: wire=EpochEnd
                continue
            xd = _from_wire_tree(act.data)
            yd = jnp.asarray(act.labels, jnp.int32)
            out_q = out_qs[n_fwd % len(out_qs)]
            out = self._aux_tick(xd, yd, len(act.labels),
                                 publish_to=out_q)
            _start_host_copy(out)
            self._publish_parts(
                out_q,
                lambda ctx, out=out, act=act, fence=self.fence,
                cl=self.cluster:
                    encode_parts(Activation(
                        data_id=act.data_id,
                        data=self._wire_host(out, "intermediate"),
                        labels=act.labels,
                        trace=list(act.trace) + [self.client_id],
                        cluster=cl, round_idx=fence),
                        self._chunk_bytes, ctx=ctx),
                kind="Activation")
            n_fwd += 1

    def _train_last_async(self) -> Pause:
        """Final-stage decoupled loop: true loss + local step per
        received batch, NO input-gradient return (the whole point) —
        reuses the whole-model step, which takes gradients wrt the
        trainables only.  PAUSE starts a bounded drain (``_drained``):
        the feeders NOTIFY the moment their data is dispatched, so the
        head's input stream is still in flight when the round closes —
        it eats until every feeder's final epoch fence lands or the
        queue idles out."""
        r = self.runner
        in_q = intermediate_queue(self.stage - 1, self.cluster,
                                  self.pair)
        fenced: set = set()
        paused: Pause | None = None
        last_rx = time.monotonic()
        while True:
            if paused is None:
                pause = self._check_pause()
                if isinstance(pause, _AbortPause):
                    return pause      # round abandoned: nothing to drain
                if pause is not None:
                    paused = pause
                    last_rx = time.monotonic()
            elif self._drained(fenced, last_rx):
                self.log.info("[<<<] PAUSE (stream drained)")
                return paused
            raw = self.bus.get(in_q, timeout=0.001)
            if raw is None:
                continue
            act = self._decode(raw, in_q)
            if act is None or act.round_idx != self.fence:
                continue
            last_rx = time.monotonic()
            if isinstance(act, EpochEnd):
                if act.epoch >= self.epochs - 1:
                    # the feeder's last fence: its stream is fully in
                    fenced.add(act.client_id)
                continue
            x = _from_wire_tree(act.data)
            labels = jnp.asarray(act.labels, jnp.int32)
            sp = self.tracer.start("sda_step", always=False,
                                   round=self.round_idx, window=1)
            t_sp = time.perf_counter()
            loss, gt, self.stats = r.whole_step(
                self.frozen, self.trainable, self.stats, x, labels,
                r.next_rng())
            self._ok_dev = jnp.logical_and(self._ok_dev,
                                           jnp.isfinite(loss))
            self.trainable, self.opt_state = r.apply_update(
                self.trainable, self.opt_state, gt)
            sp.end()
            self.hists.observe("step", time.perf_counter() - t_sp)
            self.perf.note_step(t_sp, (loss, self.trainable),
                                n=len(act.labels))
            self.num_samples += len(act.labels)

    # -- hot loops -----------------------------------------------------------

    def _train_whole(self) -> Pause:
        r = self.runner
        for _ in range(self.epochs):
            data_iter = iter(self.loader)
            while True:
                # loader fetch + host->device conversion land in the
                # perf plane's host-data attribution component
                with self.perf.host():
                    item = next(data_iter, None)
                    if item is not None:
                        x, labels = item
                        xd = jnp.asarray(x)
                        yd = jnp.asarray(labels.astype(np.int32))
                if item is None:
                    break
                t_sp = time.perf_counter()
                loss, grads, self.stats = r.whole_step(
                    self.frozen, self.trainable, self.stats,
                    xd, yd, r.next_rng())
                # folded on DEVICE; synced once in _send_update — a
                # bool() here would stall the loop every batch
                self._ok_dev = jnp.logical_and(self._ok_dev,
                                               jnp.isfinite(loss))
                self.trainable, self.opt_state = r.apply_update(
                    self.trainable, self.opt_state, grads)
                self.hists.observe("step", time.perf_counter() - t_sp)
                # sampled device fence lives INSIDE the perf plane
                # (runtime/perf.py SampledStepTimer), behind the sampler gate
                self.perf.note_step(t_sp, (loss, self.trainable),
                                    n=len(labels))
                self.num_samples += len(labels)
        self.bus.publish(RPC_QUEUE, encode(Notify(
            client_id=self.client_id, cluster=self.cluster,
            round_idx=self.fence)))
        return self._wait_pause()

    def _epoch_items(self, ep: int):
        """One epoch's ``(x, labels, cached)`` stream for the stage-1
        hot loop.  Epoch 0 consumes the sync-overlap splice first —
        ``cached`` carries the speculative ``{rng, out}`` when the
        forward was precomputed on the held seed (or just the
        device-resident batch on a re-seed round) — then continues the
        overlap's own iterator, which IS the round's epoch-0 sequence
        (same shuffle draw).  No splice: the plain loader epoch."""
        sp = self._spliced if ep == 0 else None
        if sp is not None:
            self._spliced = None
            for ent in sp["items"]:
                yield ent["x"], ent["labels"], ent
            for x, labels in sp["iter"]:
                yield x, labels, None
        else:
            for x, labels in iter(self.loader):
                yield x, labels, None

    def _train_first(self) -> Pause:
        """Bounded-in-flight 1F1B streaming (``src/train/VGG16.py:61-136``)."""
        r = self.runner
        inflight: dict[str, _Inflight] = {}
        grad_q = gradient_queue(self.stage, self.client_id)
        out_qs = self._out_queues()
        cap = max(1, r.learning.control_count)
        n_fwd = n_bwd = 0

        def fence_epoch(ep: int):
            # strict-SDA epoch fence: the head's hard window drains
            # leftovers only on this marker.  Published right AFTER the
            # final activation (per-queue FIFO orders it last) and
            # BEFORE this client's gradient wait — the leftover
            # batches' gradients are exactly what that wait needs, so
            # fencing any later would deadlock the barrier.
            if self.sda_strict and self.sda_size > 1:
                for q in out_qs:
                    self.bus.publish(q, encode(EpochEnd(
                        client_id=self.client_id,
                        round_idx=self.fence, epoch=ep)))

        for ep in range(self.epochs):
            self.gauges.set("epoch", ep)
            data_iter = self._epoch_items(ep)
            # prefetch one batch: exhaustion must be known at the LAST
            # dispatch, not when the in-flight cap next frees — with a
            # strict head holding this feeder's batches, the cap never
            # frees until the fence goes out
            with self.perf.host():
                next_item = next(data_iter, None)
            exhausted = next_item is None
            if exhausted:
                fence_epoch(ep)   # empty loader: fence immediately
            while not (exhausted and n_fwd == n_bwd):
                raw = self.bus.get(grad_q, timeout=0.0005)
                if raw is not None:
                    g = self._decode(raw, grad_q)
                    if g is None or g.round_idx != self.fence:
                        continue   # corrupt, or from a dropped round
                    ent = inflight.pop(g.data_id, None)
                    if ent is None:   # no longer tracked (cut round)
                        continue
                    sp = self.tracer.start("bwd", always=False,
                                           round=self.round_idx)
                    t_sp = time.perf_counter()
                    gt, _, self.stats = r.bwd(
                        self.frozen, self.trainable, self.stats, ent.x,
                        _from_wire_tree(g.data), ent.rng)
                    self.trainable, self.opt_state = r.apply_update(
                        self.trainable, self.opt_state, gt)
                    sp.end()
                    self.hists.observe("step",
                                       time.perf_counter() - t_sp)
                    self.perf.note_step(t_sp, (self.trainable,),
                                        n=ent.n)
                    n_bwd += 1
                    # counted here, not at dispatch: a mid-loop PAUSE
                    # abandons in-flight forwards, and the FedAvg weight
                    # must only cover samples whose update was applied
                    self.num_samples += ent.n
                    self.gauges.set("inflight", len(inflight))
                    continue
                if exhausted or len(inflight) >= cap:
                    # truly idle (no gradient, nothing to dispatch): check
                    # for early PAUSE/STOP (downstream died or the server
                    # dropped the round) rather than waiting forever for
                    # gradients that will never come — the reference hangs
                    # here (SURVEY.md §5.3).  Kept off the dispatch path so
                    # steady-state forwards pay no extra RPC.
                    pause = self._check_pause()
                    if pause is not None:
                        self.log.warning(
                            f"PAUSE mid-loop with {len(inflight)} in flight")
                        return pause
                    continue
                x, labels, cached = next_item
                with self.perf.host():
                    next_item = next(data_iter, None)
                    x = jnp.asarray(x)
                out_q = out_qs[n_fwd % len(out_qs)]
                sp = self.tracer.start("fwd", always=False,
                                       round=self.round_idx,
                                       spliced=bool(
                                           cached
                                           and cached["out"]
                                           is not None))
                if cached is not None and cached["out"] is not None:
                    # sync-overlap splice: this microbatch's forward
                    # already ran on the held seed during the server's
                    # update wall — consume it (the rng it drew is the
                    # stream's next draw, so the sequence matches a
                    # non-overlapped round bit-for-bit)
                    rng = cached["rng"]
                    out = self._wire_out(cached["out"], "intermediate",
                                         out_q)
                else:
                    rng = r.next_rng()
                    out = self._wire_out(
                        r.fwd(self.frozen, self.trainable, self.stats,
                              x, rng), "intermediate", out_q)
                sp.end()
                data_id = uuid.uuid4().hex
                inflight[data_id] = _Inflight(x=x, rng=rng,
                                              trace=[self.client_id],
                                              n=len(labels))
                self.gauges.set("inflight", len(inflight))
                # double buffer: start the non-blocking device→host
                # copy now and hand the encode+send to the async
                # sender; this thread moves straight on to batch k+1's
                # dispatch (or the next gradient) while batch k drains
                _start_host_copy(out)
                labels_np = np.asarray(labels, np.int32)
                # bind fence/cluster NOW: the thunk may run after an
                # abandoned round's _on_start moved them
                self._publish_parts(
                    out_q,
                    lambda ctx, out=out, labels_np=labels_np, d=data_id,
                    fence=self.fence, cl=self.cluster:
                        encode_parts(Activation(
                            data_id=d,
                            data=self._wire_host(out, "intermediate"),
                            labels=labels_np, trace=[self.client_id],
                            cluster=cl, round_idx=fence),
                            self._chunk_bytes, ctx=ctx),
                    kind="Activation")
                n_fwd += 1
                if next_item is None:
                    exhausted = True
                    fence_epoch(ep)
        self.bus.publish(RPC_QUEUE, encode(Notify(
            client_id=self.client_id, cluster=self.cluster,
            round_idx=self.fence)))
        self.log.info(f"[>>>] NOTIFY fwd={n_fwd} bwd={n_bwd}")
        return self._wait_pause()

    def _out_queues(self) -> list[str]:
        """Forward-dispatch queues: the next stage's per-device queues
        (DCSL round-robin scatter) when ``sda_peers`` is set, else the
        single shared/pair-indexed cluster queue.

        The rotation start is staggered by a stable hash of this
        client's id: with a small in-flight cap, producers all starting
        at peer 0 would convoy onto the same head each turn instead of
        load-balancing across heads."""
        if self.sda_peers:
            qs = [intermediate_queue(self.stage, self.cluster, p)
                  for p in self.sda_peers]
            off = zlib.crc32(self.client_id.encode()) % len(qs)
            return qs[off:] + qs[:off]
        return [intermediate_queue(self.stage, self.cluster, self.pair)]

    def _train_middle(self) -> Pause:
        r = self.runner
        in_q = intermediate_queue(self.stage - 1, self.cluster, self.pair)
        out_qs = self._out_queues()
        n_fwd = 0
        # strict-SDA fences crossing a middle stage: relay each
        # (origin, epoch) marker downstream exactly once, and only at
        # the full previous-stage quorum — every activation the marker
        # fences has then ALREADY been forwarded (this loop forwards on
        # receipt, per-queue FIFO), keeping the feeder→head ordering
        # guarantee hop by hop even when parallel previous-stage
        # devices relay at different speeds.
        fence_copies: dict[tuple[str, int], int] = {}
        quorum = max(1, self.sda_fence_quorum)
        grad_q = gradient_queue(self.stage, self.client_id)
        inflight: dict[str, _Inflight] = {}
        while True:
            pause = self._check_pause()
            if pause is not None:
                self.log.info("[<<<] PAUSE")
                return pause
            raw = self.bus.get(grad_q, timeout=0.0005)
            if raw is not None:
                g = self._decode(raw, grad_q)
                if g is None or g.round_idx != self.fence:
                    continue   # corrupt, or from a dropped round
                ent = inflight.pop(g.data_id, None)
                if ent is None:   # no longer tracked (cut round)
                    continue
                sp = self.tracer.start("bwd", always=False,
                                       round=self.round_idx)
                t_sp = time.perf_counter()
                gt, gx, self.stats = r.bwd(
                    self.frozen, self.trainable, self.stats, ent.x,
                    _from_wire_tree(g.data), ent.rng)
                self.trainable, self.opt_state = r.apply_update(
                    self.trainable, self.opt_state, gt)
                sp.end()
                self.hists.observe("step", time.perf_counter() - t_sp)
                self.perf.note_step(t_sp, (self.trainable,), n=ent.n)
                self.num_samples += ent.n   # see _train_first
                origin = ent.trace[-1]
                grad_out_q = gradient_queue(self.stage - 1, origin)
                gx = self._wire_out(gx, "gradient", grad_out_q)
                _start_host_copy(gx)
                self._publish_parts(
                    grad_out_q,
                    lambda ctx, gx=gx, d=g.data_id, tr=ent.trace[:-1],
                    fence=self.fence:
                        encode_parts(Gradient(
                            data_id=d,
                            data=self._wire_host(gx, "gradient"),
                            trace=tr, round_idx=fence),
                            self._chunk_bytes, ctx=ctx),
                    kind="Gradient")
                continue
            raw = self.bus.get(in_q, timeout=0.0005)
            if raw is None:
                continue
            act = self._decode(raw, in_q)
            if act is None or act.round_idx != self.fence:
                continue   # corrupt, or from a dropped round: discard
            if isinstance(act, EpochEnd):
                key = (act.client_id, act.epoch)
                fence_copies[key] = fence_copies.get(key, 0) + 1
                if fence_copies[key] == quorum:
                    for q in out_qs:   # fence ALL downstream devices
                        self.bus.publish(q, raw)  # slcheck: wire=EpochEnd
                continue
            x = _from_wire_tree(act.data)
            rng = r.next_rng()
            out_q = out_qs[n_fwd % len(out_qs)]
            sp = self.tracer.start("fwd", always=False,
                                   round=self.round_idx)
            out = self._wire_out(
                r.fwd(self.frozen, self.trainable, self.stats, x, rng),
                "intermediate", out_q)
            sp.end()
            inflight[act.data_id] = _Inflight(x=x, rng=rng,
                                              trace=list(act.trace),
                                              n=len(act.labels))
            self.gauges.set("queue_depth", len(inflight))
            _start_host_copy(out)
            self._publish_parts(
                out_q,
                lambda ctx, out=out, act=act, fence=self.fence,
                cl=self.cluster:
                    encode_parts(Activation(
                        data_id=act.data_id,
                        data=self._wire_host(out, "intermediate"),
                        labels=act.labels,
                        trace=list(act.trace) + [self.client_id],
                        cluster=cl, round_idx=fence),
                        self._chunk_bytes, ctx=ctx),
                kind="Activation")
            n_fwd += 1

    def _train_last(self) -> Pause:
        """Loss + backward + routed input-gradient return
        (``src/train/VGG16.py:138-191``); with ``sda_size > 1`` collects a
        window of client batches and runs them as ONE concatenated fwd/bwd
        (DCSL SDA, ``other/DCSL/src/Scheduler.py:152-191``)."""
        r = self.runner
        in_q = intermediate_queue(self.stage - 1, self.cluster, self.pair)
        # DCSL window semantics (other/DCSL/src/Scheduler.py:152-191):
        # one batch from each of ``sda_size`` DISTINCT origins.  pending
        # holds per-origin FIFOs — a second batch from an origin already
        # represented waits for the NEXT window instead of widening this
        # one, mirroring the reference's per-device queues.
        pending: dict[str, list[Activation]] = {}
        idle_flush_s = 0.25
        idle_since: float | None = None
        # aggregation.sda-strict picks the barrier discipline:
        #
        # * ELASTIC (default): the width ADAPTS — it starts at
        #   sda_size, and an idle-triggered partial flush (a feeder ran
        #   dry — uneven non-IID loaders make that the common case, not
        #   just the round tail) lowers it to the surviving feeder
        #   count so each subsequent burst doesn't re-pay the idle
        #   stall; it rises back toward sda_size the moment more
        #   distinct origins are live again.
        # * STRICT (DCSL parity, other/DCSL/src/Scheduler.py:152-191):
        #   a HARD sda_size distinct-origin barrier — a slow-but-alive
        #   feeder is waited for, and leftovers drain only when every
        #   origin still holding batches has fenced its epoch
        #   (EpochEnd marker) or the round PAUSEs.
        strict = self.sda_strict
        target = max(1, self.sda_size)
        n_epochs = max(1, self.epochs)
        # per-origin epoch-fence counts: an origin is out of the game
        # only once it has fenced EVERY epoch of the round — a feeder
        # that fenced epoch k < n still sends epoch k+1 batches, and
        # cross-epoch windows are legitimate (the reference's scheduler
        # pairs whatever distinct devices' batches are queued)
        fences: dict[str, int] = {}
        self._sda_fences = fences   # observability (tests assert the
                                    # strict drain is fence-gated)
        # (origin, epoch) -> copies received.  In >2-stage plans every
        # stage-(n-1) device relays one deduplicated copy of each
        # feeder's fence, so a fence is RECORDED only at the full
        # quorum: the first copy can overtake activations relayed via a
        # slower middle device, but the LAST copy's per-queue FIFO
        # position proves every middle-routed batch it fences is
        # already in.  Counting raw arrivals would both overshoot
        # n_epochs and record fences early.
        fence_copies: dict[tuple[str, int], int] = {}
        quorum = max(1, self.sda_fence_quorum)

        def live() -> list[str]:
            return [o for o, q in pending.items() if q]

        def pop_window(require_full: bool) -> list[Activation] | None:
            # sorted, NOT arrival order: the window's concat order feeds
            # the jitted step, and a deterministic order is what lets a
            # chaos run's aggregated params match the fault-free run
            # bit-for-bit (tests/test_chaos.py) — arrival order is
            # thread-scheduling noise even without faults
            origins = sorted(live())
            if not origins or (require_full and len(origins) < target):
                return None
            return [pending[o].pop(0)
                    for o in origins[:max(1, self.sda_size)]]

        def drain_dead_barrier():
            # strict: leftovers drain exactly when a full window can
            # NEVER form again — the origins that could still
            # contribute (feeders with unfenced epochs left, plus
            # anything already buffered) no longer reach the barrier
            # width.  Waiting longer would deadlock the feeders'
            # gradient waits; draining sooner would break the barrier
            # for a slow-but-alive feeder (the whole point of strict).
            feeders = set(self.sda_feeders or ()) or set(pending)
            while True:
                possible = ({o for o in feeders
                             if fences.get(o, 0) < n_epochs}
                            | set(live()))
                if len(possible) >= target:
                    return
                w = pop_window(require_full=False)
                if not w:
                    return
                self._sda_step(w)

        while True:
            pause = self._check_pause()
            if pause is not None:
                while True:   # drain everything buffered before PAUSE
                    w = pop_window(require_full=False)
                    if not w:
                        break
                    self._sda_step(w)
                self.log.info("[<<<] PAUSE")
                return pause
            raw = self.bus.get(in_q, timeout=0.001)
            if raw is None:
                if strict:
                    continue   # hard barrier: block until traffic,
                               # an epoch fence, or PAUSE
                # the window is a BARRIER in steady state, but a
                # starved barrier must not deadlock stage-1's gradient
                # wait — flush a partial window after a real idle spell
                # and adapt the barrier down to what is actually alive
                now = time.monotonic()
                idle_since = idle_since if idle_since is not None else now
                if now - idle_since >= idle_flush_s:
                    w = pop_window(require_full=False)
                    if w:
                        target = max(1, len(w))
                        self._sda_step(w)
                continue
            act = self._decode(raw, in_q)
            if act is None or act.round_idx != self.fence:
                continue   # corrupt, or from a dropped round: discard
            if isinstance(act, EpochEnd):
                key = (act.client_id, act.epoch)
                fence_copies[key] = fence_copies.get(key, 0) + 1
                if fence_copies[key] == quorum:
                    fences[act.client_id] = fences.get(act.client_id,
                                                       0) + 1
                if strict:
                    # full windows buffered at fence time must pop as
                    # WINDOWS, not wait to be drained as dead-barrier
                    # partials — keeps the code safe even if the
                    # arrival-time pop policy changes (ADVICE r4); loop
                    # until dry so a backlog can't strand windows
                    while True:
                        w = pop_window(require_full=True)
                        if not w:
                            break
                        self._sda_step(w)
                    drain_dead_barrier()
                continue
            # reset the idle clock only for CURRENT-round traffic — a
            # stream of stale activations must not starve the tail flush
            idle_since = None
            # window identity is the ROOT origin (trace[0], the stage-1
            # feeder = the DCSL "device"), not the immediate sender: in
            # a >2-stage plan trace[-1] is a middle device and every
            # batch would share it, so a distinct-origin window could
            # never widen past the middle-stage client count.  Gradient
            # routing below still uses trace[-1] (hop-by-hop return).
            pending.setdefault(act.trace[0], []).append(act)
            self.gauges.set("queue_depth",
                            sum(len(q) for q in pending.values()))
            n_live = len(live())
            if n_live > target:
                target = min(max(1, self.sda_size), n_live)
            w = pop_window(require_full=True)
            if w:
                self._sda_step(w)
            elif strict:
                # a batch buffered behind a dead barrier (every other
                # feeder fully fenced) must not wait for a fence that
                # already happened
                drain_dead_barrier()

    def _sda_step(self, window: list[Activation]):
        r = self.runner
        sizes = [len(a.labels) for a in window]
        sp = self.tracer.start("sda_step", always=False,
                               round=self.round_idx,
                               window=len(window))
        t_sp = time.perf_counter()
        # boundary payloads may be pytrees (mask-carrying models):
        # concatenate per leaf along the batch axis, split grads back
        x = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs),
            *[_from_wire_tree(a.data) for a in window])
        labels = jnp.concatenate(
            [jnp.asarray(a.labels, jnp.int32) for a in window])
        loss, gt, gx, self.stats = r.last_step(
            self.frozen, self.trainable, self.stats, x, labels,
            r.next_rng())
        # NaN sentinel (src/train/VGG16.py:169), folded on DEVICE and
        # synced once per round in _send_update (slcheck JX001)
        self._ok_dev = jnp.logical_and(self._ok_dev,
                                       jnp.isfinite(loss))
        self.trainable, self.opt_state = r.apply_update(
            self.trainable, self.opt_state, gt)
        sp.end()
        self.hists.observe("step", time.perf_counter() - t_sp)
        self.perf.note_step(t_sp, (loss, self.trainable),
                            n=int(sum(sizes)))
        self.num_samples += int(sum(sizes))
        grad_codec = self.codecs.get("gradient")
        if grad_codec is None:
            # plain wire: one whole-window device cast + host copy,
            # sliced after.  With a codec the dense window never
            # crosses to host — only the per-part prepared leaves do.
            gx = _cast_for_wire(gx, self._dev_cast)
            _start_host_copy(gx)
        off = 0
        for act, n in zip(window, sizes):
            # slice the raw cotangent, THEN wire-encode the part:
            # quantized/sparse wrapper leaves don't slice, per-part
            # quantization scales are tighter than one window-wide
            # scale, and the EF residual must be per ORIGIN stream
            gx_part = jax.tree_util.tree_map(
                lambda a, off=off, n=n: a[off:off + n], gx)
            off += n
            origin = act.trace[-1]
            grad_out_q = gradient_queue(self.stage - 1, origin)
            if grad_codec is not None:
                wire_part = self._wire_out(gx_part, "gradient",
                                           grad_out_q)
                _start_host_copy(wire_part)
            else:
                wire_part = gx_part   # already cast + copying above
            self._publish_parts(
                grad_out_q,
                lambda ctx, wp=wire_part, act=act, fence=self.fence:
                    encode_parts(Gradient(
                        data_id=act.data_id,
                        data=self._wire_host(wp, "gradient"),
                        trace=list(act.trace)[:-1], round_idx=fence),
                        self._chunk_bytes, ctx=ctx),
                kind="Gradient")


def main(argv=None):
    from split_learning_tpu.platform import (
        apply_compile_cache, apply_platform_env,
    )
    apply_platform_env()
    apply_compile_cache()
    ap = argparse.ArgumentParser(
        description="Split-learning protocol client (reference client.py "
                    "parity).")
    ap.add_argument("--config", default="config.yaml")
    ap.add_argument("--layer_id", type=int, required=True,
                    help="1-based stage index")
    ap.add_argument("--client_id", default=None)
    ap.add_argument("--cluster", type=int, default=None)
    ap.add_argument("--profile", default=None,
                    help="path to profiling.json (optional)")
    args = ap.parse_args(argv)
    cfg = from_yaml(args.config)
    profile = None
    if args.profile:
        import json
        with open(args.profile) as f:
            profile = json.load(f)
    client_id = args.client_id or f"client_{args.layer_id}_{uuid.uuid4().hex[:6]}"
    blackbox.install(cfg, client_id, role="client")
    client = ProtocolClient(cfg, client_id, args.layer_id,
                            cluster=args.cluster, profile=profile)
    client.run()


if __name__ == "__main__":
    main()
