"""Expert parallelism: Mixture-of-Experts routing over an ``expert`` axis.

The reference has no expert parallelism (SURVEY.md §2.2 marks EP absent)
— this is a fresh TPU-native extension completing the parallelism
surface (dp/pp/tp/sp/ep).  Design follows the GSPMD recipe rather than
hand-written collectives:

* the MoE layer computes dense ``dispatch``/``combine`` tensors
  (Switch/GShard-style top-k routing with a static per-expert capacity,
  so every shape is known to XLA — no dynamic gather/scatter);
* expert parameters carry a leading ``num_experts`` dim (``nn.vmap``
  over an FFN) and are sharded ``P("expert", ...)``;
* tokens ride the data axis; the two routing einsums
  ``tec,th->ech`` / ``tec,ech->th`` then force XLA to insert the
  expert-parallel all-to-alls on its own — the same collective an
  NCCL MoE implementation would issue by hand, but fused and
  overlapped by the compiler.

Routing math: softmax router in fp32, top-k experts per token with
renormalized gate weights, tokens over capacity dropped (their combine
weight is zero, so they pass through the residual unchanged — standard
Switch semantics).  The load-balance auxiliary loss is sown into the
``intermediates`` collection; :func:`moe_aux_loss` or the bundled
train step adds it to the objective.

:class:`HeldMoEMLP` is the other layer: no capacity and no drops (pairs
sorted by expert, grouped matrix products), for one chip's share of the
experts of a router that picks among all of them.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def topk_dispatch(probs: jnp.ndarray, k: int, capacity: int):
    """Top-k routing tensors from router probabilities.

    Args:
      probs: (T, E) fp32 router probabilities (rows sum to 1).
      k: experts per token.
      capacity: static per-expert token budget C.

    Returns ``(combine, dispatch, aux)``: combine (T, E, C) fp32 gate
    weights (renormalized over the top-k, zero for dropped tokens),
    dispatch (T, E, C) {0,1} routing mask, and the Switch load-balance
    auxiliary loss ``E * Σ_e f_e · P_e`` over first-choice assignments.
    """
    t, e = probs.shape
    if k > e:
        raise ValueError(
            f"top-k k={k} exceeds num_experts={e}: argmax over the "
            "masked-out remainder would re-select expert 0 and "
            "double-count its gate weight")
    remaining = probs
    onehots, gates = [], []
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=1)
        onehot = jax.nn.one_hot(idx, e, dtype=probs.dtype)
        onehots.append(onehot)
        gates.append(jnp.sum(probs * onehot, axis=1))
        remaining = remaining * (1.0 - onehot)

    # renormalize gate weights over the chosen k (Mixtral convention)
    denom = functools.reduce(jnp.add, gates)
    gates = [g / jnp.maximum(denom, 1e-9) for g in gates]

    combine = jnp.zeros((t, e, capacity), probs.dtype)
    dispatch = jnp.zeros((t, e, capacity), probs.dtype)
    prev_counts = jnp.zeros((e,), probs.dtype)
    for onehot, gate in zip(onehots, gates):
        # position of each token within its expert's buffer, counting
        # earlier routing rounds (priority: round 0 fills first)
        pos_all = jnp.cumsum(onehot, axis=0) - 1.0 + prev_counts[None, :]
        pos = jnp.sum(pos_all * onehot, axis=1)
        keep = (pos < capacity).astype(probs.dtype)
        prev_counts = prev_counts + jnp.sum(onehot, axis=0)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                              dtype=probs.dtype)
        d = onehot[:, :, None] * slot[:, None, :] * keep[:, None, None]
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]

    # load balance: fraction routed (first choice) x mean router prob
    frac = jnp.mean(onehots[0], axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_prob)
    return combine, dispatch, aux


class ExpertFFN(nn.Module):
    """One expert: SwiGLU FFN (LLaMA geometry)."""
    hidden_size: int
    intermediate_size: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        gate = nn.silu(dense(self.intermediate_size, name="gate_proj")(x))
        up = dense(self.intermediate_size, name="up_proj")(x)
        return dense(self.hidden_size, name="down_proj")(gate * up)


class MoEMLP(nn.Module):
    """Top-k mixture-of-experts FFN, drop-in for a dense SwiGLU MLP.

    Input/output (B, S, H).  ``capacity_factor`` scales the per-expert
    buffer ``C = ceil(k·T/E · factor)``; tokens over budget are dropped
    (combine weight 0 → they contribute nothing, the caller's residual
    carries them through).  The aux loss is sown under
    ``intermediates/aux_loss`` when that collection is mutable.

    Memory note: the dense dispatch/combine tensors are (T, E, C) with
    C ≈ k·T/E·factor, i.e. O(k·T²·factor) per MoE layer regardless of
    E.  Past a few thousand tokens a call, or where a dropped token is a
    different result, use :class:`HeldMoEMLP`: sorted dispatch, grouped
    products, no capacity and no drops.
    """
    hidden_size: int
    intermediate_size: int
    num_experts: int = 8
    k: int = 2
    capacity_factor: float = 1.5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, h = x.shape
        t = b * s
        xt = x.reshape(t, h)
        logits = nn.Dense(self.num_experts, use_bias=False,
                          dtype=jnp.float32, name="router")(
            xt.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        capacity = max(1, int(np.ceil(
            self.k * t / self.num_experts * self.capacity_factor)))
        combine, dispatch, aux = topk_dispatch(probs, self.k, capacity)
        self.sow("intermediates", "aux_loss", aux)

        # (T,E,C),(T,H) -> (E,C,H): the expert-parallel scatter all-to-all
        expert_in = jnp.einsum("tec,th->ech",
                               dispatch.astype(self.dtype), xt)
        experts = nn.vmap(
            ExpertFFN,
            in_axes=0, out_axes=0,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            axis_size=self.num_experts,
            metadata_params={nn.PARTITION_NAME: "expert"},
        )(hidden_size=self.hidden_size,
          intermediate_size=self.intermediate_size,
          dtype=self.dtype, name="experts")
        expert_out = experts(expert_in)            # (E, C, H)
        # (T,E,C),(E,C,H) -> (T,H): the gather all-to-all
        out = jnp.einsum("tec,ech->th", combine.astype(self.dtype),
                         expert_out)
        return out.reshape(b, s, h)


# --------------------------------------------------------------------------
# drop-free held-share layer: sorted dispatch, grouped products
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def take_rows(src, idx, back_idx, back_ok, fan: int):
    """``src[idx]`` whose backward pass is a gather too.  ``idx`` picks
    every row of ``src`` at most ``fan`` times, and ``back_idx[r * fan +
    j]`` (valid where ``back_ok``) is where row ``r``'s ``j``-th copy
    went: so ``d_src[r] = sum_j d_out[back_idx[r, j]]``, with no
    scatter-add."""
    return src[idx]


def _take_rows_fwd(src, idx, back_idx, back_ok, fan):
    return src[idx], (back_idx, back_ok)


def _take_rows_bwd(fan, res, g):
    back_idx, back_ok = res
    back = jnp.where(back_ok[:, None], g[back_idx], 0)
    d_src = back.reshape(-1, fan, g.shape[-1]).sum(axis=1) if fan > 1 \
        else back
    return d_src.astype(g.dtype), None, None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def route_held(probs: jnp.ndarray, k: int, first: int, n_held: int):
    """Top-``k`` routing over ALL experts, and the plan for the experts
    ``first .. first + n_held - 1`` that live here.

    Returns ``(weights, plan, aux)``: ``weights`` (T, k) are the chosen
    experts' probabilities renormalized over the k chosen; ``aux`` is the
    load-balancing term ``E * sum_e f_e P_e`` over all experts (``f_e``
    the pairs routed to ``e`` over T, ``P_e`` the mean probability);
    ``plan`` holds the (token, choice) pairs sorted by held expert, the
    pairs of absent experts last:

    * ``row_pair`` (R,): the pair ``t * k + j`` in buffer row ``r``, for
      ``R = min(k, n_held) * T`` rows — a token picks distinct experts,
      so no more of its pairs can be held: nothing is ever dropped;
    * ``pair_row`` (T * k,), ``pair_ok``: the row a pair sits in, and
      whether it is a held pair (absent experts' pairs are not);
    * ``group_sizes`` (n_held,): pairs of each held expert, in order.
    """
    t, e = probs.shape
    top_p, top_i = jax.lax.top_k(probs, k)
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    counts = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    aux = e * jnp.sum(counts / t * jnp.mean(probs, axis=0))

    local = top_i.reshape(-1) - first
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rows = min(k, n_held) * t
    pair_row = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    plan = {"row_pair": order[:rows],
            "pair_row": jnp.minimum(pair_row, rows - 1),
            "pair_ok": held,
            "group_sizes": counts[first:first + n_held].astype(jnp.int32)}
    return weights, plan, aux


class _ExpertKernel(nn.Module):
    """``(n, d_in, d_out)``: one matrix an expert, the experts leading."""
    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", nn.initializers.lecun_normal(batch_axis=(0,)),
            self.shape)


class _ExpertBank(nn.Module):
    """The held experts' SwiGLU over rows sorted by expert: three grouped
    matrix products (``jax.lax.ragged_dot``) over ``group_sizes``; rows
    past their sum belong to no expert and come out as they may (the
    caller masks them)."""
    n: int
    hidden_size: int
    intermediate_size: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, rows, group_sizes):
        n, d, f = self.n, self.hidden_size, self.intermediate_size
        gate, up, down = (
            _ExpertKernel(shape, name=name)().astype(self.dtype)
            for name, shape in (("gate_proj", (n, d, f)),
                                ("up_proj", (n, d, f)),
                                ("down_proj", (n, f, d))))
        precision = (jax.lax.Precision.HIGHEST
                     if self.dtype == jnp.float32 else None)
        dot = functools.partial(jax.lax.ragged_dot,
                                group_sizes=group_sizes,
                                precision=precision)
        return dot(nn.silu(dot(rows, gate)) * dot(rows, up), down)


class HeldMoEMLP(nn.Module):
    """Top-k mixture-of-experts FFN that computes the part of the result
    its OWN experts give, and drops nothing.

    The router is ``num_experts`` wide and picks ``k`` of ALL experts,
    their weights renormalized over the k chosen.  Of the (token, expert)
    pairs, those whose expert is one of ``held`` (consecutive ids; ``None``:
    all) are sorted by expert, their rows gathered, run through the
    experts' SwiGLU as grouped matrix products, scaled by their weights
    and summed back into their tokens.  What the absent experts would add
    is left out: on a chip of an expert-parallel job that partial sum is
    what the exchange would complete; the shares of all chips add up to
    the whole layer (``tests/test_moe_held.py``).

    There is no capacity.  The row buffer has the proven worst case,
    ``min(k, len(held)) * T`` rows (every token picking every held
    expert), and the grouped products do the work of the pairs there are;
    the gathers into and out of the buffer move all of it.  Backward
    passes are gathers as well (:func:`take_rows`).

    Sown: ``aux_loss`` under ``intermediates`` (load balance over all
    experts, from the router alone), and two counters that a step of
    ``parallel/pipeline.py`` folds and hands back (its ``COUNTER_FOLDS``):
    ``moe_pairs_held`` under ``counters_sum`` (the pairs computed here: an
    operator reads from it what share of the routed work this chip holds,
    and whether a live router drifts towards the held experts) and
    ``moe_load_max_over_mean`` under ``counters_max`` (the fullest held
    expert's pairs over the mean).  Nothing can be dropped, so there is no
    counter of dropped pairs.
    Scopes: ``moe_route`` (router, top-k, sort, gathers, weighted sum) and
    ``moe_experts`` (the grouped products).
    """
    hidden_size: int
    intermediate_size: int
    num_experts: int = 8
    k: int = 2
    held: tuple | None = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, h = x.shape
        t, k = b * s, self.k
        held = tuple(self.held) if self.held is not None \
            else tuple(range(self.num_experts))
        first, n_held = held[0], len(held)
        if held != tuple(range(first, first + n_held)) \
                or first + n_held > self.num_experts:
            raise ValueError(f"held experts must be consecutive ids "
                             f"under {self.num_experts}: {held}")
        if k > self.num_experts:
            raise ValueError(f"top-k k={k} exceeds num_experts="
                             f"{self.num_experts}")
        xt = x.reshape(t, h)
        with jax.named_scope("moe_route"):
            logits = nn.Dense(self.num_experts, use_bias=False,
                              dtype=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST,
                              name="router")(xt.astype(jnp.float32))
            weights, plan, aux = route_held(
                jax.nn.softmax(logits, axis=-1), k, first, n_held)
            row_pair, pair_row = plan["row_pair"], plan["pair_row"]
            pair_ok, sizes = plan["pair_ok"], plan["group_sizes"]
            rows = take_rows(xt, row_pair // k, pair_row, pair_ok, k)
        self.sow("intermediates", "aux_loss", aux)
        pairs = jnp.sum(sizes).astype(jnp.float32)
        self.sow("counters_sum", "moe_pairs_held", pairs)
        self.sow("counters_max", "moe_load_max_over_mean",
                 jnp.max(sizes) * n_held / jnp.maximum(pairs, 1.0))
        with jax.named_scope("moe_experts"):
            y = _ExpertBank(n_held, h, self.intermediate_size, self.dtype,
                            name="experts")(rows, sizes)
        with jax.named_scope("moe_route"):
            back = take_rows(y, pair_row, row_pair,
                             jnp.ones(row_pair.shape, bool), 1)
            # BOTH masks are needed: an absent expert's pair reads a
            # buffer row past the groups, which the grouped product
            # leaves as it may (NaN on the chip).  The outer mask keeps
            # that out of the result; the mask on the weight keeps
            # ``0 * NaN`` out of the router's gradient.
            scale = jnp.where(pair_ok, weights.reshape(-1), 0.0)
            out = jnp.where(pair_ok[:, None],
                            back * scale[:, None].astype(back.dtype), 0)
            out = out.reshape(t, k, h).sum(axis=1)
        return out.reshape(b, s, h).astype(x.dtype)


# --------------------------------------------------------------------------
# sharding rules
# --------------------------------------------------------------------------

def _names(path) -> list:
    return [str(p.key) if hasattr(p, "key") else str(p) for p in path]


def ep_spec(path, leaf, axis: str = "expert") -> P:
    """PartitionSpec for one param leaf under expert parallelism: leaves
    under an ``experts`` vmap scope shard their leading (expert) dim;
    everything else replicates.  Compose with :func:`tp_spec` for
    EP x TP by passing its result for non-expert leaves."""
    ndim = np.ndim(leaf)
    if "experts" in _names(path) and ndim >= 1:
        return P(axis, *([None] * (ndim - 1)))
    return P()


def ep_shardings(params, mesh: Mesh, axis: str = "expert"):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, ep_spec(path, leaf, axis)),
        params)


def shard_params_ep(params, mesh: Mesh, axis: str = "expert"):
    """Place a param tree onto the mesh under the EP rules."""
    return jax.tree_util.tree_map(
        jax.device_put, params, ep_shardings(params, mesh, axis))


def moe_aux_loss(intermediates: dict) -> jnp.ndarray:
    """Sum the sown ``aux_loss`` entries in an intermediates collection.

    Only leaves whose path contains the key ``aux_loss`` are summed —
    other sown diagnostics (router entropy, attention stats, ...) must
    never silently become a weighted loss term.
    """
    total = jnp.zeros(())
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        if any(getattr(p, "key", None) == "aux_loss" for p in path):
            total = total + jnp.sum(leaf)
    return total


def make_ep_train_step(model, optimizer, mesh: Mesh,
                       axis: str = "expert", dp_axis: str | None = None,
                       aux_weight: float = 0.01):
    """Jitted EP(+DP) train step for a full (unsplit) MoE model.

    Expert params stay sharded over ``axis``; the batch shards over
    ``dp_axis``.  XLA derives the dispatch/gather all-to-alls from the
    routing einsums.  The sown load-balance losses are added to the CE
    objective with weight ``aux_weight``.
    """
    import optax

    data_sh = NamedSharding(mesh, P(dp_axis) if dp_axis else P())

    def step(params, opt_state, x, labels, rng):
        def loss_fn(p):
            out, mut = model.apply(
                {"params": p}, x, train=True, rngs={"dropout": rng},
                mutable=["intermediates"])
            ce = optax.softmax_cross_entropy_with_integer_labels(
                out.astype(jnp.float32), labels).mean()
            return ce + aux_weight * moe_aux_loss(
                mut.get("intermediates", {})), ce
        (_, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, ce

    def place(params, opt_state, x, labels, rng):
        return step(params, opt_state,
                    jax.lax.with_sharding_constraint(x, data_sh),
                    jax.lax.with_sharding_constraint(labels, data_sh),
                    rng)

    return jax.jit(place, donate_argnums=(0, 1))
