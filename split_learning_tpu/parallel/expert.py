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

Routing math (:class:`MoEMLP`): softmax router in fp32, top-k experts per
token with renormalized gate weights, tokens over capacity dropped (their combine
weight is zero, so they pass through the residual unchanged — standard
Switch semantics).  The load-balance auxiliary loss is sown into the
``intermediates`` collection; :func:`moe_aux_loss` or the bundled
train step adds it to the objective.

:class:`HeldMoEMLP` is the other layer: no capacity and no drops (pairs
sorted by expert, grouped matrix products: the Pallas kernels of
``ops/grouped_matmul.py``, :func:`grouped_dot`), for one chip's share of
the experts of a router that picks among all of them.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from split_learning_tpu.ops.grouped_matmul import grouped_dot, live_rows


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


#: A feed-forward's FORM by name: the names of its kernels in the order
#: they are applied (the last one maps back to the state), and what stands
#: between the first products and the last, given ``dot(x, kernel)`` and the
#: kernels before the last.  A SwiGLU has three matrices (LLaMA geometry),
#: a ``relu2`` (the published ``mlp_hidden_act``: ``relu(x W_up)^2 W_down``)
#: two and no gate.
FORMS = {
    "swiglu": (("gate_proj", "up_proj", "down_proj"),
               lambda dot, x, gate, up: nn.silu(dot(x, gate)) * dot(x, up)),
    "relu2": (("up_proj", "down_proj"),
              lambda dot, x, up: jnp.square(nn.relu(dot(x, up)))),
}


def feed_forward(x, intermediate_size: int, dtype=jnp.float32,
                 form: str = "swiglu"):
    """The feed-forward of the ``form`` named (:data:`FORMS`) over the last
    axis of ``x``.  Its kernels are created in the CALLER's scope: a dense
    decoder block's own (``models/decoder.py``), or one expert's
    (:class:`ExpertFFN`)."""
    names, between = FORMS[form]
    dense = functools.partial(nn.Dense, use_bias=False, dtype=dtype)
    hidden = between(lambda x, name: dense(intermediate_size, name=name)(x),
                     x, *names[:-1])
    return dense(x.shape[-1], name=names[-1])(hidden)


class ExpertFFN(nn.Module):
    """One feed-forward of the ``form`` named (:data:`FORMS`) as a module:
    an expert of :class:`MoEMLP` (what ``nn.vmap`` needs), or the shared
    expert beside a held share."""
    intermediate_size: int
    dtype: jnp.dtype = jnp.float32
    form: str = "swiglu"

    @nn.compact
    def __call__(self, x):
        return feed_forward(x, self.intermediate_size, self.dtype, self.form)


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
        )(intermediate_size=self.intermediate_size, dtype=self.dtype,
          name="experts")
        expert_out = experts(expert_in)            # (E, C, H)
        # (T,E,C),(E,C,H) -> (T,H): the gather all-to-all
        out = jnp.einsum("tec,ech->th", combine.astype(self.dtype),
                         expert_out)
        return out.reshape(b, s, h)


# --------------------------------------------------------------------------
# drop-free held-share layer: sorted dispatch, grouped products
# --------------------------------------------------------------------------

#: How many times an even router's share of the pairs the common pass has
#: rows for.  The held pairs of the token cell are 1.000-1.004 of that
#: share, a router drawn whole gives 0.92-1.07, a router drifting towards
#: the held experts at 3e-4 reached 1.44 (PERF.md section 6): twice covers
#: all of them with room, and costs a quarter of the worst case where a
#: token picks 8 of 64.  What lies beyond it is computed all the same, by
#: the overflow pass.
COMMON_SHARE = 2


def common_rows(t: int, k: int, n_held: int, e: int) -> int:
    """Rows ``C`` of the common pass for ``t`` tokens picking ``k`` of ``e``
    experts, ``n_held`` of them here: ``COMMON_SHARE`` times what an even
    router sends, at most the worst case ``min(k, n_held) * t`` (every
    token picking every held expert: a token picks distinct experts, so
    no more of its pairs can be held).  From shapes alone."""
    return min(min(k, n_held) * t, -(-COMMON_SHARE * t * k * n_held // e))


#: A router's scoring function by its published name (``scoring_func``):
#: the scores of the logits; whether the load-balancing term first divides
#: them by their sum over the experts (a softmax's already sum to one); and
#: what guards the division by the chosen scores' sum (DeepSeek-V3's 1e-20:
#: chosen sigmoids may all underflow, chosen softmax shares cannot).
SCORING = {"softmax": (functools.partial(jax.nn.softmax, axis=-1), False, 0.0),
           "sigmoid": (jax.nn.sigmoid, True, 1e-20)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _chosen(scores, top_s, top_i, e: int):
    """``top_s`` (T, k), the ``scores`` (T, E) at ``top_i``, passed through
    as they were taken.  The gradient into ``scores`` is a one-hot select
    over the ``e`` experts, where a gather's transpose would scatter-add: a
    token's choices are distinct, so each ``(t, e)`` takes at most one
    term and the sum is exact."""
    return top_s


def _chosen_fwd(scores, top_s, top_i, e):
    return top_s, top_i


def _chosen_bwd(e, top_i, g):
    hit = top_i[:, :, None] == jnp.arange(e, dtype=top_i.dtype)
    return jnp.sum(jnp.where(hit, g[:, :, None], 0), axis=1), None, None


_chosen.defvjp(_chosen_fwd, _chosen_bwd)


def route_held(logits: jnp.ndarray, k: int, first: int, n_held: int,
               scoring: str = "softmax", bias=None, factor: float = 1.0):
    """Top-``k`` routing over ALL experts, and the plan for the experts
    ``first .. first + n_held - 1`` that live here.

    ``scoring`` names the function that turns the router's ``logits``
    (T, E) into scores (:data:`SCORING`).  The ``k`` experts of a token are
    those with the largest ``score + bias`` (``bias`` (E,), a buffer that
    no gradient reaches; ``None``: the scores alone); their weights are
    the scores WITHOUT the bias, renormalized over the k chosen, times
    ``factor``.

    Returns ``(weights, plan, aux)``: ``weights`` (T, k) as above; ``aux``
    is the load-balancing term ``E * sum_e f_e P_e`` over all experts
    (``f_e`` the pairs routed to ``e`` over T, ``P_e`` the mean of the
    scores, each token's divided by their sum over the experts);
    ``plan`` holds the (token, choice) pairs sorted by held expert, the
    pairs of absent experts last.  Sorted row ``r`` is where a pair's
    token is multiplied; no buffer has all the rows: a pass
    (:func:`pass_plan`) takes the range of them it was given.

    * ``order`` (T * k,): the pair ``t * k + j`` of sorted row ``r``;
    * ``pair_row`` (T * k,), ``held``: the sorted row of a pair, and
      whether its expert is held (then its row is under the sum of
      ``group_sizes``, itself at most ``min(k, n_held) * T``);
    * ``group_sizes`` (n_held,): pairs of each held expert, in order.

    Neither pass holds a scatter: the counts are a compare-and-sum, a
    pair's sorted row is its group's start plus its rank in the group
    (what the stable sort gives), and the chosen scores' gradient is a
    one-hot select (:func:`_chosen`).  On the chip's host every scatter,
    gather, sort or top-k costs the compiler a second or more.
    """
    t, e = logits.shape
    score_fn, divide, guard = SCORING[scoring]
    scores = score_fn(logits)
    fixed = jax.lax.stop_gradient(scores)
    if bias is None:
        top_s, top_i = jax.lax.top_k(fixed, k)
    else:
        _, top_i = jax.lax.top_k(fixed + jax.lax.stop_gradient(bias), k)
        top_s = jnp.take_along_axis(fixed, top_i, axis=-1)
    top_s = _chosen(scores, top_s, top_i, e)
    chosen = jnp.sum(top_s, axis=-1, keepdims=True)
    weights = top_s / (chosen + guard if guard else chosen)
    if factor != 1.0:
        weights = weights * factor
    # exact in float32: at most T * k ones an expert, under 2 ** 24
    counts = jnp.sum(top_i[:, :, None] == jnp.arange(e, dtype=top_i.dtype),
                     axis=(0, 1), dtype=jnp.float32)
    shares = scores / jnp.sum(scores, axis=-1, keepdims=True) if divide \
        else scores
    aux = e * jnp.sum(counts / t * jnp.mean(shares, axis=0))

    local = top_i.reshape(-1) - first
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = counts[first:first + n_held].astype(jnp.int32)
    # group g (the absent experts last) starts where the groups before it
    # end; a pair's rank in its group is the group's pairs before it.  The
    # groups lead, so the pairs lie along the lanes
    starts = jnp.cumsum(jnp.concatenate([jnp.zeros((1,), jnp.int32), sizes]))
    in_group = jnp.arange(n_held + 1, dtype=jnp.int32)[:, None] == key
    rank = jnp.cumsum(in_group, axis=1, dtype=jnp.int32) - 1
    pair_row = jnp.sum(jnp.where(in_group, starts[:, None] + rank, 0),
                       axis=0)
    plan = {"order": order, "pair_row": pair_row, "held": held,
            "group_sizes": sizes}
    return weights, plan, aux


def _clipped_sizes(sizes, lo: int, hi: int):
    """The groups' sizes clipped to the sorted rows ``[lo, hi)``: a group
    that straddles ``lo`` or ``hi`` gives each side its part."""
    ends = jnp.cumsum(sizes)
    return jnp.clip(ends, lo, hi) - jnp.clip(ends - sizes, lo, hi)


def pass_plan(plan: dict, lo: int, hi: int, t: int, k: int) -> dict:
    """What one pass over the sorted rows ``[lo, hi)`` needs, all of it
    integers of ``hi - lo`` rows or of ``t * k`` pairs:

    * ``sizes``: the experts' group sizes clipped to the range
      (:func:`_clipped_sizes`);
    * ``token``, ``pair``, ``live``: of each row, the token and the pair
      it holds, and whether it lies under the groups' sum;
    * ``by_token``: the rows in (token, choice) order, the live ones
      first (so ``live`` says which are live in that order too);
      ``joins[i]``: whether the row ``2 ** i`` places earlier in that
      order belongs to the same token;
    * ``last``, ``has`` (t,): where in that order a token's run of rows
      ends, and whether it has one;
    * ``pair_at``, ``inside`` (t * k,): the row of a pair, counted from
      ``lo``, and whether the pair is one of this pass's.
    """
    n = hi - lo
    sizes, pair_row = plan["group_sizes"], plan["pair_row"]
    ends = jnp.cumsum(sizes)
    pair = plan["order"][lo:hi]
    live = jnp.arange(lo, hi) < ends[-1]
    # a token's held pairs are neighbours once the live rows are sorted by
    # pair: at most min(k, n_held) of them, so that many rows back at most
    key, by_token = jax.lax.sort_key_val(
        jnp.where(live, pair, t * k), jnp.arange(n, dtype=jnp.int32))
    token_sorted = key // k
    joins, step = [], 1
    while step < min(k, sizes.shape[0], n):
        joins.append(jnp.concatenate([
            jnp.zeros((step,), bool),
            token_sorted[step:] == token_sorted[:-step]]))
        step *= 2
    inside = plan["held"] & (pair_row >= lo) & (pair_row < hi)
    count = jnp.sum(inside.reshape(t, k), axis=1, dtype=jnp.int32)
    return {"sizes": _clipped_sizes(sizes, lo, hi),
            "token": pair // k, "pair": pair, "live": live,
            "by_token": by_token, "joins": tuple(joins),
            "last": jnp.maximum(jnp.cumsum(count) - 1, 0), "has": count > 0,
            "pair_at": jnp.clip(pair_row - lo, 0, n - 1), "inside": inside}


def _fold(vals, scale, p):
    """Rows to tokens: ``out[t] = sum of scale[r] * vals[r]`` over the live
    rows of token ``t``, at the cost of the rows there are and of ``T``:
    the rows are gathered into token order, each run of a token's rows is
    summed by ``len(joins)`` masked shifted adds, and one gather of ``T``
    rows reads the runs' ends.  The adds are made in the rows' type: in
    bfloat16 a token of ``m`` rows takes up to ``ceil(log2 m)`` roundings
    more than one sum would (``tests/test_moe_held.py`` holds the fold and
    the layer to that; the sums in float32 cost more than they bought,
    PERF.md section 6).  Rows that are not live may hold anything (NaN on
    the chip): they are masked, value and scale alike."""
    z = vals[p["by_token"]]
    if scale is not None:
        z = z * scale[p["by_token"]][:, None].astype(z.dtype)
    z = jnp.where(p["live"][:, None], z, 0)
    for i, same in enumerate(p["joins"]):
        earlier = jnp.concatenate(
            [jnp.zeros((2 ** i, z.shape[1]), z.dtype), z[:-2 ** i]])
        z = z + jnp.where(same[:, None], earlier, 0)
    return jnp.where(p["has"][:, None], z[p["last"]], 0)


@jax.custom_vjp
def spread_rows(src, p):
    """Tokens to rows, ``src[p["token"]]``; its backward pass is the fold
    of the rows' cotangents into their tokens (:func:`_fold`): a gather of
    as many rows again, no scatter-add."""
    return src[p["token"]]


def _spread_rows_fwd(src, p):
    return src[p["token"]], p


def _spread_rows_bwd(p, g):
    return _fold(g, None, p).astype(g.dtype), None


spread_rows.defvjp(_spread_rows_fwd, _spread_rows_bwd)


@jax.custom_vjp
def fold_rows(vals, weights, p):
    """Rows to tokens: every live row scaled by its pair's weight
    (``weights`` (T * k,)) and summed into its token (:func:`_fold`).  The
    backward pass hands a row ``weight * d_out[token]`` and a pair the
    product of its row with ``d_out[token]``: gathers of rows and of
    scalars."""
    return _fold_rows_fwd(vals, weights, p)[0]


def _fold_rows_fwd(vals, weights, p):
    scale = jnp.where(p["live"], weights[p["pair"]], 0.0)
    return _fold(vals, scale, p), (vals, scale, p)


def _fold_rows_bwd(res, g):
    vals, scale, p = res
    live = p["live"]
    back = g[p["token"]]
    # BOTH masks are needed: a row past the groups holds what the grouped
    # product left there (NaN on the chip), and ``0 * NaN`` would reach
    # the router's gradient through the weight
    d_vals = jnp.where(live[:, None],
                       back * scale[:, None].astype(back.dtype), 0)
    d_scale = jnp.where(live, jnp.sum(
        vals.astype(jnp.float32) * back.astype(jnp.float32), axis=1), 0.0)
    d_weights = jnp.where(p["inside"], d_scale[p["pair_at"]], 0.0)
    return d_vals.astype(vals.dtype), d_weights.astype(scale.dtype), None


fold_rows.defvjp(_fold_rows_fwd, _fold_rows_bwd)


def _pass(x, weights, kernels, plan, lo: int, hi: int,
          form: str = "swiglu"):
    """The held experts' part of the result from the sorted rows
    ``[lo, hi)``: gather the rows' tokens, the experts' ``form``
    (:data:`FORMS`: a SwiGLU's three grouped products, a ``relu2``'s two;
    :func:`grouped_dot`: float32 operands at ``Precision.HIGHEST``) over
    the group sizes clipped to the range, fold the weighted results into
    their tokens."""
    t = x.shape[0]
    with jax.named_scope("moe_route"):
        p = pass_plan(plan, lo, hi, t, weights.shape[0] // t)
        rows = spread_rows(x, p)
    with jax.named_scope("moe_experts"):
        def dot(lhs, rhs):
            return grouped_dot(lhs, rhs, p["sizes"])

        y = dot(FORMS[form][1](dot, rows, *kernels[:-1]), kernels[-1])
    with jax.named_scope("moe_route"):
        return fold_rows(y, weights, p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def both_passes(x, weights, kernels, plan, c: int, worst: int,
                form: str = "swiglu"):
    """The common pass over the sorted rows ``[0, c)`` and, where the held
    pairs number more, the overflow pass over ``[c, worst)`` added to it,
    under one ``cond``: every pair is computed whatever the load.

    A pass that is skipped costs nothing of its rows' size, forward or
    backward: what the backward pass keeps of the overflow pass are its
    INPUTS (it is rare, so it is recomputed under the backward pass's own
    ``cond``), and the skipped branch hands the common pass's cotangents
    through as they are, where a differentiated ``cond`` would zero-fill
    every residual of the branch it did not take."""
    out = _pass(x, weights, kernels, plan, 0, c, form)
    return _add_overflow(out, x, weights, kernels, plan, c, worst, form)


def _add_overflow(out, x, weights, kernels, plan, c, worst, form):
    return jax.lax.cond(
        jnp.sum(plan["group_sizes"]) > c,
        lambda o: o + _pass(x, weights, kernels, plan, c, worst, form),
        lambda o: o, out)


def _both_passes_fwd(x, weights, kernels, plan, c, worst, form):
    out, pull = jax.vjp(
        lambda *a: _pass(*a, plan, 0, c, form), x, weights, kernels)
    out = _add_overflow(out, x, weights, kernels, plan, c, worst, form)
    return out, (pull, x, weights, kernels, plan)


def _both_passes_bwd(c, worst, form, res, g):
    pull, x, weights, kernels, plan = res

    def and_overflow(grads):
        _, pull_over = jax.vjp(
            lambda *a: _pass(*a, plan, c, worst, form), x, weights, kernels)
        return jax.tree_util.tree_map(jnp.add, grads, pull_over(g))

    grads = jax.lax.cond(jnp.sum(plan["group_sizes"]) > c, and_overflow,
                         lambda grads: grads, pull(g))
    return (*grads, None)


both_passes.defvjp(_both_passes_fwd, _both_passes_bwd)


class _ExpertKernel(nn.Module):
    """``(n, d_in, d_out)``: one matrix an expert, the experts leading."""
    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", nn.initializers.lecun_normal(batch_axis=(0,)),
            self.shape)


class _ExpertBank(nn.Module):
    """The held experts' kernels in the compute type, the experts leading,
    by the names and in the order of their ``form`` (:data:`FORMS`: a
    SwiGLU's ``(gate, up, down)``, a ``relu2``'s ``(up, down)``): what the
    grouped products of a pass (:func:`_pass`) multiply the rows by."""
    n: int
    hidden_size: int
    intermediate_size: int
    dtype: jnp.dtype = jnp.float32
    form: str = "swiglu"

    @nn.compact
    def __call__(self):
        n, d, f = self.n, self.hidden_size, self.intermediate_size
        names = FORMS[self.form][0]
        return tuple(
            _ExpertKernel((n, d, f) if name != names[-1] else (n, f, d),
                          name=name)().astype(self.dtype)
            for name in names)


class HeldMoEMLP(nn.Module):
    """Top-k mixture-of-experts FFN that computes the part of the result
    its OWN experts give, and drops nothing.

    The router is ``num_experts`` wide and picks ``k`` of ALL experts,
    their weights renormalized over the k chosen (:func:`route_held`:
    ``scoring`` names the scores' function, ``factor`` multiplies the
    weights; with ``score_bias`` the choice is by ``score + bias``, the
    weights by the score alone.  The bias, ``e_score_correction_bias``
    (``num_experts``,), is no parameter: it lives in ``batch_stats``, so no
    gradient and no weight decay reach it, and the step, FedAvg and the
    checkpoint carry it as they carry a BatchNorm's statistics; nothing
    here moves it).  Of the (token, expert)
    pairs, those whose expert is one of ``held`` (consecutive ids; ``None``:
    all) are sorted by expert, their rows gathered, run through the
    experts (``form``, :data:`FORMS`: a SwiGLU of three matrices or a
    ``relu2`` of two) as grouped matrix products, scaled by their weights
    and summed back into their tokens.  What the absent experts would add
    is left out: on a chip of an expert-parallel job that partial sum is
    what the exchange would complete; the shares of all chips add up to
    the whole layer (``tests/test_moe_held.py``).

    There is no capacity.  The row buffer, and every pass over rows, has
    ``C`` rows (:func:`common_rows`: twice what an even router sends here,
    from shapes alone), not the worst case ``min(k, len(held)) * T``: the
    common pass moves and multiplies the sorted rows ``[0, C)``.  Where a
    call's held pairs number more than ``C``, an overflow pass does the
    same for the rows ``[C, worst)`` and adds its part
    (:func:`both_passes`: one ``cond``, nothing of the rows' size where it
    is skipped); where ``C`` is the worst case (a share of half the
    experts or more) none is built.  The ways in and out are gathers in
    both directions (:func:`spread_rows`, :func:`fold_rows`).

    Sown: ``aux_loss`` under ``intermediates`` (load balance over all
    experts, from the router alone), and four counters that a step of
    ``parallel/pipeline.py`` folds and hands back (its ``COUNTER_FOLDS``):
    ``moe_pairs_held`` under ``counters_sum`` (the pairs computed here: an
    operator reads from it what share of the routed work this chip holds,
    and whether a live router drifts towards the held experts),
    ``moe_overflow_passes`` under ``counters_sum`` (1 where this call's
    pairs exceeded ``C`` and the overflow pass ran: a share whose calls
    mostly overflow pays for two passes), ``moe_gmm_rows`` under
    ``counters_sum`` (the rows the grouped products' tiles cover, both
    passes: over ``moe_pairs_held`` it says what the tiling adds at the
    groups' ends; 2 would mean a kernel walks the buffer) and
    ``moe_load_max_over_mean`` under ``counters_max`` (the fullest held
    expert's pairs over the mean).  Nothing can be dropped, so there is
    no counter of dropped pairs.
    Scopes: ``moe_route`` (router, top-k, sorts, gathers, folds) and
    ``moe_experts`` (the grouped products, :func:`grouped_dot`'s kernels
    ``slt_gmm*``, and the element-wise work between them), in both
    passes.
    """
    hidden_size: int
    intermediate_size: int
    num_experts: int = 8
    k: int = 2
    held: tuple | None = None
    scoring: str = "softmax"
    score_bias: bool = False
    factor: float = 1.0
    form: str = "swiglu"
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
            bias = self.variable(
                "batch_stats", "e_score_correction_bias", jnp.zeros,
                (self.num_experts,), jnp.float32).value \
                if self.score_bias else None
            weights, plan, aux = route_held(
                logits, k, first, n_held, self.scoring, bias, self.factor)
        sizes = plan["group_sizes"]
        worst = min(k, n_held) * t
        c = common_rows(t, k, n_held, self.num_experts)
        self.sow("intermediates", "aux_loss", aux)
        pairs = jnp.sum(sizes).astype(jnp.float32)
        self.sow("counters_sum", "moe_pairs_held", pairs)
        self.sow("counters_sum", "moe_overflow_passes",
                 (pairs > c).astype(jnp.float32))
        self.sow("counters_sum", "moe_gmm_rows", sum(
            live_rows(_clipped_sizes(sizes, lo, hi), hi - lo)
            for lo, hi in ((0, c), (c, worst)) if lo < hi
        ).astype(jnp.float32))
        self.sow("counters_max", "moe_load_max_over_mean",
                 jnp.max(sizes) * n_held / jnp.maximum(pairs, 1.0))
        # the kernels' casts to the compute type, and their transposes (the
        # kernels' gradients back to float32), are the experts' work
        with jax.named_scope("moe_experts"):
            kernels = _ExpertBank(n_held, h, self.intermediate_size,
                                  self.dtype, self.form, name="experts")()
        if c < worst:
            out = both_passes(xt, weights.reshape(-1), kernels, plan,
                              c, worst, self.form)
        else:
            out = _pass(xt, weights.reshape(-1), kernels, plan, 0, worst,
                        self.form)
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
