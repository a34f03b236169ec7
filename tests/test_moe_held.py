"""The drop-free held-share expert layer (``parallel/expert.py
HeldMoEMLP``): the shares of an expert-parallel job add up to the uncut
reference's layer, nothing is dropped however uneven the load (the rows
past the common pass's go through the overflow pass), no pass over rows
has the worst case's size, and the backward pass (gathers, no scatter-add)
gives the dense oracle's gradients.
The oracle is the benchmark's plain reference (``benchmarks/configs/
mellum2_12b_c3.py moe_layer``): every held expert on every token, weighted
by what the router gave it."""


import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import bench_reference

from split_learning_tpu.ops.grouped_matmul import row_tile
from split_learning_tpu.parallel import expert
from split_learning_tpu.parallel.expert import (
    HeldMoEMLP, MoEMLP, _fold, common_rows, ep_spec, moe_aux_loss,
    pass_plan, route_held,
)
from split_learning_tpu.parallel.pipeline import COUNTER_FOLDS, sown_counters

H, F, E, K, T = 32, 24, 8, 2, 48
HI = jax.lax.Precision.HIGHEST


REF = bench_reference("mellum2_12b_c3")


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI)


def _ref_layer(params, m, held, e=E, k=K):
    """The reference's expert layer holding ``held`` of ``params``' e."""
    share = {"router": params["router"],
             "experts": jax.tree_util.tree_map(
                 lambda a: a[np.asarray(held)], params["experts"])}
    s = {"num_experts": e, "num_experts_per_tok": k, "experts_held": held}
    return REF.moe_layer(share, m, s, _mm)


def _share(params, held):
    return {"router": params["router"],
            "experts": jax.tree_util.tree_map(
                lambda a: a[held[0]:held[-1] + 1], params["experts"])}


@pytest.fixture(scope="module")
def layer():
    x = jax.random.normal(jax.random.key(0), (2, T // 2, H))
    full = HeldMoEMLP(H, F, num_experts=E, k=K)
    params = full.init(jax.random.key(1), x)["params"]
    return x, params


def test_the_whole_layer_matches_the_uncut_reference(layer):
    x, params = layer
    y, mut = HeldMoEMLP(H, F, num_experts=E, k=K).apply(
        {"params": params}, x, mutable=["intermediates"])
    want, aux = _ref_layer(params, x.reshape(T, H), tuple(range(E)))
    np.testing.assert_allclose(np.asarray(y).reshape(T, H),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        float(moe_aux_loss(mut["intermediates"])), float(aux), rtol=1e-6)


@pytest.mark.parametrize("held", [(0, 1), (2, 3), (4, 5), (6, 7)])
def test_a_share_matches_the_reference_given_the_same_share(layer, held):
    x, params = layer
    y = HeldMoEMLP(H, F, num_experts=E, k=K, held=held).apply(
        {"params": _share(params, held)}, x)
    want, _ = _ref_layer(params, x.reshape(T, H), held)
    np.testing.assert_allclose(np.asarray(y).reshape(T, H),
                               np.asarray(want), rtol=1e-5, atol=1e-6)


def test_the_shares_add_up_to_the_uncut_reference_layer(layer):
    """Four chips of two experts each: their parts of the result sum to
    what the uncut reference gives for the whole layer (there is no
    shared expert to count once), and their held pairs to T * k."""
    x, params = layer
    total, pairs = 0.0, 0.0
    for first in range(0, E, 2):
        held = (first, first + 1)
        y, mut = HeldMoEMLP(H, F, num_experts=E, k=K, held=held).apply(
            {"params": _share(params, held)}, x,
            mutable=list(COUNTER_FOLDS))
        total = total + y
        pairs += float(sown_counters(mut)["counters_sum"]["moe_pairs_held"])
    want, _ = _ref_layer(params, x.reshape(T, H), tuple(range(E)))
    np.testing.assert_allclose(np.asarray(total).reshape(T, H),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
    assert pairs == T * K


def _one_expert_for_all(params, then=None):
    """A router that sends every token to expert 3 first (and to expert
    ``then`` second; left to itself the second choice is expert 0)."""
    router = np.zeros((H, E), np.float32)
    router[:, 3] = 1.0
    if then is not None:
        router[:, then] = 0.5
    return {**params, "router": {"kernel": jnp.asarray(router)}}


@pytest.mark.parametrize("then, pairs, overflow", [(None, T, 0.0),
                                                   (2, 2 * T, 1.0)])
def test_no_pair_is_dropped_when_every_token_picks_the_same_expert(
        layer, then, pairs, overflow):
    """Held (2, 3) of 8, top-2: the common pass has ``C = T`` rows.  Every
    token on expert 3 fills it to the last row; every token on 3 AND 2 is
    the worst case, ``2 T`` rows, and its second half goes through the
    overflow pass."""
    x, params = layer
    x = jnp.abs(x) + 0.1                 # so that expert 3's logit wins
    params = _one_expert_for_all(params, then)
    assert common_rows(T, K, 2, E) == T
    y, mut = HeldMoEMLP(H, F, num_experts=E, k=K, held=(2, 3)).apply(
        {"params": _share(params, (2, 3))}, x,
        mutable=list(COUNTER_FOLDS))
    want, _ = _ref_layer(params, x.reshape(T, H), (2, 3))
    np.testing.assert_allclose(np.asarray(y).reshape(T, H),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
    _, plan, _ = route_held(_mm("td,de->te", x.reshape(T, H),
                                params["router"]["kernel"]), K, 2, 2)
    assert int(plan["group_sizes"][1]) == T      # every token, none lost
    assert int(plan["held"].sum()) == pairs
    # the held pairs' sorted rows are the first ``pairs``, each once
    rows = np.asarray(plan["pair_row"])[np.asarray(plan["held"])]
    assert sorted(rows) == list(range(pairs))
    counters = {name: float(v) for names in sown_counters(mut).values()
                for name, v in names.items()}
    assert counters["moe_pairs_held"] == pairs
    assert counters["moe_overflow_passes"] == overflow
    assert counters["moe_load_max_over_mean"] > 1.0 or then is not None
    assert not any("dropped" in name for name in counters)


def test_the_capacity_layer_drops_there(layer):
    """What the new layer is for: ``MoEMLP``'s capacity loses tokens under
    the same load, so its result is another one."""
    x, params = layer
    x = jnp.abs(x) + 0.1
    params = _one_expert_for_all(params)
    old = MoEMLP(H, F, num_experts=E, k=K)
    y_old = old.apply({"params": params}, x)
    want, _ = _ref_layer(params, x.reshape(T, H), tuple(range(E)))
    assert float(jnp.abs(y_old.reshape(T, H) - want).max()) > 1e-3


@pytest.mark.parametrize("held", [None, (4, 5, 6, 7)])
def test_gradients_match_the_reference(layer, held):
    """Router, experts and input: the layer's backward pass is gathers
    (``spread_rows``, ``fold_rows``), the reference's plain autodiff of
    dense products."""
    x, params = layer
    ids = tuple(range(E)) if held is None else held
    w = jax.random.normal(jax.random.key(5), (T, H))

    def prog(p, x):
        return (HeldMoEMLP(H, F, num_experts=E, k=K, held=held).apply(
            {"params": p}, x).reshape(T, H) * w).sum()

    def ref(p, x):
        return (_ref_layer(p, x.reshape(T, H), ids)[0] * w).sum()

    got = jax.grad(prog, argnums=(0, 1))(_share(params, ids), x)
    want_p, want_x = jax.grad(ref, argnums=(0, 1))(params, x)
    want = (_share(want_p, ids), want_x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# -- the two passes under load -------------------------------------------------

# 256 tokens picking 8 of 64 experts, experts 8-15 held: an even router
# sends 256 pairs here, the common pass has C = 512 rows, the worst case
# (every token picking all eight) 2,048
BIG = dict(h=32, f=16, e=64, k=8, t=256, held=tuple(range(8, 16)))
LOADS = {"even": 0.0, "exactly_c": 0.0, "c_plus_1": 1.0, "worst": 1.0}


@pytest.fixture(scope="module")
def big():
    """For each load ``(tokens, parameters, held pairs)``: the router drawn
    WHOLE, the tokens picked from a pool by how many of its eight choices
    a token makes among the held experts."""
    h, e, k, t, held = (BIG[n] for n in ("h", "e", "k", "t", "held"))
    c = common_rows(t, k, len(held), e)
    assert (c, min(k, len(held)) * t) == (512, 2048)
    layer = HeldMoEMLP(h, BIG["f"], num_experts=e, k=k)
    pool = jax.random.normal(jax.random.key(10), (16 * t, h))
    params = layer.init(jax.random.key(11), pool[None, :t])["params"]
    params = {**params, "router": {"kernel": 2.0 * jax.random.normal(
        jax.random.key(12), (h, e))}}
    top = np.asarray(jax.lax.top_k(
        _mm("td,de->te", pool, params["router"]["kernel"]), k)[1])
    count = ((top >= held[0]) & (top <= held[-1])).sum(axis=1)
    twos, threes = np.flatnonzero(count == 2), np.flatnonzero(count == 3)
    assert len(twos) >= t and len(threes) >= 1
    # every token picks every held expert: their columns lifted clear
    lifted = np.asarray(params["router"]["kernel"]).copy()
    lifted[:, held[0]:held[-1] + 1] = 0.0
    even = int(count[:t].sum())
    assert 0 < even < c // 2 + c // 4
    return {"even": (pool[:t], params, even),
            "exactly_c": (pool[twos[:t]], params, c),
            "c_plus_1": (pool[np.append(twos[:t - 1], threes[0])], params,
                         c + 1),
            "worst": (jnp.abs(pool[:t]) + 0.1, {**params, "router": {
                "kernel": jnp.asarray(lifted - 3.0 * (lifted != 0))}}, 2048)}


def _big_share(params):
    return _share(params, BIG["held"])


def _big_layer():
    return HeldMoEMLP(BIG["h"], BIG["f"], num_experts=BIG["e"], k=BIG["k"],
                      held=BIG["held"])


@pytest.mark.parametrize("load", list(LOADS))
def test_both_passes_match_the_reference_whatever_the_load(big, load):
    """Result and gradients (input, router, expert kernels) against the
    benchmark reference's ``moe_layer``, with the router drawn whole: the
    common pass alone (well under ``C``, and full to its last row), and
    with the overflow pass (one row in it, and all of them)."""
    x, params, pairs = big[load]
    t, h = BIG["t"], BIG["h"]
    w = jax.random.normal(jax.random.key(13), (t, h))

    def prog(p, x):
        y, mut = _big_layer().apply({"params": p}, x[None],
                                    mutable=list(COUNTER_FOLDS))
        return (y.reshape(t, h) * w).sum(), (y, sown_counters(mut))

    def ref(p, x):
        y, _ = _ref_layer(p, x, BIG["held"], BIG["e"], BIG["k"])
        return (y * w).sum(), y

    (_, (y, count)), got = jax.value_and_grad(
        prog, argnums=(0, 1), has_aux=True)(_big_share(params), x)
    (_, want_y), (want_p, want_x) = jax.value_and_grad(
        ref, argnums=(0, 1), has_aux=True)(params, x)
    assert float(count["counters_sum"]["moe_pairs_held"]) == pairs
    assert float(count["counters_sum"]["moe_overflow_passes"]) == LOADS[load]
    np.testing.assert_allclose(np.asarray(y).reshape(t, h),
                               np.asarray(want_y), rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(
                        (_big_share(want_p), want_x))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("load", list(LOADS))
def test_the_products_tiles_cover_the_pairs_and_a_tile_a_group_at_most(
        big, load):
    """``moe_gmm_rows``: visits times the row tile, both passes.  Every
    held pair lies in a visited tile, and a group's end adds less than a
    tile, so the counter stands between the pairs and the pairs plus a
    tile a group and pass; a pass that is skipped adds nothing."""
    x, params, pairs = big[load]
    _, mut = _big_layer().apply({"params": _big_share(params)}, x[None],
                                mutable=list(COUNTER_FOLDS))
    count = sown_counters(mut)["counters_sum"]
    c, worst, groups = 512, 2048, len(BIG["held"])
    room = groups * row_tile(c)
    if LOADS[load]:
        room += groups * row_tile(worst - c)
    assert pairs <= float(count["moe_gmm_rows"]) <= pairs + room
    assert float(count["moe_gmm_rows"]) % min(row_tile(c),
                                              row_tile(worst - c)) == 0


def _gap(a, b):
    """Norm of the difference over the norm of ``b``, in float64."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("load", list(LOADS))
def test_in_bfloat16_both_passes_stay_near_the_float32_reference(big, load):
    """The chip's cell has tiled routers (one held pair a token) and the
    tests above run in float32, so this is where the fold's bfloat16 sums
    of SEVERAL pairs a token are held to something: result and gradients
    of the bfloat16 layer, routers drawn whole, within 0.008 of the
    float32 reference's norm (read: 0.0053-0.0063 for the result, 0.0040-
    0.0068 for the gradients; the worst-case buffer's single sum over
    ``k`` read 0.0053-0.0063 and 0.0046-0.0104, and a fold that sums in
    float32 0.0050-0.0062: the grouped products' rounded results carry
    the gap, not the order of a token's sums)."""
    x, params, _ = big[load]
    t, h = BIG["t"], BIG["h"]
    x = x.astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.key(13), (t, h))
    model = HeldMoEMLP(h, BIG["f"], num_experts=BIG["e"], k=BIG["k"],
                       held=BIG["held"], dtype=jnp.bfloat16)

    def prog(p, x):
        y = model.apply({"params": p}, x[None]).reshape(t, h)
        return (y.astype(jnp.float32) * w).sum(), y

    def ref(p, x):
        y, _ = _ref_layer(p, x, BIG["held"], BIG["e"], BIG["k"])
        return (y * w).sum(), y

    (_, y), got = jax.value_and_grad(prog, argnums=(0, 1), has_aux=True)(
        _big_share(params), x)
    (_, want_y), (want_p, want_x) = jax.value_and_grad(
        ref, argnums=(0, 1), has_aux=True)(params, x.astype(jnp.float32))
    assert y.dtype == jnp.bfloat16
    assert _gap(y, want_y) < 0.008
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(
                        (_big_share(want_p), want_x))):
        assert _gap(a, b) < 0.008


@pytest.mark.parametrize("load", ["even", "worst"])
def test_the_fold_in_bfloat16_is_within_one_more_rounding_of_the_sum(
        big, load):
    """The fold alone, rows and result in bfloat16, against the float64
    sum of each token's weighted rows: a router drawn whole gives a token
    up to three held pairs here, the worst case all eight (three shifted
    adds, each rounded).  The exact sum rounded ONCE to bfloat16 stands
    0.0016 of its norm off; the fold has to stay under 0.004 (2 ** -8;
    read: 0.0021 and 0.0031)."""
    x, params, _ = big[load]
    t, h, k, held = (BIG[n] for n in ("t", "h", "k", "held"))
    weights, plan, _ = route_held(
        _mm("td,de->te", x, params["router"]["kernel"]), k, held[0],
        len(held))
    rows = min(k, len(held)) * t
    p = pass_plan(plan, 0, rows, t, k)
    live = np.asarray(p["live"])
    a_token = np.bincount(np.asarray(p["token"])[live], minlength=t)
    assert a_token.max() == (8 if load == "worst" else 3)
    vals = jax.random.normal(jax.random.key(3), (rows, h)).astype(
        jnp.bfloat16)
    scale = jnp.where(p["live"], weights.reshape(-1)[p["pair"]], 0.0)
    want = np.zeros((t, h))
    np.add.at(want, np.asarray(p["token"])[live],
              np.asarray(vals, np.float64)[live]
              * np.asarray(scale, np.float64)[live, None])
    got = _fold(vals, scale, p)
    assert got.dtype == jnp.bfloat16
    once = jnp.asarray(want, jnp.float32).astype(jnp.bfloat16)
    assert _gap(once, want) < _gap(got, want) < 0.004


@pytest.mark.parametrize("where", ["common", "overflow"])
def test_rows_past_the_groups_may_hold_anything(layer, big, monkeypatch,
                                                where):
    """On the chip a grouped product leaves the buffer rows past its
    groups as they may (the absent experts' pairs sit there): with NaN in
    them, as the chip has, result and gradients are what they were.  The
    common pass's buffer has such rows under an even load; at ``C + 1``
    pairs it is full and all rows but one of the overflow pass's are."""
    if where == "common":
        x, params = layer
        model = HeldMoEMLP(H, F, num_experts=E, k=K, held=(2, 3))
        share = _share(params, (2, 3))
    else:
        x, params, _ = big["c_plus_1"]
        x, model, share = x[None], _big_layer(), _big_share(params)
    w = jax.random.normal(jax.random.key(6), x.shape)

    def loss(p, x):
        return (model.apply({"params": p}, x) * w).sum()

    real = expert.grouped_dot
    planted = []

    def zeros_past_the_groups(lhs, rhs, group_sizes):
        out = real(lhs, rhs, group_sizes)
        live = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(live[:, None], out, 0.0)

    def garbage_past_the_groups(lhs, rhs, group_sizes):
        out = real(lhs, rhs, group_sizes)
        live = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        planted.append(out.shape[0])
        return jnp.where(live[:, None], out, jnp.nan)

    monkeypatch.setattr(expert, "grouped_dot", zeros_past_the_groups)
    want = jax.value_and_grad(loss, argnums=(0, 1))(share, x)
    monkeypatch.setattr(expert, "grouped_dot", garbage_past_the_groups)
    got = jax.value_and_grad(loss, argnums=(0, 1))(share, x)
    # a product of the pass the case names was among them: T rows for
    # some 50 held pairs; 1,536 for the one pair past C
    assert {"common": T, "overflow": 2048 - 512}[where] in planted
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def _rows_by_columns(jaxpr, skip_branch, found):
    """Every (equation, shape) of ``jaxpr`` and of what it calls, but for
    branch ``skip_branch`` of its ``cond``s."""
    for eqn in jaxpr.eqns:
        for v in (*eqn.invars, *eqn.outvars):
            found.append((eqn.primitive.name,
                          tuple(getattr(v.aval, "shape", ()))))
        for name, param in eqn.params.items():
            subs = param if isinstance(param, (tuple, list)) else (param,)
            for i, sub in enumerate(subs):
                inner = getattr(sub, "jaxpr", sub)
                if not hasattr(inner, "eqns"):
                    continue
                if eqn.primitive.name == "cond" and name == "branches" \
                        and i == skip_branch:
                    continue
                _rows_by_columns(inner, skip_branch, found)
    return found


@pytest.mark.parametrize("router", ["tiled", "drawn_whole"])
def test_no_pass_over_rows_has_the_worst_case_outside_the_overflow_branch(
        big, router):
    """Counts, not times (this host has no chip): in the layer's value
    and gradient at T, k, held, E = 256, 8, 8, 64, outside the ``cond``'s
    overflow branch nothing has ``T * k`` rows of ``H`` columns, nor more
    than ``C``: not the gathers in and out, not the folds, not the
    grouped products.
    With the cell's tiled routers (every token one held pair) and with a
    router drawn whole (some token five or six), so that a fold which is
    cheap only at one pair a token fails here."""
    x, params, _ = big["even"]
    t, h, k, e = BIG["t"], BIG["h"], BIG["k"], BIG["e"]
    if router == "tiled":
        kernel = np.asarray(params["router"]["kernel"])
        params = {**params, "router": {"kernel": jnp.asarray(
            np.tile(kernel[:, :k], (1, e // k)))}}
    share = _big_share(params)

    def loss(p, x):
        return _big_layer().apply({"params": p}, x[None]).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
        share, x).jaxpr
    c, worst = common_rows(t, k, len(BIG["held"]), e), 2048
    # the walk sees into branches: the overflow pass's rows are there
    assert any(p == "gather" and s == (worst - c, h)
               for p, s in _rows_by_columns(jaxpr, None, []))
    found = _rows_by_columns(jaxpr, 1, [])
    assert any(p == "pallas_call" for p, _ in found)
    assert not any(p.startswith("ragged_dot") for p, _ in found)
    assert any(p == "gather" and s == (c, h) for p, s in found)
    # nothing of T * k rows, and nothing of more rows than C either
    for prim, shape in found:
        assert not (len(shape) >= 2 and shape[-1] == h
                    and int(np.prod(shape[:-1])) > c), (prim, shape)


def test_expert_parameters_keep_the_leading_axis_ep_spec_shards(layer):
    _, params = layer
    share = _share(params, (2, 3, 4, 5))
    for path, leaf in jax.tree_util.tree_leaves_with_path(share):
        spec = ep_spec(path, leaf)
        if "experts" in [str(getattr(p, "key", p)) for p in path]:
            assert leaf.shape[0] == 4 and spec[0] == "expert"
        else:
            assert tuple(spec) == ()


# -- sigmoid scores, a bias on the choice, a factor, a shared expert -----------

MOON = bench_reference("moonlight_16b_c3")
FACTOR = 2.446


def _parent_route_held(probs, k, first, n_held):
    """``route_held`` as it stood before it took a scoring function, a bias
    and a factor (PR 33), word for word."""
    t, e = probs.shape
    top_p, top_i = jax.lax.top_k(probs, k)
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    counts = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    aux = e * jnp.sum(counts / t * jnp.mean(probs, axis=0))
    local = top_i.reshape(-1) - first
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pair_row = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    plan = {"order": order, "pair_row": pair_row, "held": held,
            "group_sizes": counts[first:first + n_held].astype(jnp.int32)}
    return weights, plan, aux


def test_the_softmax_setting_is_the_parents_routing_bit_for_bit(layer):
    x, params = layer
    logits = _mm("td,de->te", x.reshape(T, H), params["router"]["kernel"])
    got = jax.jit(lambda z: route_held(z, K, 2, 4))(logits)
    want = jax.jit(lambda z: _parent_route_held(
        jax.nn.softmax(z, axis=-1), K, 2, 4))(logits)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the plan with no scatter, against the scatter form -------------------------

def _scatter_route_held(logits, k, first, n_held, scoring="softmax",
                        bias=None, factor=1.0):
    """``route_held`` as it stood with its scatters (the counts' scatter-add,
    the inverse permutation's scatter, the gather's transpose in the
    gradient), word for word."""
    t, e = logits.shape
    score_fn, divide, guard = expert.SCORING[scoring]
    scores = score_fn(logits)
    if bias is None:
        top_s, top_i = jax.lax.top_k(scores, k)
    else:
        _, top_i = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias)[None, :], k)
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    chosen = jnp.sum(top_s, axis=-1, keepdims=True)
    weights = top_s / (chosen + guard if guard else chosen)
    if factor != 1.0:
        weights = weights * factor
    counts = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    shares = scores / jnp.sum(scores, axis=-1, keepdims=True) if divide \
        else scores
    aux = e * jnp.sum(counts / t * jnp.mean(shares, axis=0))

    local = top_i.reshape(-1) - first
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pair_row = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    plan = {"order": order, "pair_row": pair_row, "held": held,
            "group_sizes": counts[first:first + n_held].astype(jnp.int32)}
    return weights, plan, aux


def _routing_case(name):
    """(x (T, 16), router (16, E), k, first, n_held, scoring, bias, factor)
    of a named case."""
    t, e, k, first, n_held, scoring, biased, factor = {
        "softmax_k8_of_64": (256, 64, 8, 0, 8, "softmax", False, 1.0),
        "sigmoid_k6_of_64": (256, 64, 6, 0, 8, "sigmoid", True, 2.446),
        "sigmoid_k6_of_128": (192, 128, 6, 0, 8, "sigmoid", True, 2.5),
        "a_share_past_the_first": (256, 64, 8, 20, 5, "softmax", False, 1.0),
        "ties": (256, 64, 8, 8, 8, "sigmoid", True, 2.446),
        "none_held": (256, 64, 8, 8, 8, "softmax", False, 1.0),
        "all_held": (256, 64, 8, 0, 64, "softmax", False, 1.0),
        "pairs_no_multiple_of_128": (37, 64, 6, 4, 8, "sigmoid", True, 2.5),
    }[name]
    x = jax.random.normal(jax.random.key(31), (t, 16))
    router = jax.random.normal(jax.random.key(32), (16, e))
    if name == "ties":
        # coarse inputs and router: many tokens' logits tie exactly, and
        # so do experts within a token
        x = jnp.round(x)
        router = jnp.round(router * 2) / 4
    if name == "none_held":
        router = router.at[:, first:first + n_held].set(0.0)
        x = jnp.concatenate([x[:, :-1], jnp.ones((t, 1))], axis=1)
        router = router.at[-1, first:first + n_held].set(-1e3)
    bias = 0.3 * jax.random.normal(jax.random.key(33), (e,)) if biased \
        else None
    return x, router, k, first, n_held, scoring, bias, factor


ROUTING_CASES = ("softmax_k8_of_64", "sigmoid_k6_of_64", "sigmoid_k6_of_128",
                 "a_share_past_the_first", "ties", "none_held", "all_held",
                 "pairs_no_multiple_of_128")


@pytest.mark.parametrize("case", ROUTING_CASES)
def test_the_plan_and_the_routers_gradient_are_the_scatter_forms(case):
    """Bit for bit: every leaf of what ``route_held`` returns, and the
    router's gradient of a weighted sum of the weights plus ``aux``."""
    x, router, k, first, n_held, scoring, bias, factor = _routing_case(case)
    t = x.shape[0]
    mix = jax.random.normal(jax.random.key(34), (t, k))

    def routed(fn, w):
        return fn(_mm("td,de->te", x, w), k, first, n_held, scoring, bias,
                  factor)

    def objective(fn):
        def f(w):
            weights, _, aux = routed(fn, w)
            return jnp.sum(weights * mix) + aux
        return jax.jit(jax.grad(f))

    got = jax.jit(lambda w: routed(route_held, w))(router)
    want = jax.jit(lambda w: routed(_scatter_route_held, w))(router)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    grad, grad_want = (objective(route_held)(router),
                       objective(_scatter_route_held)(router))
    assert np.asarray(grad_want).any()
    np.testing.assert_array_equal(np.asarray(grad), np.asarray(grad_want))
    held = int(np.asarray(got[1]["held"]).sum())
    if case == "none_held":
        assert held == 0
    elif case == "all_held":
        assert held == t * k
    elif case == "ties":
        scores = jax.nn.sigmoid(_mm("td,de->te", x, router))
        assert len(np.unique(np.asarray(scores))) < scores.size // 4


def _instructions(lowered) -> dict:
    text = lowered.as_text()
    return {"scatter": len(re.findall(r'"stablehlo\.scatter"', text)),
            "sort": len(re.findall(r'"stablehlo\.sort"', text)),
            "gather": len(re.findall(r'"stablehlo\.gather"', text)),
            "top_k": len(re.findall(r"chlo\.top_k", text))}


@pytest.mark.parametrize("case", ["softmax_k8_of_64", "sigmoid_k6_of_64"])
def test_the_plan_and_its_gradient_lower_with_no_scatter(case):
    """Each scatter, gather, sort or top-k costs the chip host's compiler a
    second or more: ``route_held`` and the VJP of its weights hold no
    scatter, and no more of the others than the scatter form (one sort; a
    gather on the biased path alone)."""
    x, router, k, first, n_held, scoring, bias, factor = _routing_case(case)
    logits = _mm("td,de->te", x, router)
    ct = jnp.ones((x.shape[0], k))

    def lowered(fn):
        def plan(z):
            return fn(z, k, first, n_held, scoring, bias, factor)

        def vjp(z, g):
            w, pull = jax.vjp(lambda z: plan(z)[0], z)
            return w, pull(g)
        return (_instructions(jax.jit(plan).lower(logits)),
                _instructions(jax.jit(vjp).lower(logits, ct)))

    (fwd, back), (fwd_was, back_was) = (lowered(route_held),
                                        lowered(_scatter_route_held))
    assert fwd_was["scatter"] == 2 and back_was["scatter"] >= 1
    assert fwd["scatter"] == 0 and back["scatter"] == 0
    assert fwd["sort"] == 1 and fwd["top_k"] == 1
    assert fwd["gather"] == (0 if bias is None else 1)
    for name in ("sort", "gather", "top_k"):
        assert fwd[name] <= fwd_was[name] and back[name] <= back_was[name]


@pytest.fixture(scope="module")
def biased(layer):
    """The layer's router with a bias that CHANGES the choice: it lifts
    every token's third-best expert over its second-best."""
    x, params = layer
    scores = jax.nn.sigmoid(_mm("td,de->te", x.reshape(T, H),
                                params["router"]["kernel"]))
    bias = 0.3 * jax.random.normal(jax.random.key(21), (E,))
    plain = np.asarray(jax.lax.top_k(scores, K)[1])
    lifted = np.asarray(jax.lax.top_k(scores + bias, K)[1])
    assert (np.sort(plain, 1) != np.sort(lifted, 1)).any(axis=1).mean() > 0.3
    return x, params, bias, scores


def _moon_layer(params, bias, m, held, shared=None, factor=FACTOR):
    """The Moonlight reference's expert layer holding ``held``."""
    share = {"router": params["router"],
             "experts": jax.tree_util.tree_map(
                 lambda a: a[np.asarray(held)], params["experts"])}
    s = {"n_routed_experts": E, "num_experts_per_tok": K,
         "experts_held": held, "routed_scaling_factor": factor}
    return MOON.moe_layer(share, bias, m, s, _mm, shared)


def _sigmoid_layer(held=None, **kw):
    return HeldMoEMLP(H, F, num_experts=E, k=K, held=held,
                      scoring="sigmoid", score_bias=True, factor=FACTOR,
                      **kw)


def _bias_stats(bias):
    return {"e_score_correction_bias": bias}


def test_sigmoid_routing_chooses_by_the_bias_and_weighs_without_it(biased):
    """Against the reference, and against the two mistakes: weights taken
    from ``s + b``, and the factor left out."""
    x, params, bias, scores = biased
    m = x.reshape(T, H)
    y, mut = _sigmoid_layer().apply(
        {"params": params, "batch_stats": _bias_stats(bias)}, x,
        mutable=["intermediates"])
    want, aux = _moon_layer(params, bias, m, tuple(range(E)))
    np.testing.assert_allclose(np.asarray(y).reshape(T, H),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        float(moe_aux_loss(mut["intermediates"])), float(aux), rtol=1e-6)
    logits = _mm("td,de->te", m, params["router"]["kernel"])
    weights, plan, _ = route_held(logits, K, 0, E, "sigmoid", bias, FACTOR)
    top_i = np.asarray(jax.lax.top_k(scores + bias, K)[1])
    top_s = np.take_along_axis(np.asarray(scores), top_i, axis=1)
    np.testing.assert_allclose(
        np.asarray(weights), FACTOR * top_s / top_s.sum(1, keepdims=True),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(1), FACTOR, rtol=1e-6)
    # weights from the biased scores are another result
    top_b = np.take_along_axis(np.asarray(scores + bias), top_i, axis=1)
    wrong = FACTOR * top_b / top_b.sum(1, keepdims=True)
    assert np.abs(wrong - np.asarray(weights)).max() > 1e-2
    # so is the layer without the factor, and the choice without the bias
    for other in (_moon_layer(params, bias, m, tuple(range(E)), factor=1.0),
                  _moon_layer(params, jnp.zeros((E,)), m, tuple(range(E)))):
        assert float(jnp.abs(other[0] - want).max()) > 1e-3


def test_no_gradient_reaches_the_bias_and_the_routers_matches(biased):
    x, params, bias, _ = biased
    w = jax.random.normal(jax.random.key(22), (T, H))

    def prog(p, b, x):
        return (_sigmoid_layer(held=(4, 5, 6, 7)).apply(
            {"params": p, "batch_stats": _bias_stats(b)}, x).reshape(T, H)
            * w).sum()

    def ref(p, b, x):
        return (_moon_layer(p, b, x.reshape(T, H), (4, 5, 6, 7))[0]
                * w).sum()
    ids = (4, 5, 6, 7)
    got = jax.grad(prog, argnums=(0, 1, 2))(_share(params, ids), bias, x)
    want = jax.grad(ref, argnums=(0, 1, 2))(params, bias, x)
    assert not np.asarray(got[1]).any() and not np.asarray(want[1]).any()
    for a, b in zip(
            jax.tree_util.tree_leaves((got[0], got[2])),
            jax.tree_util.tree_leaves((_share(want[0], ids), want[2]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.fixture(scope="module")
def with_shared(biased):
    """A block's feed-forward as ``models/decoder.py`` builds it: the held
    experts beside a shared SwiGLU of twice an expert's width."""
    import flax.linen as nn
    from split_learning_tpu.models.decoder import FEED_FORWARDS

    class Both(nn.Module):
        held: tuple | None = None

        @nn.compact
        def __call__(self, x):
            return FEED_FORWARDS["sparse_shared"](
                x, shared_intermediate_size=2 * F, intermediate_size=F,
                num_experts=E, k=K, held=self.held, scoring="sigmoid",
                score_bias=True, factor=FACTOR)
    x, params, bias, _ = biased
    shared = Both().init(jax.random.key(23), x)["params"]["shared_experts"]
    return x, params, bias, shared, Both


def test_the_shares_add_up_with_the_shared_expert_counted_once(with_shared):
    """Four chips of two experts each, every chip computing the shared
    expert alike: the routed parts sum, the shared expert counts ONCE, and
    that is the uncut reference's whole layer."""
    x, params, bias, shared, Both = with_shared
    m = x.reshape(T, H)
    own = MOON._swiglu(shared, m, _mm)
    routed = 0.0
    for first in range(0, E, 2):
        held = (first, first + 1)
        y = Both(held=held).apply(
            {"params": {"moe": _share(params, held),
                        "shared_experts": shared},
             "batch_stats": {"moe": _bias_stats(bias)}}, x).reshape(T, H)
        want, _ = _moon_layer(params, bias, m, held, shared)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        routed = routed + (y - own)
    whole, _ = _moon_layer(params, bias, m, tuple(range(E)), shared)
    np.testing.assert_allclose(np.asarray(routed + own), np.asarray(whole),
                               rtol=1e-5, atol=2e-6)
    # counted on every chip it would be another result
    assert float(jnp.abs(routed + 4 * own - whole).max()) > 1e-3


def test_rows_past_the_groups_may_hold_anything_beside_a_shared_expert(
        with_shared, monkeypatch):
    """NaN in the buffer rows past the groups, as the chip has: the held
    experts' part and the shared expert's, and every gradient, are what
    they were."""
    x, params, bias, shared, Both = with_shared
    model = Both(held=(2, 3))
    p = {"moe": _share(params, (2, 3)), "shared_experts": shared}
    w = jax.random.normal(jax.random.key(24), x.shape)

    def loss(p, x):
        return (model.apply({"params": p, "batch_stats": {
            "moe": _bias_stats(bias)}}, x) * w).sum()
    real = expert.grouped_dot

    def past_the_groups(fill):
        def dot(lhs, rhs, group_sizes):
            out = real(lhs, rhs, group_sizes)
            live = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
            return jnp.where(live[:, None], out, fill)
        return dot
    monkeypatch.setattr(expert, "grouped_dot", past_the_groups(0.0))
    want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(expert, "grouped_dot", past_the_groups(jnp.nan))
    got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# -- the experts' form: relu2 (two matrices, no gate) beside the SwiGLU -------

NEMO = bench_reference("nemotron_twotower_30b_c5")
E16, FACTOR2 = 16, 2.5


def _parent_pass(x, weights, kernels, plan, lo, hi):
    """``_pass`` as it stood before the experts' form was a field (PR 35),
    word for word."""
    import flax.linen as nn
    t = x.shape[0]
    with jax.named_scope("moe_route"):
        p = pass_plan(plan, lo, hi, t, weights.shape[0] // t)
        rows = expert.spread_rows(x, p)
    with jax.named_scope("moe_experts"):
        gate, up, down = kernels
        def dot(lhs, rhs):
            return expert.grouped_dot(lhs, rhs, p["sizes"])

        y = dot(nn.silu(dot(rows, gate)) * dot(rows, up), down)
    with jax.named_scope("moe_route"):
        return expert.fold_rows(y, weights, p)


def test_the_swiglu_form_is_the_parents_pass_bit_for_bit(layer):
    """Values and every gradient of one pass, and the lowered text of the
    pass itself: the form named ``swiglu`` computes what the parent's
    ``_pass`` computed."""
    x, params = layer
    m = x.reshape(T, H)
    logits = _mm("td,de->te", m, params["router"]["kernel"])
    weights, plan, _ = route_held(logits, K, 2, 4)
    kernels = tuple(params["experts"][n]["kernel"][2:6]
                    for n in ("gate_proj", "up_proj", "down_proj"))
    w = jax.random.normal(jax.random.key(31), (T, H))
    c = common_rows(T, K, 4, E)

    def run(fn):
        def loss(m, weights, kernels):
            return (fn(m, weights, kernels, plan, 0, c) * w).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    mine, parents = run(expert._pass), run(_parent_pass)
    args = (m, weights.reshape(-1), kernels)
    assert mine.lower(*args).as_text() == parents.lower(*args).as_text()
    for a, b in zip(jax.tree_util.tree_leaves(mine(*args)),
                    jax.tree_util.tree_leaves(parents(*args))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def relu2():
    """Sixteen relu2 experts, sigmoid scores, a bias that changes the
    choice, the factor 2.5, beside a shared relu2 expert of twice the
    width: an expert layer as ``models/decoder.py`` builds it for
    ``NemotronH_TINYSTORIES``."""
    import flax.linen as nn
    from split_learning_tpu.models.decoder import FEED_FORWARDS

    class Both(nn.Module):
        held: tuple | None = None

        @nn.compact
        def __call__(self, x):
            return FEED_FORWARDS["sparse_shared"](
                x, shared_intermediate_size=2 * F, intermediate_size=F,
                num_experts=E16, k=K, held=self.held, scoring="sigmoid",
                score_bias=True, factor=FACTOR2, form="relu2")
    x = jax.random.normal(jax.random.key(40), (2, T // 2, H))
    variables = Both().init(jax.random.key(41), x)
    bias = 0.3 * jax.random.normal(jax.random.key(42), (E16,))
    return x, variables["params"], bias, Both


def _nemo_layer(params, bias, m, held, shared=True):
    share = {"router": params["moe"]["router"],
             "experts": jax.tree_util.tree_map(
                 lambda a: a[np.asarray(held)], params["moe"]["experts"])}
    s = {"n_routed_experts": E16, "num_experts_per_tok": K,
         "experts_held": held, "routed_scaling_factor": FACTOR2}
    return NEMO.moe_layer(share, bias, m, s, _mm,
                          params["shared_experts"] if shared else None)


def _relu2_share(params, held):
    return {"moe": {"router": params["moe"]["router"],
                    "experts": jax.tree_util.tree_map(
                        lambda a: a[held[0]:held[-1] + 1],
                        params["moe"]["experts"])},
            "shared_experts": params["shared_experts"]}


def test_relu2_experts_have_two_matrices_and_match_the_reference(relu2):
    """The tree (``up_proj`` and ``down_proj``, no gate, in the routed and
    in the shared expert), the whole layer and a share against the
    reference's; a SwiGLU or a plain ``relu`` would be another result."""
    x, params, bias, Both = relu2
    m = x.reshape(T, H)
    assert set(params["moe"]["experts"]) == {"up_proj", "down_proj"}
    assert set(params["shared_experts"]) == {"up_proj", "down_proj"}
    assert params["moe"]["experts"]["up_proj"]["kernel"].shape == (E16, H, F)
    stats = {"moe": _bias_stats(bias)}
    for held in (tuple(range(E16)), (4, 5, 6, 7)):
        y = Both(held=held).apply(
            {"params": _relu2_share(params, held), "batch_stats": stats}, x)
        want, _ = _nemo_layer(params, bias, m, held)
        np.testing.assert_allclose(np.asarray(y).reshape(T, H),
                                   np.asarray(want), rtol=1e-5, atol=1e-6)
    up = params["shared_experts"]["up_proj"]["kernel"]
    down = params["shared_experts"]["down_proj"]["kernel"]
    whole, _ = _nemo_layer(params, bias, m, tuple(range(E16)))
    routed, _ = _nemo_layer(params, bias, m, tuple(range(E16)), shared=False)
    np.testing.assert_allclose(
        np.asarray(whole - routed),
        np.asarray(jnp.square(jax.nn.relu(m @ up)) @ down), rtol=1e-4,
        atol=1e-6)
    assert float(jnp.abs(jax.nn.relu(m @ up) @ down
                         - (whole - routed)).max()) > 1e-3


def test_relu2_gradients_match_the_reference(relu2):
    x, params, bias, Both = relu2
    held = (8, 9, 10, 11)
    w = jax.random.normal(jax.random.key(43), (T, H))

    def prog(p, x):
        return (Both(held=held).apply(
            {"params": p, "batch_stats": {"moe": _bias_stats(bias)}},
            x).reshape(T, H) * w).sum()

    def ref(p, x):
        return (_nemo_layer(p, bias, x.reshape(T, H), held)[0] * w).sum()
    got = jax.grad(prog, argnums=(0, 1))(_relu2_share(params, held), x)
    want = jax.grad(ref, argnums=(0, 1))(params, x)
    for a, b in zip(
            jax.tree_util.tree_leaves(got),
            jax.tree_util.tree_leaves((_relu2_share(want[0], held),
                                       want[1]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_sixteen_chips_shares_add_up_with_the_shared_expert_counted_once(
        relu2):
    """Sixteen chips of one relu2 expert each, every chip computing the
    shared expert alike: the routed parts sum, the shared expert counts
    ONCE, and that is the uncut reference's whole layer."""
    x, params, bias, Both = relu2
    m = x.reshape(T, H)
    own = NEMO._relu2(m, params["shared_experts"]["up_proj"]["kernel"],
                      params["shared_experts"]["down_proj"]["kernel"], _mm)
    routed = 0.0
    for chip in range(E16):
        y = Both(held=(chip,)).apply(
            {"params": _relu2_share(params, (chip,)),
             "batch_stats": {"moe": _bias_stats(bias)}}, x).reshape(T, H)
        routed = routed + (y - own)
    whole, _ = _nemo_layer(params, bias, m, tuple(range(E16)))
    np.testing.assert_allclose(np.asarray(routed + own), np.asarray(whole),
                               rtol=1e-5, atol=5e-6)
    # counted on every chip it would be another result
    assert float(jnp.abs(routed + E16 * own - whole).max()) > 1e-3


def test_rows_past_the_groups_may_hold_anything_under_relu2(relu2,
                                                            monkeypatch):
    """NaN in the buffer rows past the groups, as the chip has: the
    square of a ``relu`` keeps it, the fold masks it, forward and
    backward."""
    x, params, bias, Both = relu2
    held = (2, 3)
    p = _relu2_share(params, held)
    w = jax.random.normal(jax.random.key(44), x.shape)

    def loss(p, x):
        return (Both(held=held).apply({"params": p, "batch_stats": {
            "moe": _bias_stats(bias)}}, x) * w).sum()
    real = expert.grouped_dot

    def past_the_groups(fill):
        def dot(lhs, rhs, group_sizes):
            out = real(lhs, rhs, group_sizes)
            live = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
            return jnp.where(live[:, None], out, fill)
        return dot
    monkeypatch.setattr(expert, "grouped_dot", past_the_groups(0.0))
    want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(expert, "grouped_dot", past_the_groups(jnp.nan))
    got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
