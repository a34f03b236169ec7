"""The drop-free held-share expert layer (``parallel/expert.py
HeldMoEMLP``): the shares of an expert-parallel job add up to the uncut
reference's layer, nothing is dropped however uneven the load, and the
backward pass (gathers, no scatter-add) gives the dense oracle's gradients.
The oracle is the benchmark's plain reference (``benchmarks/configs/
mellum2_12b_c3.py moe_layer``): every held expert on every token, weighted
by what the router gave it."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import bench_reference

from split_learning_tpu.parallel.expert import (
    HeldMoEMLP, MoEMLP, ep_spec, moe_aux_loss, route_held,
)
from split_learning_tpu.parallel.pipeline import COUNTER_FOLDS, sown_counters

H, F, E, K, T = 32, 24, 8, 2, 48
HI = jax.lax.Precision.HIGHEST


REF = bench_reference("mellum2_12b_c3")


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI)


def _ref_layer(params, m, held):
    """The reference's expert layer holding ``held`` of ``params``' E."""
    share = {"router": params["router"],
             "experts": jax.tree_util.tree_map(
                 lambda a: a[np.asarray(held)], params["experts"])}
    s = {"num_experts": E, "num_experts_per_tok": K, "experts_held": held}
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


def _one_expert_for_all(params):
    """A router that sends every token to expert 3 first."""
    router = np.zeros((H, E), np.float32)
    router[:, 3] = 1.0
    return {**params, "router": {"kernel": jnp.asarray(router)}}


def test_no_pair_is_dropped_when_every_token_picks_the_same_expert(layer):
    x, params = layer
    x = jnp.abs(x) + 0.1                 # so that expert 3's logit wins
    params = _one_expert_for_all(params)
    y, mut = HeldMoEMLP(H, F, num_experts=E, k=K, held=(2, 3)).apply(
        {"params": _share(params, (2, 3))}, x,
        mutable=list(COUNTER_FOLDS))
    want, _ = _ref_layer(params, x.reshape(T, H), (2, 3))
    np.testing.assert_allclose(np.asarray(y).reshape(T, H),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
    probs = jax.nn.softmax(_mm("td,de->te", x.reshape(T, H),
                               params["router"]["kernel"]))
    _, plan, _ = route_held(probs, K, 2, 2)
    assert int(plan["group_sizes"][1]) == T      # every token, none lost
    counters = {name: float(v) for names in sown_counters(mut).values()
                for name, v in names.items()}
    assert counters["moe_pairs_held"] >= T
    assert counters["moe_load_max_over_mean"] > 1.0
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
    (``take_rows``), the reference's plain autodiff of dense products."""
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


def test_rows_past_the_groups_may_hold_anything(layer, monkeypatch):
    """On the chip a grouped product leaves the buffer rows past its
    groups as they may (the absent experts' pairs sit there): with NaN in
    them, as the chip has, result and gradients are what they were."""
    x, params = layer
    held = (2, 3)
    share = _share(params, held)
    w = jax.random.normal(jax.random.key(6), x.shape)

    def loss(p, x):
        return (HeldMoEMLP(H, F, num_experts=E, k=K, held=held).apply(
            {"params": p}, x) * w).sum()

    want = jax.value_and_grad(loss, argnums=(0, 1))(share, x)
    real = jax.lax.ragged_dot

    def garbage_past_the_groups(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        live = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(live[:, None], out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", garbage_past_the_groups)
    got = jax.value_and_grad(loss, argnums=(0, 1))(share, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_expert_parameters_keep_the_leading_axis_ep_spec_shards(layer):
    _, params = layer
    share = _share(params, (2, 3, 4, 5))
    for path, leaf in jax.tree_util.tree_leaves_with_path(share):
        spec = ep_spec(path, leaf)
        if "experts" in [str(getattr(p, "key", p)) for p in path]:
            assert leaf.shape[0] == 4 and spec[0] == "expert"
        else:
            assert tuple(spec) == ()
