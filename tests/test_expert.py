"""Expert-parallelism (MoE) tests: routing invariants, fwd/grad smoke,
EP sharding placement, and the load-balance aux loss reaching the
objective through both the EP train step and the split/pipeline path.

The reference has no MoE (SURVEY.md §2.2 marks EP absent); these pin the
fresh TPU-native extension's semantics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from split_learning_tpu.parallel.expert import (
    MoEMLP, ep_shardings, make_ep_train_step, moe_aux_loss, topk_dispatch,
)


def _probs(t=16, e=4, seed=0):
    logits = jax.random.normal(jax.random.key(seed), (t, e))
    return jax.nn.softmax(logits, axis=-1)


class TestTopkDispatch:
    def test_combine_weights_sum_to_one_under_capacity(self):
        """With ample capacity every token's combine weights sum to 1
        (renormalized over its top-k picks)."""
        probs = _probs()
        combine, dispatch, _ = topk_dispatch(probs, k=2, capacity=16)
        np.testing.assert_allclose(np.asarray(combine.sum(axis=(1, 2))),
                                   np.ones(16), rtol=1e-5)
        # dispatch is a {0,1} mask with exactly k entries per token
        d = np.asarray(dispatch)
        assert set(np.unique(d)).issubset({0.0, 1.0})
        np.testing.assert_array_equal(d.sum(axis=(1, 2)), np.full(16, 2))

    def test_no_slot_collisions(self):
        """No two tokens may share an (expert, slot) buffer position."""
        probs = _probs(t=32, e=4, seed=1)
        _, dispatch, _ = topk_dispatch(probs, k=2, capacity=32)
        per_slot = np.asarray(dispatch).sum(axis=0)  # (E, C)
        assert per_slot.max() <= 1.0

    def test_capacity_drops_tokens(self):
        """capacity=1 keeps at most one token per expert; dropped tokens
        get zero combine weight."""
        probs = _probs(t=16, e=2, seed=2)
        combine, dispatch, _ = topk_dispatch(probs, k=1, capacity=1)
        d = np.asarray(dispatch)
        assert d.sum() <= 2  # <= capacity per expert
        dropped = d.sum(axis=(1, 2)) == 0
        assert dropped.any()
        np.testing.assert_allclose(
            np.asarray(combine)[dropped].sum(), 0.0)

    def test_aux_loss_value_uniform_router(self):
        """A perfectly uniform router gives the aux-loss minimum
        E * sum_e (1/E * 1/E) = 1."""
        t, e = 8, 4
        probs = jnp.full((t, e), 1.0 / e)
        _, _, aux = topk_dispatch(probs, k=1, capacity=t)
        np.testing.assert_allclose(float(aux), 1.0, rtol=1e-6)

    def test_collapsed_router_has_higher_aux(self):
        probs = jnp.eye(4)[jnp.zeros(8, jnp.int32)]  # all to expert 0
        _, _, aux = topk_dispatch(probs, k=1, capacity=8)
        assert float(aux) == pytest.approx(4.0)  # E * 1 * 1

    def test_k_greater_than_experts_rejected(self):
        with pytest.raises(ValueError, match="top-k"):
            topk_dispatch(_probs(e=2), k=3, capacity=4)


class TestMoEMLP:
    def _model_and_params(self, e=4, k=2, h=8, seed=0):
        model = MoEMLP(hidden_size=h, intermediate_size=16,
                       num_experts=e, k=k)
        x = jax.random.normal(jax.random.key(seed), (2, 4, h))
        variables = model.init(jax.random.key(1), x)
        return model, variables, x

    def test_forward_and_grad(self):
        model, variables, x = self._model_and_params()
        out, mut = model.apply(variables, x, mutable=["intermediates"])
        assert out.shape == x.shape
        assert jnp.isfinite(out).all()
        aux = moe_aux_loss(mut["intermediates"])
        assert float(aux) >= 1.0 - 1e-5  # uniform is the minimum

        def loss(p):
            out, mut = model.apply({"params": p}, x,
                                   mutable=["intermediates"])
            return jnp.sum(out ** 2) + moe_aux_loss(mut["intermediates"])

        grads = jax.grad(loss)(variables["params"])
        flat = jax.tree_util.tree_leaves(grads)
        assert all(jnp.isfinite(g).all() for g in flat)
        # the router must receive gradient (via gates and aux loss)
        router_g = grads["router"]["kernel"]
        assert float(jnp.abs(router_g).sum()) > 0

    def test_expert_params_have_leading_expert_dim(self):
        _, variables, _ = self._model_and_params(e=4)
        experts = variables["params"]["experts"]
        for leaf in jax.tree_util.tree_leaves(experts):
            assert leaf.shape[0] == 4

    def test_moe_aux_loss_ignores_other_sows(self):
        """Only 'aux_loss' leaves count — other sown diagnostics must not
        leak into the objective."""
        inter = {"moe": {"aux_loss": (jnp.asarray(2.0),)},
                 "probe": {"router_entropy": (jnp.asarray(123.0),)}}
        np.testing.assert_allclose(float(moe_aux_loss(inter)), 2.0)


class TestEPSharding:
    def test_expert_leaves_sharded_rest_replicated(self, eight_devices):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(eight_devices[:4]), ("expert",))
        model = MoEMLP(hidden_size=8, intermediate_size=16, num_experts=4)
        x = jnp.zeros((2, 4, 8))
        params = model.init(jax.random.key(0), x)["params"]
        sh = ep_shardings(params, mesh)
        for path, s in jax.tree_util.tree_leaves_with_path(sh):
            names = [getattr(p, "key", "") for p in path]
            if "experts" in names:
                assert s.spec[0] == "expert", path
            else:
                assert s.spec == (), path

    def test_ep_train_step_runs_sharded(self, eight_devices):
        from jax.sharding import Mesh

        import flax.linen as nn

        class TinyMoELM(nn.Module):
            @nn.compact
            def __call__(self, x, train=False):
                h = nn.Embed(32, 8, name="embed")(x)
                h = h + MoEMLP(hidden_size=8, intermediate_size=16,
                               num_experts=4, name="moe")(h)
                return nn.Dense(32, name="head")(h)

        mesh = Mesh(np.array(eight_devices[:8]).reshape(2, 4),
                    ("data", "expert"))
        model = TinyMoELM()
        x = jnp.zeros((4, 8), jnp.int32)
        params = model.init(jax.random.key(0), x)["params"]
        from split_learning_tpu.parallel.expert import shard_params_ep
        with mesh:
            params = shard_params_ep(params, mesh)
            opt = optax.adamw(1e-3)
            step = make_ep_train_step(model, opt, mesh, dp_axis="data")
            labels = jnp.zeros((4, 8), jnp.int32)
            new_p, _, ce = step(params, opt.init(params), x, labels,
                                jax.random.key(1))
        assert np.isfinite(float(ce))


class TestMoEThroughPipeline:
    """ADVICE r1 medium: the sown aux loss must reach the objective in
    the split/pipeline training path, not only make_ep_train_step."""

    def _setup(self, moe_aux_weight):
        from split_learning_tpu.parallel.pipeline import (
            PipelineModel, init_pipeline_variables, make_train_step,
            shard_to_mesh, stack_for_clients,
        )
        from split_learning_tpu.parallel.mesh import make_mesh

        mb, M = 2, 2
        # one MoE block (the router lives in stage 1 either way): this
        # test compiles TWO full pipeline programs (aux weight is
        # static), so model size directly doubles its wall-clock
        kw = dict(vocab_size=64, hidden_size=16, num_heads=2,
                  num_kv_heads=2, intermediate_size=32, n_block=1,
                  num_experts=4, k=1)
        struct = jax.ShapeDtypeStruct((mb, 8), jnp.int32)
        pipe = PipelineModel("TinyLlamaMoE_TINYSTORIES", [2], struct,
                             num_microbatches=M, model_kwargs=kw,
                             moe_aux_weight=moe_aux_weight)
        mesh = make_mesh(1, 2, jax.devices()[:2])
        variables = init_pipeline_variables(pipe, jax.random.key(0),
                                            struct)
        opt = optax.sgd(1e-2)
        params = variables["params"]
        step = make_train_step(pipe, opt, mesh, train=True, donate=False)
        args = (
            shard_to_mesh(stack_for_clients(params, 1), mesh),
            shard_to_mesh(stack_for_clients(opt.init(params), 1), mesh),
            shard_to_mesh(stack_for_clients({}, 1), mesh),
            jax.random.randint(jax.random.key(1), (1, M, mb, 8), 0, 64),
            jax.random.randint(jax.random.key(2), (1, M, mb, 8), 0, 64),
            jax.random.split(jax.random.key(3), 1),
        )
        return step, args

    def test_aux_weight_changes_router_update(self, eight_devices):
        step0, args0 = self._setup(moe_aux_weight=0.0)
        p0, _, _, loss0, _ = step0(*args0)
        step1, args1 = self._setup(moe_aux_weight=10.0)
        p1, _, _, loss1, _ = step1(*args1)
        # reported loss is CE only: identical regardless of aux weight
        np.testing.assert_allclose(np.asarray(loss0), np.asarray(loss1),
                                   rtol=1e-5)

        def routers(tree):
            return np.concatenate([
                np.asarray(leaf).ravel()
                for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
                if any(getattr(p, "key", "") == "router" for p in path)])

        r0, r1 = routers(p0), routers(p1)
        assert r0.size > 0
        # aux gradient must flow into the router params
        assert not np.allclose(r0, r1)


@pytest.mark.slow
def test_pp_ep_pipeline_matches_pp_only(eight_devices):
    """PP x EP in ONE mesh (VERDICT r4 item 5, mirroring PP x TP): the
    pipelined train step on a (client=2, stage=2, expert=2) mesh —
    manual ppermute pipeline over `stage`, GSPMD expert sharding over
    `expert` with XLA-derived dispatch/combine all-to-alls — must
    produce the same losses and updated params as the plain
    (client=2, stage=2) pipeline with replicated experts, and the
    expert leaves must be genuinely distributed."""
    import optax
    from jax.sharding import Mesh

    from split_learning_tpu.parallel.pipeline import (
        PipelineModel, init_pipeline_variables, make_train_step,
        shard_to_mesh, stack_for_clients,
    )

    tiny = dict(vocab_size=64, hidden_size=16, num_heads=2,
                num_kv_heads=2, intermediate_size=32, n_block=2,
                num_experts=2, k=1)
    mb, m, S = 2, 2, 8
    struct = jax.ShapeDtypeStruct((mb, S), jnp.int32)
    pipe = PipelineModel("TinyLlamaMoE_TINYSTORIES", cuts=[2],
                         example_input=struct, num_microbatches=m,
                         model_kwargs=tiny)
    variables = init_pipeline_variables(pipe, jax.random.key(0), struct)
    params = variables["params"]
    stats = variables.get("batch_stats", {})
    opt = optax.sgd(1e-2)
    opt_state = opt.init(params)
    x = jax.random.randint(jax.random.key(2), (2, m, mb, S), 0,
                           tiny["vocab_size"], jnp.int32)
    y = jax.random.randint(jax.random.key(3), (2, m, mb, S), 0,
                           tiny["vocab_size"], jnp.int32)
    rngs = jax.vmap(jax.random.key)(jnp.arange(2))

    def run(mesh):
        pc = shard_to_mesh(stack_for_clients(params, 2), mesh)
        oc = shard_to_mesh(stack_for_clients(opt_state, 2), mesh)
        sc = shard_to_mesh(stack_for_clients(stats, 2), mesh)
        step = make_train_step(pipe, opt, mesh)
        return step(pc, oc, sc, x, y, rngs)[:4]

    mesh_pp = Mesh(np.array(eight_devices[:4]).reshape(2, 2),
                   ("client", "stage"))
    p2, _, _, loss2 = run(mesh_pp)

    mesh_ppep = Mesh(np.array(eight_devices).reshape(2, 2, 2),
                     ("client", "stage", "expert"))
    p3, _, _, loss3 = run(mesh_ppep)

    np.testing.assert_allclose(np.asarray(loss2), np.asarray(loss3),
                               rtol=2e-4)
    for l2, l3 in zip(jax.tree_util.tree_leaves(p2),
                      jax.tree_util.tree_leaves(p3)):
        np.testing.assert_allclose(np.asarray(l2), np.asarray(l3),
                                   rtol=2e-3, atol=1e-5)
    # expert kernels really are distributed over the expert axis
    moe = p3["layer2"]["moe"]["experts"]["gate_proj"]["kernel"]
    assert "expert" in tuple(map(str, jax.tree_util.tree_leaves(
        [moe.sharding.spec]))) or "expert" in str(moe.sharding.spec)
