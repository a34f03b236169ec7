"""Pipeline-vs-sequential equivalence on a virtual (client, stage) CPU mesh.

The compiled GPipe pipeline (ppermute hops, lax.switch stages, scan ticks)
must produce exactly the loss/grads/batch_stats that a sequential
full-model pass over the same microbatches produces — the TPU analog of
the reference's split ≡ unsplit guarantee."""


import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

pytestmark = pytest.mark.slow  # compiles real split programs

from split_learning_tpu.models import build_model
from split_learning_tpu.parallel import (
    PipelineModel, make_train_step, make_fedavg_step, make_mesh,
)
from split_learning_tpu.parallel.pipeline import (
    init_pipeline_variables, stack_for_clients, shard_to_mesh,
)


def _ref_loss(model, params, stats, x_mb, labels, rng, train):
    """Sequential full-model mean loss over microbatches (same rng folding
    per microbatch as the pipeline)."""
    M = x_mb.shape[0]
    losses = []
    for i in range(M):
        variables = {"params": params}
        if stats:
            variables["batch_stats"] = stats
        out, mut = model.apply(
            variables, x_mb[i], train=train, mutable=["batch_stats"],
            rngs={"dropout": jax.random.fold_in(rng, i)} if train else None)
        stats = {**stats, **mut.get("batch_stats", {})} if stats else stats
        losses.append(optax.softmax_cross_entropy_with_integer_labels(
            out, labels[i]).mean())
    return jnp.mean(jnp.asarray(losses)), stats


@pytest.mark.parametrize("cuts,M", [([9], 4), ([5, 9, 13], 3)])
def test_kwt_pipeline_matches_sequential(eight_devices, cuts, M):
    mb, C = 2, 2
    S = len(cuts) + 1
    pipe = PipelineModel(
        "KWT_SPEECHCOMMANDS", cuts,
        jax.ShapeDtypeStruct((mb, 40, 98), jnp.float32),
        num_microbatches=M)
    mesh = make_mesh(C, S, eight_devices[:C * S])

    variables = init_pipeline_variables(
        pipe, jax.random.key(0), jax.ShapeDtypeStruct((mb, 40, 98),
                                                      jnp.float32))
    params = variables["params"]
    x = jax.random.normal(jax.random.key(1), (C, M, mb, 40, 98))
    labels = jax.random.randint(jax.random.key(2), (C, M, mb), 0, 10)
    rng = jax.random.key(3)

    # pipeline loss+grads per client via the real train step machinery
    opt = optax.sgd(0.1)
    step = make_train_step(pipe, opt, mesh, train=False, donate=False)
    p_stack = shard_to_mesh(stack_for_clients(params, C), mesh)
    o_stack = shard_to_mesh(stack_for_clients(opt.init(params), C), mesh)
    s_stack = shard_to_mesh(stack_for_clients({}, C), mesh)
    rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(C))
    new_p, _, _, loss, _ = step(p_stack, o_stack, s_stack, x, labels, rngs)

    # reference: per-client sequential full model + manual SGD
    model = build_model("KWT_SPEECHCOMMANDS")
    for c in range(C):
        ref_loss, _ = _ref_loss(model, params, {}, x[c], labels[c],
                                jax.random.fold_in(rng, c), False)
        np.testing.assert_allclose(float(loss[c]), float(ref_loss),
                                   rtol=1e-5, err_msg=f"client {c}")
        g_ref = jax.grad(
            lambda p: _ref_loss(model, p, {}, x[c], labels[c],
                                jax.random.fold_in(rng, c), False)[0]
        )(params)
        p_ref = optax.apply_updates(
            params, opt.update(g_ref, opt.init(params), params)[0])
        got = jax.tree_util.tree_map(lambda a: np.asarray(a[c]), new_p)
        ref_leaves = dict(jax.tree_util.tree_leaves_with_path(p_ref))
        for path, leaf in jax.tree_util.tree_leaves_with_path(got):
            np.testing.assert_allclose(
                leaf, np.asarray(ref_leaves[path]), rtol=2e-4, atol=1e-5,
                err_msg=f"client {c} {path}")


@pytest.mark.parametrize("stage_devs", [2, 1])
def test_vgg_pipeline_train_mode_with_batchnorm(eight_devices, stage_devs):
    """Train-mode pipeline: BN batch_stats and dropout must match the
    sequential reference; bubble ticks must NOT pollute stats.

    ``stage_devs=1`` runs both stages chained on ONE device (the
    single-chip virtual-stage path) — same oracle, exercising the
    train-mode rng/batch_stats flow through chained remat stages."""
    mb, C, M, cuts = 2, 1, 3, [7]
    pipe = PipelineModel(
        "VGG16_CIFAR10", cuts,
        jax.ShapeDtypeStruct((mb, 32, 32, 3), jnp.float32),
        num_microbatches=M)
    mesh = make_mesh(C, stage_devs, eight_devices[:stage_devs])

    variables = init_pipeline_variables(
        pipe, jax.random.key(0),
        jax.ShapeDtypeStruct((mb, 32, 32, 3), jnp.float32))
    params, stats = variables["params"], variables["batch_stats"]
    x = jax.random.normal(jax.random.key(1), (C, M, mb, 32, 32, 3))
    labels = jax.random.randint(jax.random.key(2), (C, M, mb), 0, 10)
    rng = jax.random.key(3)

    opt = optax.sgd(0.05)
    step = make_train_step(pipe, opt, mesh, train=True, donate=False)
    p_stack = shard_to_mesh(stack_for_clients(params, C), mesh)
    o_stack = shard_to_mesh(stack_for_clients(opt.init(params), C), mesh)
    s_stack = shard_to_mesh(stack_for_clients(stats, C), mesh)
    rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(C))
    _, _, new_stats, loss, _ = step(p_stack, o_stack, s_stack, x, labels, rngs)

    model = build_model("VGG16_CIFAR10")
    ref_loss, ref_stats = _ref_loss(model, params, stats, x[0], labels[0],
                                    jax.random.fold_in(rng, 0), True)
    np.testing.assert_allclose(float(loss[0]), float(ref_loss), rtol=1e-4)
    ref_leaves = dict(jax.tree_util.tree_leaves_with_path(ref_stats))
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            jax.tree_util.tree_map(lambda a: np.asarray(a[0]), new_stats)):
        np.testing.assert_allclose(leaf, np.asarray(ref_leaves[path]),
                                   rtol=1e-4, atol=1e-5, err_msg=str(path))


def test_single_stage_pipeline_degenerates(eight_devices):
    """cuts=[] (whole model on one 'stage') — the reference's layers [0,0]
    whole-model client (src/Server.py:241-243)."""
    mb, M = 2, 3
    pipe = PipelineModel(
        "KWT_SPEECHCOMMANDS", [],
        jax.ShapeDtypeStruct((mb, 40, 98), jnp.float32),
        num_microbatches=M)
    assert pipe.n_stages == 1
    mesh = make_mesh(1, 1, eight_devices[:1])
    variables = init_pipeline_variables(
        pipe, jax.random.key(0), jax.ShapeDtypeStruct((mb, 40, 98),
                                                      jnp.float32))
    x = jax.random.normal(jax.random.key(1), (1, M, mb, 40, 98))
    labels = jax.random.randint(jax.random.key(2), (1, M, mb), 0, 10)
    opt = optax.sgd(0.1)
    step = make_train_step(pipe, opt, mesh, train=False, donate=False)
    out = step(stack_for_clients(variables["params"], 1),
               stack_for_clients(opt.init(variables["params"]), 1),
               stack_for_clients({}, 1), x, labels,
               jax.random.key(5)[None])
    model = build_model("KWT_SPEECHCOMMANDS")
    ref_loss, _ = _ref_loss(model, variables["params"], {}, x[0], labels[0],
                            jax.random.key(9), False)
    np.testing.assert_allclose(float(out[3][0]), float(ref_loss), rtol=1e-5)


def test_fedavg_step_on_mesh(eight_devices):
    mesh = make_mesh(4, 2, eight_devices)
    fedavg = make_fedavg_step(mesh)
    params = {"w": jnp.stack([jnp.full((3,), float(i + 1))
                              for i in range(4)])}
    weights = jnp.array([1.0, 1.0, 1.0, 5.0])
    out = fedavg(shard_to_mesh(params, mesh), weights)
    expect = (1 + 2 + 3 + 4 * 5) / 8.0
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.full((4, 3), expect), rtol=1e-6)


def test_bert_pipeline_int_tokens(eight_devices):
    """Token-id (int) stage-0 input survives the float wire exactly."""
    mb, M, cuts = 2, 2, [7]
    kw = dict(vocab_size=97, hidden_size=32, num_heads=2,
              intermediate_size=64, max_position_embeddings=64)
    pipe = PipelineModel(
        "BERT_AGNEWS", cuts, jax.ShapeDtypeStruct((mb, 16), jnp.int32),
        num_microbatches=M, model_kwargs=kw)
    mesh = make_mesh(1, 2, eight_devices[:2])
    variables = init_pipeline_variables(
        pipe, jax.random.key(0), jax.ShapeDtypeStruct((mb, 16), jnp.int32))
    x = jax.random.randint(jax.random.key(1), (1, M, mb, 16), 0, 97)
    labels = jax.random.randint(jax.random.key(2), (1, M, mb), 0, 4)
    opt = optax.adamw(1e-3)
    step = make_train_step(pipe, opt, mesh, train=False, donate=False)
    out = step(stack_for_clients(variables["params"], 1),
               stack_for_clients(opt.init(variables["params"]), 1),
               stack_for_clients({}, 1), x, labels, jax.random.key(5)[None])
    model = build_model("BERT_AGNEWS", **kw)
    ref_loss, _ = _ref_loss(model, variables["params"], {}, x[0], labels[0],
                            jax.random.key(9), False)
    np.testing.assert_allclose(float(out[3][0]), float(ref_loss), rtol=1e-5)


@pytest.mark.parametrize("n_stage_devs", [1, 2])
def test_virtual_stages_match_full_mesh(eight_devices, n_stage_devs):
    """4 pipeline stages blocked onto a smaller stage axis (k=4 on one
    device, k=2 on two) must produce the same loss and updated params as
    the one-stage-per-device mapping — the single-chip split path."""
    mb, M, C, cuts = 2, 3, 2, [1, 2, 3]
    kw = dict(vocab_size=64, hidden_size=32, num_heads=2,
              intermediate_size=64, max_position_embeddings=16, n_block=4)
    x_struct = jax.ShapeDtypeStruct((mb, 16), jnp.int32)

    def run(a):
        pipe = PipelineModel("BERT_AGNEWS", cuts, x_struct,
                             num_microbatches=M, model_kwargs=kw)
        mesh = make_mesh(C, a, eight_devices[:C * a])
        variables = init_pipeline_variables(pipe, jax.random.key(0),
                                            x_struct)
        params = variables["params"]
        opt = optax.sgd(1e-2)
        x = jax.random.randint(jax.random.key(1), (C, M, mb, 16), 0, 64)
        labels = jax.random.randint(jax.random.key(2), (C, M, mb), 0, 4)
        step = make_train_step(pipe, opt, mesh, train=False, donate=False)
        new_p, _, _, loss, _ = step(
            shard_to_mesh(stack_for_clients(params, C), mesh),
            shard_to_mesh(stack_for_clients(opt.init(params), C), mesh),
            shard_to_mesh(stack_for_clients({}, C), mesh),
            x, labels, jax.random.split(jax.random.key(3), C))
        return (jax.tree_util.tree_map(np.asarray, new_p),
                np.asarray(loss))

    got_p, got_loss = run(n_stage_devs)
    ref_p, ref_loss = run(4)
    np.testing.assert_allclose(got_loss, ref_loss, rtol=1e-6)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(got_p),
            jax.tree_util.tree_leaves_with_path(ref_p)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                   err_msg=str(path))


def test_wire_packing_roundtrip_pytree_boundary():
    """_to_wire/_from_wire must be exact for multi-leaf pytree
    boundaries with mixed dtypes (BERT's (hidden, bool mask) wire) and
    pad to the widest boundary without corrupting narrower ones."""
    pipe = PipelineModel(
        "BERT_AGNEWS", cuts=[3],
        example_input=jax.ShapeDtypeStruct((2, 16), jnp.int32),
        num_microbatches=2,
        model_kwargs=dict(hidden_size=32, num_heads=2,
                          intermediate_size=64, vocab_size=128,
                          max_position_embeddings=16, n_block=2))
    rng = np.random.default_rng(0)
    # boundary[:-1]: only stage INPUTS ride the wire — the final output
    # returns through its own exact-width switch slot
    for struct in pipe.boundary[:-1]:
        leaves, treedef = jax.tree_util.tree_flatten(struct)
        data = [
            (rng.random(l.shape) < 0.5) if l.dtype == jnp.bool_
            else rng.integers(0, 100, l.shape).astype(l.dtype)
            if jnp.issubdtype(l.dtype, jnp.integer)
            else rng.standard_normal(l.shape).astype(l.dtype)
            for l in leaves
        ]
        tree = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(d) for d in data])
        wire = pipe._to_wire(tree)
        assert wire.shape == (leaves[0].shape[0], pipe.max_flat)
        assert wire.dtype == pipe.wire_dtype
        back = jax.tree_util.tree_unflatten(
            treedef, jax.tree_util.tree_leaves(
                pipe._from_wire(wire, struct)))
        for orig, rt in zip(jax.tree_util.tree_leaves(tree),
                            jax.tree_util.tree_leaves(back)):
            assert orig.dtype == rt.dtype and orig.shape == rt.shape
            np.testing.assert_array_equal(np.asarray(orig),
                                          np.asarray(rt))


def test_wire_width_excludes_final_logits():
    """The hop wire is sized to the widest stage INPUT, not the final
    logits: an LLM head (seq x vocab, ~16x wider than hidden) must not
    inflate every ppermute buffer and scan carry (round-5 memory fix —
    the config-5 plan showed the logits-wide wire costing ~2 GB/chip)."""
    tiny = dict(vocab_size=512, hidden_size=16, num_heads=2,
                num_kv_heads=2, intermediate_size=32, n_block=2)
    pipe = PipelineModel(
        "TinyLlama_TINYSTORIES", cuts=[2],
        example_input=jax.ShapeDtypeStruct((2, 8), jnp.int32),
        num_microbatches=2, model_kwargs=tiny)
    # interior boundary = (mb, 8, 16) hidden -> 128/sample; logits =
    # (mb, 8, 512) -> 4096/sample
    assert pipe.n_out == 8 * 512
    assert pipe.max_flat == 8 * 16
    assert pipe.max_flat < pipe.n_out
