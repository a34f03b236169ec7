"""Tensor parallelism: sharding rules, numerical parity with the
unsharded model, and a TP x DP train step on the 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from split_learning_tpu.models import build_model
from split_learning_tpu.parallel.tensor import (
    make_tp_train_step, shard_params_tp, tp_spec,
)

TINY_LLAMA = dict(vocab_size=128, hidden_size=32, num_heads=4,
                  num_kv_heads=4, intermediate_size=64, n_block=2)


def _llama(key=0):
    model = build_model("TinyLlama_TINYSTORIES", **TINY_LLAMA)
    x = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.key(key), x, train=False)["params"]
    return model, params


def test_tp_spec_rules():
    _, params = _llama()
    blk = "layer2"
    attn = params[blk]["attention"]
    q_spec = tp_spec(
        [jax.tree_util.DictKey(blk), jax.tree_util.DictKey("attention"),
         jax.tree_util.DictKey("q_proj"), jax.tree_util.DictKey("kernel")],
        attn["q_proj"]["kernel"])
    assert q_spec == P(None, "model")
    o_spec = tp_spec(
        [jax.tree_util.DictKey(blk), jax.tree_util.DictKey("attention"),
         jax.tree_util.DictKey("o_proj"), jax.tree_util.DictKey("kernel")],
        attn["o_proj"]["kernel"])
    assert o_spec == P("model", None)
    norm_spec = tp_spec(
        [jax.tree_util.DictKey(blk), jax.tree_util.DictKey("input_norm"),
         jax.tree_util.DictKey("scale")],
        params[blk]["input_norm"]["scale"])
    assert norm_spec == P()


def test_tp_forward_matches_unsharded(eight_devices):
    mesh = Mesh(np.array(eight_devices).reshape(8), ("model",))
    model, params = _llama()
    x = jax.random.randint(jax.random.key(1), (2, 16), 0, 128)
    ref = model.apply({"params": params}, x, train=False)
    params_tp = shard_params_tp(params, mesh)
    # params really are distributed
    k = params_tp["layer2"]["attention"]["q_proj"]["kernel"]
    assert len(k.sharding.device_set) == 8
    out = jax.jit(lambda p, x: model.apply({"params": p}, x,
                                           train=False))(params_tp, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_tp_dp_train_step(eight_devices):
    """2-way DP x 4-way TP: loss decreases, params stay TP-sharded."""
    mesh = Mesh(np.array(eight_devices).reshape(2, 4), ("data", "model"))
    model, params = _llama()
    opt = optax.adamw(1e-3)
    params = shard_params_tp(params, mesh)
    opt_state = opt.init(params)
    step = make_tp_train_step(model, opt, mesh, dp_axis="data")
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, size=(4, 17))
    x = jnp.asarray(ids[:, :-1], jnp.int32)
    y = jnp.asarray(ids[:, 1:], jnp.int32)
    losses = []
    for i in range(4):
        params, opt_state, loss = step(params, opt_state, x, y,
                                       jax.random.key(i))
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    k = params["layer2"]["attention"]["q_proj"]["kernel"]
    assert len(k.sharding.device_set) >= 4


@pytest.mark.slow
@pytest.mark.parametrize("family", ["llama", "bert"])
def test_pp_tp_pipeline_matches_pp_only(eight_devices, family):
    """PP x TP in ONE mesh (VERDICT r3 item 2): the pipelined train step
    on a (client=2, stage=2, model=2) mesh — manual ppermute pipeline
    over `stage`, GSPMD tensor sharding over `model` — must produce the
    same losses and updated params as the plain (client=2, stage=2)
    pipeline, with TP params genuinely distributed.  The BERT case also
    covers a pytree stage boundary (hidden, attention_mask) crossing
    the wire under an auto `model` axis."""
    from split_learning_tpu.parallel.pipeline import (
        PipelineModel, init_pipeline_variables, make_train_step,
        shard_to_mesh, stack_for_clients,
    )

    mb, m = 2, 2
    if family == "llama":
        name = "TinyLlama_TINYSTORIES"
        kw = dict(TINY_LLAMA, n_block=2)
        n_out = kw["vocab_size"]
        label_shape = (2, m, mb, 16)
        tp_probe = ("layer2", "attention", "q_proj", "kernel")
    else:
        name = "BERT_AGNEWS"
        kw = dict(hidden_size=32, num_heads=2, intermediate_size=64,
                  n_block=2, vocab_size=97, max_position_embeddings=64)
        n_out = 4
        label_shape = (2, m, mb)
        tp_probe = ("layer2", "attention", "query", "kernel")
    struct = jax.ShapeDtypeStruct((mb, 16), jnp.int32)
    pipe = PipelineModel(name, cuts=[2], example_input=struct,
                         num_microbatches=m, model_kwargs=kw)
    variables = init_pipeline_variables(pipe, jax.random.key(0), struct)
    params, stats = variables["params"], variables.get("batch_stats", {})
    opt = optax.sgd(1e-2)
    opt_state = opt.init(params)
    x = jax.random.randint(jax.random.key(2), (2, m, mb, 16), 0,
                           kw["vocab_size"], jnp.int32)
    y = jax.random.randint(jax.random.key(3), label_shape, 0, n_out,
                           jnp.int32)
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

    mesh_pptp = Mesh(np.array(eight_devices).reshape(2, 2, 2),
                     ("client", "stage", "model"))
    p3, _, _, loss3 = run(mesh_pptp)

    np.testing.assert_allclose(np.asarray(loss2), np.asarray(loss3),
                               rtol=2e-4)
    for l2, l3 in zip(jax.tree_util.tree_leaves(p2),
                      jax.tree_util.tree_leaves(p3)):
        np.testing.assert_allclose(np.asarray(l2), np.asarray(l3),
                                   rtol=2e-3, atol=1e-5)
    k = p3
    for part in tp_probe:
        k = k[part]
    assert "model" in tuple(k.sharding.spec)
