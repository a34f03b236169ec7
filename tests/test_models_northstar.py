"""North-star models (BASELINE.json configs #3/#5): ResNet-50 and the
TinyLlama-style decoder — golden split tests + a 4-stage compiled
pipeline run on the virtual 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

pytestmark = pytest.mark.slow  # compiles real split programs

from split_learning_tpu.models import build_model, num_layers, shard_params

TINY_LLAMA = dict(vocab_size=128, hidden_size=32, num_heads=4,
                  num_kv_heads=2, intermediate_size=64, n_block=4)


def _split_apply(name, variables, x, cuts, train=False, **kw):
    """Apply consecutive shards for an arbitrary cut list."""
    specs = build_model(name, **kw).specs
    bounds = [0] + list(cuts) + [len(specs)]
    h = x
    for a, b in zip(bounds[:-1], bounds[1:]):
        m = build_model(name, start_layer=a, end_layer=b, **kw)
        v = {col: shard_params(tree, specs, a, b)
             for col, tree in variables.items()}
        h = m.apply(v, h, train=train)
    return h


def test_resnet50_21_layers_and_3way_split():
    assert num_layers("ResNet50_CIFAR100") == 21
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    model = build_model("ResNet50_CIFAR100")
    variables = model.init(jax.random.key(0), x, train=False)
    ref = model.apply(variables, x, train=False)
    assert ref.shape == (2, 100)
    # the target config's 3-way split (cut=[3,6]) and others
    for cuts in ([3, 6], [4, 12], [10]):
        out = _split_apply("ResNet50_CIFAR100", variables, x, cuts)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"cuts={cuts}")


def test_tinyllama_split_and_causal_shift():
    name = "TinyLlama_TINYSTORIES"
    assert num_layers(name, **TINY_LLAMA) == 7   # 1+4+1+1
    x = jax.random.randint(jax.random.key(1), (2, 16), 0, 128)
    model = build_model(name, **TINY_LLAMA)
    variables = model.init(jax.random.key(0), x, train=False)
    ref = model.apply(variables, x, train=False)
    assert ref.shape == (2, 16, 128)
    for cuts in ([1, 3, 5], [2]):
        out = _split_apply(name, variables, x, cuts, **TINY_LLAMA)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"cuts={cuts}")
    # causality: logits at position t must not depend on tokens > t
    x2 = x.at[:, -1].set((x[:, -1] + 1) % 128)
    out2 = model.apply(variables, x2, train=False)
    np.testing.assert_allclose(np.asarray(out2[:, :-1]),
                               np.asarray(ref[:, :-1]), rtol=1e-5,
                               atol=1e-5)
    assert not np.allclose(np.asarray(out2[:, -1]),
                           np.asarray(ref[:, -1]))


def test_tinyllama_4stage_pipeline_mesh(eight_devices):
    """Full compiled train step: 4-stage pipeline x 2 clients of the
    decoder on the virtual mesh, next-token loss decreasing."""
    from jax.sharding import Mesh
    from split_learning_tpu.parallel.pipeline import (
        PipelineModel, init_pipeline_variables, make_train_step,
        shard_to_mesh, stack_for_clients,
    )

    mesh = Mesh(np.array(eight_devices).reshape(2, 4), ("client", "stage"))
    mb, seq, M = 2, 16, 2
    pipe = PipelineModel(
        "TinyLlama_TINYSTORIES", cuts=[1, 3, 5],
        example_input=jax.ShapeDtypeStruct((mb, seq), jnp.int32),
        num_microbatches=M, model_kwargs=TINY_LLAMA)
    variables = init_pipeline_variables(
        pipe, jax.random.key(0), jax.ShapeDtypeStruct((mb, seq), jnp.int32))
    params, stats = variables["params"], variables.get("batch_stats", {})
    opt = optax.adamw(1e-3)
    params_c = shard_to_mesh(stack_for_clients(params, 2), mesh)
    opt_c = shard_to_mesh(stack_for_clients(opt.init(params), 2), mesh)
    stats_c = shard_to_mesh(stack_for_clients(stats, 2), mesh)
    step = make_train_step(pipe, opt, mesh)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, size=(2, M, mb, seq + 1))
    x = jnp.asarray(ids[..., :-1], jnp.int32)
    labels = jnp.asarray(ids[..., 1:], jnp.int32)
    rngs = jax.vmap(jax.random.key)(jnp.arange(2))
    losses = []
    for _ in range(4):
        params_c, opt_c, stats_c, loss, _ = step(params_c, opt_c, stats_c,
                                              x, labels, rngs)
        losses.append(float(np.asarray(loss).mean()))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
