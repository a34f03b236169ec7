"""ZeRO-1 sharded optimizer state + bf16-moment AdamW.

The ZeRO-1 step (moments flattened, padded, sharded along ``stage``;
params rebuilt by all_gather) must train identically to the dense
pipelined step with replicated AdamW state, up to bf16 moment rounding —
the memory layout changes, the math must not (VERDICT r2 item 3).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from split_learning_tpu.parallel import (
    PipelineModel, make_train_step, make_mesh,
)
from split_learning_tpu.parallel.pipeline import (
    init_pipeline_variables, stack_for_clients, shard_to_mesh,
)
from split_learning_tpu.parallel.zero import (
    adamw_bf16_states, init_zero1_opt_state, make_zero1_train_step,
    scale_by_adam_bf16, shard_zero1_to_mesh,
)


def test_scale_by_adam_bf16_tracks_optax_adam():
    params = {"w": jnp.linspace(-1.0, 1.0, 32).reshape(8, 4),
              "b": jnp.ones((4,))}
    ref = optax.scale_by_adam()
    low = scale_by_adam_bf16()
    s_ref, s_low = ref.init(params), low.init(params)
    assert s_low.mu["w"].dtype == jnp.bfloat16
    assert s_low.nu["w"].dtype == jnp.bfloat16
    key = jax.random.key(0)
    for i in range(5):
        key, k = jax.random.split(key)
        g = jax.tree_util.tree_map(
            lambda p: jax.random.normal(k, p.shape), params)
        u_ref, s_ref = ref.update(g, s_ref, params)
        u_low, s_low = low.update(g, s_low, params)
        for name in params:
            np.testing.assert_allclose(
                np.asarray(u_low[name]), np.asarray(u_ref[name]),
                rtol=2e-2, atol=2e-2, err_msg=f"step {i} {name}")


def test_adamw_bf16_states_trains_quadratic():
    """bf16-moment AdamW minimizes a simple quadratic like f32 AdamW."""
    opt = adamw_bf16_states(0.1, weight_decay=0.0)
    params = {"w": jnp.full((8,), 5.0)}
    state = opt.init(params)
    for _ in range(60):
        g = jax.tree_util.tree_map(lambda w: 2 * w, params)
        upd, state = opt.update(g, state, params)
        params = optax.apply_updates(params, upd)
    assert float(jnp.abs(params["w"]).max()) < 0.5


@pytest.mark.slow
def test_zero1_step_matches_dense_adamw(eight_devices):
    """ZeRO-1 (sharded bf16 moments) ≡ dense replicated AdamW, up to
    bf16 rounding, on a real 2-stage pipelined step."""
    mb, M, C, cuts = 2, 2, 2, [2]
    kw = dict(vocab_size=64, hidden_size=32, num_heads=2,
              intermediate_size=64, max_position_embeddings=16, n_block=2)
    x_struct = jax.ShapeDtypeStruct((mb, 16), jnp.int32)
    pipe = PipelineModel("BERT_AGNEWS", cuts, x_struct,
                         num_microbatches=M, model_kwargs=kw)
    mesh = make_mesh(C, 2, eight_devices[:C * 2])
    variables = init_pipeline_variables(pipe, jax.random.key(0), x_struct)
    params = variables["params"]
    x = jax.random.randint(jax.random.key(1), (C, M, mb, 16), 0, 64)
    labels = jax.random.randint(jax.random.key(2), (C, M, mb), 0, 4)
    rngs = jax.random.split(jax.random.key(3), C)
    lr, wd = 1e-2, 0.01

    # dense path: replicated f32 adamw state
    opt = optax.adamw(lr, weight_decay=wd)
    dense = make_train_step(pipe, opt, mesh, train=False, donate=False)
    p0 = shard_to_mesh(stack_for_clients(params, C), mesh)
    dp, _, _, dense_loss, _ = dense(
        p0, shard_to_mesh(stack_for_clients(opt.init(params), C), mesh),
        shard_to_mesh(stack_for_clients({}, C), mesh), x, labels, rngs)

    # ZeRO-1 path: sharded bf16 moments
    z_opt = shard_zero1_to_mesh(init_zero1_opt_state(params, C, 2), mesh)
    zstep = make_zero1_train_step(pipe, mesh, learning_rate=lr,
                                  weight_decay=wd, train=False,
                                  donate=False)
    zp, z_opt2, _, z_loss = zstep(
        p0, z_opt, shard_to_mesh(stack_for_clients({}, C), mesh),
        x, labels, rngs)

    np.testing.assert_allclose(np.asarray(z_loss), np.asarray(dense_loss),
                               rtol=1e-5)
    # moments stay sharded bf16
    assert z_opt2["mu"].dtype == jnp.bfloat16
    assert z_opt2["mu"].shape[0] == C
    # parameter *updates* agree up to bf16 moment rounding
    for (path, a), (_, b), (_, p) in zip(
            jax.tree_util.tree_leaves_with_path(
                jax.tree_util.tree_map(np.asarray, zp)),
            jax.tree_util.tree_leaves_with_path(
                jax.tree_util.tree_map(np.asarray, dp)),
            jax.tree_util.tree_leaves_with_path(
                jax.tree_util.tree_map(
                    np.asarray, shard_to_mesh(
                        stack_for_clients(params, C), mesh)))):
        np.testing.assert_allclose(a - p, b - p, rtol=3e-2, atol=1e-4,
                                   err_msg=str(path))


@pytest.mark.slow
def test_zero1_from_yaml_runs_end_to_end(tmp_path, eight_devices):
    """learning.optimizer: adamw-zero1 from pure YAML (VERDICT r3 item
    3): run_local trains a cut BERT with stage-sharded bf16 moments —
    including the shared-stage-2 sync group the [2, 1] client shape
    creates — and the round succeeds with finite validation."""
    from split_learning_tpu.config import from_dict
    from split_learning_tpu.run import run_local
    from split_learning_tpu.runtime.log import Logger

    cfg = from_dict(dict(
        model="BERT", dataset="AGNEWS", clients=[2, 1],
        global_rounds=1, synthetic_size=16, val_max_batches=1,
        val_batch_size=4, compute_dtype="float32",
        model_kwargs={"hidden_size": 32, "num_heads": 2,
                      "intermediate_size": 64, "n_block": 2},
        log_path=str(tmp_path / "logs"),
        learning={"batch_size": 2, "control_count": 2,
                  "optimizer": "adamw-zero1", "learning-rate": 1e-3},
        distribution={"num_samples": 8},
        checkpoint={"save": False},
        topology={"cut_layers": [2], "force_pipeline": True},
    ))
    res = run_local(cfg, logger=Logger(cfg.log_path, console=False))
    rec = res.history[-1]
    assert rec.ok
    assert rec.val_accuracy is not None
    assert np.isfinite(rec.val_loss)


def test_zero1_rejected_with_clip_or_lora():
    from split_learning_tpu.config import ConfigError, from_dict

    with pytest.raises(ConfigError):
        from_dict({"learning": {"optimizer": "adamw-zero1",
                                "clip_grad_norm": 1.0}})
    with pytest.raises(ConfigError):
        from_dict({"learning": {"optimizer": "adamw-zero1",
                                "lora_rank": 4}})


def test_zero1_rejected_with_tensor_parallel(tmp_path, eight_devices):
    """adamw-zero1 + tensor-parallel must fail fast: the flat moment
    shards are sized to unsharded params, so silently forfeiting TP
    (or mis-sharding moments) is worse than an error."""
    from split_learning_tpu.config import from_dict
    from split_learning_tpu.runtime.context import MeshContext
    from split_learning_tpu.runtime.plan import plan_clusters, Registration

    cfg = from_dict(dict(
        model="TinyLlama", dataset="TINYSTORIES", clients=[2, 2],
        synthetic_size=8, log_path=str(tmp_path),
        model_kwargs={"hidden_size": 32, "num_heads": 2,
                      "num_kv_heads": 2, "intermediate_size": 64,
                      "n_block": 2},
        learning={"batch_size": 2, "control_count": 2,
                  "optimizer": "adamw-zero1", "learning_rate": 1e-3},
        distribution={"num_samples": 8},
        checkpoint={"save": False},
        topology={"cut_layers": [2], "tensor_parallel": 2,
                  "force_pipeline": True}))
    regs = [Registration(client_id=f"c{s}_{i}", stage=s)
            for s in (1, 2) for i in range(2)]
    plan = plan_clusters(cfg, regs)[0]
    ctx = MeshContext(cfg)
    c, s, cuts, tp, _sp, _ep = ctx._geometry(plan, 2)
    assert tp == 2
    with pytest.raises(ValueError, match="tensor-parallel"):
        ctx._compiled(plan, c, s, cuts, None, (), None, tp=tp)
