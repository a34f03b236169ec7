"""Streamed-loss / selective-remat / stage-sliced-params equivalence.

Round-6 perf tentpole: the compiled pipeline's default path changed to
(a) per-tick streamed loss (no ``(M, mb, n_out)`` logits collect
buffer), (b) per-stage ``wide`` remat policy instead of the blanket
checkpoint, and (c) an optional stage-sliced flat parameter wire.  All
three must be NUMERICALLY INVISIBLE: these tests pin each one against
the materialized / blanket-remat / replicated oracle at fp32 tolerance.

Gradient comparison trick: the steps run ``optax.sgd(1.0)``, so the
difference between initial and updated params IS the gradient tree —
asserting updated params match asserts loss AND grads match.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from split_learning_tpu.parallel import (
    PipelineModel, make_train_step, make_sliced_train_step, make_mesh,
    slice_params_for_mesh, shard_sliced_opt_to_mesh,
)
from split_learning_tpu.parallel.pipeline import (
    init_pipeline_variables, stack_for_clients, shard_to_mesh,
)

TINY_BERT = dict(vocab_size=97, hidden_size=32, num_heads=2,
                 intermediate_size=64, max_position_embeddings=64,
                 n_block=6)
X_STRUCT = jax.ShapeDtypeStruct((2, 16), jnp.int32)


def _run_step(cuts, M, C, A, devices, *, stream_loss, remat,
              sliced=False, train=False):
    """One sgd(1.0) train step; returns (loss[C], full param tree of
    client 0 after the update)."""
    pipe = PipelineModel("BERT_AGNEWS", cuts, X_STRUCT,
                         num_microbatches=M, model_kwargs=TINY_BERT,
                         stream_loss=stream_loss, remat=remat)
    mesh = make_mesh(C, A, devices[:C * A])
    variables = init_pipeline_variables(pipe, jax.random.key(0), X_STRUCT)
    params = variables["params"]
    opt = optax.sgd(1.0)
    x = jax.random.randint(jax.random.key(1), (C, M, 2, 16), 0, 97)
    labels = jax.random.randint(jax.random.key(2), (C, M, 2), 0, 4)
    rngs = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(3), i))(
        jnp.arange(C))
    stats_c = shard_to_mesh(stack_for_clients({}, C), mesh)
    if sliced:
        layout = pipe.stage_param_layout(A)
        step = make_sliced_train_step(pipe, opt, mesh, train=train,
                                      donate=False)
        p_c = slice_params_for_mesh(pipe, params, C, mesh)
        o_c = shard_sliced_opt_to_mesh(stack_for_clients(
            opt.init(jnp.zeros((A * layout.seg_len,), jnp.float32)), C),
            mesh)
        new_p, _, _, loss = step(p_c, o_c, stats_c, x, labels, rngs)
        tree = layout.unpack(np.asarray(new_p)[0])
        return np.asarray(loss), tree
    step = make_train_step(pipe, opt, mesh, train=train, donate=False)
    p_c = shard_to_mesh(stack_for_clients(params, C), mesh)
    o_c = shard_to_mesh(stack_for_clients(opt.init(params), C), mesh)
    new_p, _, _, loss, _ = step(p_c, o_c, stats_c, x, labels, rngs)
    tree = jax.tree_util.tree_map(lambda a: np.asarray(a)[0], new_p)
    return np.asarray(loss), tree


def _assert_trees_close(got, ref, rtol=2e-5, atol=1e-6):
    ref_leaves = dict(jax.tree_util.tree_leaves_with_path(ref))
    got_leaves = jax.tree_util.tree_leaves_with_path(got)
    assert len(got_leaves) == len(ref_leaves)
    for path, leaf in got_leaves:
        np.testing.assert_allclose(np.asarray(leaf),
                                   np.asarray(ref_leaves[path]),
                                   rtol=rtol, atol=atol,
                                   err_msg=str(path))


@pytest.mark.parametrize("cuts", [[3], [2, 4]])
def test_streamed_loss_matches_materialized(eight_devices, cuts):
    """Per-tick loss accumulation == collect-then-CE, loss and grads
    (2- and 3-stage cuts; single-chip virtual stages)."""
    l_mat, t_mat = _run_step(cuts, 3, 1, 1, eight_devices,
                             stream_loss=False, remat="all")
    l_str, t_str = _run_step(cuts, 3, 1, 1, eight_devices,
                             stream_loss=True, remat="all")
    np.testing.assert_allclose(l_str, l_mat, rtol=1e-5)
    _assert_trees_close(t_str, t_mat)


@pytest.mark.slow
def test_streamed_loss_matches_materialized_on_mesh(eight_devices):
    """Same parity with a REAL 2-wide stage axis (ppermute hops and the
    exact-width tail slot in play)."""
    l_mat, t_mat = _run_step([3], 3, 2, 2, eight_devices,
                             stream_loss=False, remat="all")
    l_str, t_str = _run_step([3], 3, 2, 2, eight_devices,
                             stream_loss=True, remat="all")
    np.testing.assert_allclose(l_str, l_mat, rtol=1e-5)
    _assert_trees_close(t_str, t_mat)


def test_remat_policies_equivalent(eight_devices):
    """'wide' and 'none' gradients agree with the blanket 'all' policy
    (remat changes scheduling, never math)."""
    l_all, t_all = _run_step([3], 3, 1, 1, eight_devices,
                             stream_loss=True, remat="all")
    l_wide, t_wide = _run_step([3], 3, 1, 1, eight_devices,
                               stream_loss=True, remat="wide")
    l_none, t_none = _run_step([3], 3, 1, 1, eight_devices,
                               stream_loss=True, remat="none")
    np.testing.assert_allclose(l_wide, l_all, rtol=1e-6)
    np.testing.assert_allclose(l_none, l_all, rtol=1e-6)
    _assert_trees_close(t_wide, t_all)
    _assert_trees_close(t_none, t_all)


@pytest.mark.slow
def test_sliced_params_match_replicated(eight_devices):
    """Stage-sliced flat param wire == replicated full tree after one
    update (C=2 clients x A=2 stage devices; no grad psum ran on the
    sliced path)."""
    l_rep, t_rep = _run_step([3], 3, 2, 2, eight_devices,
                             stream_loss=True, remat="wide")
    l_sl, t_sl = _run_step([3], 3, 2, 2, eight_devices,
                           stream_loss=True, remat="wide", sliced=True)
    np.testing.assert_allclose(l_sl, l_rep, rtol=1e-5)
    assert set(t_sl) == set(t_rep)
    _assert_trees_close(t_sl, t_rep)


def test_stage_param_layout_roundtrip():
    """pack -> unpack is exact for every (A | n_stages) blocking,
    including stages with no parametric layers."""
    pipe = PipelineModel("BERT_AGNEWS", [2, 4], X_STRUCT,
                         num_microbatches=2, model_kwargs=TINY_BERT)
    variables = init_pipeline_variables(pipe, jax.random.key(0), X_STRUCT)
    params = variables["params"]
    for A in (1, 3):
        layout = pipe.stage_param_layout(A)
        wire = layout.pack(params)
        assert wire.shape == (A, layout.seg_len)
        back = layout.unpack(wire)
        ref = dict(jax.tree_util.tree_leaves_with_path(params))
        got = jax.tree_util.tree_leaves_with_path(back)
        assert len(got) == len(ref)
        for path, leaf in got:
            assert leaf.dtype == ref[path].dtype
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(ref[path]))


def test_wide_policy_selects_wide_stages_only():
    """'wide' remats exactly the stages whose boundary exceeds the
    threshold; 'all'/'none' and the legacy bools map as documented."""
    mk = lambda **kw: PipelineModel(  # noqa: E731
        "BERT_AGNEWS", [3], X_STRUCT, num_microbatches=2,
        model_kwargs=TINY_BERT, **kw)
    # tiny BERT boundaries are ~16*32=512 floats/sample: below the
    # default threshold -> no remat anywhere
    assert mk().stage_remat == [False, False]
    # force the threshold under the boundary width -> everything remats
    assert mk(remat_threshold=100).stage_remat == [True, True]
    assert mk(remat="all").stage_remat == [True, True]
    assert mk(remat="none", remat_threshold=100).stage_remat == \
        [False, False]
    assert mk(remat=True).stage_remat == [True, True]
    assert mk(remat=False).stage_remat == [False, False]
    with pytest.raises(ValueError, match="remat"):
        mk(remat="sometimes")


def test_streamed_loss_is_default_and_buffers_absent():
    """The default pipe streams its loss, and a wide-output head under
    'wide' is rematerialized (the combination that eliminates the
    logits collect buffer at LLM scale — bench._llama_memory_plan)."""
    tiny = dict(vocab_size=512, hidden_size=16, num_heads=2,
                num_kv_heads=2, intermediate_size=32, n_block=2)
    pipe = PipelineModel(
        "TinyLlama_TINYSTORIES", cuts=[2],
        example_input=jax.ShapeDtypeStruct((2, 8), jnp.int32),
        num_microbatches=2, model_kwargs=tiny, remat_threshold=1000)
    assert pipe.stream_loss
    # head stage output (8*512/sample) exceeds the threshold
    assert pipe.stage_remat[-1]


def test_scan_unroll_policy(eight_devices):
    """'auto' fully unrolls short tick loops on CPU meshes (the
    while-loop thunk serialization fix), caps at SCAN_UNROLL_MAX_TICKS,
    and an explicit int always wins."""
    mk = lambda **kw: PipelineModel(  # noqa: E731
        "BERT_AGNEWS", [3], X_STRUCT, num_microbatches=kw.pop("M", 3),
        model_kwargs=TINY_BERT, **kw)
    m1 = make_mesh(1, 1, eight_devices[:1])
    m2 = make_mesh(1, 2, eight_devices[:2])
    assert mk().scan_unroll_for(m1) == 3          # M + A - 1 = 3 ticks
    assert mk().scan_unroll_for(m2) == 4
    assert mk(M=20).scan_unroll_for(m1) == 1      # too long: keep scan
    assert mk(scan_unroll=2).scan_unroll_for(m1) == 2
    with pytest.raises(ValueError, match="scan_unroll"):
        mk(scan_unroll="always")


def test_streamed_loss_traces_under_bf16_compute(eight_devices):
    """bf16 compute dtype: the fused loss must come back f32 or
    lax.switch rejects the branch signatures (caught by the round-6
    quickstart drive — every interior branch returns f32 zeros).
    Trace-only (`.lower`), so no XLA compile."""
    struct = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    pipe = PipelineModel("VGG16_CIFAR10", [7], struct,
                         num_microbatches=2,
                         model_kwargs={"dtype": jnp.bfloat16})
    mesh = make_mesh(1, 1, eight_devices[:1])
    variables = init_pipeline_variables(pipe, jax.random.key(0), struct)
    opt = optax.sgd(0.1)
    step = make_train_step(pipe, opt, mesh, donate=False)
    p_c = stack_for_clients(variables["params"], 1)
    step.lower(p_c, stack_for_clients(opt.init(variables["params"]), 1),
               stack_for_clients(variables["batch_stats"], 1),
               jax.ShapeDtypeStruct((1, 2, 2, 32, 32, 3), jnp.float32),
               jax.ShapeDtypeStruct((1, 2, 2), jnp.int32),
               jax.eval_shape(lambda: jax.random.split(
                   jax.random.key(0), 1)))
