"""The state-space scan's kernels (``ops/ssd_scan.py``, through the Pallas
interpreter here) against the recurrence a position at a time, and against
the plain chunked form at a group's real widths: forward and every
gradient, at a row several chunks long, at one that is no multiple of the
chunk, one shorter than a chunk, and with a decay so strong that a chunk's
whole decay underflows."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.ops.ssd_scan import (
    ssd_scan, ssd_scan_chunked, ssd_scan_reference,
)

NAMES = ("x", "dt", "A", "B", "C", "D_skip")


def _operands(seq, dt_scale=0.1, seed=0, rows=2, heads=4, head_dim=8,
              groups=2, state=16, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (rows, seq, heads, head_dim))
    dt = dt_scale * jax.nn.softplus(jax.random.normal(k[1],
                                                      (rows, seq, heads)))
    a = -jnp.exp(2 * jax.random.uniform(k[2], (heads,)))
    b = jax.random.normal(k[3], (rows, seq, groups, state))
    c = jax.random.normal(k[4], (rows, seq, groups, state))
    skip = 1 + 0.3 * jax.random.normal(k[5], (heads,))
    w = jax.random.normal(k[6], x.shape)
    return (x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype),
            skip), w


def _with_skip(scan):
    """``y_t = S_t C_t + D x_t``, as the mixer forms it around the scan."""
    def fn(x, dt, a, b, c, skip):
        return scan(x, dt, a, b, c).astype(jnp.float32) \
            + skip[:, None] * x.astype(jnp.float32)
    return fn


CASES = {"four_chunks": (64, 16, 0.1), "three_chunks_and_a_bit": (50, 16, 0.1),
         "shorter_than_a_chunk": (11, 16, 0.1), "strong_decay": (64, 16, 8.0)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_and_every_gradient_match_the_recurrence(case):
    seq, chunk, dt_scale = CASES[case]
    args, w = _operands(seq, dt_scale)
    mine = _with_skip(lambda *a: ssd_scan(*a, chunk))
    ref = _with_skip(ssd_scan_reference)
    y, y_r = mine(*args), ref(*args)
    scale = float(jnp.abs(y_r).max())
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=1e-4, atol=1e-6 * scale)
    g = jax.grad(lambda *a: (mine(*a) * w).sum(), argnums=range(6))(*args)
    g_r = jax.grad(lambda *a: (ref(*a) * w).sum(), argnums=range(6))(*args)
    for name, got, want in zip(NAMES, g, g_r):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-3,
            atol=1e-4 * float(jnp.abs(want).max()), err_msg=name)


def test_a_chunks_decay_may_underflow():
    """``dt A`` sums to about -100 over a chunk of 128: ``exp`` of it is a
    float32 subnormal (nought in bfloat16, and ``exp(+100)``, which the
    factored form would need, is no float32 at all).  The scan stays
    finite and on the recurrence, forward and backward, in float32 and
    with bfloat16 operands."""
    seq, chunk = 320, 128
    (x, _, _, b, c, skip), w = _operands(seq, heads=2, groups=1)
    dt = jnp.full(x.shape[:3], 0.78)
    a = jnp.asarray([-1.0, -0.01])          # one head forgets, one keeps
    whole = np.exp(np.float32(chunk * 0.78 * -1.0))     # numpy keeps subnormals
    assert 0 < float(whole) < 1e-38
    assert float(whole.astype(jnp.bfloat16)) == 0
    mine = _with_skip(lambda *t: ssd_scan(*t, chunk))
    ref = _with_skip(ssd_scan_reference)
    args = (x, dt, a, b, c, skip)
    y_r = ref(*args)
    np.testing.assert_allclose(np.asarray(mine(*args)), np.asarray(y_r),
                               rtol=1e-4, atol=1e-5 * float(
                                   jnp.abs(y_r).max()))
    g = jax.grad(lambda *t: (mine(*t) * w).sum(), argnums=range(6))(*args)
    g_r = jax.grad(lambda *t: (ref(*t) * w).sum(), argnums=range(6))(*args)
    for name, got, want in zip(NAMES, g, g_r):
        assert bool(jnp.isfinite(got).all()), name
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-3,
            atol=1e-4 * float(jnp.abs(want).max()), err_msg=name)
    # bfloat16 operands, float32 decays and state: finite, and as near the
    # float32 result as bfloat16 products are
    low = tuple(v.astype(jnp.bfloat16) if i in (0, 3, 4) else v
                for i, v in enumerate(args))
    y_low = mine(*low)
    g_low = jax.grad(lambda *t: (mine(*t) * w).sum(),
                     argnums=range(6))(*low)
    assert y_low.dtype == jnp.float32
    assert all(bool(jnp.isfinite(v).all()) for v in (y_low, *g_low))
    assert float(jnp.abs(y_low - y_r).max()) < 0.05 * float(
        jnp.abs(y_r).max())
    assert g_low[0].dtype == jnp.bfloat16


def test_the_state_is_carried_from_chunk_to_chunk():
    """With a weak decay the last chunk's result hangs on the first
    chunk's input: a scan that started every chunk from nought would not
    see it."""
    args, _ = _operands(64, dt_scale=0.02)
    x = args[0]
    moved = (x.at[:, :16].add(1.0), *args[1:5])
    delta = ssd_scan(*moved, 16) - ssd_scan(*args[:5], 16)
    want = ssd_scan_reference(*moved) - ssd_scan_reference(*args[:5])
    assert float(jnp.abs(want[:, 48:]).max()) > 0.1
    np.testing.assert_allclose(np.asarray(delta[:, 48:]),
                               np.asarray(want[:, 48:]), rtol=1e-3, atol=1e-5)


def test_a_head_reads_its_own_group():
    """Head ``h`` of ``H`` reads group ``h // (H / G)``: with the groups'
    ``B`` and ``C`` exchanged the first half of the heads gives what the
    second half's groups gave."""
    (x, dt, a, b, c, _), _ = _operands(32, heads=4, groups=2)
    same_heads = (jnp.concatenate([x[:, :, :2]] * 2, axis=2),
                  jnp.concatenate([dt[:, :, :2]] * 2, axis=2),
                  jnp.concatenate([a[:2]] * 2))
    y = ssd_scan(*same_heads, b, c, 16)
    swapped = ssd_scan(*same_heads, b[:, :, ::-1], c[:, :, ::-1], 16)
    np.testing.assert_allclose(np.asarray(y[:, :, :2]),
                               np.asarray(swapped[:, :, 2:]), rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.abs(y[:, :, :2] - y[:, :, 2:]).max()) > 0.1


def test_the_backward_pass_keeps_the_operands_and_nothing_else():
    """What the forward pass saves for the backward pass is the five
    operands: no ``Q x Q`` decay, no chunk's state."""
    args, _ = _operands(64)
    _, pull = jax.vjp(lambda *a: ssd_scan(*a, 16), *args[:5])
    kept = sorted(int(np.prod(v.shape)) for v in
                  jax.tree_util.tree_leaves(pull))
    assert kept == sorted(int(np.prod(v.shape)) for v in args[:5])


def test_the_scan_has_no_loop_over_positions():
    """The kernels' sequential grid axis is the chunks (forward once, the
    backward pass's there and back): the only loops, and none is as long
    as the row."""
    args, w = _operands(64)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: (ssd_scan(*a, 16) * w).sum(), argnums=range(5)))(
            *args[:5])
    from split_learning_tpu.analysis.pallas_check import pallas_calls
    calls = {eqn.params["name"]: eqn for eqn in pallas_calls(jaxpr)}
    assert sorted(calls) == ["slt_ssd_bwd", "slt_ssd_fwd"]
    # (rows, groups, chunks) and (rows, groups, chunks there and back)
    assert calls["slt_ssd_fwd"].params["grid_mapping"].grid == (2, 2, 4)
    assert calls["slt_ssd_bwd"].params["grid_mapping"].grid == (2, 2, 8)
    for eqn in calls.values():
        semantics = eqn.params["compiler_params"]["mosaic_tpu"] \
            .dimension_semantics
        assert tuple(semantics) == ("parallel", "parallel", "arbitrary")
    text = str(jaxpr)
    lengths = {int(part.split("length=")[1].split()[0].rstrip(","))
               for part in text.split("scan[")[1:] if "length=" in part}
    assert not lengths or max(lengths) < 64


def test_a_mamba2_layers_kernels_run_under_its_scan_scope_in_both_passes():
    """The compiled gradient of a small ``Mamba2`` names ``ssm_scan`` in the
    ``op_name`` of the kernels' operations, forward and backward: the
    scope the benchmark's ``ssm_scan_ms`` and ``ssd_roofline`` read."""
    from split_learning_tpu.models import decoder
    mixer = decoder.Mamba2(hidden_size=32, num_heads=2, head_dim=64,
                           n_groups=1, ssm_state_size=16, chunk_size=128)
    x = jnp.zeros((1, 256, 32))
    params = jax.eval_shape(lambda k: mixer.init(k, x),
                            jax.random.key(0))["params"]
    text = jax.jit(jax.grad(
        lambda p, xx: mixer.apply({"params": p}, xx).sum(),
        argnums=(0, 1))).trace(params, x).lower().compile().as_text()
    names = set(re.findall(r'op_name="([^"]*slt_ssd_(?:fwd|bwd))', text))
    assert any(n.endswith("slt_ssd_fwd") and "/jvp(Mamba2)/ssm_mixer/"
               "ssm_scan/" in n for n in names), names
    assert any(n.endswith("slt_ssd_bwd") and "/transpose(jvp(Mamba2))/"
               "ssm_mixer/ssm_scan/" in n for n in names), names


def test_a_mamba2_layer_counts_the_blocks_its_forward_kernel_walks():
    """``ssd_kernel_chunks``: (row, group, chunk) blocks, a row that is no
    multiple of the chunk counting its last, partial chunk."""
    from split_learning_tpu.models import decoder
    mixer = decoder.Mamba2(hidden_size=32, num_heads=4, head_dim=8,
                           n_groups=2, ssm_state_size=16, chunk_size=8)
    x = jnp.zeros((3, 20, 32))
    params = mixer.init(jax.random.key(0), x)["params"]
    _, sown = mixer.apply({"params": params}, x, mutable=["counters_sum"])
    count = jax.tree_util.tree_leaves(sown["counters_sum"])
    assert [float(v) for v in count] == [3 * 2 * 3]


# the cases above at a group's real widths (8 heads of 64 a group, a state
# of 128, chunks of 128) in bfloat16: the kernels against the plain form
WIDE = {"three_chunks": (384, 0.1), "two_chunks_and_a_bit": (300, 0.1),
        "shorter_than_a_chunk": (100, 0.1), "strong_decay": (256, 8.0)}


@pytest.mark.parametrize("case", sorted(WIDE))
def test_forward_and_every_gradient_match_the_chunked_form_at_real_widths(
        case):
    seq, dt_scale = WIDE[case]
    args, w = _operands(seq, dt_scale, rows=1, heads=16, head_dim=64,
                        groups=2, state=128, dtype=jnp.bfloat16)
    args = args[:5]
    w = w.astype(jnp.bfloat16)
    y = ssd_scan(*args, 128)
    y_c = ssd_scan_chunked(*args, 128)
    assert y.dtype == y_c.dtype == jnp.bfloat16

    def rel(got, want):
        got, want = (np.asarray(v, np.float32) for v in (got, want))
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))
    assert rel(y, y_c) < 2e-3
    g = jax.grad(lambda *a: (ssd_scan(*a, 128) * w).astype(
        jnp.float32).sum(), argnums=range(5))(*args)
    g_c = jax.grad(lambda *a: (ssd_scan_chunked(*a, 128) * w).astype(
        jnp.float32).sum(), argnums=range(5))(*args)
    for name, got, want in zip(NAMES, g, g_c):
        assert got.dtype == want.dtype, name
        assert bool(jnp.isfinite(got).all()), name
        assert rel(got, want) < 1e-2, name


# --------------------------------------------------------------------------
# PK001: the kernels lower for the TPU, and the layer dispatches to them
# --------------------------------------------------------------------------

def _ssd_cases():
    from split_learning_tpu.analysis.pallas_check import ssd_lowering_cases
    return {c[0]: c for c in ssd_lowering_cases()}


_SSD_CASES = _ssd_cases()


@pytest.mark.parametrize("name", sorted(_SSD_CASES))
def test_the_kernels_lower_for_tpu_at_the_cells_shapes(name):
    """Forward, and backward under a gradient, at the state-space cell's
    shapes (2 rows of 4,096, 64 heads of 64 in 8 groups, a state of 128,
    chunks of 128, bfloat16) lower for the TPU natively."""
    from split_learning_tpu.analysis.pallas_check import (
        check_tpu_lowering, lowering_cases,
    )
    assert check_tpu_lowering(*_SSD_CASES[name]) == []
    assert name in {c[0] for c in lowering_cases()}


def test_the_mamba2_layer_dispatches_to_the_kernels():
    from split_learning_tpu.analysis import pallas_check
    assert pallas_check._check_ssd_dispatch() == []


def test_a_shape_the_kernels_cannot_tile_on_the_tpu_is_refused():
    """Off the interpreter a call the blocks cannot tile raises; it does
    not fall back to another path."""
    args, _ = _operands(64)
    with pytest.raises(ValueError, match="cannot tile"):
        jax.eval_shape(lambda *a: ssd_scan(*a, 16, False), *args[:5])
