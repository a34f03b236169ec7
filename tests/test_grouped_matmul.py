"""The grouped products (``ops/grouped_matmul.py``) through the Pallas
interpreter: each of the three kernels, and the operation's value and
gradients, against a per-group dense product in float32
``Precision.HIGHEST``.  The row tile is small here so that groups end
inside tiles and a product takes several visits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.ops import grouped_matmul as gm

ROWS, K, N, G = 64, 24, 40, 4
HI = jax.lax.Precision.HIGHEST
#: group sizes over 64 rows in tiles of 16; the live rows are their sum
SIZES = {
    "an_empty_group": (16, 0, 30, 18),
    "ends_inside_a_tile": (5, 22, 9, 28),
    "fewer_than_the_rows": (7, 13, 3, 6),
    "clipped_as_an_overflow_pass": (0, 0, 11, 21),
    "one_group_holds_everything": (0, 64, 0, 0),
    "no_rows_at_all": (0, 0, 0, 0),
}
TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _operands(dtype, sizes):
    """``lhs``, ``rhs``, ``dout`` in ``dtype`` with NaN planted in every
    row past the groups of BOTH row operands, and the same with zeros
    there for the oracle."""
    keys = jax.random.split(jax.random.key(sum(sizes) + len(sizes)), 3)
    lhs = jax.random.normal(keys[0], (ROWS, K)).astype(dtype)
    rhs = jax.random.normal(keys[1], (G, K, N)).astype(dtype)
    dout = jax.random.normal(keys[2], (ROWS, N)).astype(dtype)
    dead = jnp.arange(ROWS)[:, None] >= sum(sizes)
    return tuple((jnp.where(dead, fill, lhs), rhs, jnp.where(dead, fill, dout))
                 for fill in (jnp.nan, 0))


def _dense(lhs, rhs, dout, sizes):
    """(rows x expert, rows x expert transposed, experts' gradients) a
    group at a time, in float32."""
    lhs, rhs, dout = (a.astype(jnp.float32) for a in (lhs, rhs, dout))
    out, dlhs = np.zeros((ROWS, N), np.float32), np.zeros((ROWS, K),
                                                          np.float32)
    drhs, lo = np.zeros((G, K, N), np.float32), 0
    for g, size in enumerate(sizes):
        rows = slice(lo, lo + size)
        out[rows] = jnp.matmul(lhs[rows], rhs[g], precision=HI)
        dlhs[rows] = jnp.matmul(dout[rows], rhs[g].T, precision=HI)
        drhs[g] = jnp.matmul(lhs[rows].T, dout[rows], precision=HI)
        lo += size
    return out, dlhs, drhs


def _close(got, want, dtype, live=None):
    got = np.asarray(got.astype(jnp.float32))[:live]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want[:live], **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(SIZES))
def test_each_kernel_matches_the_dense_product_a_group(case, dtype):
    sizes = SIZES[case]
    (lhs, rhs, dout), clean = _operands(dtype, sizes)
    out, dlhs, drhs = _dense(*clean, sizes)
    group_sizes, live = jnp.asarray(sizes, jnp.int32), sum(sizes)
    _close(gm.gmm(lhs, rhs, group_sizes, tm=16), out, dtype, live)
    _close(gm.gmm(dout, rhs, group_sizes, transposed=True, tm=16), dlhs,
           dtype, live)
    # every expert's gradient whole, the empty ones' zeros
    got = gm.gmm_drhs(lhs, dout, group_sizes, tm=16)
    assert got.shape == (G, K, N) and got.dtype == dtype
    _close(got, drhs, dtype)
    for g, size in enumerate(sizes):
        if not size:
            assert not np.asarray(got[g].astype(jnp.float32)).any()
    # the wider operand first: the kernel turns the other one
    _close(gm.gmm_drhs(dout, lhs, group_sizes, tm=16),
           drhs.transpose(0, 2, 1), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(SIZES))
def test_the_operation_and_its_gradients_match_the_oracle(case, dtype,
                                                          monkeypatch):
    """``grouped_dot`` (its own tiles: a row tile of 16 stands in for the
    real one) against ``jax.lax.ragged_dot`` differentiated by jax, with
    NaN in the dead rows of ``lhs`` and of the cotangent."""
    monkeypatch.setattr(gm, "ROW_TILE", 16)
    sizes = SIZES[case]
    (lhs, rhs, dout), clean = _operands(dtype, sizes)
    group_sizes, live = jnp.asarray(sizes, jnp.int32), sum(sizes)
    got, pull = jax.vjp(lambda a, b: gm.grouped_dot(a, b, group_sizes),
                        lhs, rhs)
    want, pull_want = jax.vjp(
        lambda a, b: jax.lax.ragged_dot(
            a.astype(jnp.float32), b.astype(jnp.float32), group_sizes,
            precision=HI), *clean[:2])
    assert got.dtype == dtype
    _close(got, np.asarray(want), dtype, live)
    (dlhs, drhs), (dlhs_want, drhs_want) = pull(dout), pull_want(
        clean[2].astype(jnp.float32))
    assert (dlhs.dtype, drhs.dtype) == (dtype, dtype)
    _close(dlhs, np.asarray(dlhs_want), dtype, live)
    _close(drhs, np.asarray(drhs_want), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_matrix_block_may_stand_over_the_edge(dtype):
    """A width of 200 in blocks of 128 columns (the last holds 72): every
    kernel against the dense product a group, whichever operand is the
    one whose blocks stand over the edge."""
    rows, k, n, sizes = 64, 40, 200, [20, 0, 30]
    keys = jax.random.split(jax.random.key(5), 3)
    lhs = jax.random.normal(keys[0], (rows, k)).astype(dtype)
    rhs = jax.random.normal(keys[1], (3, k, n)).astype(dtype)
    dout = jax.random.normal(keys[2], (rows, n)).astype(dtype)
    group_sizes, live = jnp.asarray(sizes, jnp.int32), sum(sizes)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    out = np.zeros((rows, n), np.float32)
    drhs, lo = np.zeros((3, k, n), np.float32), 0
    for g, size in enumerate(sizes):
        out[lo:lo + size] = f32(lhs)[lo:lo + size] @ f32(rhs)[g]
        drhs[g] = f32(lhs)[lo:lo + size].T @ f32(dout)[lo:lo + size]
        lo += size
    _close(gm.gmm(lhs, rhs, group_sizes, tm=16, tn=128), out, dtype, live)
    _close(gm.gmm(lhs, jnp.swapaxes(rhs, 1, 2), group_sizes,
                  transposed=True, tm=16, tn=128), out, dtype, live)
    _close(gm.gmm_drhs(lhs, dout, group_sizes, tm=16, tk=k, tn=128), drhs,
           dtype)
    _close(gm.gmm_drhs(dout, lhs, group_sizes, tm=16, tk=128, tn=k),
           drhs.transpose(0, 2, 1), dtype)


def test_a_product_visits_the_tiles_that_hold_live_rows_and_no_other():
    """The visit plan: a tile a group for every tile the group has rows
    in, in row order; past the last visit the indices stay the last
    visit's (such a step moves nothing), and ``live_rows`` is visits
    times the tile."""
    sizes = jnp.asarray([5, 0, 22, 9], jnp.int32)      # ends 5, 5, 27, 36
    offsets, group, tile, num = gm.visit_plan(sizes, 64, 16)
    assert offsets.tolist() == [0, 5, 5, 27, 36]
    assert int(num[0]) == 5 and group.shape == (4 + 4 - 1,)
    assert group.tolist() == [0, 2, 2, 3, 3, 3, 3]
    assert tile.tolist() == [0, 0, 1, 1, 2, 2, 2]
    # the experts' gradients owe the empty group its zeros: one visit more
    _, group, tile, num = gm.visit_plan(sizes, 64, 16, empty_groups=True)
    assert int(num[0]) == 6
    assert group.tolist()[:6] == [0, 1, 2, 2, 3, 3]
    # 36 live rows of 1,024 in tiles of 256: one tile, three visits
    assert gm.row_tile(1024) == 256 and gm.row_tile(48) == 48
    assert int(gm.live_rows(sizes, 1024)) == 3 * 256
    assert int(gm.live_rows(jnp.zeros((4,), jnp.int32), 1024)) == 0


def test_the_time_cannot_follow_the_buffer():
    """Counts, not times: whatever the buffer's rows, the steps that do
    anything number the live tiles plus the groups' ends."""
    sizes = jnp.asarray([300, 40, 0, 172], jnp.int32)
    for rows in (512, 4096, 49152):
        assert int(gm.live_rows(sizes, rows)) == (2 + 1 + 1) * 256


@pytest.mark.parametrize("width, depth, itemsize, want", [
    (896, 2304, 2, 896),        # the cell's matrices whole
    (2304, 896, 2, 2304),
    (2304, 896, 4, 1152),       # float32: the largest aligned divisor
    (40, 24, 4, 40),            # no multiple of 128: the whole dimension
    (128 * 7, 1 << 16, 4, 128),
    (1856, 2688, 2, 768),       # 14.5 lane widths, too large whole: the
    (1856, 2688, 4, 384),       # largest aligned tile, the last over the edge
    (1856, 384, 4, 1856),
])
def test_a_matrix_block_is_whole_or_a_lane_aligned_divisor(width, depth,
                                                           itemsize, want):
    assert gm._col_tile(width, depth, itemsize) == want


# --------------------------------------------------------------------------
# PK001: the kernels lower for the TPU, and the layer dispatches to them
# --------------------------------------------------------------------------

def _grouped_cases():
    from split_learning_tpu.analysis.pallas_check import (
        grouped_lowering_cases,
    )
    return {c[0]: c for c in grouped_lowering_cases()}


_GROUPED_CASES = _grouped_cases()


@pytest.mark.parametrize("name", sorted(_GROUPED_CASES))
def test_grouped_kernels_lower_for_tpu(name):
    """The three kernels at the token cell's shapes (a common pass's
    16,384 rows and the overflow pass's 49,152, both products' widths, 8
    groups, bfloat16) lower for TPU natively with the tiles the
    operation picks."""
    from split_learning_tpu.analysis.pallas_check import (
        check_tpu_lowering,
    )
    assert len(_GROUPED_CASES) == 24    # eight shapes, three kernels
    assert check_tpu_lowering(*_GROUPED_CASES[name]) == []


def test_the_lowering_gate_holds_the_grouped_cases():
    from split_learning_tpu.analysis.pallas_check import lowering_cases
    assert set(_GROUPED_CASES) <= {c[0] for c in lowering_cases()}


def test_the_held_layer_dispatches_to_the_kernels():
    from split_learning_tpu.analysis import pallas_check
    assert pallas_check._check_grouped_dispatch() == []


def test_the_dispatch_gate_fires_on_a_ragged_dot():
    from split_learning_tpu.analysis import pallas_check
    jaxpr = jax.make_jaxpr(lambda a, b, s: jax.lax.ragged_dot(a, b, s))(
        jnp.zeros((8, 4)), jnp.zeros((2, 4, 4)), jnp.asarray([3, 5]))
    assert any(p.startswith("ragged_dot")
               for p in pallas_check.primitives(jaxpr))
    assert not pallas_check.contains_pallas_call(jaxpr)
