"""Grouped matrix products: Pallas TPU kernels + their VJP.

The hot op of a mixture-of-experts layer whose rows are sorted by expert
(``parallel/expert.py HeldMoEMLP``): row ``r`` of group ``g`` is multiplied
by matrix ``g``.  :func:`grouped_dot` has the contract of
``jax.lax.ragged_dot``: ``lhs`` ``(rows, K)`` sorted by group, ``rhs``
``(groups, K, N)``, ``group_sizes`` ``(groups,)`` int32 that may sum to
FEWER than ``rows``; what the rows past the groups' sum hold in the result
is unspecified (whatever the buffer held: NaN on the chip), and what they
hold in the operands never reaches a live row or a gradient.

Three kernels, one ``jax.custom_vjp``:

* ``slt_gmm``: rows x expert, ``(rows, K) x (groups, K, N) -> (rows, N)``.
  A grid step is a VISIT: one tile of ``tm`` rows against one group's
  matrix.  A tile that a group's end crosses is visited once for each
  group it holds rows of, and a visit writes only its group's rows (the
  output tile stays resident between them).  The matrix's block index is
  the group's, so a group's matrix is fetched once for all its tiles.
* ``slt_gmm_t``: the same against the TRANSPOSED matrices, ``(rows, N) x
  (groups, K, N) -> (rows, K)``, for the rows' cotangents: the kernel
  contracts over the matrices' last axis, no transposed copy is made.
* ``slt_gmm_drhs``: the groups' gradients, ``(rows, K)^T (rows, N) ->
  (groups, K, N)``, contracting over each group's rows ONLY: visits in
  the same order, a float32 tile that stays resident over a group's
  visits; the tile a group's end crosses is masked in BOTH operands (a
  dead row may hold NaN, and ``0 * NaN`` is NaN), a group with no rows
  gets one visit that adds nothing, so its gradient is zeros.

The visits are computed from ``group_sizes`` (:func:`visit_plan`) and read
by the kernels as scalar prefetch.  Only tiles that hold live rows are
visited: the grid has the most visits the shapes allow (``tiles + groups
- 1``), the steps past the last visit are skipped whole and their block
indices stay the last visit's, so they move nothing: a kernel's time
follows the live rows, whatever the buffer.

Products accumulate in float32 and come out in ``lhs``'s type; float32
operands multiply at ``Precision.HIGHEST``.  Every block holds the whole
contracted dimension: tiles are a function of the shapes and the type
(:func:`row_tile`, :func:`_col_tile`), no option reaches them.
``interpret=None`` picks the Pallas interpreter off the TPU, so the same
kernels run in CPU tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from split_learning_tpu.ops.flash_attention import _dot, _pick_precision
from split_learning_tpu.ops.kernels.util import LANES, resolve_interpret

#: Rows of a tile.  A group's end costs a visit of this many rows more
#: than its share (``HeldMoEMLP``'s counter ``moe_gmm_rows`` over
#: ``moe_pairs_held`` reads the sum); a tile of fewer rows feeds the
#: matrix unit worse (PERF.md section 6, PR 33, has the readings).
ROW_TILE = 256
#: Bytes one block of a matrix operand may take (the pipeline holds two):
#: the token cell's ``(2304, 896)`` bfloat16 matrix whole.
MATRIX_BLOCK_BYTES = 9 * 512 * 1024
#: Scoped VMEM a kernel has without asking (v5e).  The cell's calls fit
#: it and ask for no more: what a kernel reserves, XLA cannot give the
#: operations around it as fast memory (PERF.md section 6, PR 33).
SCOPED_VMEM_BYTES = 16 * 1024 * 1024


def row_tile(rows: int) -> int:
    """Rows of a tile for a ``rows``-row operand: :data:`ROW_TILE`, or
    all the rows where they are fewer (a block that is the whole
    dimension is legal whatever its size)."""
    return min(rows, ROW_TILE)


def _col_tile(width: int, depth: int, itemsize: int) -> int:
    """Columns of a matrix block ``depth`` deep: the whole ``width`` where
    that fits :data:`MATRIX_BLOCK_BYTES`, else its largest lane-aligned
    divisor that fits; a width that is no multiple of the lane count has
    no such divisor and takes the largest lane-aligned tile that fits, its
    last block standing over the edge (the calls' grids round up: what a
    block reads past the edge only reaches columns that are not written)."""
    if depth * width * itemsize <= MATRIX_BLOCK_BYTES:
        return width
    fits = [c for c in range(LANES, width, LANES)
            if (width % c == 0 or width % LANES)
            and depth * c * itemsize <= MATRIX_BLOCK_BYTES]
    return max(fits, default=LANES)


def _vmem_limit(block_bytes: int, acc_bytes: int) -> int:
    """Scoped VMEM a call asks for: its blocks twice (the pipeline's two
    buffers), its float32 tile and 2 MiB of room; the default at least."""
    return max(SCOPED_VMEM_BYTES, 2 * block_bytes + acc_bytes + 2 * 2 ** 20)


def _visits_a_group(group_sizes, tm: int, empty_groups: bool):
    """``(ends, first, count)`` of every group: the row where it ends, the
    tile its first row lies in, and how many tiles it has rows in (for a
    group with none: 1 with ``empty_groups``, else 0)."""
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    return ends, first, jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                                  int(empty_groups))


def visit_plan(group_sizes, rows: int, tm: int, empty_groups: bool = False):
    """Which tile of ``tm`` rows meets which group, in the order the
    kernels walk them: ``(offsets, group, tile, num)``.

    * ``offsets`` ``(groups + 1,)``: row where each group starts, and
      where the last one ends;
    * ``group``, ``tile`` ``(tiles + groups - 1,)``: of visit ``v``; a group
      with rows has one visit for every tile it has rows in, in order; an
      empty group none, or one with ``empty_groups`` (the kernel that
      owes it zeros).  Past ``num`` both repeat the last visit's;
    * ``num`` ``(1,)``: how many visits there are.
    """
    g, tiles = group_sizes.shape[0], pl.cdiv(rows, tm)
    ends, first, count = _visits_a_group(group_sizes, tm, empty_groups)
    stop = jnp.cumsum(count)
    num = stop[-1]
    v = jnp.minimum(jnp.arange(tiles + g - 1, dtype=jnp.int32),
                    jnp.maximum(num - 1, 0))
    group = jnp.minimum(
        jnp.sum(v[:, None] >= stop[None, :], axis=1, dtype=jnp.int32), g - 1)
    tile = jnp.clip(first[group] + v - (stop - count)[group], 0, tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile, num.reshape(1)


def live_rows(group_sizes, rows: int):
    """Rows the visits of a ``rows``-row product cover (visits x the row
    tile): the live rows and what the tiling adds at the groups' ends,
    from ``group_sizes`` and the tile alone."""
    tm = row_tile(rows)
    return jnp.sum(_visits_a_group(group_sizes, tm, False)[2]) * tm


def _inside(shape, row0, lo, hi):
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= lo) & (row < hi)


def _gmm_kernel(offs, group, tile, num, lhs_ref, rhs_ref, out_ref, *,
                tm: int, transposed: bool, precision):
    v = pl.program_id(1)

    @pl.when(v < num[0])
    def _():
        g, row0 = group[v], tile[v] * tm
        lo, hi = offs[g], offs[g + 1]
        acc = _dot(lhs_ref[...], rhs_ref[...],
                   ((1,), (1 if transposed else 0,)), precision)
        whole = (lo <= row0) & (row0 + tm <= hi)

        @pl.when(whole)
        def _():
            out_ref[...] = acc.astype(out_ref.dtype)

        # a tile shared with other groups: their rows stay as the visit
        # before left them (or as the buffer was, for rows of no group)
        @pl.when(jnp.logical_not(whole))
        def _():
            out_ref[...] = jnp.where(
                _inside(acc.shape, row0, lo, hi), acc,
                out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def gmm(lhs, rhs, group_sizes, *, transposed: bool = False,
        tm: int | None = None, tn: int | None = None,
        interpret: bool | None = None):
    """``lhs[r] @ rhs[g]`` for every row ``r`` of group ``g``; with
    ``transposed``, ``lhs[r] @ rhs[g].T`` (``rhs`` is read as it lies)."""
    rows, depth = lhs.shape
    width = rhs.shape[1] if transposed else rhs.shape[2]
    assert rhs.shape[2 if transposed else 1] == depth, (lhs.shape, rhs.shape)
    return _gmm(
        lhs, rhs, group_sizes, transposed,
        row_tile(rows) if tm is None else tm,
        _col_tile(width, depth, rhs.dtype.itemsize) if tn is None else tn,
        resolve_interpret(interpret))


# jitted, so that the calls of a step that share a signature (a block's
# three passes, every block) are traced and lowered for Mosaic once
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _gmm(lhs, rhs, group_sizes, transposed: bool, tm: int, tn: int,
         interpret: bool):
    (rows, depth), width = lhs.shape, rhs.shape[1 if transposed else 2]
    plan = visit_plan(group_sizes, rows, tm)
    vmem = _vmem_limit(
        (tm * depth + depth * tn + tm * tn) * lhs.dtype.itemsize, tm * tn * 4)
    if transposed:
        rhs_spec = pl.BlockSpec((None, tn, depth),
                                lambda j, v, o, g, t, n: (g[v], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, depth, tn),
                                lambda j, v, o, g, t, n: (g[v], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transposed=transposed,
                          precision=_pick_precision(lhs.dtype)),
        out_shape=jax.ShapeDtypeStruct((rows, width), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(pl.cdiv(width, tn), plan[1].shape[0]),
            in_specs=[pl.BlockSpec((tm, depth),
                                   lambda j, v, o, g, t, n: (t[v], 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, o, g, t, n: (t[v], j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="slt_gmm_t" if transposed else "slt_gmm",
    )(*plan, lhs, rhs)


def _drhs_kernel(offs, group, tile, num, lhs_ref, dout_ref, out_ref, acc_ref,
                 *, tm: int, precision):
    v = pl.program_id(2)
    g = group[v]

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(v < num[0])
    def _():
        row0 = tile[v] * tm
        lo, hi = offs[g], offs[g + 1]
        whole = (lo <= row0) & (row0 + tm <= hi)

        def add(a, b):
            acc_ref[...] += _dot(a, b, ((0,), (0,)), precision)

        @pl.when(whole)
        def _():
            add(lhs_ref[...], dout_ref[...])

        @pl.when(jnp.logical_not(whole))
        def _():
            a, b = lhs_ref[...], dout_ref[...]
            add(jnp.where(_inside(a.shape, row0, lo, hi), a, 0),
                jnp.where(_inside(b.shape, row0, lo, hi), b, 0))

        nxt = group[jnp.minimum(v + 1, group.shape[0] - 1)]

        @pl.when((v == num[0] - 1) | (nxt != g))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def gmm_drhs(lhs, dout, group_sizes, *, tm: int | None = None,
             tk: int | None = None, tn: int | None = None,
             interpret: bool | None = None):
    """``lhs[rows of g].T @ dout[rows of g]`` for every group ``g``:
    ``(groups, K, N)``, zeros for a group with no rows."""
    rows, k = lhs.shape
    n = dout.shape[1]
    assert dout.shape[0] == rows, (lhs.shape, dout.shape)
    if k > n:
        # the matrix unit contracts over an operand's columns, so every
        # visit turns its tile of ``lhs``: turn the narrower operand, and
        # the result once (XLA, at the memory's pace)
        return jnp.swapaxes(gmm_drhs(dout, lhs, group_sizes, tm=tm, tk=tn,
                                     tn=tk, interpret=interpret), 1, 2)
    # the resident float32 tile is a matrix block of four bytes an element
    tn = _col_tile(n, k, 4) if tn is None else tn
    return _gmm_drhs(lhs, dout, group_sizes,
                     row_tile(rows) if tm is None else tm,
                     _col_tile(k, tn, 4) if tk is None else tk, tn,
                     resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _gmm_drhs(lhs, dout, group_sizes, tm: int, tk: int, tn: int,
              interpret: bool):
    (rows, k), n = lhs.shape, dout.shape[1]
    plan = visit_plan(group_sizes, rows, tm, empty_groups=True)
    vmem = _vmem_limit(
        (tm * tk + tm * tn + tk * tn) * lhs.dtype.itemsize, 2 * tk * tn * 4)
    return pl.pallas_call(
        functools.partial(_drhs_kernel, tm=tm,
                          precision=_pick_precision(lhs.dtype)),
        out_shape=jax.ShapeDtypeStruct((group_sizes.shape[0], k, n),
                                       lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(pl.cdiv(k, tk), pl.cdiv(n, tn), plan[1].shape[0]),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda i, j, v, o, g, t, n: (t[v], i)),
                      pl.BlockSpec((tm, tn),
                                   lambda i, j, v, o, g, t, n: (t[v], j))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda i, j, v, o, g, t, n: (g[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="slt_gmm_drhs",
    )(*plan, lhs, dout)


@jax.custom_vjp
def grouped_dot(lhs, rhs, group_sizes):
    """``jax.lax.ragged_dot(lhs, rhs, group_sizes)`` as Pallas kernels: the
    module's docstring has the contract."""
    return gmm(lhs, rhs, group_sizes)


def _grouped_dot_fwd(lhs, rhs, group_sizes):
    return gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_dot_bwd(res, dout):
    lhs, rhs, group_sizes = res
    dout = dout.astype(lhs.dtype)
    return (gmm(dout, rhs, group_sizes, transposed=True),
            gmm_drhs(lhs, dout, group_sizes).astype(rhs.dtype), None)


grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)
