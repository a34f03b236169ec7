"""Fused blockwise (flash) attention: Pallas TPU kernels + blockwise VJP.

The hot op of every transformer in the zoo.  The reference computes
attention as separate matmul + softmax + matmul torch calls
(``/root/reference/src/model/BERT_AGNEWS.py:56-80``); on TPU that
materializes the (S, S) score matrix in HBM.  These kernels stream K/V
blocks through VMEM with the online-softmax accumulator, so the score
matrix never leaves the core: O(S) memory, MXU-shaped (block_q x D) @
(D x block_k) contractions.

* forward (``slt_flash_fwd``): a grid over (batch*heads, query rows);
  a head's K/V rows stay in VMEM and each query tile walks the key blocks
  it may see.  Also emits the per-row logsumexp (FlashAttention-2's
  L = m + log l) for the backward.
* backward: two kernels (the standard FA-2 decomposition).
  ``slt_flash_bwd_dkv``: a grid over key rows, each tile walks the query
  blocks that may see it and owns its float32 dK/dV tile, no atomics.
  ``slt_flash_bwd_dq``: a grid over query rows, walking key blocks.
  Probabilities are rebuilt as ``exp(s - lse)`` (no second online pass
  needed), and ``delta = rowsum(dO * O)`` is a cheap XLA-fused pre-pass.
* the per-row statistics ``lse`` and ``delta`` travel between the calls
  as lane-dense rows (B*heads, 1, S).  As (B*heads, S, 1) columns, the
  shape a kernel's row statistics have in registers, HBM pads them to
  128 lanes: XLA then spent more time turning and padding them around
  the calls than the calls' own copies of ``q``, ``k`` and ``v`` cost
  (PERF.md, section 6).  A kernel turns a tile's column into a row, or
  back, on the transpose unit (:func:`_as_row`).
* ``window`` (causal only): a query at ``p`` sees keys ``p - window + 1
  .. p``.  No kernel walks a block wholly outside that band, so a window
  of a quarter of the row does about a quarter of a full layer's work;
  only the blocks the band's two edges cross pay for the mask (a compare
  and a select against offsets made once a grid step).
* grouped-query heads: ``k``/``v`` may carry fewer heads than ``q``.
  Query head ``h`` reads key-value head ``h // rep`` by BLOCK INDEX (no
  repeated copy of K/V is made); the ``dKV`` kernel's grid gets a third,
  innermost axis over the group's query heads and sums their
  contributions in the resident float32 output tile.
* ``interpret=None`` auto-selects the Pallas interpreter off-TPU, so the
  same code path runs in CPU tests and compiles natively on TPU.

**How a call is tiled** (:func:`tiling`, :func:`_walk`; measured on a TPU
v5e, PERF.md section 6, PR 35).  The caller's ``block_q`` / ``block_k``
are caps; what a kernel does under them comes from ``(S, window)``:

* The cap's tile is the cheapest an element: at 128 x 128 an element
  costs three times what it costs at 512 x 512 (a narrow key block pays a
  lane reduction and an accumulator's rescale for every vreg of scores,
  and short products leave the matrix unit waiting), at 256 x 256 a sixth
  to a third more.  So a tile is the cap, but for the two backward
  kernels of a banded call: the matrix unit bounds them, their time
  follows the pairs they visit, and where the band is at most two tiles
  wide half the tile (not under 256) visits a sixth less for a seventh
  more an element.  The halved tiles are sub-tiles walked inside a grid
  step that still owns the cap's rows: a grid step costs a third of a
  microsecond, and ``dkv`` fetches a head's whole ``q`` / ``do`` rows a
  step.
* What a band costs beyond its pairs is not the mask but the LOOPS: three
  loops a tile (the window's edge, the blocks seen whole, the diagonal),
  each entered for one block, cost about half a block apiece to enter.
  So the blocks of a banded call are laid from the band's end, not from
  the row's: every tile whose band lies inside the row then walks the same
  few blocks with the same edges, written one after the other with no
  loop at all.  A causal triangle takes one loop and its diagonal after
  it.  :func:`forward_pairs` counts what the forward walks, by the walk
  itself.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from split_learning_tpu.ops.kernels.util import (
    pick_block as _pick_block, resolve_interpret,
)

NEG_INF = -1e30
#: the least rows of a tile that :func:`tiling` makes smaller than the cap
_LEAST_TILE = 256


def _pick_precision(dtype):
    """Full-f32 MXU accumulation for genuinely-f32 inputs (the MXU's
    native multiply is bf16; DEFAULT would silently truncate); bf16
    inputs keep the fast single-pass path.  Forward and backward MUST
    agree or gradients desync from the primal."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _dot(a, b, dims, precision):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=precision)


# --------------------------------------------------------------------------
# the tiling of a call, and which tiles a kernel visits
# --------------------------------------------------------------------------

class Tiling(NamedTuple):
    """How one kernel walks one call.  A grid step owns ``grid`` rows
    (queries in ``fwd`` and ``dq``, keys in ``dkv``) and walks them in
    sub-tiles inside the step; each sub-tile sweeps the other side's
    blocks that its band crosses.  One pass of the inner body multiplies
    ``block_q`` query rows with ``block_k`` key rows."""
    grid: int
    block_q: int
    block_k: int

    @property
    def step(self) -> int:
        """Every block of a walk starts on a multiple of it."""
        return math.gcd(self.block_q, self.block_k)


KERNELS = ("fwd", "dq", "dkv")


def _band_window(s: int, window):
    """``window`` as the kernels take it: None where the band is the
    causal triangle."""
    return None if window is None or window >= s else window


def tiling(kernel: str, s: int, window, cap_q: int, cap_k: int) -> Tiling:
    """The tiling of one call of ``kernel`` (:data:`KERNELS`) over rows of
    ``s`` tokens with ``window`` keys seen (None: the causal triangle).
    ``cap_q`` / ``cap_k`` (the caller's ``block_q`` / ``block_k``) bound
    every tile; every tile divides ``s``.  How it is chosen: the module
    docstring."""
    if kernel not in KERNELS:
        raise ValueError(f"kernel {kernel!r}: one of {KERNELS}")
    block_q, block_k = _pick_block(s, cap_q), _pick_block(s, cap_k)
    if _band_window(s, window) is not None and kernel != "fwd":
        # bound by the matrix unit, so their time follows the pairs they
        # visit: half the tile where the band is at most two tiles wide
        block_q, block_k = (_half(b, window) for b in (block_q, block_k))
    return Tiling(_pick_block(s, cap_k if kernel == "dkv" else cap_q),
                  block_q, block_k)


def _half(block: int, window: int) -> int:
    half = block // 2
    return half if block % 2 == 0 and half >= _LEAST_TILE \
        and window <= 2 * block else block


def _cdiv(a, b):
    return (a + b - 1) // b


def _key_blocks(q0, block_q: int, block_k: int, nk: int, causal: bool,
                window, least=jnp.minimum, most=jnp.maximum):
    """Key blocks ``[lo, hi)`` that the ``block_q`` queries from ``q0`` on
    may see, and inside them ``[full_lo, full_hi)``: the blocks every one
    of them sees whole, which need no mask.  Blocks count from the key
    that ``q0`` is counted from.  ``least`` / ``most``: the minimum and
    maximum of the type ``q0`` has (traced in a kernel, a Python int in
    :func:`forward_pairs`)."""
    if not causal:
        return 0, 0, nk, nk
    hi = least(nk, _cdiv(q0 + block_q, block_k))
    full_hi = (q0 + 1) // block_k
    if window is None:
        lo = full_lo = 0
    else:
        lo = most(0, q0 - window + 1) // block_k
        full_lo = _cdiv(most(0, q0 + block_q - window), block_k)
    full_lo = least(most(full_lo, lo), hi)
    return lo, full_lo, least(most(full_hi, full_lo), hi), hi


def _query_blocks(k0, block_q: int, block_k: int, nq: int, causal: bool,
                  window, least=jnp.minimum, most=jnp.maximum):
    """The same for the ``block_k`` keys from ``k0`` on: the query blocks
    that may see them."""
    if not causal:
        return 0, 0, nq, nq
    lo = k0 // block_q
    full_lo = _cdiv(k0 + block_k - 1, block_q)
    if window is None:
        hi = full_hi = nq
    else:
        hi = least(nq, (k0 + block_k + window - 2) // block_q + 1)
        full_hi = most(0, k0 + window) // block_q
    full_lo = least(most(full_lo, lo), hi)
    return lo, full_lo, least(most(full_hi, full_lo), hi), hi


class _Driver(NamedTuple):
    """What :func:`_walk` needs of the type a tile's start has: a kernel's
    traced scalars (:data:`_TRACED`) or Python ints (:data:`_COUNTED`)."""
    loop: Callable      # loop(lo, hi, step, carry): step(i, carry) -> carry
    either: Callable    # either(flag, yes, no): the one, else the other
    least: Callable
    most: Callable


def _count_loop(lo, hi, step, carry):
    for i in range(lo, hi):
        carry = step(i, carry)
    return carry


def _when_else(flag, yes, no):
    pl.when(flag)(yes)
    pl.when(jnp.logical_not(flag))(no)


_TRACED = _Driver(jax.lax.fori_loop, _when_else, jnp.minimum, jnp.maximum)
_COUNTED = _Driver(_count_loop, lambda flag, yes, no: yes() if flag else no(),
                   min, max)
#: blocks a walk lays out one after the other where their number is known
#: from shapes; more of them go round a loop
_UNROLL = 4


def _edge_of(i: int, bounds, window, block_q: int, block_k: int,
             over_keys: bool):
    """Which of the band's edges (causal, window) can cross block ``i`` of
    ``bounds`` (:func:`_band`'s ``edges``; None: neither).  The window's
    edge comes first in a walk over keys, the causal one in a walk over
    queries; a window of ``block_q + block_k`` or more keeps the two edges
    in different blocks, and each then pays one compare."""
    _, full_lo, full_hi, _ = bounds
    if full_lo <= i < full_hi:
        return None
    apart = window is None or window >= block_q + block_k
    near, far = (True, not apart), (not apart, True)
    return (near if over_keys else far) if i >= full_hi else \
        (far if over_keys else near)


def _walk(start, tile: Tiling, s: int, causal: bool, window,
          over_keys: bool, block, carry, finish, on: _Driver = _TRACED):
    """One tile's walk over the other side's blocks.  ``start``: the first
    of the tile's ``block_q`` queries (``over_keys``: the walk of ``fwd``
    and ``dq``) or of its ``block_k`` keys (``dkv``'s walk over queries).
    ``block(at, carry, edges)`` multiplies the tile with the other side's
    block that starts at row ``at`` (``edges``: :func:`_band`'s);
    ``finish(carry)`` runs once, on the path taken.

    Entering a loop costs about what half a 512 x 512 block costs (PERF.md
    section 6, PR 35), so a walk has as few as the shapes allow:

    * a banded call: the blocks are laid from the band's end (its last key
      for a query tile, its first query for a key tile), so every tile
      whose band lies inside the row walks the SAME blocks with the same
      edges, one after the other with no loop (past :data:`_UNROLL` blocks
      without an edge: one loop between the edges); a tile whose band the
      row's end cuts takes one loop from the row's end, the mask on every
      block;
    * a causal triangle: one loop over the blocks seen whole, and the
      diagonal's blocks after it (before it, over queries), their number
      known from the tile's shape; tiles that divide neither way take one
      loop with the mask on every block;
    * no mask at all: one loop.
    """
    bq, bk = tile.block_q, tile.block_k
    own, other = (bq, bk) if over_keys else (bk, bq)
    n_all = s // other

    def ends(at, n, on=on):
        return (_key_blocks if over_keys else _query_blocks)(
            at, bq, bk, n, causal, window, on.least, on.most)


    def loop(lo, hi, edges, carry, base=0):
        return on.loop(lo, hi, lambda i, c: block(base + i * other, c,
                                                  edges), carry)

    def laid(lo, hi, edges, carry, base=0):
        """Blocks ``[lo, hi)`` (Python ints) one after the other, block
        ``i`` with ``edges[i]``."""
        for i in range(lo, hi):
            carry = block(base + i * other, carry, edges[i])
        return carry

    if not causal:
        return finish(loop(0, n_all, None, carry))
    whole = ends(start, n_all)
    masked_loop = lambda: finish(loop(    # noqa: E731
        whole[0], whole[3], (True, window is not None), carry))
    if window is None:
        diagonal = None if 1 in (own, other) else own // other \
            if own % other == 0 else 1 if other % own == 0 else None
        if diagonal is None:
            return masked_loop()
        lo, full_lo, full_hi, _ = whole
        edges = [(True, False)] * diagonal
        if over_keys:
            carry = loop(0, full_hi, None, carry)
            return finish(laid(0, diagonal, edges, carry, full_hi * other))
        carry = laid(0, diagonal, edges, carry, lo * other)
        return finish(loop(full_lo, n_all, None, carry))
    n = _cdiv(window + own - 1, other)          # blocks a whole band takes
    if n * other > s:
        return masked_loop()
    # counted from the band's end the tile starts at a fixed row, so the
    # blocks' edges are known here
    inside = ends(n * other - own if over_keys else 0, n, _COUNTED)
    edges = [_edge_of(i, inside, window, bq, bk, over_keys)
             for i in range(n)]
    base = start + own - n * other if over_keys else start

    def chain():
        _, full_lo, full_hi, _ = inside
        c = laid(0, full_lo, edges, carry, base)
        if full_hi - full_lo > _UNROLL:
            c = loop(full_lo, full_hi, None, c, base)
        else:
            c = laid(full_lo, full_hi, edges, c, base)
        finish(laid(full_hi, n, edges, c, base))

    on.either(base >= 0 if over_keys else base + n * other <= s,
              chain, masked_loop)


def forward_pairs(s: int, window, tile: Tiling) -> tuple[int, int, int]:
    """(query, key) pairs of one head over one causal row of ``s`` tokens:
    those the algorithm owes (``seen``: ``min(p + 1, window)`` keys for
    the query at ``p``), those the forward kernel's blocks cover under
    ``tile`` (``visited``), and those of them in blocks run with the mask
    (``masked``) — by the walk the kernel itself takes."""
    window = _band_window(s, window)
    w = s if window is None else window
    seen = w * (w + 1) // 2 + (s - w) * w
    total = [0, 0]

    def finish(carry):
        total[0] += carry[0]
        total[1] += carry[1]

    for q0 in range(0, s, tile.block_q):
        _walk(q0, tile, s, True, window, True,
              lambda at, c, edges: (c[0] + 1, c[1] + (edges is not None)),
              (0, 0), finish, on=_COUNTED)
    area = tile.block_q * tile.block_k
    return seen, total[0] * area, total[1] * area


def _band_offsets(block_q: int, block_k: int, keys_first: bool = False):
    """``key index - query index`` inside one tile of scores, (queries,
    keys) or (keys, queries) with ``keys_first``: made once a grid step,
    so a masked tile pays a compare and a select and no iota."""
    shape = (block_k, block_q) if keys_first else (block_q, block_k)
    q_axis, k_axis = (1, 0) if keys_first else (0, 1)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
            - jax.lax.broadcasted_iota(jnp.int32, shape, q_axis))


def _band(s, offsets, q0, k0, window, edges):
    """Scores outside the band set to NEG_INF.  ``edges``: which of the
    band's two edges (causal, window) can cross this tile."""
    causal_edge, window_edge = edges
    shift = q0 - k0
    seen = None
    if causal_edge:
        seen = offsets <= shift
    if window_edge:
        behind = offsets > shift - window
        seen = behind if seen is None else seen & behind
    return jnp.where(seen, s, NEG_INF)


#: lanes of a vector register
LANES = 128


def _as_row(col):
    """An (n, 1) float32 column as a (1, n) row: broadcast over the lanes
    and turned on the transpose unit.  Reshaped in place, a vector turned
    from a column into a row costs a kernel about a nanosecond an element
    on a TPU v5e (PERF.md, section 6)."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], LANES)))[:1]


def _as_column(row):
    """:func:`_as_row`'s inverse: a (1, n) row as an (n, 1) column."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[1])))[:, :1]


def _sub_tiles(n: int, body):
    """``body(j)`` for the ``n`` sub-tiles of a grid step's rows."""
    if n == 1:
        body(0)
    else:
        jax.lax.fori_loop(0, n, lambda j, _: body(j), None)


def _rows(j, size: int):
    """Sub-tile ``j``'s rows inside a grid step's block."""
    return _span(j * size, size, size)


def _span(start, size: int, step: int):
    """``size`` rows from ``start`` on, a multiple of ``step``."""
    return pl.ds(start if isinstance(start, int)
                 else pl.multiple_of(start, step), size)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, tile: Tiling,
                causal: bool, scale: float, precision, window):
    grid, block_q, block_k = tile
    offsets = _band_offsets(block_q, block_k) if causal else None
    first = pl.program_id(1) * grid     # (read outside the loops' bodies)

    def sub_tile(j):
        rows = _rows(j, block_q)
        q0 = first + j * block_q
        q = q_ref[0, rows, :]                          # (block_q, D)

        def block(k0, carry, edges):
            m, l, acc = carry
            k = k_ref[0, _span(k0, block_k, tile.step), :]
            v = v_ref[0, _span(k0, block_k, tile.step), :]
            s = _dot(q, k, ((1,), (1,)), precision) * scale  # (bq, bk)
            if edges:
                s = _band(s, offsets, q0, k0, window, edges)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1, keepdims=True)
            acc_new = acc * corr + _dot(p.astype(v.dtype), v,
                                        ((1,), (0,)), precision)
            return m_new, l_new, acc_new

        def finish(carry):
            m, l, acc = carry
            l_safe = jnp.where(l > 0, l, 1.0)
            o_ref[0, rows, :] = (acc / l_safe).astype(o_ref.dtype)
            # logsumexp of the SCALED scores: exp(s - lse) rebuilds softmax
            # rows exactly in the backward kernels
            lse_ref[0, :, rows] = _as_row(m + jnp.log(l_safe))

        # causal: K/V blocks entirely in these queries' future (or behind
        # their window) contribute exactly zero — never walked
        _walk(q0, tile, k_ref.shape[1], causal, window, True, block,
              (jnp.full((block_q, 1), NEG_INF, jnp.float32),
               jnp.zeros((block_q, 1), jnp.float32),
               jnp.zeros((block_q, o_ref.shape[2]), jnp.float32)), finish)

    _sub_tiles(grid // block_q, sub_tile)


def _flash_fwd_bhsd(q, k, v, causal: bool, interpret: bool, tile: Tiling,
                    window, rep: int):
    """(BH, S, D) flattened forward via pallas_call -> (o, lse); ``k``
    is ``(BH / rep, S, D)`` and ``v`` ``(BH / rep, S, Dv)``: the scores
    are taken over ``D``, the result is ``Dv`` wide.

    ``lse`` (and the backward's ``delta``) are per-row values kept as
    ``(BH, 1, S)`` rows: a ``(1, 1, block_q)`` block is legal on TPU
    where a ``(1, block_q)`` slice of a ``(BH, S)`` array is not, and a
    ``(BH, S, 1)`` column is padded to 128 lanes in HBM."""
    bh, s, d = q.shape
    dv = v.shape[-1]
    scale = 1.0 / np.sqrt(d)
    precision = _pick_precision(q.dtype)
    kernel = functools.partial(_fwd_kernel, tile=tile, causal=causal,
                               scale=scale, precision=precision,
                               window=window)
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, s), jnp.float32)],
        grid=(bh, s // tile.grid),
        in_specs=[
            pl.BlockSpec((1, tile.grid, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b // rep, 0, 0)),
            pl.BlockSpec((1, s, dv), lambda b, i: (b // rep, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, tile.grid, dv), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, 1, tile.grid), lambda b, i: (b, 0, i))],
        interpret=interpret,
        name="slt_flash_fwd",
    )(q, k, v)


# --------------------------------------------------------------------------
# backward (FA-2 decomposition: dKV over K-blocks, dQ over Q-blocks)
# --------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, tile: Tiling, causal: bool,
                    scale: float, precision, window):
    """Works on the TRANSPOSED scores (keys, queries): ``dV = P^T dO`` and
    ``dK = dS^T Q`` are then plain products, and the queries' statistics
    ``lse``/``delta`` come as lane-dense ``(1, S)`` rows that broadcast
    down the keys (as ``(S, 1)`` columns they took 2 MB of VMEM each,
    padded to 128 lanes)."""
    grid, block_q, block_k = tile
    offsets = _band_offsets(block_q, block_k, keys_first=True) \
        if causal else None
    # the grid's innermost axis walks the query heads that share this
    # key-value head: their contributions add up in the resident tile
    first_head = pl.program_id(2) == 0
    first = pl.program_id(1) * grid     # (read outside the loops' bodies)

    def sub_tile(j):
        rows = _rows(j, block_k)
        k0 = first + j * block_k
        k = k_ref[0, rows, :]                          # (block_k, D)
        v = v_ref[0, rows, :]

        def block(q0, carry, edges):
            dk, dv = carry
            q = q_ref[0, _span(q0, block_q, tile.step), :]
            do = do_ref[0, _span(q0, block_q, tile.step), :]
            lse = lse_ref[0, :, _span(q0, block_q, tile.step)]    # (1, bq)
            delta = delta_ref[0, :, _span(q0, block_q, tile.step)]
            s = _dot(k, q, ((1,), (1,)), precision) * scale  # (bk, bq)
            if edges:
                s = _band(s, offsets, q0, k0, window, edges)
            p = jnp.exp(s - lse)                       # exact softmax rows
            dv_new = dv + _dot(p.astype(do.dtype), do, ((1,), (0,)),
                               precision)
            dp = _dot(v, do, ((1,), (1,)), precision)  # (bk, bq)
            ds = p * (dp - delta) * scale
            dk_new = dk + _dot(ds.astype(q.dtype), q, ((1,), (0,)),
                               precision)
            return dk_new, dv_new

        def finish(carry):
            dk, dv = carry

            @pl.when(first_head)
            def _():
                dk_ref[0, rows, :] = dk
                dv_ref[0, rows, :] = dv

            @pl.when(jnp.logical_not(first_head))
            def _():
                dk_ref[0, rows, :] += dk
                dv_ref[0, rows, :] += dv

        # causal: Q blocks entirely before these keys (or past their
        # window) see none of them — never walked
        _walk(k0, tile, q_ref.shape[1], causal, window, False, block,
              (jnp.zeros(k.shape, jnp.float32),
               jnp.zeros(v.shape, jnp.float32)), finish)

    _sub_tiles(grid // block_k, sub_tile)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, tile: Tiling, causal: bool, scale: float,
                   precision, window):
    grid, block_q, block_k = tile
    offsets = _band_offsets(block_q, block_k) if causal else None
    first = pl.program_id(1) * grid     # (read outside the loops' bodies)

    def sub_tile(j):
        rows = _rows(j, block_q)
        q0 = first + j * block_q
        q = q_ref[0, rows, :]                          # (block_q, D)
        do = do_ref[0, rows, :]
        lse = _as_column(lse_ref[0, :, rows])          # (block_q, 1)
        delta = _as_column(delta_ref[0, :, rows])

        def block(k0, dq, edges):
            k = k_ref[0, _span(k0, block_k, tile.step), :]
            v = v_ref[0, _span(k0, block_k, tile.step), :]
            s = _dot(q, k, ((1,), (1,)), precision) * scale  # (bq, bk)
            if edges:
                s = _band(s, offsets, q0, k0, window, edges)
            p = jnp.exp(s - lse)
            dp = _dot(do, v, ((1,), (1,)), precision)
            ds = p * (dp - delta) * scale
            return dq + _dot(ds.astype(k.dtype), k, ((1,), (0,)),
                             precision)

        def finish(dq):
            dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)

        _walk(q0, tile, k_ref.shape[1], causal, window, True, block,
              jnp.zeros(q.shape, jnp.float32), finish)

    _sub_tiles(grid // block_q, sub_tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, interpret, tiles, window, rep):
    """``tiles``: the :class:`Tiling` of each of :data:`KERNELS`."""
    o, _ = _flash_fwd_bhsd(q, k, v, causal, interpret, tiles[0], window,
                           rep)
    return o


def _flash_fwd_rule(q, k, v, causal, interpret, tiles, window, rep):
    o, lse = _flash_fwd_bhsd(q, k, v, causal, interpret, tiles[0], window,
                             rep)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, interpret, tiles, window, rep, res, do):
    q, k, v, o, lse = res
    bh, s, d = q.shape
    d_v = v.shape[-1]
    scale = 1.0 / np.sqrt(d)
    precision = _pick_precision(q.dtype)
    _, dq_tile, dkv_tile = tiles
    # delta = rowsum(dO * O): cheap elementwise pre-pass, XLA fuses it
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        axis=-1).reshape(bh, 1, s)
    static = dict(causal=causal, scale=scale, precision=precision,
                  window=window)

    # dKV: one instance a (key-value head, key block, query head of the
    # group); the float32 tiles stay resident over the last axis
    head = lambda b, j, r: (b * rep + r, 0, 0)         # noqa: E731
    k_block = pl.BlockSpec((1, dkv_tile.grid, d), lambda b, j, r: (b, j, 0))
    v_block = pl.BlockSpec((1, dkv_tile.grid, d_v),
                           lambda b, j, r: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, tile=dkv_tile, **static),
        out_shape=[jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        grid=(bh // rep, s // dkv_tile.grid, rep),
        in_specs=[pl.BlockSpec((1, s, d), head), k_block, v_block,
                  pl.BlockSpec((1, s, d_v), head),
                  pl.BlockSpec((1, 1, s), head),
                  pl.BlockSpec((1, 1, s), head)],
        out_specs=[k_block, v_block],
        interpret=interpret,
        name="slt_flash_bwd_dkv",
    )(q, k, v, do, lse, delta)

    kv_head = lambda b, i: (b // rep, 0, 0)            # noqa: E731
    q_block = pl.BlockSpec((1, dq_tile.grid, d), lambda b, i: (b, i, 0))
    do_block = pl.BlockSpec((1, dq_tile.grid, d_v), lambda b, i: (b, i, 0))
    row_q = pl.BlockSpec((1, 1, dq_tile.grid), lambda b, i: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, tile=dq_tile, **static),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(bh, s // dq_tile.grid),
        in_specs=[q_block, pl.BlockSpec((1, s, d), kv_head),
                  pl.BlockSpec((1, s, d_v), kv_head), do_block, row_q, row_q],
        out_specs=q_block,
        interpret=interpret,
        name="slt_flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = False,
                    interpret: bool | None = None,
                    block_q: int = 128, block_k: int = 128,
                    window: int | None = None) -> jnp.ndarray:
    """Fused attention over ``q`` (B, S, H, D), ``k`` (B, S, KV, D) and
    ``v`` (B, S, KV, Dv) with ``H`` a multiple of ``KV``: query head ``h``
    reads key-value head ``h // (H / KV)``.  The scores are taken over
    ``D`` and scaled by ``1 / sqrt(D)``; the result is (B, S, H, Dv), and
    ``Dv`` may differ from ``D`` (latent attention: a rotary part beside
    the keys' own, none beside the values).

    ``window`` (with ``causal``): a query at ``p`` sees keys
    ``p - window + 1 .. p``.  ``interpret=None`` runs the Pallas
    interpreter unless on real TPU.  ``block_q`` / ``block_k`` are caps:
    each kernel's tiles come from :func:`tiling`, divide S and stay at or
    under them.
    """
    interpret = resolve_interpret(interpret)
    b, s, h, d = q.shape
    kv = k.shape[2]
    if h % kv or v.shape[:3] != k.shape[:3] or k.shape[3] != d:
        raise ValueError(f"{h} query heads over key/value {k.shape}, "
                         f"{v.shape}")
    if window is not None and (not causal or window < 1):
        raise ValueError("a window needs causal=True and window >= 1")
    window = _band_window(s, window)
    tiles = tuple(tiling(kernel, s, window, block_q, block_k)
                  for kernel in KERNELS)
    to_bhsd = lambda t: t.transpose(0, 2, 1, 3).reshape(  # noqa: E731
        b * t.shape[2], s, t.shape[3])
    out = _flash(to_bhsd(q), to_bhsd(k), to_bhsd(v), causal, interpret,
                 tiles, window, h // kv)
    return out.reshape(b, h, s, v.shape[3]).transpose(0, 2, 1, 3)
