"""The state-space scan of a Mamba-2 layer (SSD), chunked, as Pallas kernels.

For one head with a float32 state ``S`` (P x N), decay ``a_t = dt_t * A``
(``A`` < 0, so ``a_t`` <= 0) and inputs ``x_t`` (P), ``B_t``, ``C_t`` (N; a
group of heads shares them)::

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

in chunks of ``chunk`` positions: inside a chunk the result is a masked
product (``(C_i . B_j) exp(cum_i - cum_j) dt_j`` over ``j <= i``, times
``x``), plus what the chunk reads of the state it was handed; the state a
chunk hands on is the one it was handed, decayed over the chunk, plus the
chunk's own.

The kernels (:func:`ssd_scan`, two kernels under one custom VJP) read the
operands TRANSPOSED, a row's positions along the lanes, as the mixer's
layers lay them out (XLA keeps a row's positions minor through the
projections, the convolution and the gated norm around the scan): ``x``
and ``y`` as ``(B, H * P, S)``, ``B`` and ``C`` as ``(B, G * N, S)``,
``dt`` as ``(B, H, S)``.  A group's block of a chunk is ``(R * P, Q)`` of
``x`` (512 x 128 at the token cell's sizes), ``(N, Q)`` of ``B`` and ``C``
(128 x 128), ``(R, Q)`` of ``dt``: a position's decays are rows, and what a
head owes a position is summed down the sublanes.

* ``slt_ssd_fwd``: a grid over (row, group, chunk), the chunks a
  sequential (``"arbitrary"``) axis.  The group's state (``R * P x N``
  float32) lives in a VMEM scratch and is carried from one grid step to
  the next: no loop over positions, and no ``Q x Q`` decay, score or
  state reaches HBM.  A step reads the state it was handed (one product
  for the group), a head's chunk through its ``Q x Q`` matrix (one product
  a head), and hands the state on (one product for the group).
* ``slt_ssd_bwd``: a grid over (row, group, 2 x chunks).  The first
  ``chunks`` steps walk the chunks forward and keep the state each chunk
  is handed in a VMEM scratch (``chunks x R * P x N`` float32: 8 MiB a
  group at the token cell's sizes, under a raised VMEM limit); the next
  ``chunks`` walk them backward, carrying the state's cotangent in VMEM,
  and write every operand's gradient a chunk at a time.  ``dB`` and
  ``dC`` are summed over the group's heads inside the kernel.  The
  forward pass keeps its five operands and nothing else.

The tiling is a function of ``(chunk, P, N, heads a group)``; on the TPU a
call the blocks cannot tile raises (:func:`_tiles`), and nothing falls
back.

Types: the decays (``dt``, ``A``, every ``exp``) and the state are
float32; ``x``, ``B`` and ``C`` are multiplied in the type they come in
(bfloat16 in the token cells) with float32 accumulation, a float32 factor
cast to that type first (float32 operands multiply at full precision).
Every decay is ``exp`` of a DIFFERENCE of cumulative sums that is taken
and masked first and is never positive: a chunk whose whole decay
underflows costs nothing but that chunk's memory of what came before it
(the factored form ``exp(cum_i) * exp(-cum_j)`` would overflow there).

The state is carried from one chunk to the next across the whole row, and
so across the ends of the documents packed into it, as attention sees
across them (PERF.md section 7).

The plain chunked form in ``jax.numpy`` (:func:`ssd_scan_chunked`: the
chunks' states handed on by a ``chunks x chunks`` product, a ``lax.map``
over the groups) is the kernels' parity oracle at sizes where the
position-by-position recurrence (:func:`ssd_scan_reference`) is too slow;
no layer runs it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from split_learning_tpu.ops.flash_attention import _pick_precision
from split_learning_tpu.ops.grouped_matmul import _vmem_limit
from split_learning_tpu.ops.kernels.util import (
    LANES, SUBLANES, resolve_interpret,
)

CHUNK = 128
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: contractions of ``jax.lax.dot_general`` (no batch axes): ``a @ b``,
#: ``a @ b.T``, ``a.T @ b``
NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
#: VMEM a backward call may keep the chunks' states in (v5e has 128 MiB)
STATES_VMEM_BYTES = 64 * 2 ** 20
#: VMEM for a grid step's float32 temporaries (a few ``Q x Q`` and
#: ``Q x slab`` values), over the blocks and the scratch
TEMPORARY_VMEM_BYTES = 8 * 2 ** 20


def ssd_scan_reference(x, dt, a_log_neg, b, c):
    """The recurrence as written, a position at a time, in float32:
    ``x`` (B, S, H, P), ``dt`` (B, S, H), ``a_log_neg`` = ``A`` (H,),
    ``b``, ``c`` (B, S, G, N) -> ``y`` (B, S, H, P).  Small sizes only."""
    bsz, _, h, p = x.shape
    g, n = b.shape[2:]
    rep = h // g
    f32 = functools.partial(jnp.asarray, dtype=F32)
    x, dt, b, c = f32(x), f32(dt), f32(b), f32(c)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = (jnp.repeat(v, rep, axis=1) for v in (b_t, c_t))
        state = jnp.exp(dt_t * a_log_neg)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)
    _, y = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, n), F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


# --------------------------------------------------------------------------
# the plain chunked form: the kernels' oracle
# --------------------------------------------------------------------------

def _dot(eq, lhs, rhs):
    return jnp.einsum(eq, lhs, rhs, preferred_element_type=F32)


# One group of heads at a time (``lax.map``): what a pass holds of the
# chunks' ``Q x Q`` decays is a group's, not the layer's.  Of one group:
# ``x`` (B, c, R, Q, P), ``dt`` and its running sum ``cum`` (B, c, R, Q),
# ``a`` (R,), ``b`` and ``c`` (B, c, Q, N): chunks, then the group's heads,
# positions before widths.

def _chunk_states(x, dt, cum, b):
    """What each chunk adds to the state by its end: (B, c, R, P, N),
    float32."""
    to_end = jnp.exp(cum[..., -1:] - cum) * dt
    return _dot("bcrqp,bcqn->bcrpn",
                (to_end[..., None] * x).astype(x.dtype), b)


def _handed(states, whole):
    """The state each chunk is handed: nought for the first, then what the
    chunks before it added, each decayed over the chunks between
    (``whole`` (B, c, R): the decay summed over each chunk; ``exp`` of a
    difference of its running sums, taken first and never positive), as
    one small product in float32 at full precision."""
    k = whole.shape[1]
    upto = jnp.cumsum(whole, axis=1)
    # over[i, j]: the decay from chunk j's end to chunk i's start, j < i
    between = (upto - whole)[:, :, None] - upto[:, None, :]
    over = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((k, k), bool), -1)[None, :, :, None], between,
        -jnp.inf))
    return jnp.einsum("bijr,bjrpn->birpn", over, states, precision=HIGHEST)


def _chunk_outputs(x, dt, cum, b, c, handed):
    """``y`` of every chunk given the state it was handed."""
    q = x.shape[-2]
    # (C_i . B_j) exp(cum_i - cum_j) dt_j over j <= i: the difference first,
    # masked before the exp, so nothing overflows
    within = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((q, q), bool)),
        cum[..., :, None] - cum[..., None, :], -jnp.inf))
    mixed = _dot("bcin,bcjn->bcij", c, b)[:, :, None] * within \
        * dt[..., None, :]
    y = _dot("bcrij,bcrjp->bcrip", mixed.astype(x.dtype), x)
    read = _dot("bcin,bcrpn->bcrip", c, handed.astype(x.dtype))
    return y + jnp.exp(cum)[..., None] * read


def _group(x, dt, a, b, c):
    """One group's ``y`` (B, c, R, Q, P), float32."""
    # the decay summed from a chunk's start to each position (<= 0)
    cum = jnp.cumsum(dt * a[:, None], axis=-1)
    handed = _handed(_chunk_states(x, dt, cum, b), cum[..., -1])
    return _chunk_outputs(x, dt, cum, b, c, handed)


def _heads_by_group(v, groups: int, chunk: int):
    """``v`` (B, S, H, ...) of every head as (G, B, c, R, Q, ...): the
    groups leading, chunks, a group's heads, positions before widths.  A
    row that is no multiple of the chunk is padded with noughts (a
    position of ``dt`` nought neither decays the state nor adds to it)."""
    bsz, s, h = v.shape[:3]
    v = jnp.pad(v, ((0, 0), (0, -s % chunk)) + ((0, 0),) * (v.ndim - 2))
    v = v.reshape(bsz, -1, chunk, groups, h // groups, *v.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(v, 3, 0), 3, 4)


def _by_group(x, dt, a, b, c, chunk):
    """The operands a group at a time, in chunks: ``x`` (G, B, c, R, Q, P),
    ``dt`` (G, B, c, R, Q), ``a`` (G, R), ``b`` and ``c`` (G, B, c, Q, N)."""
    g = b.shape[2]
    # a group is its own one "head" of ``b`` and ``c``
    b, c = (_heads_by_group(v, g, chunk)[:, :, :, 0] for v in (b, c))
    return _heads_by_group(x, g, chunk), \
        _heads_by_group(dt.astype(F32), g, chunk), \
        a.astype(F32).reshape(g, -1), b, c


def _rows_of(y, shape):
    """``y`` (G, B, c, R, Q, P) back as rows (B, S, H, P) of ``shape``."""
    bsz, s, h, p = shape
    return y.transpose(1, 2, 4, 0, 3, 5).reshape(bsz, -1, h, p)[:, :s]


def ssd_scan_chunked(x, dt, a, b, c, chunk: int = CHUNK):
    """:func:`ssd_scan` in plain ``jax.numpy``, a ``lax.map`` over the
    groups, differentiated as written: the kernels' parity oracle."""
    return _rows_of(jax.lax.map(lambda at: _group(*at), _by_group(
        x, dt, a, b, c, chunk)), x.shape).astype(x.dtype)


# --------------------------------------------------------------------------
# the kernels
# --------------------------------------------------------------------------

class _Tiles(NamedTuple):
    """How a call is cut: ``chunk`` positions a grid step, ``heads`` a
    group, of ``head_dim`` = P rows each, and ``state`` = N."""
    chunk: int
    heads: int
    head_dim: int
    state: int

    def rows(self, r: int):
        """Head ``r``'s rows of a group's ``(heads x P, ...)`` block."""
        return slice(r * self.head_dim, (r + 1) * self.head_dim)


def _tiles(x_shape, groups: int, state: int, chunk: int,
           interpret: bool) -> _Tiles:
    """The tiling of a call on ``x`` (B, S, H, P) with ``groups`` groups of
    ``state``-wide ``B`` and ``C``; on the TPU a ValueError where the
    blocks cannot tile it."""
    _, _, h, p = x_shape
    if h % groups:
        raise ValueError(f"ssd_scan: {h} heads in {groups} groups")
    tiles = _Tiles(chunk, h // groups, p, state)
    if interpret:
        return tiles
    whole = groups == 1     # a group's block is then the whole height
    refused = [why for bad, why in (
        (chunk % LANES, f"a chunk of {chunk} positions is no multiple of "
                        f"{LANES} lanes"),
        (tiles.heads % 8 and not whole,
         f"{tiles.heads} heads a group is no multiple of 8 (dt's rows)"),
        (p % SUBLANES, f"a head's {p} rows are no multiple of {SUBLANES}"),
        (state % SUBLANES and not whole,
         f"a state of {state} is no multiple of {SUBLANES} rows")) if bad]
    if refused:
        raise ValueError("ssd_scan: the kernels cannot tile x of "
                         f"{tuple(x_shape)} in {groups} groups of state "
                         f"{state}, chunk {chunk}: " + "; ".join(refused))
    return tiles


def _cumsum_lanes(v, reverse: bool = False):
    """Running sums along the lanes of ``v`` (R, Q) in float32, from the
    first lane (or from the last): log2(Q) shifted adds, exact to the
    rounding of each add."""
    q = v.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    shift = 1
    while shift < q:
        if reverse:     # lane j takes lane j + shift
            v = v + jnp.where(lane < q - shift, pltpu.roll(v, q - shift, 1),
                              0)
        else:           # lane j takes lane j - shift
            v = v + jnp.where(lane >= shift, pltpu.roll(v, shift, 1), 0)
        shift *= 2
    return v


class _Decays:
    """One chunk's decays for a group's heads, float32, positions along
    the lanes: as rows (R, Q) ``dt``, its cumulative sum ``cum`` of ``dt
    A``, ``exp(cum)`` (``e``), ``exp(cum_last - cum_j)`` (``to_end``: how a
    position's input decays by the chunk's end) and ``w`` = ``to_end dt``;
    ``exp(cum_last)`` (``whole``, (R, 1)); and ``cum``, ``dt`` as columns
    (``cum_t``, ``dt_t``, (Q, R)) for the ``Q x Q`` matrices."""

    def __init__(self, dt, a):
        q = dt.shape[1]
        row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
        self.causal, self.causal_t = col <= row, row <= col  # j <= i
        self.dt = dt
        self.cum = _cumsum_lanes(dt * a)
        self.e = jnp.exp(self.cum)
        self.to_end = jnp.exp(self.cum[:, q - 1:] - self.cum)
        self.w = self.to_end * dt
        self.whole = jnp.exp(self.cum[:, q - 1:])
        self.cum_t, self.dt_t = self.cum.T, dt.T

    def mixed(self, scores, r: int):
        """Head ``r``'s ``(C_i . B_j) exp(cum_i - cum_j) dt_j`` over ``j <=
        i``, else 0, at ``[i, j]`` (``scores`` at ``[i, j]``), and its
        ``exp`` alone: the difference masked before the exp."""
        within = jnp.exp(jnp.where(
            self.causal, self.cum_t[:, r:r + 1] - self.cum[r:r + 1], -jnp.inf))
        return scores * within * self.dt[r:r + 1], within

    def mixed_t(self, scores_t, r: int):
        """The same transposed, at ``[j, i]``."""
        within_t = jnp.exp(jnp.where(
            self.causal_t, self.cum[r:r + 1] - self.cum_t[:, r:r + 1],
            -jnp.inf))
        return scores_t * within_t * self.dt_t[:, r:r + 1], within_t

    def by_row(self, vals, tiles: _Tiles):
        """``vals`` (R, W) of a group's heads down its ``heads x P`` rows:
        (R x P, W)."""
        return jnp.concatenate(
            [jnp.broadcast_to(vals[r:r + 1], (tiles.head_dim, vals.shape[1]))
             for r in range(tiles.heads)], axis=0)


def _sum_rows(v):
    return jnp.sum(v, axis=0, keepdims=True)


def _mm(a, b, dims, precision):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=F32)


def _handed_on(state, decays, x, bt, tiles, precision):
    """A group's state a chunk hands on (heads x P, N), given the one it
    was handed: decayed over the chunk, plus ``(x w) B``."""
    xw = (x.astype(F32) * decays.by_row(decays.w, tiles)).astype(x.dtype)
    return decays.by_row(decays.whole, tiles) * state \
        + _mm(xw, bt, NT, precision)


# The kernels work on the operands TRANSPOSED, the chunk's positions along
# the lanes: ``x`` and ``y`` (heads x P, Q), ``B`` and ``C`` (N, Q), ``dt``
# (heads, Q), as the mixer lays them out (its layers keep a row's positions
# minor), and a group's state (heads x P, N).

def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_ref, *,
                tiles: _Tiles, precision):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    decays = _Decays(dt_ref[...], a_ref[...])
    x, bt, ct = x_ref[...], b_ref[...], c_ref[...]
    lo, state = x.dtype, s_ref[...]
    scores_t = _mm(bt, ct, TN, precision)                    # [j, i]
    # y_i += exp(cum_i) C_i S^T: the read of the state the chunk was handed
    y = decays.by_row(decays.e, tiles) * _mm(state.astype(lo), ct, NN,
                                             precision)
    for r in range(tiles.heads):
        rows = tiles.rows(r)
        mixed_t, _ = decays.mixed_t(scores_t, r)
        y_ref[rows, :] = (y[rows] + _mm(x[rows], mixed_t.astype(lo), NN,
                                        precision)).astype(y_ref.dtype)
    s_ref[...] = _handed_on(state, decays, x, bt, tiles, precision)


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, states_ref, ds_ref,
                *, tiles: _Tiles, precision, chunks: int):
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _():
        states_ref[0] = jnp.zeros(states_ref.shape[1:], F32)

    @pl.when(step < chunks - 1)
    def _():
        # walking forward: the state chunk ``step`` hands on
        states_ref[step + 1] = _handed_on(
            states_ref[step], _Decays(dt_ref[...], a_ref[...]), x_ref[...],
            b_ref[...], tiles, precision)

    @pl.when(step == chunks)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        da_ref[...] = jnp.zeros_like(da_ref)

    @pl.when(step >= chunks)
    def _():
        # walking backward: chunk ``2 chunks - 1 - step``
        _chunk_backward(
            x_ref, dt_ref, a_ref, b_ref, c_ref, dy_ref, dx_ref, ddt_ref,
            da_ref, db_ref, dc_ref, states_ref[2 * chunks - 1 - step],
            ds_ref, tiles, precision)


def _chunk_backward(x_ref, dt_ref, a_ref, b_ref, c_ref, dy_ref, dx_ref,
                    ddt_ref, da_ref, db_ref, dc_ref, state, ds_ref,
                    tiles: _Tiles, precision):
    """Every gradient of one chunk, given the state it was handed
    (``state``) and the cotangent of the state it hands on (``ds_ref``,
    which leaves as the cotangent of the state it was handed).  What a
    head owes a position is summed down the rows (the sublanes) alone:
    over the head's P rows, and over the ``Q x Q`` matrices in whichever
    of their two orientations puts the sum there."""
    a = a_ref[...]
    decays = _Decays(dt_ref[...], a)
    x, bt, ct, dy = x_ref[...], b_ref[...], c_ref[...], dy_ref[...]
    lo, q = x.dtype, tiles.chunk
    scores_t, scores = _mm(bt, ct, TN, precision), _mm(ct, bt, TN, precision)
    ds_out = ds_ref[...]
    state_lo, ds_lo = state.astype(lo), ds_out.astype(lo)
    e, w = decays.by_row(decays.e, tiles), decays.by_row(decays.w, tiles)
    # y_i += e_i C_i S^T: the read of the state the chunk was handed
    d_read = dy.astype(F32) * e
    read = _mm(state_lo, ct, NN, precision) * dy.astype(F32)
    ds_ref[...] = decays.by_row(decays.whole, tiles) * ds_out \
        + _mm(d_read.astype(lo), ct, NT, precision)
    dc = _mm(state_lo, d_read.astype(lo), TN, precision)    # (N, Q)
    # the state handed on: whole S + (x w) B
    d_xw = _mm(ds_lo, bt, NN, precision)
    db = _mm(ds_lo, (x.astype(F32) * w).astype(lo), TN, precision)
    dx = w * d_xw
    d_w = d_xw * x.astype(F32)
    d_whole = ds_out * state
    d_scores = jnp.zeros_like(scores)
    d_cum = d_dt = jnp.zeros_like(decays.dt)                 # (R, Q)
    head = jax.lax.broadcasted_iota(jnp.int32, d_cum.shape, 0)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    for r in range(tiles.heads):
        rows = tiles.rows(r)
        xr, dyr = x[rows], dy[rows]
        mixed, within = decays.mixed(scores, r)
        mixed_t, _ = decays.mixed_t(scores_t, r)
        d_mixed = _mm(dyr, xr, TN, precision)                # [i, j]
        d_mixed_t = _mm(xr, dyr, TN, precision)              # [j, i]
        dx_ref[rows, :] = (dx[rows] + _mm(dyr, mixed.astype(lo), NN,
                                          precision)).astype(dx_ref.dtype)
        dt_r, w_r = decays.dt[r:r + 1], decays.w[r:r + 1]
        d_scores += d_mixed * within * dt_r
        u = d_mixed * scores * within
        dw = _sum_rows(d_w[rows])                            # (1, Q)
        # d cum_i: sum_j T_ij - sum_j T_ji (T = dM * M), the read, and w
        d_cum_r = _sum_rows(d_mixed_t * mixed_t - u * dt_r) \
            + decays.e[r:r + 1] * _sum_rows(read[rows]) - dw * w_r \
            + jnp.where(last, jnp.sum(dw * w_r, keepdims=True)
                        + decays.whole[r:r + 1] * jnp.sum(
                            d_whole[rows], keepdims=True), 0)
        d_cum = jnp.where(head == r, d_cum_r, d_cum)
        d_dt = jnp.where(head == r, _sum_rows(u)
                         + dw * decays.to_end[r:r + 1], d_dt)
    # scores_ij = C_i . B_j
    dc += _mm(bt, d_scores.astype(lo), NT, precision)
    db += _mm(ct, d_scores.astype(lo), NN, precision)
    db_ref[...] = db.astype(db_ref.dtype)
    dc_ref[...] = dc.astype(dc_ref.dtype)
    # cum = cumsum(dt a): d(dt a)_j = sum_{i >= j} d cum_i
    d_da = _cumsum_lanes(d_cum, reverse=True)
    ddt_ref[...] = d_dt + d_da * a
    da_ref[...] += d_da * decays.dt


def _minor(v, chunk: int):
    """``v`` (B, S, ...) as (B, width, S'), positions minor, ``S'`` the row
    padded with noughts to a whole number of chunks (a position of ``dt``
    nought neither decays the state nor adds to it)."""
    bsz, s = v.shape[:2]
    v = v.reshape(bsz, s, -1)
    pad = -s % chunk
    return jnp.swapaxes(jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                        if pad else v, 1, 2)


def _layout(x, dt, a, b, c, chunk: int):
    """The operands as the kernels read them (:func:`_minor`): ``x`` (B, H *
    P, S'), ``dt`` (B, H, S') in float32, ``a`` (G, R, 1), ``b`` and ``c``
    (B, G * N, S')."""
    h, g = x.shape[2], b.shape[2]
    b, c = (_minor(v.astype(x.dtype), chunk) for v in (b, c))
    return (_minor(x, chunk), _minor(dt.astype(F32), chunk),
            a.astype(F32).reshape(g, h // g, 1), b, c)


def _from_layout(v, s: int, shape):
    """A kernel's ``(B, width, S')`` result as ``shape`` (B, S, ...)."""
    return jnp.swapaxes(v, 1, 2)[:, :s].reshape(shape)


def _specs(tiles: _Tiles, walk):
    """BlockSpecs of ``x``, ``dt``, ``a``, ``b``, ``c`` for a grid (row,
    group, step) whose step reads chunk ``walk(step)``."""
    q, rep, p, n = tiles.chunk, tiles.heads, tiles.head_dim, tiles.state
    return [pl.BlockSpec((None, rep * p, q), lambda i, g, k: (i, g, walk(k))),
            pl.BlockSpec((None, rep, q), lambda i, g, k: (i, g, walk(k))),
            pl.BlockSpec((None, rep, 1), lambda i, g, k: (g, 0, 0)),
            pl.BlockSpec((None, n, q), lambda i, g, k: (i, g, walk(k))),
            pl.BlockSpec((None, n, q), lambda i, g, k: (i, g, walk(k)))]


def _block_bytes(tiles: _Tiles, itemsize: int) -> int:
    """Bytes of one grid step's blocks of ``x``, ``b``, ``c`` and ``dt``."""
    q = tiles.chunk
    return q * (tiles.heads * tiles.head_dim + 2 * tiles.state) * itemsize \
        + 4 * tiles.heads * q


# jitted, so that a step's calls that share a signature (every layer, and
# the recomputed forward) are traced and lowered for Mosaic once
@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(x, dt, a, b, c, chunk: int, interpret: bool):
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    tiles = _tiles(x.shape, g, n, chunk, interpret)
    ops = _layout(x, dt, a, b, c, chunk)
    length = ops[0].shape[2]
    rep = tiles.heads
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, tiles=tiles,
                          precision=_pick_precision(x.dtype)),
        out_shape=jax.ShapeDtypeStruct(ops[0].shape, x.dtype),
        grid=(bsz, g, length // chunk),
        in_specs=_specs(tiles, lambda k: k),
        out_specs=_specs(tiles, lambda k: k)[0],
        scratch_shapes=[pltpu.VMEM((rep * p, n), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                _block_bytes(tiles, x.dtype.itemsize)
                + chunk * rep * p * x.dtype.itemsize,
                rep * p * n * 4 + TEMPORARY_VMEM_BYTES)),
        interpret=interpret,
        name="slt_ssd_fwd",
    )(*ops)
    return _from_layout(y, s, x.shape)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _backward(x, dt, a, b, c, dy, chunk: int, interpret: bool):
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    tiles = _tiles(x.shape, g, n, chunk, interpret)
    ops = _layout(x, dt, a, b, c, chunk)
    dy = _minor(dy.astype(x.dtype), chunk)
    length = ops[0].shape[2]
    chunks, rep = length // chunk, tiles.heads
    states = chunks * rep * p * n * 4
    if states > STATES_VMEM_BYTES:
        raise ValueError(
            f"ssd_scan: a group's {chunks} chunk states take {states} bytes "
            f"of VMEM, over {STATES_VMEM_BYTES}")

    def walk(k):        # forward over the chunks, then back
        return jnp.minimum(k, 2 * chunks - 1 - k)

    def back(k):        # the last chunk until the walk turns
        return jnp.minimum(chunks - 1, 2 * chunks - 1 - k)

    walking, backward = _specs(tiles, walk), _specs(tiles, back)
    grads = pl.pallas_call(
        functools.partial(_bwd_kernel, tiles=tiles, chunks=chunks,
                          precision=_pick_precision(x.dtype)),
        out_shape=[jax.ShapeDtypeStruct(ops[0].shape, x.dtype),
                   jax.ShapeDtypeStruct(ops[1].shape, F32),
                   jax.ShapeDtypeStruct((bsz, h, chunk), F32),
                   jax.ShapeDtypeStruct(ops[3].shape, b.dtype),
                   jax.ShapeDtypeStruct(ops[4].shape, c.dtype)],
        grid=(bsz, g, 2 * chunks),
        in_specs=walking[:4] + backward[4:] + backward[:1],
        out_specs=[backward[0], backward[1],
                   pl.BlockSpec((None, rep, chunk),
                                lambda i, gg, k: (i, gg, 0)),
                   backward[3], backward[4]],
        scratch_shapes=[pltpu.VMEM((chunks, rep * p, n), F32),
                        pltpu.VMEM((rep * p, n), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                2 * _block_bytes(tiles, x.dtype.itemsize)
                + 2 * chunk * rep * p * x.dtype.itemsize,
                states + rep * p * n * 4 + TEMPORARY_VMEM_BYTES)),
        interpret=interpret,
        name="slt_ssd_bwd",
    )(*ops, dy)
    dx, ddt, da, db, dc = grads
    return (_from_layout(dx, s, x.shape), _from_layout(ddt, s, dt.shape),
            da.sum(axis=(0, 2)).astype(a.dtype), _from_layout(db, s, b.shape),
            _from_layout(dc, s, c.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def ssd_scan(x, dt, a, b, c, chunk: int = CHUNK, interpret=None):
    """``y`` (B, S, H, P) of the recurrence in the module's docstring for
    ``x`` (B, S, H, P), ``dt`` (B, S, H; after the softplus), ``a`` (H,;
    negative), ``b`` and ``c`` (B, S, G, N; head ``h`` reads group ``h //
    (H / G)``), in chunks of ``chunk`` positions, by the kernels.
    ``interpret=None`` runs the Pallas interpreter unless on the TPU."""
    return _forward(x, dt, a, b, c, chunk, resolve_interpret(interpret))


def _ssd_fwd(x, dt, a, b, c, chunk, interpret):
    return ssd_scan(x, dt, a, b, c, chunk, interpret), (x, dt, a, b, c)


def _ssd_bwd(chunk, interpret, res, dy):
    return _backward(*res, dy, chunk, resolve_interpret(interpret))


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)
