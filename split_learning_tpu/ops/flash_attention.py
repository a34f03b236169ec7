"""Fused blockwise (flash) attention: Pallas TPU kernels + blockwise VJP.

The hot op of every transformer in the zoo.  The reference computes
attention as separate matmul + softmax + matmul torch calls
(``/root/reference/src/model/BERT_AGNEWS.py:56-80``); on TPU that
materializes the (S, S) score matrix in HBM.  These kernels stream K/V
blocks through VMEM with the online-softmax accumulator, so the score
matrix never leaves the core: O(S) memory, MXU-shaped (block_q x D) @
(D x block_k) contractions.

* forward: ``pl.pallas_call`` over a (batch*heads, S/block_q) grid;
  K/V blocks iterated inside with ``lax.fori_loop``; causal masking via
  2-D ``broadcasted_iota`` against the grid position.  Also emits the
  per-row logsumexp (FlashAttention-2's L = m + log l) for the backward.
* backward: two Pallas kernels (the standard FA-2 decomposition).
  ``dKV``: grid over K/V blocks, inner loop over Q blocks — each
  instance owns one (block_k, D) dK/dV tile, no atomics.  ``dQ``: grid
  over Q blocks, inner loop over K/V blocks.  Probabilities are
  rebuilt as ``exp(s - lse)`` (no second online pass needed), and
  ``delta = rowsum(dO * O)`` is a cheap XLA-fused pre-pass.
  Causal runs skip fully-masked blocks in both kernels (~2x fewer MXU
  contractions at large S).
* ``window`` (causal only): a query at ``p`` sees keys ``p - window + 1
  .. p``.  The loop bounds of all three kernels skip the blocks wholly
  outside that band, so a window of a quarter of the row does about a
  quarter of a full layer's work; only the blocks the band's two edges
  cross pay for the mask.
* grouped-query heads: ``k``/``v`` may carry fewer heads than ``q``.
  Query head ``h`` reads key-value head ``h // rep`` by BLOCK INDEX (no
  repeated copy of K/V is made); the ``dKV`` kernel's grid gets a third,
  innermost axis over the group's query heads and sums their
  contributions in the resident float32 output tile.
* ``interpret=None`` auto-selects the Pallas interpreter off-TPU, so the
  same code path runs in CPU tests and compiles natively on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from split_learning_tpu.ops.kernels.util import (
    pick_block as _pick_block, resolve_interpret,
)

NEG_INF = -1e30


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
# which blocks a kernel visits
# --------------------------------------------------------------------------

def _cdiv(a, b):
    return (a + b - 1) // b


def _key_blocks(qi, block_q: int, block_k: int, nk: int, causal: bool,
                window):
    """Key blocks ``[lo, hi)`` that query block ``qi`` may see, and inside
    them ``[full_lo, full_hi)``: the blocks every one of its queries sees
    whole, which need no mask."""
    if not causal:
        return 0, 0, nk, nk
    q0 = qi * block_q
    hi = jnp.minimum(nk, _cdiv(q0 + block_q, block_k))
    full_hi = (q0 + 1) // block_k
    if window is None:
        lo = full_lo = 0
    else:
        lo = jnp.maximum(0, q0 - window + 1) // block_k
        full_lo = _cdiv(jnp.maximum(0, q0 + block_q - window), block_k)
    full_lo = jnp.clip(full_lo, lo, hi)
    return lo, full_lo, jnp.clip(full_hi, full_lo, hi), hi


def _query_blocks(kb, block_q: int, block_k: int, nq: int, causal: bool,
                  window):
    """The same for key block ``kb``: the query blocks that may see it."""
    if not causal:
        return 0, 0, nq, nq
    k0 = kb * block_k
    lo = k0 // block_q
    full_lo = _cdiv(k0 + block_k - 1, block_q)
    if window is None:
        hi = full_hi = nq
    else:
        hi = jnp.minimum(nq, (k0 + block_k + window - 2) // block_q + 1)
        full_hi = jnp.maximum(0, k0 + window) // block_q
    full_lo = jnp.clip(full_lo, lo, hi)
    return lo, full_lo, jnp.clip(full_hi, full_lo, hi), hi


def _band(s, q0, k0, window, keys_first: bool = False):
    """Scores outside the causal band (and the window) set to NEG_INF;
    ``s`` is (queries, keys), or (keys, queries) with ``keys_first``."""
    q_axis, k_axis = (1, 0) if keys_first else (0, 1)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis)
    seen = k_pos <= q_pos
    if window is not None:
        seen &= k_pos > q_pos - window
    return jnp.where(seen, s, NEG_INF)


def _sweep(bounds, body, carry, causal: bool):
    """``body(i, carry, masked)`` over ``[lo, hi)``: the blocks the band's
    edges cross with the mask, the blocks between them without."""
    lo, full_lo, full_hi, hi = bounds
    if not causal:
        return jax.lax.fori_loop(
            lo, hi, functools.partial(body, masked=False), carry)
    edge = functools.partial(body, masked=True)
    carry = jax.lax.fori_loop(lo, full_lo, edge, carry)
    carry = jax.lax.fori_loop(
        full_lo, full_hi, functools.partial(body, masked=False), carry)
    return jax.lax.fori_loop(full_hi, hi, edge, carry)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                causal: bool, scale: float, block_q: int, precision,
                window):
    qi = pl.program_id(1)
    q = q_ref[0]                                       # (block_q, D)
    nk = k_ref.shape[1] // block_k

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros(o_ref.shape[1:], jnp.float32)

    def body(kb, carry, masked):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = _dot(q, k, ((1,), (1,)), precision) * scale  # (bq, bk)
        if masked:
            s = _band(s, qi * block_q, kb * block_k, window)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_new = acc * corr + _dot(p.astype(v.dtype), v, ((1,), (0,)),
                                    precision)
        return m_new, l_new, acc_new

    # causal: K/V blocks entirely in this query block's future (or behind
    # its window) contribute exactly zero — skip them
    m, l, acc = _sweep(
        _key_blocks(qi, block_q, block_k, nk, causal, window), body,
        (m0, l0, acc0), causal)
    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # logsumexp of the SCALED scores: exp(s - lse) rebuilds softmax rows
    # exactly in the backward kernels
    lse_ref[0] = m + jnp.log(l_safe)


def _flash_fwd_bhsd(q, k, v, causal: bool, interpret: bool,
                    block_q: int, block_k: int, window, rep: int):
    """(BH, S, D) flattened forward via pallas_call -> (o, lse); ``k``
    is ``(BH / rep, S, D)`` and ``v`` ``(BH / rep, S, Dv)``: the scores
    are taken over ``D``, the result is ``Dv`` wide.

    ``lse`` (and the backward's ``delta``) are per-row values kept as
    ``(BH, S, 1)`` columns: a ``(block_q, 1)`` block is legal on TPU
    where a ``(1, block_q)`` slice of a ``(BH, S)`` array is not, and
    it is the shape the kernels' row statistics already have."""
    bh, s, d = q.shape
    dv = v.shape[-1]
    scale = 1.0 / np.sqrt(d)
    grid = (bh, s // block_q)
    precision = _pick_precision(q.dtype)
    kernel = functools.partial(_fwd_kernel, block_k=block_k,
                               causal=causal, scale=scale,
                               block_q=block_q, precision=precision,
                               window=window)
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b // rep, 0, 0)),
            pl.BlockSpec((1, s, dv), lambda b, i: (b // rep, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))],
        interpret=interpret,
        name="slt_flash_fwd",
    )(q, k, v)


# --------------------------------------------------------------------------
# backward (FA-2 decomposition: dKV over K-blocks, dQ over Q-blocks)
# --------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, block_q: int, block_k: int,
                    causal: bool, scale: float, precision, window):
    """Works on the TRANSPOSED scores (keys, queries): ``dV = P^T dO`` and
    ``dK = dS^T Q`` are then plain products, and the queries' statistics
    ``lse``/``delta`` come as lane-dense ``(1, S)`` rows that broadcast
    down the keys (as ``(S, 1)`` columns they took 2 MB of VMEM each,
    padded to 128 lanes)."""
    kb = pl.program_id(1)
    k = k_ref[0]                                       # (block_k, D)
    v = v_ref[0]
    nq = q_ref.shape[1] // block_q

    def body(qb, carry, masked):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, :, pl.ds(qb * block_q, block_q)]   # (1, bq)
        delta = delta_ref[0, :, pl.ds(qb * block_q, block_q)]
        s = _dot(k, q, ((1,), (1,)), precision) * scale  # (bk, bq)
        if masked:
            s = _band(s, qb * block_q, kb * block_k, window,
                      keys_first=True)
        p = jnp.exp(s - lse)                           # exact softmax rows
        dv_new = dv + _dot(p.astype(do.dtype), do, ((1,), (0,)), precision)
        dp = _dot(v, do, ((1,), (1,)), precision)      # (bk, bq)
        ds = p * (dp - delta) * scale
        dk_new = dk + _dot(ds.astype(q.dtype), q, ((1,), (0,)), precision)
        return dk_new, dv_new

    # causal: Q blocks entirely before this K block (or past its window)
    # see none of it
    dk, dv = _sweep(
        _query_blocks(kb, block_q, block_k, nq, causal, window), body,
        (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)),
        causal)

    # the grid's innermost axis walks the query heads that share this
    # key-value head: their contributions add up in the resident tile
    @pl.when(pl.program_id(2) == 0)
    def _():
        dk_ref[0] = dk
        dv_ref[0] = dv

    @pl.when(pl.program_id(2) != 0)
    def _():
        dk_ref[0] += dk
        dv_ref[0] += dv


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, block_q: int, block_k: int, causal: bool,
                   scale: float, precision, window):
    qi = pl.program_id(1)
    q = q_ref[0]                                       # (block_q, D)
    do = do_ref[0]
    lse = lse_ref[0]                                   # (block_q, 1)
    delta = delta_ref[0]
    nk = k_ref.shape[1] // block_k

    def body(kb, dq, masked):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = _dot(q, k, ((1,), (1,)), precision) * scale  # (bq, bk)
        if masked:
            s = _band(s, qi * block_q, kb * block_k, window)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, ((1,), (1,)), precision)
        ds = p * (dp - delta) * scale
        return dq + _dot(ds.astype(k.dtype), k, ((1,), (0,)), precision)

    dq = _sweep(_key_blocks(qi, block_q, block_k, nk, causal, window),
                body, jnp.zeros(q.shape, jnp.float32), causal)
    dq_ref[0] = dq.astype(dq_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, interpret, block_q, block_k, window, rep):
    o, _ = _flash_fwd_bhsd(q, k, v, causal, interpret, block_q, block_k,
                           window, rep)
    return o


def _flash_fwd_rule(q, k, v, causal, interpret, block_q, block_k, window,
                    rep):
    o, lse = _flash_fwd_bhsd(q, k, v, causal, interpret, block_q, block_k,
                             window, rep)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, interpret, block_q, block_k, window, rep, res,
                    do):
    q, k, v, o, lse = res
    bh, s, d = q.shape
    d_v = v.shape[-1]
    scale = 1.0 / np.sqrt(d)
    precision = _pick_precision(q.dtype)
    # delta = rowsum(dO * O): cheap elementwise pre-pass, XLA fuses it
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        axis=-1, keepdims=True)
    static = dict(block_q=block_q, block_k=block_k, causal=causal,
                  scale=scale, precision=precision, window=window)

    # dKV: one instance a (key-value head, key block, query head of the
    # group); the float32 tiles stay resident over the last axis
    head = lambda b, j, r: (b * rep + r, 0, 0)         # noqa: E731
    k_block = pl.BlockSpec((1, block_k, d), lambda b, j, r: (b, j, 0))
    v_block = pl.BlockSpec((1, block_k, d_v), lambda b, j, r: (b, j, 0))
    as_row = lambda t: t.reshape(bh, 1, s)             # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **static),
        out_shape=[jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        grid=(bh // rep, s // block_k, rep),
        in_specs=[pl.BlockSpec((1, s, d), head), k_block, v_block,
                  pl.BlockSpec((1, s, d_v), head),
                  pl.BlockSpec((1, 1, s), head),
                  pl.BlockSpec((1, 1, s), head)],
        out_specs=[k_block, v_block],
        interpret=interpret,
        name="slt_flash_bwd_dkv",
    )(q, k, v, do, as_row(lse), as_row(delta))

    kv_head = lambda b, i: (b // rep, 0, 0)            # noqa: E731
    q_block = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    do_block = pl.BlockSpec((1, block_q, d_v), lambda b, i: (b, i, 0))
    row_q = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **static),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(bh, s // block_q),
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
    interpreter unless on real TPU.  S must be divisible by the
    (auto-shrunk) block sizes.
    """
    interpret = resolve_interpret(interpret)
    b, s, h, d = q.shape
    kv = k.shape[2]
    if h % kv or v.shape[:3] != k.shape[:3] or k.shape[3] != d:
        raise ValueError(f"{h} query heads over key/value {k.shape}, "
                         f"{v.shape}")
    if window is not None and (not causal or window < 1):
        raise ValueError("a window needs causal=True and window >= 1")
    if window is not None and window >= s:
        window = None                   # the band is the causal triangle
    block_q = _pick_block(s, block_q)
    block_k = _pick_block(s, block_k)
    to_bhsd = lambda t: t.transpose(0, 2, 1, 3).reshape(  # noqa: E731
        b * t.shape[2], s, t.shape[3])
    out = _flash(to_bhsd(q), to_bhsd(k), to_bhsd(v), causal, interpret,
                 block_q, block_k, window, h // kv)
    return out.reshape(b, h, s, v.shape[3]).transpose(0, 2, 1, 3)
