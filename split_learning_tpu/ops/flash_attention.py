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
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                causal: bool, scale: float, block_q: int, precision):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (block_q, D)
    s_total = k_ref.shape[1]
    nk = s_total // block_k

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)
    # causal: K/V blocks entirely in this query block's future contribute
    # exactly zero — skip them (~2x fewer MXU contractions at large S)
    nk_eff = jnp.minimum(
        nk, ((qi + 1) * block_q + block_k - 1) // block_k) if causal \
        else nk

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = _dot(q, k, ((1,), (1,)), precision)        # (block_q, block_k)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_new = acc * corr + _dot(p, v, ((1,), (0,)), precision)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m0, l0, acc0))
    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # logsumexp of the SCALED scores: exp(s - lse) rebuilds softmax rows
    # exactly in the backward kernels
    lse_ref[0] = m + jnp.log(l_safe)


def _flash_fwd_bhsd(q, k, v, causal: bool, interpret: bool,
                    block_q: int, block_k: int):
    """(BH, S, D) flattened forward via pallas_call -> (o, lse).

    ``lse`` (and the backward's ``delta``) are per-row values kept as
    ``(BH, S, 1)`` columns: a ``(block_q, 1)`` block is legal on TPU
    where a ``(1, block_q)`` slice of a ``(BH, S)`` array is not, and
    it is the shape the kernels' row statistics already have."""
    bh, s, d = q.shape
    scale = 1.0 / np.sqrt(d)
    grid = (bh, s // block_q)
    precision = _pick_precision(q.dtype)
    kernel = functools.partial(_fwd_kernel, block_k=block_k,
                               causal=causal, scale=scale,
                               block_q=block_q, precision=precision)
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))],
        interpret=interpret,
        name="slt_flash_fwd",
    )(q, k, v)


# --------------------------------------------------------------------------
# backward (FA-2 decomposition: dKV over K-blocks, dQ over Q-blocks)
# --------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, block_q: int, block_k: int,
                    causal: bool, scale: float, precision):
    kb = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)                   # (block_k, D)
    v = v_ref[0].astype(jnp.float32)
    s_total = q_ref.shape[1]
    nq = s_total // block_q

    # causal: Q blocks entirely before this K block see none of it
    qb_start = (kb * block_k) // block_q if causal else 0

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :]   # (bq, 1)
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :]
        s = _dot(q, k, ((1,), (1,)), precision) * scale  # (bq, bk)
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                           # exact softmax rows
        dv_new = dv + _dot(p, do, ((0,), (0,)), precision)
        dp = _dot(do, v, ((1,), (1,)), precision)      # (bq, bk)
        ds = p * (dp - delta) * scale
        dk_new = dk + _dot(ds, q, ((0,), (0,)), precision)
        return dk_new, dv_new

    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(qb_start, nq, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, block_q: int, block_k: int, causal: bool,
                   scale: float, precision):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                   # (block_q, D)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]                                   # (block_q, 1)
    delta = delta_ref[0]
    s_total = k_ref.shape[1]
    nk = s_total // block_k
    nk_eff = jnp.minimum(
        nk, ((qi + 1) * block_q + block_k - 1) // block_k) if causal \
        else nk

    def body(kb, dq):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = _dot(q, k, ((1,), (1,)), precision) * scale  # (bq, bk)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, ((1,), (1,)), precision)
        ds = p * (dp - delta) * scale
        return dq + _dot(ds, k, ((1,), (0,)), precision)

    dq = jax.lax.fori_loop(0, nk_eff, body,
                           jnp.zeros(q.shape, jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, interpret, block_q, block_k):
    o, _ = _flash_fwd_bhsd(q, k, v, causal, interpret, block_q, block_k)
    return o


def _flash_fwd_rule(q, k, v, causal, interpret, block_q, block_k):
    o, lse = _flash_fwd_bhsd(q, k, v, causal, interpret, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, interpret, block_q, block_k, res, do):
    q, k, v, o, lse = res
    bh, s, d = q.shape
    scale = 1.0 / np.sqrt(d)
    precision = _pick_precision(q.dtype)
    # delta = rowsum(dO * O): cheap elementwise pre-pass, XLA fuses it
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        axis=-1, keepdims=True)

    full = pl.BlockSpec((1, s, d), lambda b, j: (b, 0, 0))
    row_full = pl.BlockSpec((1, s, 1), lambda b, j: (b, 0, 0))
    row_q = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale,
                          precision=precision),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        grid=(bh, s // block_k),
        in_specs=[full,
                  pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                  pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                  full, row_full, row_full],
        out_specs=[pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                   pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0))],
        interpret=interpret,
        name="slt_flash_bwd_dkv",
    )(q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale,
                          precision=precision),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(bh, s // block_q),
        in_specs=[pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                  full, full,
                  pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                  row_q, row_q],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        interpret=interpret,
        name="slt_flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    return dq, dk, dv


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = False,
                    interpret: bool | None = None,
                    block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    """Fused attention over (B, S, H, D) tensors.

    ``interpret=None`` runs the Pallas interpreter unless on real TPU.
    S must be divisible by the (auto-shrunk) block sizes.
    """
    interpret = resolve_interpret(interpret)
    b, s, h, d = q.shape
    block_q = _pick_block(s, block_q)
    block_k = _pick_block(s, block_k)
    to_bhsd = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, d)  # noqa
    out = _flash(to_bhsd(q), to_bhsd(k), to_bhsd(v), causal, interpret,
                 block_q, block_k)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
