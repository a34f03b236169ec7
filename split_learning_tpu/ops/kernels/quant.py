"""Fused tiled-absmax quantize / dequantize Pallas kernels.

The XLA chain in ``runtime/codec/quant.py`` (``_quantize_dev``) is ~8
separate ops — abs, tile max, scale select, divide, round, clip, NaN
mask, int cast — each a full HBM round-trip over the leaf.  These
kernels do that chain in one VMEM-resident pass per block of tiles: a
grid instance loads ``(block, tile)`` floats once and emits the int8
codes plus the per-tile scales.

Numerics are the oracle's, op for op: ``scale = amax/qmax`` (qmax 127
int8 / 7 int4), all-zero tile -> scale 1, NON-FINITE tile -> NaN scale
sentinel with zeroed codes.  The int4 nibble pack (and its unpack) is
not in the kernel: it pairs NEIGHBOURING codes, a lane compaction
Mosaic has no lowering for, so the caller packs the kernel's int8
codes with the same strided XLA ops the chain uses.

Layout: the caller hands the ALREADY padded+tiled ``(T, tile)`` f32
array (padding is a cheap XLA prologue — the expensive multi-pass math
is what moves into the kernel).  Scales travel as a ``(T, 1)`` column
so that every block's last two dimensions are legal on TPU (a row of
``block`` scales in a ``(nb, block)`` array is not).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from split_learning_tpu.ops.kernels.util import (
    resolve_interpret, vmem_block,
)


def _quantize_kernel(t_ref, q_ref, s_ref, *, qmax: float):
    t = t_ref[...].astype(jnp.float32)            # (block, tile)
    amax = jnp.max(jnp.abs(t), axis=1, keepdims=True)
    scale = jnp.where(jnp.isfinite(amax),
                      jnp.where(amax > 0, amax / qmax, 1.0),
                      jnp.nan).astype(jnp.float32)
    codes = jnp.clip(jnp.round(t / scale), -qmax, qmax)
    # NaN codes (non-finite tile: scale is NaN) become 0 — the NaN
    # scale alone carries the divergence (oracle semantics)
    q_ref[...] = jnp.where(jnp.isfinite(codes), codes,
                           0.0).astype(jnp.int8)
    s_ref[...] = scale


def quantize_tiles(tiles, *, bits: int, interpret: bool | None = None):
    """One-pass (codes, scales) for a padded ``(T, tile)`` f32 array.

    Returns the ``(T, tile)`` int8 codes (in [-7, 7] for bits=4, not
    yet nibble-packed) and the ``(T,)`` f32 scale vector — the values
    of the ``_quantize_dev`` XLA chain.
    """
    interpret = resolve_interpret(interpret)
    t_count, tile = tiles.shape
    qmax = 127.0 if bits == 8 else 7.0
    b, _ = vmem_block(t_count, tile, split_cols=False)
    q, s = pl.pallas_call(
        functools.partial(_quantize_kernel, qmax=qmax),
        out_shape=[jax.ShapeDtypeStruct((t_count, tile), jnp.int8),
                   jax.ShapeDtypeStruct((t_count, 1), jnp.float32)],
        grid=(pl.cdiv(t_count, b),),
        in_specs=[pl.BlockSpec((b, tile), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((b, tile), lambda i: (i, 0)),
                   pl.BlockSpec((b, 1), lambda i: (i, 0))],
        interpret=interpret,
        name="slt_quantize",
    )(tiles)
    return q, s.reshape(-1)


def _dequantize_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def dequantize_tiles(codes, scale, *, interpret: bool | None = None):
    """Mirror pass: ``(T, tile)`` int8 codes + ``(T,)`` scales ->
    ``(T, tile)`` f32 (the caller slices off the padding and
    reshapes)."""
    interpret = resolve_interpret(interpret)
    t_count, tile = codes.shape
    b, _ = vmem_block(t_count, tile, split_cols=False)
    return pl.pallas_call(
        _dequantize_kernel,
        out_shape=jax.ShapeDtypeStruct((t_count, tile), jnp.float32),
        grid=(pl.cdiv(t_count, b),),
        in_specs=[pl.BlockSpec((b, tile), lambda i: (i, 0)),
                  pl.BlockSpec((b, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((b, tile), lambda i: (i, 0)),
        interpret=interpret,
        name="slt_dequantize",
    )(codes, scale.reshape(t_count, 1))
