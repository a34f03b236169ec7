"""Tiled absmax int8/int4 quantization — the activation/gradient wire
codec (``transport.codec: {intermediate: int8, ...}``).

The quantizer runs ON DEVICE, before the device→host fetch (slcheck
JX002 discipline: the PCIe/ICI hop moves quantized bytes, not fp32):
per-tile absmax scales are computed by a jitted kernel, int4 codes are
nibble-packed on device, and only the code array + the (tiny) scale
vector cross to host.  Dequantization is the mirror jitted kernel on
the receiver, so neither endpoint touches fp32 payload bytes on the
hot path.

Numerics: ``x ≈ q * scale`` per tile with ``scale = max|x| / qmax``
(qmax 127 for int8, 7 for int4).  An all-zero tile uses scale 1 (any
scale dequantizes zeros exactly); a NON-FINITE tile ships a NaN scale
so the diverged values survive the hop and the receiver's NaN sentinel
(``src/train/VGG16.py:169-171``) still fires — per tile, so one NaN no
longer forces the whole leaf back to raw fp32 the way the legacy
per-tensor int8 wire dtype did.

A numpy twin of each kernel (``quantize_np``/``dequantize_leaf_np``)
serves the once-per-round Update/delta path, whose payloads are
already host-side; the hot data plane must use the device half (the
``codec`` slcheck analyzer flags host quantization inside tick loops).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.runtime.codec.specs import CodecSpec
from split_learning_tpu.runtime.protocol import QuantLeaf


class DevQuant:
    """Device-staged quantized leaf: codes + scales still on device so
    ``copy_to_host_async`` can prefetch them; the async sender's encode
    thunk turns it into a wire :class:`QuantLeaf`.  Registered as a
    pytree (unlike QuantLeaf) so ``_start_host_copy``/``tree_map`` walk
    into the device arrays."""

    def __init__(self, q: Any, scale: Any, bits: int, tile: int,
                 shape: tuple):
        self.q = q
        self.scale = scale
        self.bits = bits
        self.tile = tile
        self.shape = tuple(int(s) for s in shape)


jax.tree_util.register_pytree_node(
    DevQuant,
    lambda d: ((d.q, d.scale), (d.bits, d.tile, d.shape)),
    lambda aux, ch: DevQuant(ch[0], ch[1], *aux))


def _qmax(bits: int) -> float:
    return 127.0 if bits == 8 else 7.0


def _pack_nibbles(q):
    """Flat int8 codes in [-7, 7] -> two's-complement nibbles, two per
    uint8 byte, lo nibble first."""
    u = q.astype(jnp.uint8) & 0xF
    return (u[0::2] | (u[1::2] << 4)).astype(jnp.uint8)


def _unpack_nibbles(q):
    """Mirror of :func:`_pack_nibbles`: flat sign-extended codes."""
    u = q.astype(jnp.uint8)
    lo, hi = u & 0xF, u >> 4
    codes = jnp.stack([lo, hi], axis=-1).reshape(-1)
    return jnp.where(codes < 8, codes, codes.astype(jnp.int32) - 16)


@functools.partial(jax.jit, static_argnames=("tile", "bits", "kernel"))
def _quantize_dev(x, tile: int, bits: int, kernel: bool = False):
    """(codes, per-tile scales) for one float leaf, on device.

    Codes are the FLAT padded array: int8 for bits=8; for bits=4 two
    two's-complement nibbles packed per uint8 byte (lo nibble first).
    ``kernel`` routes the tiled math through the fused Pallas kernel
    (``ops/kernels/quant.py``) — one VMEM-resident pass instead of
    this chain of full-leaf HBM round-trips; the pad/reshape prologue
    and the nibble pack stay here either way."""
    qmax = _qmax(bits)
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = (-n) % tile
    # int4 packs code pairs: the padded count must also be even (an odd
    # tile width can leave it odd — one more tile of zeros fixes both)
    if bits == 4 and (n + pad) % 2:
        pad += tile
    flat = jnp.pad(flat, (0, pad))
    tiles = flat.reshape(-1, tile)
    if kernel:
        from split_learning_tpu.ops.kernels.quant import quantize_tiles
        q, scale = quantize_tiles(tiles, bits=bits)
    else:
        amax = jnp.max(jnp.abs(tiles), axis=1)
        scale = jnp.where(jnp.isfinite(amax),
                          jnp.where(amax > 0, amax / qmax, 1.0),
                          jnp.nan).astype(jnp.float32)
        codes = jnp.clip(jnp.round(tiles / scale[:, None]), -qmax, qmax)
        # NaN codes (non-finite tile: scale is NaN) become 0 — the NaN
        # scale alone carries the divergence, and int8-casting NaN
        # would be platform-defined where everything else here is
        # deterministic
        q = jnp.where(jnp.isfinite(codes), codes, 0.0).astype(jnp.int8)
    q = q.reshape(-1)
    if bits == 4:
        q = _pack_nibbles(q)
    return q, scale


@functools.partial(jax.jit, static_argnames=("tile", "bits", "n",
                                             "shape", "kernel"))
def _dequantize_dev(q, scale, tile: int, bits: int, n: int,
                    shape: tuple, kernel: bool = False):
    codes = _unpack_nibbles(q) if bits == 4 else q
    # the fused mirror kernel applies only to well-formed tiled codes
    # (exactly scale.count * tile codes — what OUR quantizers emit);
    # anything ragged keeps the legacy XLA chain below
    if kernel and codes.shape[0] == scale.shape[0] * tile:
        from split_learning_tpu.ops.kernels.quant import (
            dequantize_tiles,
        )
        out = dequantize_tiles(
            codes.astype(jnp.int8).reshape(-1, tile), scale)
        return out.reshape(-1)[:n].reshape(shape)
    flat = codes.astype(jnp.float32)
    padded = jnp.pad(flat, (0, (-flat.shape[0]) % tile)) \
        if flat.shape[0] % tile else flat
    out = (padded.reshape(-1, tile)
           * scale[:, None]).reshape(-1)[:n]
    return out.reshape(shape)


class QuantCodec:
    """Per-family activation/gradient quantizer (stateless)."""

    name = "quant"
    COUNTERS = ("quant_nonfinite",)

    def __init__(self, spec: CodecSpec, faults=None, kernels=None):
        self.bits = spec.bits
        self.tile = spec.tile
        # explicit kernel plan wins; None defers to the process-wide
        # plan at prepare time (ops/kernels.configure — installed by
        # make_codecs from the loaded config)
        self._kernels = kernels
        if faults is None:
            from split_learning_tpu.runtime.trace import (
                default_fault_counters,
            )
            faults = default_fault_counters
        self.faults = faults

    def _use_kernel(self) -> bool:
        from split_learning_tpu.ops import kernels as kplane
        return kplane.as_plan(self._kernels).quantize

    def prepare(self, tree, key: str = ""):
        """Device-side stage (training thread): float leaves become
        :class:`DevQuant` holders; int/bool leaves pass through."""
        kernel = self._use_kernel()

        def conv(leaf):
            ldt = getattr(leaf, "dtype", None)
            if (ldt is None or ldt == jax.dtypes.float0
                    or not jnp.issubdtype(ldt, jnp.floating)):
                return leaf
            x = jnp.asarray(leaf)
            q, scale = _quantize_dev(x, self.tile, self.bits,
                                     kernel=kernel)
            return DevQuant(q, scale, self.bits, self.tile, x.shape)
        return jax.tree_util.tree_map(
            conv, tree, is_leaf=lambda o: isinstance(o, DevQuant))

    def encode(self, prepared):
        """Host-side stage (async sender thread): fetch the staged
        device arrays and build wire :class:`QuantLeaf` leaves."""
        def conv(leaf):
            if isinstance(leaf, DevQuant):
                scale = np.asarray(leaf.scale)
                if not np.isfinite(scale).all():
                    # a diverged payload crossed the wire: visible in
                    # the counters, not just in the eventual NaN loss
                    self.faults.inc("quant_nonfinite")
                return QuantLeaf(q=np.asarray(leaf.q), scale=scale,
                                 bits=leaf.bits, tile=leaf.tile,
                                 shape=leaf.shape)
            if getattr(leaf, "dtype", None) == jax.dtypes.float0:
                return np.zeros(np.shape(leaf), np.float32)
            return np.asarray(leaf)
        return jax.tree_util.tree_map(
            conv, prepared, is_leaf=lambda o: isinstance(o, DevQuant))


def dequantize_leaf(leaf: QuantLeaf, kernels=None):
    """Wire QuantLeaf -> device float32 array (receiver hot path).

    Handles both generations: the legacy per-tensor scalar-scale form
    keeps its exact original computation (bit parity with the int8
    wire-dtype path), the tiled form runs the jitted kernel.  Decode is
    self-describing (no sender config in scope), so the fused Pallas
    mirror engages through the RECEIVER's kernel plan — the explicit
    ``kernels`` argument, or the process-wide plan."""
    if leaf.tile == 0 and leaf.shape is None:
        return jnp.asarray(leaf.q, jnp.float32) * np.float32(leaf.scale)
    from split_learning_tpu.ops import kernels as kplane
    kernel = kplane.as_plan(kernels).dequantize
    n = int(np.prod(leaf.shape)) if leaf.shape else 1
    return _dequantize_dev(jnp.asarray(leaf.q), jnp.asarray(leaf.scale),
                           leaf.tile, leaf.bits, n, tuple(leaf.shape),
                           kernel=kernel)


# -- numpy twins (once-per-round Update/delta path; host-side inputs) ------

def quantize_np(x: np.ndarray, tile: int, bits: int) -> QuantLeaf:
    qmax = _qmax(bits)
    flat = np.asarray(x, np.float32).reshape(-1)
    n = flat.size
    pad = (-n) % tile
    if bits == 4 and (n + pad) % 2:
        pad += tile   # keep tile alignment AND an even code count
    padded = np.pad(flat, (0, pad))
    tiles = padded.reshape(-1, tile)
    amax = np.max(np.abs(tiles), axis=1)
    with np.errstate(invalid="ignore"):
        scale = np.where(np.isfinite(amax),
                         np.where(amax > 0, amax / qmax, 1.0),
                         np.nan).astype(np.float32)
        q = np.clip(np.round(tiles / scale[:, None]), -qmax,
                    qmax)
    q = np.nan_to_num(q, nan=0.0).astype(np.int8).reshape(-1)
    if bits == 4:
        u = (q.astype(np.uint8) & 0xF)
        q = (u[0::2] | (u[1::2] << 4)).astype(np.uint8)
    return QuantLeaf(q=q, scale=scale, bits=bits, tile=tile,
                     shape=tuple(int(s) for s in np.shape(x)))


def dequantize_leaf_np(leaf: QuantLeaf) -> np.ndarray:
    if leaf.tile == 0 and leaf.shape is None:
        return np.asarray(leaf.q, np.float32) * np.float32(leaf.scale)
    if leaf.bits == 4:
        u = np.asarray(leaf.q, np.uint8)
        lo, hi = u & 0xF, u >> 4
        codes = np.stack([lo, hi], axis=-1).reshape(-1).astype(np.int32)
        codes = np.where(codes < 8, codes, codes - 16)
    else:
        codes = np.asarray(leaf.q, np.int32)
    flat = codes.astype(np.float32)
    if flat.size % leaf.tile:
        flat = np.pad(flat, (0, (-flat.size) % leaf.tile))
    scale = np.asarray(leaf.scale, np.float32)
    n = int(np.prod(leaf.shape)) if leaf.shape else 1
    out = (flat.reshape(-1, leaf.tile) * scale[:, None]).reshape(-1)[:n]
    return out.reshape(leaf.shape)
