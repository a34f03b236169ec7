"""Shared helpers for the Pallas kernel plane.

One copy of the decisions every kernel call site makes:

* :func:`pick_block` — exact-divisor grid block sizing for kernels
  whose inner loops need whole blocks (flash attention's K/V and Q
  sweeps);
* :func:`vmem_block` — block sizing for row-independent kernels
  (quantize, dequantize, the stage update): a ``(rows, cols)`` block
  Mosaic accepts — each of the last two block dimensions either a
  multiple of the native tile or the whole array dimension — that
  fits a fixed VMEM budget whatever the leaf's size.  The grid is
  ``cdiv`` over it; Pallas masks the ragged edge blocks;
* :func:`resolve_interpret` — the ``interpret=None`` auto-select: the
  Pallas interpreter off-TPU (CPU tests run the SAME kernel code), the
  native Mosaic lowering on real TPU.
"""

from __future__ import annotations

#: Rows of one native tile of the narrowest dtype the kernels move
#: (int8 packs (32, 128); bf16 (16, 128); f32 (8, 128)) — a row block
#: that is a multiple of it is aligned for every operand of a call.
SUBLANES = 32
LANES = 128
#: VMEM bytes one f32 block may occupy.  The widest call (the momentum
#: update) has five operands, each double-buffered by the pipeline:
#: 10 x 512 KiB = 5 MiB, inside the 16 MiB scoped-VMEM default of the
#: smallest supported chip (v5e).
BLOCK_BYTES = 512 * 1024


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pick_block(s: int, target: int = 128) -> int:
    """Largest divisor of s that is <= target (TPU-friendly when s is a
    multiple of 128; exact fallback for small/odd test shapes)."""
    b = min(s, target)
    while s % b:
        b -= 1
    return b


def vmem_block(rows: int, cols: int, *, split_cols: bool = True):
    """``(block_rows, block_cols)`` for a ``(rows, cols)`` f32 operand.

    ``block_cols`` is the whole row while ``SUBLANES`` rows of it fit
    :data:`BLOCK_BYTES`; a longer row is cut into lane-aligned pieces
    (``split_cols=False`` — a kernel that reduces along the row —
    raises instead).  ``block_rows`` is every row when they fit, else
    the largest ``SUBLANES`` multiple that does."""
    max_cols = BLOCK_BYTES // (4 * SUBLANES)
    if _round_up(cols, LANES) <= max_cols:
        bc = cols
    elif split_cols:
        bc = max_cols
    else:
        raise ValueError(
            f"a {cols}-wide row does not fit one VMEM block "
            f"(limit {max_cols})")
    fit = BLOCK_BYTES // (4 * _round_up(bc, LANES))
    br = rows if rows <= fit else fit // SUBLANES * SUBLANES
    return br, bc


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` runs the Pallas interpreter unless on real
    TPU, so the same kernel code path serves CPU tests and compiles
    natively on TPU."""
    if interpret is None:
        import jax
        return jax.default_backend() != "tpu"
    return interpret
