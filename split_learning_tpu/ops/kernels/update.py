"""Fused round-boundary stage-update Pallas kernels.

``MeshFoldBackend._fused_update`` finishes a stage in one jitted
program, but inside that program each leaf is still an XLA chain —
divide, (momentum multiply-add, subtract,) cast — i.e. several HBM
round-trips over every full-stage buffer at every round boundary.
These kernels collapse each leaf's finish into one VMEM-resident pass:

* :func:`finalize_leaf` — FedAvg divide (+ round for int leaves) +
  wire-dtype cast;
* :func:`momentum_leaf` — the FedAvgM step
  ``v' = m*v + (base - acc/tw); p' = (base - v').astype(wire_dtype)``
  emitting both the new params and the carried velocity in one pass.

The op order inside the kernel matches the jnp oracle exactly, so mesh
and host folds stay bit-identical on CPU (the 2-round velocity-carry
parity test pins it).  The math is elementwise, so a leaf is viewed as
``(rows, last_dim)`` and gridded in blocks sized by
:func:`~.util.vmem_block`: legal on TPU for every leaf shape (conv
kernels, 1-D biases) and bounded in VMEM however large the leaf.  The
jit/donation wrapper stays in ``runtime/aggregate.py`` (JX007 audits
it there); these are pure per-leaf ops traced into that program.

Scalars (total weight, momentum) arrive as traced values and ride in
as (1, 1) blocks broadcast to every grid instance — a new total weight
does NOT recompile the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from split_learning_tpu.ops.kernels.util import (
    resolve_interpret, vmem_block,
)


def kernel_ok(leaf) -> bool:
    """Kernel-eligible: at least 1-D and non-empty (0-d/empty leaves
    fall back to the XLA chain — no grid to block)."""
    return getattr(leaf, "ndim", 0) >= 1 and getattr(leaf, "size", 0) > 0


def _rows(x):
    """Leaf -> (rows, last_dim) view: the minor axis stays on the
    lanes, everything before it is flattened."""
    return x.reshape(-1, x.shape[-1])


def _grid(x):
    """(grid, leaf BlockSpec, scalar BlockSpec) for a 2-D operand."""
    br, bc = vmem_block(*x.shape)
    grid = (pl.cdiv(x.shape[0], br), pl.cdiv(x.shape[1], bc))
    return (grid, pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)))


def _finalize_kernel(acc_ref, tw_ref, out_ref, *, rnd: bool):
    a32 = acc_ref[...] / tw_ref[0, 0]
    if rnd:
        a32 = jnp.round(a32)
    out_ref[...] = a32.astype(out_ref.dtype)


def finalize_leaf(acc, tw, dtype, *, rnd: bool = False,
                  interpret: bool | None = None):
    """``(acc / tw)`` (+ round for int wire dtypes) cast to ``dtype``,
    one pass."""
    interpret = resolve_interpret(interpret)
    x = _rows(acc)
    grid, leaf2, scalar = _grid(x)
    tw2 = jnp.reshape(tw, (1, 1)).astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_finalize_kernel, rnd=rnd),
        out_shape=jax.ShapeDtypeStruct(x.shape, dtype),
        grid=grid,
        in_specs=[leaf2, scalar],
        out_specs=leaf2,
        interpret=interpret,
        name="slt_finalize_update",
    )(x, tw2)
    return out.reshape(acc.shape)


def _momentum_kernel(acc_ref, base_ref, vel_ref, tw_ref, m_ref,
                     p_ref, nv_ref):
    a32 = acc_ref[...] / tw_ref[0, 0]
    nv = m_ref[0, 0] * vel_ref[...] + (base_ref[...] - a32)
    nv_ref[...] = nv
    p_ref[...] = (base_ref[...] - nv).astype(p_ref.dtype)


def momentum_leaf(acc, base, vel, tw, m, dtype, *,
                  interpret: bool | None = None):
    """FedAvgM finish for one leaf: returns ``(params.astype(dtype),
    new_velocity f32)`` in one pass, oracle op order."""
    interpret = resolve_interpret(interpret)
    x = _rows(acc)
    grid, leaf2, scalar = _grid(x)
    tw2 = jnp.reshape(tw, (1, 1)).astype(jnp.float32)
    m2 = jnp.reshape(m, (1, 1)).astype(jnp.float32)
    p, nv = pl.pallas_call(
        _momentum_kernel,
        out_shape=[jax.ShapeDtypeStruct(x.shape, dtype),
                   jax.ShapeDtypeStruct(x.shape, jnp.float32)],
        grid=grid,
        in_specs=[leaf2, leaf2, leaf2, scalar, scalar],
        out_specs=[leaf2, leaf2],
        interpret=interpret,
        name="slt_momentum_update",
    )(x, _rows(base), _rows(vel), tw2, m2)
    return p.reshape(acc.shape), nv.reshape(acc.shape)
