"""Pallas hot-path kernel plane (ROADMAP item 1, second half).

Single-pass fused kernels for the two remaining named hot blocks that
were plain XLA op chains:

* :mod:`~split_learning_tpu.ops.kernels.quant` — fused tiled absmax
  quantize (absmax reduce, scale, round/clip, NaN-scale sentinel, int4
  nibble-pack) and its dequantize mirror, one VMEM-resident pass per
  leaf instead of the ~8-op XLA chain's repeated HBM round-trips;
* :mod:`~split_learning_tpu.ops.kernels.update` — the fused
  round-boundary stage update (FedAvg divide + FedAvgM momentum + wire
  dtype cast) as one pass over each stage leaf.

All kernels follow ``ops/flash_attention.py``'s ``interpret=None``
auto-select (:func:`~.util.resolve_interpret`): the SAME kernel code
runs under the Pallas interpreter in CPU tests and lowers natively on
TPU.  Every call site keeps the pre-existing jitted XLA chain as the
parity oracle — kernels are bit-identical for int8 codec + update on
CPU, tolerance-pinned for int4 rounding edges — and the slcheck
``pallas`` analyzer (PK001) asserts an ENABLED kernel's ``pallas_call``
actually appears in the traced hot-path jaxpr, so a refactor cannot
silently fall back to XLA while the config claims kernels are on.

Gating: the ``kernels:`` config block becomes a :class:`KernelPlan`.
The plan travels two ways — explicitly (``QuantCodec(...,
kernels=...)``, ``MeshFoldBackend(kernels=...)``) or through the
process-wide default installed by :func:`configure` (which
``make_codecs``/``make_fold_backend`` call with the loaded config, so
the self-describing receiver decode path — which has no config in
scope — follows the same plan).  Default: everything off; behavior is
byte-for-byte the pre-kernel XLA path.
"""

from __future__ import annotations

import contextlib
import dataclasses

from split_learning_tpu.ops.kernels.util import (  # noqa: F401
    pick_block, resolve_interpret, vmem_block,
)

__all__ = ["KernelPlan", "DISABLED", "as_plan", "configure", "plan",
           "override", "pick_block", "resolve_interpret", "vmem_block"]


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Which Pallas kernels are live."""
    quantize: bool = False
    dequantize: bool = False
    stage_update: bool = False

    @property
    def any(self) -> bool:
        return self.quantize or self.dequantize or self.stage_update


DISABLED = KernelPlan()
_active: KernelPlan = DISABLED


def as_plan(obj) -> KernelPlan:
    """Coerce a config ``kernels:`` section (or a plan, or None) into a
    :class:`KernelPlan`.  None means "no opinion": the process-wide
    plan — so partial config shims (e.g. the scheduler's codec-retune
    shim) never silently disable configured kernels."""
    if obj is None:
        return _active
    if isinstance(obj, KernelPlan):
        return obj
    return KernelPlan(
        quantize=bool(getattr(obj, "quantize", False)),
        dequantize=bool(getattr(obj, "dequantize", False)),
        stage_update=bool(getattr(obj, "stage_update", False)))


def configure(obj) -> KernelPlan:
    """Install the process-wide kernel plan from a loaded config's
    ``kernels`` section.  ``configure(None)`` is a no-op returning the
    current plan."""
    global _active
    if obj is not None:
        _active = as_plan(obj)
    return _active


def plan() -> KernelPlan:
    """The process-wide kernel plan (default: :data:`DISABLED`)."""
    return _active


@contextlib.contextmanager
def override(**fields):
    """Test helper: temporarily replace fields of the process plan."""
    global _active
    prev = _active
    _active = dataclasses.replace(prev, **fields)
    try:
        yield _active
    finally:
        _active = prev
