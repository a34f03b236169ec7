"""``slcheck`` — static analysis for the split-learning runtime.

Three analyzers over the three subsystems whose invariants used to be
enforced only by runtime tests:

* :mod:`~split_learning_tpu.analysis.protocol_check` — the wire
  protocol: every send/recv site against the declarative message
  state machine in :mod:`~split_learning_tpu.analysis.model`, codec
  coverage (encode/decode/crc/chaos-injection) for every frame kind;
* :mod:`~split_learning_tpu.analysis.jaxpr_audit` — the compiled hot
  path: host syncs in tick loops, fp32 upcasts on the bf16 wire,
  recompile hazards, donated-buffer reuse;
* :mod:`~split_learning_tpu.analysis.concurrency` — the transport
  threads: lock ordering, blocking-under-lock, thread shutdown paths
  (with a runtime twin in :mod:`~split_learning_tpu.analysis.locks`,
  ``SLCHECK_LOCKS=1``);
* :mod:`~split_learning_tpu.analysis.codec_check` — the wire codecs:
  every codec counter registered, no host-side quantization in hot
  loops, quantizer kernels actually staged on device;
* :mod:`~split_learning_tpu.analysis.pallas_check` — the Pallas
  kernel plane (PK001): every enableable kernel (fused quantize/
  dequantize, fused stage_update, the decoders' flash attention) traced
  with the kernel on must show its ``pallas_call`` in the hot-path
  jaxpr — kernels cannot silently fall back to XLA.

CLI: ``python -m split_learning_tpu.analysis`` (wrapper:
``tools/slcheck.py``).  This package is import-light on purpose —
``runtime/bus.py`` imports :mod:`~split_learning_tpu.analysis.locks`
at startup, so nothing here may pull in jax at module scope.
"""

from __future__ import annotations

__all__ = ["run_analyzers", "ANALYZERS"]


def __getattr__(name):
    if name in __all__:
        from split_learning_tpu.analysis import __main__ as _cli
        return getattr(_cli, name)
    raise AttributeError(name)
