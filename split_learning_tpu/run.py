"""Single-process training cell: the whole deployment on one mesh.

The reference needs one ``server.py`` process, N ``client.py`` processes,
and a RabbitMQ broker to train at all (``/root/reference/README.md:144-171``).
On TPU the natural unit is one SPMD program, so this driver collapses the
deployment: logical clients are synthesized from the config's per-stage
counts, planned into clusters, and trained by the compiled mesh backend —
no transport in the hot path.  The multi-process protocol mode
(``python -m split_learning_tpu.server`` / ``.client``) shares every
piece of this except the context.

Usage::

    python -m split_learning_tpu.run --config config.yaml
"""

from __future__ import annotations

import argparse

from split_learning_tpu.config import Config, from_yaml
from split_learning_tpu.runtime.context import MeshContext
from split_learning_tpu.runtime.log import Logger
from split_learning_tpu.runtime.loop import TrainResult, run_training
from split_learning_tpu.runtime.plan import Registration, plan_clusters


def synthesize_registrations(cfg: Config,
                             profiles: dict | None = None) -> list:
    """Logical clients for in-process mode: ``client_{stage}_{i}`` per the
    config's per-stage counts (the reference's CLI ``--layer_id`` surface,
    ``client.py:14-17``)."""
    regs = []
    for stage, count in enumerate(cfg.clients, start=1):
        for i in range(count):
            cid = f"client_{stage}_{i}"
            regs.append(Registration(
                client_id=cid, stage=stage,
                profile=(profiles or {}).get(cid)))
    return regs


def run_local(cfg: Config, devices=None,
              logger: Logger | None = None,
              profiles: dict | None = None) -> TrainResult:
    from split_learning_tpu.parallel.multihost import ensure_initialized
    if ensure_initialized():
        import jax
        print(f"multi-host: process {jax.process_index()}"
              f"/{jax.process_count()}")
    logger = logger or Logger.for_run(cfg, "server", console=True)
    regs = synthesize_registrations(cfg, profiles)
    plans = plan_clusters(cfg, regs)
    ctx = MeshContext(cfg, devices=devices)
    try:
        return run_training(cfg, ctx, plans, logger)
    finally:
        ctx.shutdown()


def main(argv=None) -> int:
    from split_learning_tpu.platform import (
        apply_compile_cache, apply_platform_env,
    )
    apply_platform_env()
    apply_compile_cache()
    ap = argparse.ArgumentParser(
        description="Run a full split-learning training cell in-process.")
    ap.add_argument("--config", default="config.yaml")
    args = ap.parse_args(argv)
    cfg = from_yaml(args.config)
    from split_learning_tpu.runtime import blackbox
    blackbox.install(cfg, "server", role="server")
    result = run_local(cfg)
    for rec in result.history:
        acc = (f" val_acc={rec.val_accuracy:.4f}"
               if rec.val_accuracy is not None else "")
        print(f"round {rec.round_idx}: ok={rec.ok} "
              f"samples={rec.num_samples}{acc}")
    # the loop records a diverged round and carries on (reference
    # semantics); the process must still say that one happened
    return 0 if all(rec.ok for rec in result.history) else 1


if __name__ == "__main__":
    raise SystemExit(main())
