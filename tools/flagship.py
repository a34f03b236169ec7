"""Flagship multi-round learning run (VERDICT r4 next-step #2).

Drives the reference's experiment shape — ``configs/baseline1.yaml``
geometry (VGG16/CIFAR10, cut 7, 2x2 clients, IID) at the reference's
experiment scale of ~50 global rounds
(``/root/reference/other/Vanilla_SL/README.md:50-51``) — through the
real round loop, and commits the per-round validation-accuracy
trajectory as an in-repo artifact:

    python tools/flagship.py --rounds 50 --samples 250 \
        --out artifacts/flagship_cpu

Data honesty: this image has zero network egress and no real CIFAR-10
bytes anywhere on disk, so the run uses the framework's synthetic
CIFAR-10 stand-in (class-template Gaussians + noise,
``data/datasets.py:_synthetic_images``) and SAYS so in the artifact.
Operators with network run ``python -m split_learning_tpu.data --fetch
cifar10`` first and the identical command trains on real bytes.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--samples", type=int, default=250,
                    help="per-feeder samples per round")
    ap.add_argument("--synthetic-size", type=int, default=2500,
                    help="per-feeder synthetic dataset size")
    ap.add_argument("--lr", type=float, default=5e-4,
                    help="reference default (config.yaml): 5e-4")
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--optimizer", default="sgd",
                    help="sgd (reference default) | adamw | adamw-bf16")
    ap.add_argument("--clip", type=float, default=None,
                    help="clip-grad-norm (Vanilla_SL parity knob)")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--mb", type=int, default=4,
                    help="control-count (microbatches per optimizer "
                         "step — optimizer steps/round = samples/"
                         "(batch*mb); keep it small on small rounds or "
                         "adam resets every single step)")
    ap.add_argument("--out", default="artifacts/flagship_cpu")
    ap.add_argument("--tag", default=None,
                    help="label recorded in the artifact (default: "
                         "jax backend name)")
    args = ap.parse_args(argv)

    # one process, like the run CLI: the backend JAX_PLATFORMS names
    # (or the accelerator jax finds), and the shared compile cache — a
    # resumed/repeated flagship run must not repay VGG16's compiles
    from split_learning_tpu.platform import (
        apply_compile_cache, apply_platform_env,
    )
    apply_platform_env()
    apply_compile_cache()

    from split_learning_tpu.config import from_dict
    from split_learning_tpu.run import run_local
    from split_learning_tpu.runtime.log import Logger

    # stage into a sibling dir and swap only on success: a failure or
    # a kill mid-run must not have already destroyed the previously
    # committed artifact
    final_out = REPO / args.out
    out = final_out.with_name(final_out.name + ".tmp")
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True, exist_ok=True)
    cfg = from_dict({
        "model": "VGG16", "dataset": "CIFAR10",
        "clients": [2, 2],                       # baseline1 geometry
        "global-rounds": args.rounds,
        "synthetic-size": args.synthetic_size,
        "val-max-batches": 4, "val-batch-size": 125,
        "compute-dtype": "float32",
        "topology": {"cut-layers": [7]},
        "distribution": {"mode": "iid", "num-samples": args.samples},
        "aggregation": {"strategy": "fedavg"},
        "learning": {"batch-size": args.batch,
                     "control-count": args.mb,
                     "optimizer": args.optimizer,
                     "learning-rate": args.lr,
                     "momentum": args.momentum,
                     **({"clip-grad-norm": args.clip}
                        if args.clip else {})},
        "checkpoint": {"directory": str(out / "ckpt"), "save": False},
        "log-path": str(out),
    })
    import jax
    backend = args.tag or jax.default_backend()
    t0 = time.time()
    result = run_local(cfg, logger=Logger(str(out), console=False))
    wall = time.time() - t0
    # one summary builder (tools/flagship_summary.py) for completed and
    # cut-short runs alike, so the two artifact shapes cannot drift;
    # run-specific metadata layers on top
    from flagship_summary import summarize
    summary = summarize(out)
    summary.update(
        backend=backend,
        rounds=args.rounds,
        samples_per_round=2 * args.samples,
        learning={"optimizer": args.optimizer, "lr": args.lr,
                  "momentum": args.momentum, "batch": args.batch,
                  "control_count": args.mb,
                  "clip_grad_norm": args.clip},
        total_wall_s=round(wall, 1),
    )
    (out / "FLAGSHIP.json").write_text(json.dumps(summary, indent=1)
                                       + "\n")
    shutil.rmtree(final_out, ignore_errors=True)
    out.rename(final_out)
    print(json.dumps({k: v for k, v in summary.items()
                      if k != "trajectory"}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
