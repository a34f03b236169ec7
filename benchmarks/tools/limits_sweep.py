#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, what the limits of
``correct`` are set from: for each seed, in one process (one compilation),
the program's numbers against the reference (the lower readings), and for
the first ``--controls`` seeds the control's — the reference computed in
the next precision below the configuration's (float8_e4m3 operands for a
bfloat16 configuration) and put in the program's place — the same in the
configuration's own bfloat16 (what rounding alone reads) and the planted
faults' (``half_batch``, ``stale_val``).  A state left unchanged reads 1
by the measure and needs no run.  Every leaf's gap is kept
(``leaf_gaps``), so another statistic can be read without the chip.  Training readings need no measured window: each seed
runs set-up's two rounds only.

    python3 benchmarks/tools/limits_sweep.py --workload vgg16_c7.round \
        --seeds 12 --controls 3 > chiprun_out/sweep_vgg.jsonl
"""

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import run_cell  # noqa: E402

KEYS = ("loss", "loss1", "grad", "grad_med", "grad_all", "dparam",
        "dparam_med", "fedavg", "val_loss", "leaf_gaps")


def fp8(a):
    import jax.numpy as jnp
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def bf16(a):
    import jax.numpy as jnp
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_100_000_007)
    args = ap.parse_args()
    env = run_cell.Env(args.workload)
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        job = run_cell.Job(env, seed)
        job.warm()
        row = {"seed": seed, "program": job.compare()}
        if i < args.controls:
            row["control_fp8"] = job.compare(cast=fp8)
            row["same_precision_bf16"] = job.compare(cast=bf16)
            row["fault_half_batch"] = job.compare(fault="half_batch")
            row["fault_stale_val"] = job.compare(fault="stale_val")
        print(json.dumps({k: ({n: v[n] for n in v if n in KEYS
                               or n.endswith("_leaf")}
                              if isinstance(v, dict) else v)
                          for k, v in row.items()}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
