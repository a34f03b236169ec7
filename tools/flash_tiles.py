#!/usr/bin/env python3
"""Time the three flash kernels alone at one call's shapes under given
tilings, from a device trace (own device time of the instructions named
``slt_flash_*``; a host clock around a 2 ms kernel reads the dispatch).

A probe for whoever changes ``ops/flash_attention.py tiling``: it ranks
tilings of ONE call; the cell decides (PERF.md section 6, PRs 31 and 35).
On the chip, through ``chiprun``:

    python3 tools/flash_tiles.py --window 1024 512,128,128 512,256,256
    python3 tools/flash_tiles.py --heads 16 --kv-heads 16 --d 192 chosen

A tiling is ``grid,block_q,block_k`` (``Tiling``), given to all three
kernels, or ``chosen``: what ``tiling`` picks under ``--cap``.  One line
of JSON a tiling: milliseconds a call of each kernel, mean over ``--reps``.
"""

from __future__ import annotations

import argparse
import collections
import importlib
import json
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def kernel_ms(trace_dir: pathlib.Path, reps: int) -> dict:
    """{kernel: ms a call} from the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    total = collections.Counter()
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                # the instruction's own name holds the kernel's, bare or
                # wrapped (``jvp_slt_flash_fwd_.1`` under a plain ``grad``)
                name = ev.name.split(" = ", 1)[0]
                for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
                    if f"slt_flash_{kernel}" in name:
                        total[kernel] += ev.duration_ns
    return {k: round(v / reps / 1e6, 4) for k, v in sorted(total.items())}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("tilings", nargs="+")
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--dv", type=int, default=128)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--cap", type=int, default=512)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/flash_tiles.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    # the package exports the function under the module's name
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")
    from split_learning_tpu.platform import apply_compile_cache
    apply_compile_cache()

    b, s, h, g = args.rows, args.seq, args.heads, args.kv_heads
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (b * h, s, args.d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b * g, s, args.d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b * g, s, args.dv), jnp.bfloat16)
    w = jax.random.normal(keys[3], (b * h, s, args.dv), jnp.bfloat16)
    window = fa._band_window(s, args.window)
    interpret = fa.resolve_interpret(None)      # a CPU rehearsal: no times
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for text in args.tilings:
        if text == "chosen":
            tiles = tuple(fa.tiling(kernel, s, window, args.cap, args.cap)
                          for kernel in fa.KERNELS)
        else:
            tiles = (fa.Tiling(*map(int, text.split(","))),) * 3

        @jax.jit
        def grads(q, k, v, w, tiles=tiles):
            return jax.grad(lambda *a: (fa._flash(
                *a, True, interpret, tiles, window, h // g) * w).astype(
                    jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

        record = {"shape": [b, s, h, g, args.d, args.dv, args.window],
                  "tilings": [list(t) for t in tiles]}
        try:
            jax.block_until_ready(grads(q, k, v, w))
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                for _ in range(args.reps):
                    jax.block_until_ready(grads(q, k, v, w))
                jax.profiler.stop_trace()
                record["ms"] = kernel_ms(pathlib.Path(tmp), args.reps)
        except Exception as e:                 # a tiling Mosaic refuses
            record["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        line = json.dumps(record)
        print(line, flush=True)
        with out.open("a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
