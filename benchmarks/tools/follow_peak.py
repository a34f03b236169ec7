#!/usr/bin/env python3
"""What ``compare.follow`` holds on the device: drive a reference module
alone (no program in the process, so the peak is the reference's) through
three AdamW steps on a feed of the given shape, and print the device's
``peak_bytes_in_use`` beside the parameter count.

    python3 benchmarks/tools/follow_peak.py \
        --reference benchmarks/tests/data/tokens_fixture.py \
        --kwargs '{"n_block": 10}' --feed 2 4 2048 --vocab 32000

``--compare`` names another ``compare.py`` (a parent's), to read the two
side by side in one call.  ``seconds`` says where ``follow``'s time went
(each part waited for, so the parts do not overlap as they would), and
``peak_after_first_and_last_call`` which part set the peak.
"""

import argparse
import collections
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
import run_cell  # noqa: E402


def time_parts(compare, seconds, peaks):
    """Wrap the parts of ``follow`` that move or compute whole trees:
    each part's seconds, and the device's peak once it has run (a peak
    never falls, so the part that first shows a value set it)."""
    import jax
    dev = jax.devices()[0]

    def timed(name):
        fn = getattr(compare, name)

        def wrapper(*a, **kw):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(*a, **kw))
            seconds[name] += time.perf_counter() - t0
            peaks.setdefault(name, []).append(
                (dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
            return out
        setattr(compare, name, wrapper)
    for name in ("microbatch_grad", "optimizer_step", "_add_into",
                 "leaf_norms", "diff_norms"):
        if hasattr(compare, name):
            timed(name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reference", required=True)
    ap.add_argument("--kwargs", default="{}")
    ap.add_argument("--feed", type=int, nargs="+", required=True,
                    help="microbatches, rows, then a row's shape")
    ap.add_argument("--vocab", type=int, required=True,
                    help="ids (and, for a token model, labels) below this")
    ap.add_argument("--classes", type=int, default=0,
                    help="labels one a row below this (else one a position)")
    ap.add_argument("--compare", default=str(BENCH / "compare.py"))
    ap.add_argument("--seed", type=int, default=2_100_000_007)
    args = ap.parse_args()
    import jax
    import numpy as np
    compare = run_cell.load_module(pathlib.Path(args.compare))
    seconds, peaks = collections.Counter(), {}
    time_parts(compare, seconds, peaks)
    ref = run_cell.load_module(pathlib.Path(args.reference))
    kwargs = json.loads(args.kwargs)
    rng = np.random.default_rng(args.seed)
    feed = []
    for t in range(compare.STEPS):
        x = rng.integers(1, args.vocab, size=args.feed).astype(np.int32)
        labels = rng.integers(0, args.classes, size=args.feed[:2]) \
            if args.classes else rng.integers(0, args.vocab, size=args.feed)
        feed.append((x, labels.astype(np.int32), np.asarray(
            jax.random.key_data(jax.random.key(t)))))
    params, stats = jax.jit(lambda k: ref.init(k, kwargs))(
        jax.random.key(args.seed % 2 ** 32))
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    params = jax.tree_util.tree_map(np.asarray, params)
    learning = {"optimizer": "adamw", "learning-rate": 1e-4,
                "weight-decay": 0.01}
    dev = jax.devices()[0]
    before = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    t0 = time.perf_counter()
    out = compare.follow(ref, learning, params, stats, feed)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)
    print(json.dumps({
        "compare": args.compare, "platform": dev.platform,
        "parameters": n, "peak_bytes_before": before,
        "peak_bytes_in_use": peak,
        "peak_bytes_a_parameter": peak / n,
        "peak_bytes_reserved": stats.get("peak_bytes_reserved"),
        "follow_s": time.perf_counter() - t0, "seconds": dict(seconds),
        "peak_after_first_and_last_call": {
            k: [v[0], v[-1]] for k, v in peaks.items()},
        "losses": out["losses"]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
