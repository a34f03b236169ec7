#!/usr/bin/env python3
"""Spreads of a cell's runs, as the bound's rule reads them: for each set
of result lines (one file a run, ``set<k>.<seed>.out``) and each metric,
the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; then the
wider of the sets' spreads and five times it, and the two readings the
driver's check takes: for tightness the mean of the sets' spreads with each
set's run farthest from its median left out (at most half the bound), for
looseness the spread of all the runs together (at least an eighth of it).

    python3 benchmarks/tools/spreads.py chiprun_out/sets_vgg
"""

import collections
import json
import pathlib
import statistics
import sys


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(directory: str) -> int:
    sets = collections.defaultdict(lambda: collections.defaultdict(list))
    for path in sorted(pathlib.Path(directory).glob("set*.out")):
        line = path.read_text().strip().splitlines()[-1]
        result = json.loads(line)
        if not result["correct"]:
            print(f"{path.name}: correct is false")
        for name, m in result["metrics"].items():
            sets[path.name.split(".")[0]][name].append(m["value"])
    widest = collections.defaultdict(float)
    for set_name, metrics in sorted(sets.items()):
        for name, values in metrics.items():
            med = statistics.median(values)
            widest[name] = max(widest[name], spread(values))
            print(f"{set_name} {name}: n={len(values)} median={med:.6g} "
                  f"min={min(values):.6g} max={max(values):.6g} "
                  f"spread={100 * spread(values):.3f}%")
    for name, wide in widest.items():
        per_set = [m[name] for m in sets.values()]
        trimmed = [sorted(v, key=lambda x, v=v: abs(
            x - statistics.median(v)))[:-1] for v in per_set]
        tight = statistics.mean(spread(v) for v in trimmed)
        loose = spread([x for v in per_set for x in v])
        print(f"widest {name}: {100 * wide:.3f}%  x5 = {100 * 5 * wide:.2f}%"
              f"  tightness {100 * tight:.3f}%  looseness {100 * loose:.3f}%")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
