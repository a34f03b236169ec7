#!/usr/bin/env python3
"""Play a later PR's move on a copy of the benchmark: add the token-model
fixture (``tests/data/tokens_fixture.{yaml,py}``) as a configuration and a
cell by NEW files and NEW entries alone, editing no file of the copy.

    python3 benchmarks/tools/with_fixture.py benchmarks/_work/fixture_tree
    PYTHONPATH=$PWD python3 benchmarks/_work/fixture_tree/benchmarks/run_cell.py \
        --workload tokens_fixture.round --seed 7 --seconds 30 --trace 0

The copy holds ``BENCHMARK.json`` and ``benchmarks/``; the program is
imported from where ``PYTHONPATH`` says.  The fixture is a proof of the
harness and never an entry of the repository's own ``BENCHMARK.json``.
"""

import json
import pathlib
import shutil
import sys

import yaml

BENCH = pathlib.Path(__file__).resolve().parent.parent
NAME = "tokens_fixture"
CELL = f"{NAME}.round"


def add_fixture(dest: pathlib.Path) -> pathlib.Path:
    """Make the copy under ``dest`` and return its ``benchmarks``."""
    bench = dest / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "_work", "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    for ext in ("yaml", "py"):
        shutil.copy(BENCH / "tests" / "data" / f"{NAME}.{ext}",
                    bench / "configs" / f"{NAME}.{ext}")
    conf = yaml.safe_load((bench / "configs" / f"{NAME}.yaml").read_text())
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": NAME, "source": conf["source"],
        "file": f"benchmarks/configs/{NAME}.yaml",
        "reduced": conf["reduced"], "why": "harness fixture"})
    spec["workloads"].append({
        "name": CELL, "config": NAME, "traffic": "round", "chips": 1,
        "why": "harness fixture: whole rounds of 8 steps of 8 sequences x "
               "2,048 tokens"})
    # a metric that names its cells gets the new cell's name, as the PR
    # that adds a cell would give it
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (dest / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    edited = [str(p) for p, b in before.items() if p.read_bytes() != b]
    assert not edited, edited
    return bench


if __name__ == "__main__":
    print(add_fixture(pathlib.Path(sys.argv[1]).resolve()))
