"""The token-model fixture (``data/tokens_fixture.{yaml,py}``: the
program's TinyLlama on the ``tokens`` data set kind) added to a copy of
the benchmark by new files and new entries alone, as a later PR would add
a causal language model, and rehearsed at its ``toy`` size through
``run_cell.main``: ``correct`` true, and false with the timed path broken
underneath.  The fixture is never an entry of the repository's own
``BENCHMARK.json``.  Slow: about a minute a run.
"""

import json
import pathlib
import sys

import pytest

import run_cell
from test_rehearsal import _half_batch, _state_unchanged

BENCH = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH / "tools"))
import with_fixture  # noqa: E402


@pytest.fixture
def copy(tmp_path, monkeypatch):
    bench = with_fixture.add_fixture(tmp_path)
    monkeypatch.setattr(run_cell, "ROOT", tmp_path)
    monkeypatch.setattr(run_cell, "HERE", bench)
    return bench


def _run(capsys, seed):
    from split_learning_tpu.runtime import context
    context._GLOBAL_STEP_CACHE.clear()
    assert run_cell.main(["--workload", with_fixture.CELL, "--seed",
                          str(seed), "--seconds", "1", "--trace", "0"]) == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


def test_the_fixture_is_in_no_list_of_the_benchmark():
    spec = (BENCH.parent / "BENCHMARK.json").read_text()
    assert with_fixture.NAME not in spec and "Llama" not in spec


def test_the_copy_adds_files_and_entries_and_edits_none(copy):
    ours = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    theirs = json.loads((copy.parent / "BENCHMARK.json").read_text())
    assert theirs["configs"][:-1] == ours["configs"]
    assert theirs["workloads"][:-1] == ours["workloads"]
    assert theirs["end_to_end"] == ours["end_to_end"]
    for p in BENCH.rglob("*"):
        rel = p.relative_to(BENCH)
        if p.is_file() and rel.parts[0] not in ("_work", "tests") \
                and "__pycache__" not in rel.parts:
            assert (copy / rel).read_bytes() == p.read_bytes(), rel
    got = run_cell.load_cell(with_fixture.CELL, rehearsal=True)
    assert got["conf"]["dataset"] == {"kind": "tokens", "seq-len": 32,
                                      "train": 32, "val": 8, "val-batch": 4}
    assert got["reference"].DATASET == "tokens"
    # and the cells that are there read what they read
    for cell in ours["workloads"]:
        assert run_cell.load_cell(cell["name"], rehearsal=False)["conf"] \
            ["dataset"]["kind"] in ("cifar10", "agnews")


def test_the_fixtures_toy_rehearsal_is_correct(copy, capsys):
    out, err = _run(capsys, seed=3_000_000_019)
    assert out["correct"] is True, err
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert {"loss", "grad", "dparam", "fedavg", "ckpt"} <= set(out["compared"])
    assert list(out)[-1] == "compared"
    # the validation rows the generator returned are the rows the program
    # validated on: the reference's loss over them is the program's
    assert out["info"]["not_held"]["val_loss"] < 0.02


@pytest.mark.parametrize("fault,caught_by", [
    (_state_unchanged, {"dparam"}), (_half_batch, {"loss", "grad"})],
    ids=["state_unchanged", "half_batch"])
def test_the_fixture_with_a_broken_step_is_not_correct(fault, caught_by, copy,
                                                       capsys, monkeypatch):
    from split_learning_tpu.runtime import context
    make = getattr(context.make_train_step, "_bench_original",
                   context.make_train_step)
    monkeypatch.setattr(context, "make_train_step",
                        lambda *a, **kw: fault(make(*a, **kw)))
    out, err = _run(capsys, seed=3_000_000_023)
    assert out["correct"] is False, err
    failed = {k for k, c in out["compared"].items()
              if not c["value"] <= c["limit"]}
    assert failed & caught_by, (failed, out["compared"])
