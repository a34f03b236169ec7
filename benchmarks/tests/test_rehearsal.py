"""Each cell's toy rehearsal on the CPU: the result line's keys, no number
under a device metric's name, and ``correct`` coming out false when the
timed path is broken underneath or the control stands in the program's
place.  The whole harness runs except its look for a chip (the rehearsal
backend stands in for it).  Slow: about a minute a run.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

import run_cell

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
REQUIRED = {"correct", "attempted", "failed", "metrics", "device"}


def _run(workload, trace, capsys, seed=11):
    from split_learning_tpu.runtime import context
    context._GLOBAL_STEP_CACHE.clear()
    assert run_cell.main(["--workload", workload, "--seed", str(seed),
                          "--seconds", "1", "--trace", str(trace)]) == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
@pytest.mark.parametrize("trace", [0, 1])
def test_toy_rehearsal_prints_the_result_line(cell, trace, capsys):
    out, err = _run(cell, trace, capsys)
    assert REQUIRED <= set(out)
    assert set(out) - REQUIRED <= {"info", "compared", "breakdown"}
    assert list(out)[-1] == "compared"
    assert out["correct"] is True, err
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["device"]["platform"] == "cpu"
    # a CPU run gives no device number: only the program's own counts
    counters = {m["name"] for m in SPEC["per_layer"]
                if m["source"] == "program_counter"}
    assert set(out["metrics"]) <= (counters if trace else set())
    assert err.strip().splitlines()[-1] == "correct: True"
    for name, c in out["compared"].items():
        assert f"compared {name}:" in err and set(c) == {"value", "limit"}


def test_no_chip_and_no_rehearsal_variable_fails_without_a_result():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, "-c",
         "import os; os.environ.pop('JAX_PLATFORMS', None);"
         "import jax; jax.config.update('jax_platforms', 'cpu');"
         "import sys; sys.path.insert(0, 'benchmarks'); import run_cell;"
         "run_cell.main(['--workload', 'bert_base_c7.round'])"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


# -- the timed path broken underneath -----------------------------------------

def _state_unchanged(step):
    def broken(params, opt, stats, x, labels, rngs):
        import jax
        import jax.numpy as jnp
        # the step donates its inputs: hand back copies of the old state
        old = jax.tree_util.tree_map(jnp.copy, params)
        out = step(params, opt, stats, x, labels, rngs)
        return old, out[1], out[2], out[3]
    return broken


def _half_batch(step):
    def broken(params, opt, stats, x, labels, rngs):
        import jax.numpy as jnp
        half = x.shape[1] // 2
        # the second half of the microbatches left out: the mean is taken
        # over the first half, seen twice
        x = jnp.concatenate([x[:, :half], x[:, :half]], axis=1)
        labels = jnp.concatenate([labels[:, :half], labels[:, :half]], axis=1)
        return step(params, opt, stats, x, labels, rngs)
    return broken


# half the batch left out is planted here for BERT alone: at the toy's 8
# images a microbatch VGG16's two halves read alike (0.035 on the median
# leaf, inside the toy's limits); at the cell's own size the fault reads
# 29 times the sound runs (PERF.md section 2)
@pytest.mark.parametrize("fault,cell,caught_by", [
    (_state_unchanged, "bert_base_c7.round", {"dparam"}),
    (_state_unchanged, "vgg16_c7.round", {"dparam", "dparam_med"}),
    (_half_batch, "bert_base_c7.round", {"loss", "grad"})],
    ids=["state_unchanged-bert", "state_unchanged-vgg", "half_batch-bert"])
def test_a_broken_step_comes_out_not_correct(fault, caught_by, cell, capsys,
                                             monkeypatch):
    from split_learning_tpu.runtime import context
    make = getattr(context.make_train_step, "_bench_original",
                   context.make_train_step)
    monkeypatch.setattr(context, "make_train_step",
                        lambda *a, **kw: fault(make(*a, **kw)))
    out, err = _run(cell, 0, capsys, seed=12)
    assert out["correct"] is False, err
    failed = {k for k, c in out["compared"].items()
              if not c["value"] <= c["limit"]}
    assert failed & caught_by, (failed, out["compared"])


def test_the_control_in_the_programs_place_is_not_correct(
        cell="bert_base_c7.round"):
    """The reference computed in float8_e4m3 — the next precision below
    the configurations' bfloat16 — must fail a limit that the program's
    own run holds.  BERT only: at the toy's batch of 8 images VGG16's
    control reads twice the program's gap, inside the toy's limits; at
    the cell's own size it reads thousands of times (PERF.md section 2)."""
    sys.path.insert(0, str(ROOT / "benchmarks" / "tools"))
    import limits_sweep
    from split_learning_tpu.runtime import context
    context._GLOBAL_STEP_CACHE.clear()
    env = run_cell.Env(cell)
    job = run_cell.Job(env, 13)
    job.warm()
    limits = job.conf["limits"]
    sound = job.compare()
    control = job.compare(cast=limits_sweep.fp8)
    held = [k for k in limits if k in sound and k in control]
    assert all(sound[k] <= limits[k] for k in held), sound
    assert any(control[k] > limits[k] for k in held), control
