"""bench.py orchestrator: one child at a time, a global budget, an
artifact that survives, and no path that hides the device.

The orchestrator never imports jax; where the sections run is decided
once from the environment (``JAX_PLATFORMS=cpu`` -> toy size on CPU,
otherwise the chip or a non-zero exit).  ``run_plan``'s decisions are
driven with a scripted ``run_section``; the process-level contracts run
the real script.
"""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent
_spec = importlib.util.spec_from_file_location(
    "slt_bench", HERE.parent / "bench.py")
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


class _FakeBudget:
    """Budget stub with scripted remaining() values (last one sticks)."""

    def __init__(self, remainings, total=100.0):
        self.seq = list(remainings)
        self.total = total

    def remaining(self):
        return self.seq.pop(0) if len(self.seq) > 1 else self.seq[0]

    def elapsed(self):
        return self.total - self.seq[0]


def _script(monkeypatch, outcomes):
    """Replace run_section: ``outcomes`` maps a section name to a
    result dict (success) or an error string."""
    calls = []

    def run(name, timeout, ctx):
        calls.append((name, timeout))
        out = outcomes[name]
        if isinstance(out, str):
            return None, out
        return {"result": dict(out), "backend": ctx["mode"],
                "device_kind": "TPU fake"}, None

    monkeypatch.setattr(bench, "run_section", run)
    return calls


def test_budget_exhaustion_skips_remaining_sections(monkeypatch):
    # first section fits; the budget is gone before the second — it and
    # everything after must be recorded as skipped, never started
    plan = [("headline", 50), ("round", 50),
            ("resnet50_cifar100_3way_cut_3_6", 50)]
    calls = _script(monkeypatch, {
        "headline": {"samples_per_sec": 5.0, "batch": 1},
        "round": {"rounds": 1},
        "resnet50_cifar100_3way_cut_3_6": {"samples_per_sec": 1.0}})
    flushes = []
    cfgs, extra = {}, {}
    results = bench.run_plan(
        plan, {"mode": "tpu"}, cfgs, extra,
        budget=_FakeBudget([200.0, 10.0]),
        on_section=lambda: flushes.append(True))
    assert [n for n, _ in calls] == ["headline"]
    assert results == {"headline": {"samples_per_sec": 5.0, "batch": 1}}
    assert extra["round"] == {"error": "skipped (budget)"}
    assert cfgs["resnet50_cifar100_3way_cut_3_6"] == {
        "error": "skipped (budget)"}
    assert extra["reliability"]["budget_skipped"] == [
        "round", "resnet50_cifar100_3way_cut_3_6"]
    # flushed after the completed section AND after marking the skips
    assert len(flushes) == 2


def test_budget_clips_section_deadline(monkeypatch):
    calls = _script(monkeypatch,
                    {"headline": {"samples_per_sec": 1.0, "batch": 1}})
    bench.run_plan([("headline", 900)], {"mode": "tpu"}, {}, {},
                   budget=_FakeBudget([300.0]))
    assert calls == [("headline", 300.0)]


def test_failed_section_is_recorded_and_the_plan_moves_on(monkeypatch):
    """A section that dies (a kernel that does not compile, an OOM, a
    deadline) is that section's error.  Nothing re-runs it smaller or
    elsewhere, and the mode never changes under the later sections."""
    calls = _script(monkeypatch, {
        "headline": {"samples_per_sec": 5.0, "batch": 1},
        "tinyllama_tinystories_4stage": "rc=1 after 3.0s",
        "round": "deadline: section killed after 50s",
        "mfu": {"headline_tflops": 1.0}})
    ctx = {"mode": "tpu"}
    cfgs, extra = {}, {}
    results = bench.run_plan(
        [("headline", 50), ("tinyllama_tinystories_4stage", 50),
         ("round", 50), ("mfu", 50)], ctx, cfgs, extra)
    assert [n for n, _ in calls] == [
        "headline", "tinyllama_tinystories_4stage", "round", "mfu"]
    assert cfgs["tinyllama_tinystories_4stage"] == {
        "error": "rc=1 after 3.0s"}
    assert extra["round"] == {"error": "deadline: section killed after 50s"}
    assert set(results) == {"headline", "mfu"}
    assert ctx["mode"] == "tpu"
    assert extra["chip"] == "TPU fake"


def test_cpu_platform_never_fills_the_per_chip_value():
    art = bench.Artifact(baseline=10.0)
    art.results["headline"] = {"samples_per_sec": 50.0, "batch": 32}
    assert art.payload()["value"] == 50.0
    art.extra["platform"] = "cpu"
    rec = art.payload()
    assert rec["metric"].endswith("_per_chip") and rec["value"] is None
    assert rec["vs_baseline"] is None
    assert rec["extra"]["platform"] == "cpu"


def test_section_child_roundtrip_on_cpu(monkeypatch):
    """One real section child (the Pallas codec cell, interpreted):
    the payload names the backend it ran on, and the kernels hold
    parity through the bench's own entry points."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("SLT_BENCH_PALLAS_REPS", "1")
    payload, err = bench.run_section("pallas_codec", 300, {"mode": "cpu"})
    assert err is None, err
    assert payload["backend"] == "cpu" and payload["device_kind"] == "cpu"
    res = payload["result"]
    assert res["quant_parity_bitwise"] and res["update_parity_bitwise"]
    # an interpreter timing is not a device number
    assert res["quant_kernel_wall_ratio"] is None
    assert res["update_kernel_wall_ratio"] is None


def _run_bench_main(env_extra, tmp_path, drop=(), timeout=300):
    env = os.environ.copy()
    env.update({"JAX_PLATFORMS": "cpu", "SLT_BENCH_FAKE_BASELINE": "100",
                "SLT_BENCH_PARTIAL_PATH": str(tmp_path / "partial.json"),
                # bench.json artifacts land in tmp, not the checkout
                "SLT_BENCH_ARTIFACT_DIR": str(tmp_path)})
    env.update(env_extra)
    for k in drop:
        env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, str(HERE.parent / "bench.py")], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=timeout)
    return proc


def _record(proc) -> dict:
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line on stdout: {proc.stdout!r}"
    return json.loads(lines[-1])


def test_artifact_lands_under_tiny_budget(tmp_path):
    # a budget too small for ANY section must still produce one valid
    # JSON line (rc=0 path), labelled with the platform it was for
    proc = _run_bench_main({"SLT_BENCH_BUDGET_S": "1",
                            "SLT_BENCH_PLAN": "headline"}, tmp_path)
    rec = _record(proc)
    assert proc.returncode == 0
    assert rec["value"] is None and rec["unit"] == "samples/sec/chip"
    assert rec["extra"]["platform"] == "cpu"
    assert rec["extra"]["headline"] == {"error": "skipped (budget)"}
    assert rec["extra"]["reliability"]["budget_skipped"] == ["headline"]


def test_orchestrator_exception_still_emits_artifact(tmp_path):
    # an orchestrator bug must not lose the artifact: the record lands
    # on stdout with the error noted, and the rc stays nonzero
    proc = _run_bench_main({"SLT_BENCH_BUDGET_S": "60",
                            "SLT_BENCH_FAKE_BASELINE": "notafloat",
                            "SLT_BENCH_PLAN": "headline"}, tmp_path)
    rec = _record(proc)
    assert proc.returncode != 0
    assert rec["value"] is None
    assert "ValueError" in rec["extra"]["reliability"]["orchestrator_error"]


def test_without_a_chip_bench_fails_and_prints_no_metric(tmp_path):
    """No ``JAX_PLATFORMS=cpu`` in the environment means the bench needs
    the accelerator.  On a host without one the first section child
    finds the CPU backend, and the run stops: non-zero exit, nothing on
    stdout — never a CPU number under a chip name."""
    proc = _run_bench_main({"SLT_BENCH_PLAN": "headline,round"}, tmp_path,
                           drop=("JAX_PLATFORMS",))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs the chip" in proc.stderr
