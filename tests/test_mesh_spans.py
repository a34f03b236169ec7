"""The tracer below the round loop: spans of the mesh path's own work in
the journal and in a profiler capture, ``Laps``' shared clock readings,
the scopes inside the compiled step, and ``tools/sl_trace.py``'s reading
of a mesh run."""

import importlib.util
import json
import pathlib
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from split_learning_tpu.config import from_dict
from split_learning_tpu.run import run_local
from split_learning_tpu.runtime import spans as spans_mod
from split_learning_tpu.runtime.checkpoint import (
    load_checkpoint, save_checkpoint,
)
from split_learning_tpu.runtime.context import MeshContext
from split_learning_tpu.runtime.log import Logger
from split_learning_tpu.runtime.spans import NULL_SPAN, Laps, Tracer

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "tools"))
import sl_trace  # noqa: E402

TINY_KWT = {"embed_dim": 16, "num_heads": 2, "mlp_dim": 32}


def _journal(directory, participant="server"):
    path = pathlib.Path(directory) / f"spans-{participant}.jsonl"
    return [json.loads(line) for line in path.read_text().splitlines()]


def _program_trace():
    """``benchmarks/program_trace.py``: the classifier the benchmark
    runs on the device trace's ``op_name`` paths."""
    bench = str(ROOT / "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        "program_trace", ROOT / "benchmarks" / "program_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# a span in a profiler capture
# --------------------------------------------------------------------------

def _host_events(trace_dir):
    """{name: [stats dict]} of the host planes' ``sl/*`` events."""
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    found: dict = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sl/"):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    return found


@pytest.mark.parametrize("enabled", [True, False])
def test_span_in_a_profiler_capture(tmp_path, enabled):
    """A context-manager span is an ``sl/<name>`` event with its round on
    the thread's line of a running capture, and a journal record; a
    disabled tracer leaves neither; ``start()``/``end()`` and
    ``record()`` spans, which may end on another thread, are journaled
    only."""
    tracer = Tracer("server", enabled=enabled, journal_dir=tmp_path)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        with tracer.span("round", round=7):
            with tracer.span("inner"):
                time.sleep(0.001)
            tracer.start("loose", round=7).end()
            tracer.record("timed", time.time() - 0.001, time.time(),
                          always=True)
    finally:
        jax.profiler.stop_trace()
    tracer.close()
    events = _host_events(tmp_path / "trace")
    if not enabled:
        assert events == {}
        assert not (tmp_path / "spans-server.jsonl").exists()
        return
    assert set(events) == {"sl/round", "sl/inner"}
    assert events["sl/round"] == [{"round": 7}]
    assert events["sl/inner"] == [{}]
    recs = {r["name"]: r for r in _journal(tmp_path)}
    assert set(recs) == {"round", "inner", "loose", "timed"}
    assert recs["inner"]["parent"] == recs["round"]["span"]
    assert recs["round"]["round"] == 7


def test_span_without_a_capture_costs_a_flag_test(tmp_path):
    tracer = Tracer("server", journal_dir=tmp_path)
    t0 = time.perf_counter()
    for _ in range(2000):
        with tracer.span("x", round=1):
            pass
    per_span = (time.perf_counter() - t0) / 2000
    tracer.close()
    assert len(_journal(tmp_path)) == 2000
    # uuid4 + two clock readings + a buffered dict: tens of microseconds
    assert per_span < 5e-4
    off = Tracer("server", enabled=False)
    assert off.span("x").__enter__() is NULL_SPAN


# --------------------------------------------------------------------------
# Laps
# --------------------------------------------------------------------------

def test_laps_share_their_clock_readings(tmp_path):
    tracer = Tracer("server", journal_dir=tmp_path)
    with tracer.span("train", round=2) as train:
        with Laps(tracer, round=2) as laps:
            for _ in range(3):
                laps.lap("feed", always=False)
                time.sleep(0.001)
                laps.lap("dispatch", always=False)
            laps.lap("sync")
            time.sleep(0.001)
    tracer.close()
    recs = _journal(tmp_path)
    laps_recs = [r for r in recs if r["name"] != "train"]
    assert [r["name"] for r in laps_recs] == ["feed", "dispatch"] * 3 \
        + ["sync"]
    for prev, nxt in zip(laps_recs, laps_recs[1:]):
        # one reading per boundary: a lap starts where the last ended
        assert nxt["ts"] == pytest.approx(prev["ts"] + prev["dur"],
                                          abs=2e-6)
    for r in laps_recs:
        assert r["parent"] == train.id and r["round"] == 2
    for name in ("feed", "dispatch", "sync"):
        journaled = sum(r["dur"] for r in laps_recs if r["name"] == name)
        assert laps.totals[name] == pytest.approx(journaled, abs=1e-5)
    assert laps.totals["feed"] >= 0.003
    # the thread's parenting stack is as it was
    assert tracer.current_id() is None


@pytest.mark.parametrize("tracer_kind", ["none", "disabled", "sampled_out"])
def test_laps_time_without_a_journal(tmp_path, tracer_kind):
    """The caller's accounting does not depend on what is journaled."""
    tracer = {"none": None,
              "disabled": Tracer("server", enabled=False),
              "sampled_out": Tracer("server", sample_rate=0.0,
                                    journal_dir=tmp_path)}[tracer_kind]
    with Laps(tracer, round=0) as laps:
        laps.lap("feed", always=False)
        time.sleep(0.002)
        laps.lap("upload", always=False)
        laps.stop()
        laps.stop()                      # idempotent
    assert laps.totals["feed"] >= 0.002
    assert set(laps.totals) == {"feed", "upload"}
    if tracer_kind == "sampled_out":
        tracer.close()
        assert _journal(tmp_path) == []


def test_laps_leave_the_stack_clean_on_an_error(tmp_path):
    tracer = Tracer("server", journal_dir=tmp_path)
    with pytest.raises(RuntimeError):
        with tracer.span("train"):
            with Laps(tracer) as laps:
                laps.lap("feed")
                raise RuntimeError("boom")
    assert tracer.current_id() is None
    tracer.close()
    assert {r["name"] for r in _journal(tmp_path)} == {"train", "feed"}


# --------------------------------------------------------------------------
# checkpoint spans
# --------------------------------------------------------------------------

def test_save_checkpoint_journals_its_two_halves(tmp_path):
    tracer = Tracer("server", journal_dir=tmp_path / "j")
    params = {"layer1": {"w": jnp.arange(6.0).reshape(2, 3)}}
    done = threading.Event()

    def write():
        with tracer.span("checkpoint_write", parent="feedfacefeedface",
                         round=4):
            save_checkpoint(tmp_path / "ck", "M_D", params, {},
                            round_idx=5, tracer=tracer)
        done.set()

    worker = threading.Thread(target=write, name="ck-worker")
    worker.start()
    worker.join()
    assert done.is_set()
    tracer.close()
    recs = {r["name"]: r for r in _journal(tmp_path / "j")}
    assert set(recs) == {"checkpoint_write", "ckpt_pull", "ckpt_store"}
    assert recs["checkpoint_write"]["parent"] == "feedfacefeedface"
    for child in ("ckpt_pull", "ckpt_store"):
        assert recs[child]["parent"] == recs["checkpoint_write"]["span"]
        assert recs[child]["thread"] == "ck-worker"
    back = load_checkpoint(tmp_path / "ck", "M_D")
    assert back["round_idx"] == 5
    np.testing.assert_array_equal(back["params"]["layer1"]["w"],
                                  np.arange(6.0).reshape(2, 3))
    # and without a tracer, as every other caller saves
    save_checkpoint(tmp_path / "ck", "M_D", params, {}, round_idx=6)
    assert load_checkpoint(tmp_path / "ck", "M_D")["round_idx"] == 6


def test_trace_module_keeps_no_profiler_wrappers():
    from split_learning_tpu.runtime import trace
    assert not hasattr(trace, "annotate") and not hasattr(trace, "trace")
    assert "annotate" not in trace.__doc__


# --------------------------------------------------------------------------
# a toy round on the mesh path
# --------------------------------------------------------------------------

PER_STEP = ["feed", "upload", "dispatch"]
TRAIN_LEAVES = {
    "resident": ["round_setup"] + PER_STEP * 2 + ["sync", "fedavg"],
    "host": ["round_setup"] + PER_STEP * 2
    + ["sync", "pull", "extract", "aggregate"],
}
DETAIL_KEYS = {
    "resident": {"host_data_s", "dispatch_s", "device_sync_s",
                 "fedavg_dispatch_s"},
    "host": {"host_data_s", "dispatch_s", "device_sync_s"},
}


@pytest.fixture(scope="module", params=["resident", "host"])
def mesh_run(request, tmp_path_factory):
    """Two rounds of a tiny two-stage job through ``run_local`` on the
    device-resident path, and on the host path (resident reports
    ineligible)."""
    tmp = tmp_path_factory.mktemp(request.param)
    cfg = from_dict(dict(
        model="KWT", dataset="SPEECHCOMMANDS", clients=[2, 1],
        global_rounds=2, synthetic_size=64, val_max_batches=2,
        val_batch_size=16, compute_dtype="float32",
        model_kwargs=TINY_KWT, log_path=str(tmp / "logs"),
        learning={"batch_size": 4, "control_count": 2,
                  "optimizer": "adamw", "learning_rate": 1e-3},
        distribution={"num_samples": 16},
        topology={"cut_layers": [2]},
        checkpoint={"directory": str(tmp / "ckpt"), "save": True}))
    patch = pytest.MonkeyPatch()
    if request.param == "host":
        patch.setattr(MeshContext, "train_cluster_resident",
                      lambda self, *a, **k: None)
    try:
        result = run_local(cfg, logger=Logger(str(tmp / "logs"),
                                              console=False))
    finally:
        patch.undo()
    assert [h.ok for h in result.history] == [True, True]
    rounds = [json.loads(line) for line in
              (tmp / "logs" / "metrics.jsonl").read_text().splitlines()]
    return {"path": request.param, "spans": _journal(tmp / "logs"),
            "rounds": [r for r in rounds if r.get("kind") == "round"]}


def test_mesh_round_journals_its_work(mesh_run):
    spans = mesh_run["spans"]
    assert sl_trace.validate_spans(spans) == []
    assert sl_trace.orphan_spans(spans) == []
    by_id = {s["span"]: s for s in spans}
    for r in (0, 1):
        train = next(s for s in spans
                     if s["name"] == "train" and s["round"] == r)
        leaves = [s for s in spans if s.get("parent") == train["span"]]
        assert [s["name"] for s in leaves] == TRAIN_LEAVES[mesh_run["path"]]
        # back to back on the loop's thread, inside the train span
        for s in leaves:
            assert s["thread"] == train["thread"]
            assert s["ts"] >= train["ts"] - 1e-6
            assert s["ts"] + s["dur"] <= train["ts"] + train["dur"] + 1e-5
        write = next(s for s in spans if s["name"] == "checkpoint_write"
                     and s["round"] == r)
        assert by_id[write["parent"]]["name"] == "checkpoint"
        assert by_id[write["parent"]]["round"] == r
        assert write["thread"] != train["thread"]
        kids = [s["name"] for s in spans if s.get("parent") == write["span"]]
        assert kids == ["ckpt_pull", "ckpt_store"]


def test_train_detail_is_the_spans_seconds(mesh_run):
    spans = mesh_run["spans"]
    for rec in mesh_run["rounds"]:
        detail = rec["train_detail"]
        assert set(detail) == DETAIL_KEYS[mesh_run["path"]]

        def total(*names):
            return sum(s["dur"] for s in spans if s["name"] in names
                       and s.get("round") == rec["round_idx"])
        # one measurement: the record's seconds are the journal's
        assert detail["host_data_s"] == pytest.approx(
            total("feed", "upload"), abs=2e-3)
        assert detail["dispatch_s"] == pytest.approx(total("dispatch"),
                                                     abs=2e-3)
        assert detail["device_sync_s"] == pytest.approx(total("sync"),
                                                        abs=2e-3)
        if "fedavg_dispatch_s" in detail:
            assert detail["fedavg_dispatch_s"] == pytest.approx(
                total("fedavg"), abs=2e-3)


def test_critical_path_of_a_mesh_round(mesh_run):
    """The train phase is the mesh context's spans, not one stretch of
    ``queue_wait``; the worker thread's save is not on the path."""
    reports = sl_trace.critical_path(mesh_run["spans"])
    assert [r["round"] for r in reports] == [0, 1]
    for rep in reports:
        c = rep["components_s"]
        assert rep["components_sum_s"] == pytest.approx(rep["wall_s"],
                                                        rel=1e-6)
        assert c["compute"] > 0 and c["input"] > 0 and c["control"] > 0
        if mesh_run["path"] == "resident":
            # (on the host path the per-cluster fold runs between
            # `extract` and `aggregate`, in no span of its own)
            assert c["queue_wait"] < 0.15 * rep["wall_s"]
    txt = sl_trace.render_report(reports)
    assert "input=" in txt
    events = sl_trace.build_trace(mesh_run["spans"])["traceEvents"]
    cats = {e["name"]: e["cat"] for e in events if e.get("ph") == "X"}
    assert cats["feed"] == cats["upload"] == "input"
    assert cats["checkpoint_write"] == "aggregate"
    assert cats["round_setup"] == "control"


def test_background_save_is_not_on_the_critical_path():
    def span(name, ts, dur, thread="main", parent=None):
        return {"v": 1, "trace": "t", "span": name + str(ts),
                "parent": parent, "name": name, "part": "server",
                "thread": thread, "ts": ts, "dur": dur, "round": 1}
    spans = [span("train", 10.0, 1.0),
             span("feed", 10.0, 0.4), span("dispatch", 10.4, 0.6),
             # the last round's save, still writing beside this train
             span("checkpoint_write", 9.9, 0.9, thread="pool")]
    rep = sl_trace.critical_path(spans)[0]
    assert rep["components_s"]["input"] == pytest.approx(0.4)
    assert rep["components_s"]["compute"] == pytest.approx(0.6)
    assert rep["components_s"]["aggregate"] == 0.0


# --------------------------------------------------------------------------
# the scopes inside the compiled step
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def step_op_names(eight_devices):
    """``op_name`` paths of a toy ``sl_train_step`` (two stages, both
    rematerialized), from the compiled program's text: XLA writes the
    whole path once it has inlined the stages' calls.  Compiled with
    the persistent cache off: its key leaves the names out, so it may
    hand back the same operations under the names of an older
    checkout."""
    from jax.experimental.compilation_cache import compilation_cache
    from split_learning_tpu.parallel import (
        PipelineModel, make_mesh, make_train_step,
    )
    from split_learning_tpu.parallel.pipeline import (
        init_pipeline_variables, shard_to_mesh, stack_for_clients,
    )
    mb, M = 2, 2
    example = jax.ShapeDtypeStruct((mb, 40, 98), jnp.float32)
    pipe = PipelineModel("KWT_SPEECHCOMMANDS", [9], example,
                         num_microbatches=M, remat="all",
                         model_kwargs=TINY_KWT, scan_unroll=1)
    mesh = make_mesh(1, 1, eight_devices[:1])
    params = init_pipeline_variables(pipe, jax.random.key(0),
                                     example)["params"]
    opt = optax.adamw(1e-3)
    step = make_train_step(pipe, opt, mesh, donate=False)
    put = lambda tree: shard_to_mesh(stack_for_clients(tree, 1), mesh)  # noqa: E731
    lowered = step.lower(
        put(params), put(opt.init(params)), put({}),
        jnp.zeros((1, M, mb, 40, 98)), jnp.zeros((1, M, mb), jnp.int32),
        jax.vmap(jax.random.key)(jnp.arange(1)))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert re.search(r"HloModule jit_sl_train_step\b", text)
    return sorted(set(re.findall(r'op_name="([^"]+)"', text)))


@pytest.mark.parametrize("scope,phase", [
    ("stage1", "fwd"), ("stage1", "remat"), ("stage1", "bwd"),
    ("stage2", "fwd"), ("stage2", "remat"), ("stage2", "bwd"),
    ("loss", "fwd"), ("loss", "remat"), ("loss", "bwd"),
    ("pipeline", "fwd"), ("pipeline", "bwd"),
    ("hop", "fwd"), ("optimizer", None), ("grad_sync", None)])
def test_compiled_step_names_every_phase(step_op_names, scope, phase):
    """The benchmark's one rule on the path finds every (scope, phase)
    in the real step: a jax that spells these paths differently fails
    here and not in a metric."""
    classify = _program_trace().classify
    mine = [n for n in step_op_names if classify(n) == (scope, phase)]
    assert mine, (scope, phase)
    whole = [n for n in mine if n.startswith("jit(sl_train_step)/")]
    assert whole, mine[:3]
    if phase == "remat":
        assert all("rematted_computation" in n for n in whole)
    if phase == "bwd":
        assert all("transpose(jvp" in n for n in whole)
    if phase == "fwd":
        assert not any("transpose(" in n for n in whole)


def test_step_paths_spell_the_phases_as_the_rule_expects(step_op_names):
    whole = [n for n in step_op_names
             if n.startswith("jit(sl_train_step)/")]
    remat = [n for n in whole if "rematted_computation" in n]
    # the recomputed forward runs inside the backward pass, under the
    # checkpoint's name, and carries the stage's scope
    assert remat and all("transpose(jvp" in n and "/checkpoint/" in n
                         for n in remat)
    assert {m for n in remat for m in re.findall(r"stage\d+", n)} \
        == {"stage1", "stage2"}
    # most of the step's operations are under one of the scopes
    classify = _program_trace().classify
    other = [n for n in whole if classify(n)[0] == "other"]
    assert len(other) < 0.1 * len(whole)
    # the tick loop itself, and so the stacking of its residuals, is
    # under the pipeline's name in both passes
    assert "jit(sl_train_step)/jvp(pipeline)/while" in whole
    assert "jit(sl_train_step)/transpose(jvp(pipeline))/while" in whole


@pytest.mark.parametrize("make,name", [
    ("fedavg", "jit_sl_fedavg"), ("eval", "jit_sl_eval_step")])
def test_boundary_programs_have_fixed_names(eight_devices, make, name):
    if make == "fedavg":
        from split_learning_tpu.parallel import make_fedavg_step, make_mesh
        mesh = make_mesh(2, 1, eight_devices[:2])
        lowered = make_fedavg_step(mesh).lower(
            {"w": jnp.ones((2, 3))}, jnp.ones((2,)))
    else:
        from split_learning_tpu.models import build_model
        from split_learning_tpu.runtime.validation import make_eval_step
        model = build_model("KWT_SPEECHCOMMANDS", **TINY_KWT)
        x = jnp.zeros((2, 40, 98))
        variables = model.init(jax.random.key(0), x, train=False)
        lowered = make_eval_step(model, False).lower(
            variables, x, jnp.zeros((2,), jnp.int32))
    assert f"module @{name} " in lowered.as_text()


def test_module_docstring_names_the_profiler_bridge():
    assert "sl/<name>" in spans_mod.__doc__
