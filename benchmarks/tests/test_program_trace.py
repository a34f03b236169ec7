"""``program_trace.py``: the rule that gives an operation its (scope,
phase), the gap attribution on hand-made intervals, the wire-format reader
on the two traces recorded on a TPU v5e, and the readers in ``metrics/``
on a run that has nothing to read."""

import json
import pathlib

import pytest

import program_trace as pt
import run_cell
import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data"
TINY = DATA / "tiny_tpu.xplane.pb"
SCOPES = DATA / "tiny_scopes_tpu.xplane.pb"
NEW_METRICS = ["step_fwd_ms", "step_remat_ms", "step_bwd_ms", "step_opt_ms",
               "fedavg_device_ms", "feed_build_ms", "feed_upload_ms",
               "checkpoint_write_ms", "idle_feed", "idle_validate",
               "idle_checkpoint", "idle_unattributed"]


@pytest.mark.parametrize("path,expected", [
    # a scan of two checkpointed scoped stages under value_and_grad
    ("jit(step)/stage1/dot_general", ("stage1", "fwd")),
    ("jit(step)/jvp()/while/body/closed_call/stage2/SplitModel/layer10/"
     "mlp_in/dot_general:", ("stage2", "fwd")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/stage1/tanh", ("stage1", "remat")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "stage1/mul", ("stage1", "bwd")),
    ("jit(step)/optimizer/add", ("optimizer", None)),
    # a plain grad without scan or checkpoint
    ("jit(f)/jvp(stage1)/mul", ("stage1", "fwd")),
    ("jit(f)/transpose(jvp(stage1))/mul", ("stage1", "bwd")),
    # the operation `transpose` is not the transform `transpose(`
    ("jit(step)/jvp()/while/body/closed_call/stage1/SplitModel/layer4/"
     "attention/transpose", ("stage1", "fwd")),
    # the loss inside the last stage counts by its own name
    ("jit(step)/jvp()/while/body/closed_call/stage2/loss/jit(_take)/gather",
     ("loss", "fwd")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/stage2/loss/reduce_sum", ("loss", "remat")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/hop/pad",
     ("hop", "bwd")),
    ("jit(step)/grad_sync/psum", ("grad_sync", None)),
    # the tick loop's own work: residuals stacked and read back
    ("jit(step)/jvp(pipeline)/while/body/dynamic_update_slice:",
     ("pipeline", "fwd")),
    ("jit(step)/transpose(jvp(pipeline))/while/body/squeeze:",
     ("pipeline", "bwd")),
    ("jit(step)/transpose(jvp(pipeline))/while/body/closed_call/checkpoint/"
     "stage2/dot_general:", ("stage2", "bwd")),
    ("jit(step)/jvp()/while/body/add", ("other", None)),
    ("jit(<lambda>)/dot_general:", ("other", None)),
    ("", ("other", None)),
])
def test_classify(path, expected):
    assert pt.classify(path) == expected


MAIN = sorted([
    (0, 1000, "round"), (10, 600, "train"), (20, 100, "round_setup"),
    (100, 200, "feed"), (200, 220, "upload"), (220, 300, "dispatch"),
    (300, 400, "feed"), (400, 420, "upload"), (420, 500, "dispatch"),
    (500, 590, "sync"), (610, 800, "validate"), (810, 820, "checkpoint"),
])


@pytest.mark.parametrize("at,name", [
    (150, "feed"), (210, "upload"), (299, "dispatch"), (595, "train"),
    (605, "round"), (700, "validate"), (815, "checkpoint"), (900, "round"),
    (1500, None), (-5, None)])
def test_innermost_span(at, name):
    assert pt.innermost(MAIN, at) == name


def test_idle_goes_to_the_innermost_span_at_the_gaps_midpoint():
    busy = [(230, 290), (430, 520), (650, 700)]
    idle = pt.idle_by_span(busy, -100, 1100, MAIN)
    # [-100, 230] mid 65: round_setup; [290, 430] mid 360: feed;
    # [520, 650] mid 585: sync; [700, 1100] mid 900: round
    assert dict(idle) == {"round_setup": 330, "feed": 140, "sync": 130,
                          "round": 400}
    assert sum(idle.values()) == 1200 - tr.length(busy)
    # no spans at all: every gap is nobody's
    assert dict(pt.idle_by_span(busy, 0, 1000, [])) == {None: 800}


def test_wire_reader_on_the_first_recorded_trace():
    trace = pt.read(TINY)
    assert [c["name"] for c in trace["device"]] == ["/device:TPU:0"]
    chip = trace["device"][0]
    assert len(chip["ops"]) == 12 and len(chip["modules"]) == 4
    fusions = [op for op in chip["ops"]
               if op[2].startswith("%convolution_tanh_fusion")]
    assert len(fusions) == 4
    assert {op[3] for op in fusions} == {"jit(<lambda>)/dot_general:"}
    assert all(name.startswith("jit__lambda(") for _, _, name
               in chip["modules"])
    assert trace["mark"] is not None and trace["spans"] == {}
    # the same events at the same times as jax's own reader gives
    plane = next(p for p in tr.load(TINY).planes
                 if p.name == "/device:TPU:0")
    theirs = tr.line_events(plane, tr.OPS_LINE)
    assert len(theirs) == len(chip["ops"])
    for (s, e, _), (mine_s, mine_e, _, _) in zip(theirs, chip["ops"]):
        # (jax's reader cuts a time to whole nanoseconds)
        assert abs(s - mine_s) < 1 and abs(e - mine_e) < 2
    mark = next(ev for p in tr.load(TINY).planes for line in p.lines
                for ev in line.events if ev.name == tr.CLOCK_MARK)
    assert trace["mark"][0] == pytest.approx(mark.start_ns, abs=1)
    # a trace of another program: nothing named, nothing to report
    got = pt.reduce(trace, 0.05, rounds=1)
    assert got["steps"] == 0 and got["span_ms"] == {}
    assert got["fedavg_device_ms"] is None
    assert got["idle_s"] == {"unattributed": pytest.approx(
        0.05 * got["idle_worst"])}


@pytest.fixture(scope="module")
def scopes():
    trace = pt.read(SCOPES)
    lo = trace["mark"][1]
    hi = max(e for spans in trace["spans"].values() for _, e, _ in spans)
    return trace, pt.reduce(trace, (hi - lo) / 1e9, rounds=1)


def test_recorded_scopes_trace_holds_the_programs_spans(scopes):
    trace, _ = scopes
    main = sorted(trace["spans"][trace["mark"][2]])
    assert [name for _, _, name in main] == [
        "round", "train"] + ["feed", "upload", "dispatch"] * 3 + [
        "sync", "fedavg", "validate", "checkpoint"]
    others = [spans for line, spans in trace["spans"].items()
              if line != trace["mark"][2]]
    assert [[name for _, _, name in spans] for spans in others] \
        == [["checkpoint_write"]]
    chip = trace["device"][0]
    programs = {name.split("(")[0] for _, _, name in chip["modules"]}
    assert {"jit_sl_train_step", "jit_sl_fedavg"} <= programs
    found = {pt.classify(op[3]) for op in chip["ops"]}
    # (the compiler kept one of the two recomputations)
    assert {("stage1", "fwd"), ("stage1", "bwd"),
            ("stage2", "fwd"), ("stage2", "remat"), ("stage2", "bwd"),
            ("pipeline", "fwd"), ("pipeline", "bwd"),
            ("optimizer", None)} <= found


def test_reduction_of_the_recorded_scopes_trace(scopes):
    _, got = scopes
    assert got["steps"] == 3
    for phase in pt.PHASES:
        assert got["phase_ms"][phase] > 0
    assert got["opt_ms"] > 0 and got["fedavg_device_ms"] > 0
    assert set(got["step_ms"]) >= {"stage1", "stage2", "pipeline",
                                   "optimizer"}
    # the parts are the whole, and the whole is the programs' device time
    parts = sum(got["phase_ms"].values()) + got["opt_ms"] \
        + got["step_ms"].get("other", 0.0) \
        + got["step_ms"].get("grad_sync", 0.0) \
        + sum(got["step_ms"].get("hop", {}).values())
    assert parts == pytest.approx(got["step_total_ms"], rel=1e-9)
    assert got["unscoped_share"] < 0.05
    # every idle second has an owner, and the owners' seconds make up
    # the idle share
    idle_s = sum(got["idle_s"].values())
    assert idle_s == pytest.approx(got["idle_worst"] * got["window_s"],
                                   rel=1e-6)
    # the host slept 2 ms in every feed and 3 ms in validate
    assert got["idle_s"]["feed"] > 0.004
    assert got["idle_s"]["validate"] > 0.002
    assert got["span_ms"]["feed"]["n"] == 3
    assert got["span_ms"]["feed"]["mean"] > 2.0
    assert got["span_ms"]["checkpoint_write"]["n"] == 1
    json.dumps(got)                     # one line of JSON


def _run(monkeypatch, tmp_path, trace_file=None, window_s=0.05):
    """A ``run`` as ``run_cell`` hands it to a metric, with the trace
    where ``run_cell`` keeps it."""
    monkeypatch.setattr(pt, "HERE", tmp_path)
    if trace_file is not None:
        dest = tmp_path / "_work" / "cell.x" / "trace" / "plugins"
        dest.mkdir(parents=True)
        (dest / "t.xplane.pb").write_bytes(trace_file.read_bytes())
    return {"cell": {"name": "cell.x"}, "window_s": window_s,
            "window_rounds": [{}]}


def _metric(name):
    return run_cell.load_module(
        pathlib.Path(run_cell.HERE) / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_reads_none_where_nothing_is_named(name, monkeypatch,
                                                  tmp_path, capsys):
    """On the parent commit's program (no span, no scope, no program
    name), on a CPU rehearsal (no device plane) and without any trace the
    reader returns None and does not raise."""
    for trace_file in (TINY, None):
        run = _run(monkeypatch, tmp_path / str(trace_file is None),
                   trace_file)
        assert _metric(name).read(run) is None
        assert "_program_trace" in run


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_reads_a_number_from_the_scopes_trace(name, monkeypatch,
                                                     tmp_path, capsys,
                                                     scopes):
    run = _run(monkeypatch, tmp_path, SCOPES, scopes[1]["window_s"])
    value = _metric(name).read(run)
    assert isinstance(value, float) and value >= 0.0
    err = capsys.readouterr().err
    line = next(ln for ln in err.splitlines()
                if ln.startswith("program_trace: "))
    assert json.loads(line[len("program_trace: "):])["steps"] == 3
    # read once a run: a second metric prints nothing
    _metric(name).read(run)
    assert "program_trace" not in capsys.readouterr().err


def test_idle_metrics_and_the_lines_other_spans_make_up_the_idle_share(
        monkeypatch, tmp_path, scopes):
    run = _run(monkeypatch, tmp_path, SCOPES, scopes[1]["window_s"])
    named = sum(_metric(n).read(run) for n in (
        "idle_feed", "idle_validate", "idle_checkpoint",
        "idle_unattributed"))
    got = run["_program_trace"]
    others = sum(v for k, v in got["idle_s"].items() if k not in (
        "feed", "upload", "validate", "checkpoint", "unattributed"))
    assert named + 100.0 * others / got["window_s"] == pytest.approx(
        100.0 * got["idle_worst"], abs=1e-6)


def test_every_new_metric_is_declared_for_both_cells():
    spec = json.loads((pathlib.Path(run_cell.ROOT)
                       / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        assert declared[name]["moves"] == "round_throughput"
        assert declared[name]["workloads"] == ["bert_base_c7.round",
                                               "vgg16_c7.round"]
    # appended after the older ones (seven since PR 28 retired
    # `host_data_share`), which are as they were
    assert [m["name"] for m in spec["per_layer"]][7:] == NEW_METRICS
