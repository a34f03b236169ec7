"""``gate_trace``: the own time under ``attn_gate``, a scope nested in
``attn_proj``, over a made-up trace whose arithmetic can be done by hand;
``layer_trace``'s partition of the same step still adds up, with the gate
inside ``attn_proj``; None where the program has no gate or no trace."""

import pytest

import gate_trace
import layer_trace
import run_cell

STEP = "jit_sl_train_step(1)"
BLOCK = "jit(sl_train_step)/jvp(pipeline)/while/body/closed_call/stage1/" \
    "DecoderBlock/attention/"
BWD = "jit(sl_train_step)/transpose(jvp(pipeline))/while/body/" \
    "closed_call/checkpoint/stage1/DecoderBlock/attention/"


def _trace(ops, step_ns=20_000_000):
    mark = (0.0, 1_000.0, 7)
    lo = mark[1]
    return {"mark": mark, "spans": {}, "device": [{
        "name": "/device:TPU:0",
        "modules": [(lo + 10, lo + 10 + step_ns, STEP)],
        "ops": sorted((lo + 10 + s, lo + 10 + e, n, t)
                      for s, e, n, t in ops)}]}


def _made_up(gate: bool = True):
    """One step of 20 ms: 2 ms of ``q_proj`` and 1 ms of ``o_proj`` under
    ``attn_proj``; with the gate, a fusion of 0.75 ms under
    ``attn_proj/attn_gate`` in the forward and a ``while`` of 1 ms there in
    the backward pass that holds 0.25 ms of ``o_proj``'s gradient (a
    nesting the reader must net out); 3 ms of a flash kernel under
    ``attn_window``; 0.5 ms of ``norm_residual``; one gate fusion after
    the step, outside it."""
    scope = BLOCK + "attn_proj/attn_gate/"
    ops = [
        (0, 2e6, "%fusion.1 = fusion()", BLOCK + "attn_proj/q_proj/dot:"),
        (3e6, 6e6, "%slt_flash_fwd.2 = custom-call()",
         BLOCK + "attn_window/slt_flash_fwd:"),
        (7e6, 8e6, "%fusion.3 = fusion()", BLOCK + "attn_proj/o_proj/dot:"),
        (9e6, 9.5e6, "%fusion.4 = fusion()",
         "jit(sl_train_step)/jvp(pipeline)/while/body/closed_call/stage1/"
         "DecoderBlock/norm_residual/add:"),
    ]
    if gate:
        ops += [
            (10e6, 10.75e6, "%fusion.5 = fusion()",
             scope + "g_proj/dot_general:"),
            (12e6, 13e6, "%while.6 = while()",
             BWD + "attn_proj/attn_gate/while:"),
            (12.5e6, 12.75e6, "%fusion.7 = fusion()",
             BWD + "attn_proj/o_proj/transpose(dot):"),
            (25e6, 26e6, "%fusion.8 = fusion()", scope + "logistic:"),
        ]
    return _trace(ops)


def _run():
    return {"cell": {"name": "laguna_xs2_c3.round",
                     "config": "laguna_xs2_c3"}, "window_s": 0.05}


def _read(name, run):
    return run_cell.load_module(
        run_cell.HERE / "metrics" / f"{name}.py").read(run)


def _given(monkeypatch, trace, reads):
    monkeypatch.setattr(layer_trace.trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(layer_trace, "read",
                        lambda path: reads.append(path) or trace)


@pytest.mark.parametrize("op_name,inside", [
    (BLOCK + "attn_proj/attn_gate/g_proj/dot_general:", True),
    (BWD + "attn_proj/attn_gate/logistic:", True),
    ("jit(sl_train_step)/transpose(jvp(attn_gate))/mul:", True),
    (BLOCK + "attn_proj/o_proj/dot_general:", False),
    (BLOCK + "attn_proj/attn_gate_like/mul:", False),
    ("", False),
])
def test_an_operation_is_the_gates_where_its_path_holds_the_scope(
        op_name, inside):
    assert gate_trace.under(op_name) == inside
    if inside and "attn_proj" in op_name:
        # the partition knows no attn_gate: the projections keep it
        assert layer_trace.classify(op_name) == "attn_proj"


def test_the_gate_reads_its_own_time_inside_the_projections(monkeypatch):
    reads = []
    _given(monkeypatch, _made_up(), reads)
    run = _run()
    assert _read("attn_proj_ms", run) == pytest.approx(4.75)
    # 0.75 + the while's 1.0 net of the 0.25 it holds; not the fusion
    # after the step
    assert _read("attn_gate_ms", run) == pytest.approx(1.5)
    # the trace was read once, by layer_trace, and its parse kept
    assert len(reads) == 1 and "_trace_read" in run


def test_the_partition_still_adds_up_to_the_step(monkeypatch):
    """Every operation inside the step once, net of what it holds, in
    exactly one scope of ``layer_trace``: the gate's time is a part of
    ``attn_proj``'s and nothing is counted twice."""
    got = layer_trace.scope_times(_made_up(), 0.05)
    assert got["ms"] == pytest.approx({"attn_proj": 4.75,
                                       "attn_window": 3.0,
                                       "norm_residual": 0.5})
    assert sum(got["ms"].values()) == pytest.approx(8.25)
    # what the gate adds to attn_proj: its own 1.5 and the 0.25 of
    # o_proj's gradient that its while holds
    plain = layer_trace.scope_times(_made_up(gate=False), 0.05)
    assert got["ms"]["attn_proj"] - plain["ms"]["attn_proj"] \
        == pytest.approx(gate_trace.scope_ms(_made_up(), 0.05) + 0.25)


def test_without_the_gate_it_reads_nothing(monkeypatch):
    """A model without a gate (or a program without the scope): None, and
    the other metrics read as before."""
    _given(monkeypatch, _made_up(gate=False), [])
    run = _run()
    assert _read("attn_gate_ms", run) is None
    assert _read("attn_proj_ms", run) == pytest.approx(3.0)
    assert gate_trace.scope_ms({"mark": None, "device": [], "spans": {}},
                               0.05) is None


def test_a_run_without_a_trace_reads_nothing(capsys):
    """A CPU rehearsal, or a run whose trace is missing: None, no raise."""
    run = dict(_run(), cell={"name": "no_such_cell.round"})
    assert _read("attn_gate_ms", run) is None
    assert "layer_trace: not read" in capsys.readouterr().err
