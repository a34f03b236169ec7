"""``layer_trace``: which scope an operation's path gives it (the innermost
one), own times a step that add up to the step's own total, the ten
longest instructions of a scope, and the five metric files over a made-up
trace whose arithmetic can be done by hand; None where the program lacks
the scopes."""

import pytest

import layer_trace
import run_cell

STEP = "jit_sl_train_step(1)"
BLOCK = "jit(sl_train_step)/jvp(pipeline)/while/body/closed_call/stage1/"
BWD = "jit(sl_train_step)/transpose(jvp(pipeline))/while/body/closed_call/" \
    "checkpoint/stage1/"
NEW = ("attn_proj_ms", "ffn_dense_ms", "norm_residual_ms", "vocab_ms",
       "step_unscoped_ms")


@pytest.mark.parametrize("op_name,scope", [
    (BLOCK + "DecoderBlock/attention/attn_proj/q_proj/dot_general:",
     "attn_proj"),
    (BLOCK + "DecoderBlock/attention/attn_window/slt_flash_fwd:",
     "attn_window"),
    (BLOCK + "DecoderBlock/norm_residual/input_norm/rsqrt:",
     "norm_residual"),
    (BWD + "DecoderBlock/attention/ssm_mixer/ssm_scan/slt_ssd_bwd:",
     "ssm_scan"),
    ("jit(sl_train_step)/transpose(jvp(ssm_mixer))/ssm_scan/exp:",
     "ssm_scan"),
    (BWD + "DecoderBlock/attention/ssm_mixer/in_proj/dot_general:",
     "ssm_mixer"),
    (BLOCK + "DecoderBlock/ffn_dense/gate_proj/dot_general:", "ffn_dense"),
    (BLOCK + "DecoderBlock/moe_shared/shared_experts/up_proj/dot_general:",
     "moe_shared"),
    (BLOCK + "embed/Embed/jit(_take)/gather:", "embed"),
    (BLOCK + "head/Dense/dot_general:", "head"),
    ("jit(sl_train_step)/jvp(pipeline)/while/body/closed_call/stage2/"
     "loss/log_softmax:", "loss"),
    ("jit(sl_train_step)/optimizer/mul:", "optimizer"),
    ("jit(sl_train_step)/jvp(pipeline)/while/body/hop/concatenate:",
     "unscoped"),
    ("jit(sl_train_step)/transpose(jvp(pipeline))/while/body/add_any:",
     "unscoped"),
    ("jit(sl_train_step)/grad_sync/psum:", "unscoped"),
    (BLOCK + "DecoderBlock/moe/reshape:", "unscoped"),
    ("", "unscoped"),
])
def test_an_operation_goes_to_the_innermost_scope_of_its_path(op_name,
                                                               scope):
    assert layer_trace.classify(op_name) == scope


@pytest.mark.parametrize("name", ["tiny_scopes_tpu", "tiny_tpu"])
def test_the_lean_read_is_program_traces_but_the_host_spans(name):
    """Recorded TPU traces: the same device planes and clock mark as
    ``program_trace.read``, and no host span."""
    path = run_cell.HERE / "tests" / "data" / f"{name}.xplane.pb"
    whole, lean = layer_trace.program_trace.read(path), layer_trace.read(path)
    assert lean["device"] == whole["device"] and lean["device"]
    assert lean["mark"] == whole["mark"] is not None
    assert lean["spans"] == {}


def test_the_mark_is_found_past_a_false_match_of_its_id():
    """A line whose bytes hold the mark's id outside an event's head is
    looked through and passed over; the mark's own line gives it."""
    fields = layer_trace.program_trace.fields
    key = 2 ** 20 + 5
    head = b"\x08" + layer_trace._varint_bytes(key)
    assert dict(fields(head))[1] == key

    def field(no, payload):
        return bytes([no << 3 | 2]) + layer_trace._varint_bytes(
            len(payload)) + payload

    def event(meta, offset_ps, duration_ps):
        return b"\x08" + layer_trace._varint_bytes(meta) \
            + b"\x10" + layer_trace._varint_bytes(offset_ps) \
            + b"\x18" + layer_trace._varint_bytes(duration_ps)

    decoy = b"\x08\x01" + field(2, b"x" + head) + b"\x18\x64" \
        + field(4, event(3, 1000, 2000))
    mine = b"\x08\x02\x18\xe8\x07" + field(4, event(3, 0, 5000)) \
        + field(4, event(key, 4000, 3000))
    meta = field(2, layer_trace.MARK_BYTES)
    entry = b"\x08" + layer_trace._varint_bytes(key) + field(
        2, b"\x08" + layer_trace._varint_bytes(key) + meta)
    assert layer_trace._clock_mark([memoryview(decoy), memoryview(mine)],
                                   [memoryview(entry)]) == (1004.0, 1007.0, 2)


def _trace(ops, step_ns=10_000_000):
    mark = (0.0, 1_000.0, 7)
    lo = mark[1]
    return {"mark": mark, "spans": {}, "device": [{
        "name": "/device:TPU:0",
        "modules": [(lo + 10, lo + 10 + step_ns, STEP)],
        "ops": sorted((lo + 10 + s, lo + 10 + e, n, t)
                      for s, e, n, t in ops)}]}


def _made_up():
    """One step of 30 ms: a ``while`` of 4 ms under ``ssm_mixer`` whose
    body holds 1 ms under ``ssm_scan`` and 0.5 ms with no name; 2 ms of
    ``attn_proj`` in two instructions; 1.5 ms of ``norm_residual``; 0.75
    ms of ``ffn_dense``; 1 ms each of ``embed``, ``head`` and ``loss``;
    0.25 ms of ``optimizer``; one operation after the step, outside it."""
    mixer = BLOCK + "DecoderBlock/attention/ssm_mixer/"
    ops = [
        (0, 4e6, "%while.1 = while()", mixer + "while:"),
        (1e5, 11e5, "%slt_ssd_fwd.3 = custom-call()",
         mixer + "ssm_scan/slt_ssd_fwd:"),
        (2e6, 2.5e6, "%copy.4 = copy()", ""),
        (5e6, 6.5e6, "%fusion.7 = fusion()",
         BLOCK + "DecoderBlock/attention/attn_proj/q_proj/dot_general:"),
        (7e6, 7.5e6, "%fusion.8 = fusion()",
         BWD + "DecoderBlock/attention/attn_proj/o_proj/dot_general:"),
        (8e6, 9.5e6, "%multiply_reduce_fusion.2 = fusion()",
         BLOCK + "DecoderBlock/norm_residual/input_norm/mul:"),
        (10e6, 10.75e6, "%fusion.9 = fusion()",
         BLOCK + "DecoderBlock/ffn_dense/up_proj/dot_general:"),
        (11e6, 12e6, "%gather.1 = gather()", BLOCK + "embed/gather:"),
        (13e6, 14e6, "%fusion.10 = fusion()", BLOCK + "head/dot_general:"),
        (15e6, 16e6, "%reduce.5 = reduce()", BLOCK + "loss/reduce_max:"),
        (17e6, 17.25e6, "%fusion.11 = fusion()",
         "jit(sl_train_step)/optimizer/add:"),
        (31e6, 32e6, "%fusion.12 = fusion()", BLOCK + "head/dot_general:"),
    ]
    return _trace(ops, step_ns=30_000_000)


def test_own_times_a_step_by_scope_add_up_to_the_steps_total():
    got = layer_trace.scope_times(_made_up(), 0.05)
    assert got["steps"] == 1
    want = {"ssm_mixer": 2.5, "ssm_scan": 1.0, "unscoped": 0.5,
            "attn_proj": 2.0, "norm_residual": 1.5, "ffn_dense": 0.75,
            "embed": 1.0, "head": 1.0, "loss": 1.0, "optimizer": 0.25}
    assert got["ms"] == pytest.approx(want)
    # every operation inside the step once, net of what it holds: 11.5 ms
    assert sum(got["ms"].values()) == pytest.approx(11.5)
    assert got["top"]["attn_proj"] == [["fusion.7", pytest.approx(1.5)],
                                       ["fusion.8", pytest.approx(0.5)]]
    assert got["top"]["unscoped"] == [["copy.4", pytest.approx(0.5)]]


def test_the_ten_longest_instructions_of_a_scope():
    ops = [(i * 1e5, i * 1e5 + (i + 1) * 1e3, f"%fusion.{i} = fusion()",
            BLOCK + "DecoderBlock/norm_residual/add:") for i in range(12)]
    top = layer_trace.scope_times(_trace(ops), 0.05)["top"]["norm_residual"]
    assert [name for name, _ in top] == [f"fusion.{i}"
                                         for i in range(11, 1, -1)]


def _run():
    return {"cell": {"name": "moonlight_16b_c3.round",
                     "config": "moonlight_16b_c3"}, "window_s": 0.05}


def _read(name, run):
    return run_cell.load_module(
        run_cell.HERE / "metrics" / f"{name}.py").read(run)


def test_the_five_metric_files_read_one_reduction(monkeypatch, capsys):
    trace, reads = _made_up(), []
    monkeypatch.setattr(layer_trace.trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(layer_trace, "read",
                        lambda path: reads.append(path) or trace)
    run = _run()
    got = {name: _read(name, run) for name in NEW}
    assert got == pytest.approx({
        "attn_proj_ms": 2.0, "ffn_dense_ms": 0.75, "norm_residual_ms": 1.5,
        "vocab_ms": 3.0, "step_unscoped_ms": 0.5})
    assert len(reads) == 1 and run["_trace_read"] is trace
    line = [x for x in capsys.readouterr().err.splitlines()
            if x.startswith("layer_trace: ")]
    assert len(line) == 1 and '"read_s"' in line[0]


def test_a_scope_the_program_lacks_reads_nothing(monkeypatch):
    """A step with no ``ffn_dense`` and no ``attn_proj`` (a latent
    attention model without a dense block) reads None for those two."""
    ops = [(0, 1e6, "%fusion.1 = fusion()",
            BLOCK + "DecoderBlock/norm_residual/add:"),
           (2e6, 3e6, "%fusion.2 = fusion()",
            BLOCK + "DecoderBlock/attention/mla_latent/dot_general:")]
    trace = _trace(ops)
    monkeypatch.setattr(layer_trace.trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(layer_trace, "read", lambda p: trace)
    run = _run()
    assert _read("attn_proj_ms", run) is None
    assert _read("ffn_dense_ms", run) is None
    assert _read("vocab_ms", run) is None
    assert _read("norm_residual_ms", run) == pytest.approx(1.0)


def test_a_program_without_the_partition_reads_nothing(monkeypatch):
    """The parent's program names its mixers, experts and loss, and none
    of the scopes that partition a block: every new metric reads None,
    though ``loss`` and what lies under no scope are in the trace."""
    ops = [(0, 1e6, "%fusion.1 = fusion()", BLOCK + "loss/log_softmax:"),
           (2e6, 3e6, "%fusion.2 = fusion()",
            BLOCK + "DecoderBlock/attention/attn_full/slt_flash_fwd:"),
           (4e6, 5e6, "%fusion.3 = fusion()",
            BLOCK + "DecoderBlock/attention/q_proj/dot_general:")]
    trace = _trace(ops)
    monkeypatch.setattr(layer_trace.trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(layer_trace, "read", lambda p: trace)
    run = _run()
    for name in NEW:
        assert _read(name, run) is None
    assert run["_layer_trace"]["ms"]["unscoped"] == pytest.approx(1.0)
    assert layer_trace.scope_times(
        {"mark": None, "device": [], "spans": {}}, 0.02) is None


def test_a_run_without_a_trace_reads_nothing(capsys):
    """A CPU rehearsal, or a run whose trace is missing: None, no raise."""
    run = dict(_run(), cell={"name": "no_such_cell.round"})
    for name in NEW:
        assert _read(name, run) is None
    assert "layer_trace: not read" in capsys.readouterr().err
