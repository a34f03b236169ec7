"""``ssm_trace``: the scan's operations and bytes against hand counts at
two shapes, which events lie under its two scopes, and the three metric
files over a made-up trace whose arithmetic can be done by hand."""

import ssm_trace
import run_cell

STEP = "jit_sl_train_step(1)"
PATH = "jit(sl_train_step)/transpose(jvp())/checkpoint/stage1/DecoderBlock/"
# the scans of one optimizer step of the cell: 3 Mamba-2 layers x 4
# microbatches of 2 rows of 4,096; 64 heads of 64 in 8 groups, a state of
# 128, chunks of 128
CELL = {"rows": 2, "microbatches": 4, "seq": 4096, "layers": 3, "heads": 64,
        "head_dim": 64, "groups": 8, "state": 128, "chunk": 128}
PEAKS = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


def test_operations_against_hand_counts_at_two_shapes():
    """Forward a token and layer: a head's product inside the chunk, its
    part of the chunk's state and its read of the handed state, a group's
    scores; backward twice that."""
    forward = 64 * (2 * 128 * 64 + 4 * 128 * 64) + 8 * 2 * 128 * 128
    assert forward == 3407872
    assert ssm_trace.ssd_flops(1, 64, 64, 8, 128, 128) == 3 * forward
    assert ssm_trace.ssd_flops(8192, 64, 64, 8, 128, 128) \
        == 3 * 8192 * forward == 83751862272
    # a toy: 2 heads of 3 in 1 group, a state of 5, chunks of 4, 10 tokens
    assert ssm_trace.ssd_flops(10, 2, 3, 1, 5, 4) \
        == 3 * 10 * (2 * (2 * 4 * 3 + 4 * 5 * 3) + 2 * 4 * 5)


def test_bytes_against_hand_counts_each_operand_and_gradient_once():
    tokens = 2 * 4096
    x = tokens * 64 * 64 * 2
    bc = tokens * 8 * 128 * 2
    dt = tokens * 64 * 4
    assert ssm_trace.ssd_bytes(tokens, 64, 64, 8, 128) \
        == 2 * (x + x + bc + bc + dt) == 339738624
    assert ssm_trace.ssd_bytes(10, 2, 3, 1, 5) \
        == 2 * 10 * (2 * 2 * 3 * 2 + 2 * 1 * 5 * 2 + 2 * 4)


def test_the_least_time_of_a_step_is_twelve_scans():
    """At the cell's shapes the operations bound a scan by a hair (0.425
    ms against the bytes' 0.415), and a step has 3 layers x 4
    microbatches of them."""
    flops = ssm_trace.ssd_flops(8192, 64, 64, 8, 128, 128) / 197e12
    moved = ssm_trace.ssd_bytes(8192, 64, 64, 8, 128) / 819e9
    assert moved < flops < 1.05 * moved
    assert ssm_trace.least_seconds(CELL, PEAKS) == 12 * flops
    assert 0.005 < 12 * flops < 0.0052


def test_cell_shapes_are_the_configurations_and_none_without_a_scan():
    assert ssm_trace.cell_shapes(
        {"cell": {"config": "nemotron_twotower_30b_c5"}}) == CELL
    for other in ("moonlight_16b_c3", "mellum2_12b_c3", "vgg16_c7"):
        assert ssm_trace.cell_shapes({"cell": {"config": other}}) is None


def test_classify_reads_the_scan_inside_the_mixer():
    assert ssm_trace.classify(
        PATH + "attention/ssm_mixer/in_proj/dot_general:") == "ssm_mixer"
    assert ssm_trace.classify(
        PATH + "attention/ssm_mixer/ssm_scan/while/body/dot_general:") \
        == "ssm_scan"
    assert ssm_trace.classify(
        "jit(sl_train_step)/transpose(jvp(ssm_mixer))/ssm_scan/exp:") \
        == "ssm_scan"
    assert ssm_trace.classify(
        PATH + "attention/attn_full/slt_flash_fwd/pallas_call:") is None
    assert ssm_trace.classify(PATH + "moe/moe_route/gather:") is None


def _trace(ops, step_ns=10_000_000):
    mark = (0.0, 1_000.0, 7)
    lo = mark[1]
    return {"mark": mark, "spans": {}, "device": [{
        "name": "/device:TPU:0",
        "modules": [(lo + 10, lo + 10 + step_ns, STEP)],
        "ops": sorted((lo + 10 + s, lo + 10 + e, n, t)
                      for s, e, n, t in ops)}]}


def _made_up():
    """One step: under ``ssm_scan`` a ``while`` of four times the scans'
    least time that holds an operation of the same scope and one with no
    scope at all (0.25 ms, not the scan's), 1.5 ms under ``ssm_mixer``
    outside the scan."""
    took = 4 * ssm_trace.least_seconds(CELL, PEAKS) * 1e9 + 0.25e6
    inside = PATH + "attention/ssm_mixer/ssm_scan/while/body/"
    ops = [
        (0, took, "%while.1 = while()",
         PATH + "attention/ssm_mixer/ssm_scan/while:"),
        (1e5, 2e5, "%fusion.1 = dot()", inside + "dot_general:"),
        (3e5, 5.5e5, "%copy.4 = copy()", ""),
        (25e6, 27e6, "%fusion.2 = dot()",
         PATH + "attention/ssm_mixer/in_proj/dot_general:"),
        (25.5e6, 26e6, "%copy.2 = copy()", PATH + "attention/reshape:"),
        (28e6, 28.75e6, "%fusion.3 = dot()",
         PATH + "moe_shared/shared_experts/up_proj/dot_general:"),
    ]
    return _trace(ops, step_ns=30_000_000)


def test_scope_times_are_own_times_a_step():
    got = ssm_trace.scope_times(_made_up(), 0.05)
    assert got["steps"] == 1
    assert abs(got["ms"]["ssm_mixer"] - 1.5) < 1e-9
    assert abs(got["ms"]["ssm_scan"]
               - 4e3 * ssm_trace.least_seconds(CELL, PEAKS)) < 1e-9


def test_the_three_metric_files_read_the_reduction(monkeypatch):
    trace = _made_up()
    run = {"cell": {"name": "nemotron_twotower_30b_c5.round",
                    "config": "nemotron_twotower_30b_c5"},
           "window_s": 0.05, "peaks": PEAKS}
    monkeypatch.setattr(ssm_trace.trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(ssm_trace.program_trace, "read", lambda path: trace)
    read = lambda name: run_cell.load_module(  # noqa: E731
        run_cell.HERE / "metrics" / f"{name}.py").read(run)
    assert abs(read("ssm_mixer_ms") - 1.5) < 1e-9
    assert abs(read("ssm_scan_ms")
               - 4e3 * ssm_trace.least_seconds(CELL, PEAKS)) < 1e-9
    assert abs(read("ssd_roofline") - 25.0) < 1e-6
    assert run["_ssm_trace"]["steps"] == 1      # one reduction for the three


def test_a_trace_without_the_scopes_reads_nothing(monkeypatch):
    """The parent's program has neither scope: every reader returns None
    and nothing raises."""
    ops = [(0, 1e6, "%fusion.9 = dot()", PATH + "attention/q_proj/dot:")]
    assert ssm_trace.scope_times(_trace(ops), 0.02) is None
    assert ssm_trace.scope_times(
        {"mark": None, "device": [], "spans": {}}, 0.02) is None
    run = {"cell": {"name": "mellum2_12b_c3.round",
                    "config": "mellum2_12b_c3"},
           "window_s": 0.02, "peaks": PEAKS}
    for name in ("ssm_scan_ms", "ssm_mixer_ms", "ssd_roofline"):
        assert run_cell.load_module(
            run_cell.HERE / "metrics" / f"{name}.py").read(run) is None
    assert ssm_trace.roofline(None, CELL, PEAKS) is None
    assert ssm_trace.roofline({"ms": {"ssm_scan": 1.0}}, None, PEAKS) is None
