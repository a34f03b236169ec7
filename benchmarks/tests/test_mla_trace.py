"""``mla_trace``: the latent kernels' operations and bytes against hand
counts at two shapes, which events lie under its two scopes, and the three
metric files over a made-up trace whose arithmetic can be done by hand."""

import mixer_trace
import mla_trace
import run_cell

STEP = "jit_sl_train_step(1)"
PATH = "jit(sl_train_step)/transpose(jvp())/checkpoint/stage1/DecoderBlock/"
# one call of the cell's kernels: 2 rows of 4,096, 16 heads scoring over
# 128 + 64 and weighing values of 128
CELL = {"rows": 2, "seq": 4096, "heads": 16, "nope_dim": 128, "rope_dim": 64,
        "value_dim": 128}
PEAKS = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


def test_operations_against_hand_counts_at_two_shapes():
    """A seen pair and head: forward ``S`` over 192 and ``PV`` over 128;
    ``dq`` ``S`` again and ``dQ`` over 192, ``dP`` over 128; ``dkv`` ``dK``
    over 192 and ``dV`` over 128.  2 FLOPs a multiply-add."""
    pairs = 4096 * 4097 // 2                   # the causal triangle
    assert mla_trace.mla_flops("fwd", 2, 4096, 16, 192, 128) \
        == 2 * (192 + 128) * 16 * 2 * pairs == 171840634880
    assert mla_trace.mla_flops("bwd_dq", 2, 4096, 16, 192, 128) \
        == 2 * (192 + 128 + 192) * 16 * 2 * pairs
    assert mla_trace.mla_flops("bwd_dkv", 2, 4096, 16, 192, 128) \
        == 2 * (192 + 128) * 16 * 2 * pairs
    # a toy: 1 row of 4 tokens (10 pairs), 2 heads, scores over 3, values 5
    assert mla_trace.mla_flops("fwd", 1, 4, 2, 3, 5) == 2 * 8 * 2 * 10
    assert mla_trace.mla_flops("bwd_dq", 1, 4, 2, 3, 5) == 2 * 11 * 2 * 10
    assert mla_trace.mla_flops("bwd_dkv", 1, 4, 2, 3, 5) == 2 * 8 * 2 * 10
    # equal widths: mixer_trace's count for the grouped-query kernels
    for kernel in mixer_trace.KERNELS:
        assert mla_trace.mla_flops(kernel, 2, 4096, 32, 128, 128) \
            == mixer_trace.flash_flops(kernel, 2, 4096, 32, 128)
    total = sum(mla_trace.mla_flops(k, 2, 4096, 16, 192, 128)
                for k in mixer_trace.KERNELS)
    assert total == 2304 * 16 * 2 * pairs      # PERF.md's 2,304 a pair


def test_bytes_against_hand_counts_the_rotary_key_moved_once():
    tokens = 2 * 4096
    q = tokens * 16 * 192 * 2
    k = tokens * (16 * 128 + 64) * 2           # ONE rotary key a token
    v = tokens * 16 * 128 * 2
    stat = tokens * 16 * 4
    assert mla_trace.mla_bytes("fwd", 2, 4096, 16, 128, 64, 128) \
        == q + k + v + v + stat == 152567808
    assert mla_trace.mla_bytes("bwd_dq", 2, 4096, 16, 128, 64, 128) \
        == q + k + v + v + 2 * stat + q
    assert mla_trace.mla_bytes("bwd_dkv", 2, 4096, 16, 128, 64, 128) \
        == q + k + v + v + 2 * stat + 2 * (k + v)
    # the toy: 4 tokens, 2 heads, nope 2 + rope 1, values 5, 2 bytes
    assert mla_trace.mla_bytes("fwd", 1, 4, 2, 2, 1, 5) \
        == 4 * 2 * 3 * 2 + 4 * (2 * 2 + 1) * 2 + 2 * 4 * 2 * 5 * 2 + 4 * 2 * 4
    # copied to every head the keys would be as wide as the queries
    assert k < q


def test_the_operations_bound_every_call_at_the_cells_shapes():
    for kernel in mixer_trace.KERNELS:
        flops = mla_trace.mla_flops(kernel, 2, 4096, 16, 192, 128)
        moved = mla_trace.mla_bytes(kernel, 2, 4096, 16, 128, 64, 128)
        assert flops / 197e12 > moved / 819e9
        assert mla_trace.least_seconds(kernel, CELL, PEAKS) == flops / 197e12


def test_cell_shapes_are_the_configurations_and_none_without_a_latent():
    assert mla_trace.cell_shapes(
        {"cell": {"config": "moonlight_16b_c3"}}) == CELL
    assert mla_trace.cell_shapes(
        {"cell": {"config": "mellum2_12b_c3"}}) is None


def test_classify_reads_the_two_scopes_and_nothing_else():
    assert mla_trace.classify(
        PATH + "attention/mla_latent/q_proj/dot_general:") == "mla_latent"
    assert mla_trace.classify(
        "jit(sl_train_step)/transpose(jvp(mla_latent))/o_proj/dot:") \
        == "mla_latent"
    assert mla_trace.classify(
        PATH + "moe_shared/shared_experts/gate_proj/dot_general:") \
        == "moe_shared"
    # the kernels run beside ``mla_latent``, under ``attn_full``
    assert mla_trace.classify(
        PATH + "attention/attn_full/slt_flash_fwd/pallas_call:") is None
    assert mla_trace.classify(PATH + "moe/moe_route/gather:") is None


def _trace(ops, step_ns=10_000_000):
    mark = (0.0, 1_000.0, 7)
    lo = mark[1]
    return {"mark": mark, "spans": {}, "device": [{
        "name": "/device:TPU:0",
        "modules": [(lo + 10, lo + 10 + step_ns, STEP)],
        "ops": sorted((lo + 10 + s, lo + 10 + e, n, t)
                      for s, e, n, t in ops)}]}


def _made_up():
    """One step: the forward kernel at a quarter of its least time, 1.5 ms
    under ``mla_latent`` (a 2 ms fusion holding 0.5 ms of another
    operation), 0.75 ms under ``moe_shared``."""
    took = 4 * mla_trace.least_seconds("fwd", CELL, PEAKS)
    ops = [
        (0, took * 1e9, "%slt_flash_fwd.1 = custom-call()",
         PATH + "attention/attn_full/slt_flash_fwd/pallas_call:"),
        (5e6, 7e6, "%fusion.1 = dot()",
         PATH + "attention/mla_latent/q_proj/dot_general:"),
        (5.5e6, 6e6, "%copy.2 = copy()", PATH + "attention/reshape:"),
        (8e6, 8.75e6, "%fusion.3 = dot()",
         PATH + "moe_shared/shared_experts/up_proj/dot_general:"),
        (9e6, 9.5e6, "%fusion.9 = gather()", PATH + "moe/moe_route/gather:"),
    ]
    return _trace(ops), took


def test_scope_times_are_own_times_a_step():
    trace, _ = _made_up()
    got = mla_trace.scope_times(trace, 0.02)
    assert got["steps"] == 1
    assert abs(got["ms"]["mla_latent"] - 1.5) < 1e-9
    assert abs(got["ms"]["moe_shared"] - 0.75) < 1e-9


def test_roofline_is_the_least_time_over_mixer_traces_own_time():
    trace, took = _made_up()
    mixers = mixer_trace.reduce(trace, 0.02, None, PEAKS)
    assert mixers["flash_calls_a_step"] == {"fwd.attn_full": 1.0}
    assert abs(mla_trace.roofline(mixers, CELL, PEAKS) - 25.0) < 1e-6
    assert mla_trace.roofline(None, CELL, PEAKS) is None
    assert mla_trace.roofline(mixers, None, PEAKS) is None


def test_the_three_metric_files_read_the_reduction(monkeypatch):
    trace, _ = _made_up()
    run = {"cell": {"name": "moonlight_16b_c3.round",
                    "config": "moonlight_16b_c3"},
           "window_s": 0.02, "peaks": PEAKS}
    monkeypatch.setattr(mla_trace.trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(mla_trace.program_trace, "read", lambda path: trace)
    read = lambda name: run_cell.load_module(  # noqa: E731
        run_cell.HERE / "metrics" / f"{name}.py").read(run)
    assert abs(read("mla_latent_ms") - 1.5) < 1e-9
    assert abs(read("moe_shared_ms") - 0.75) < 1e-9
    assert abs(read("mla_roofline") - 25.0) < 1e-6
    assert run["_mla_trace"]["steps"] == 1      # one reduction for the three


def test_a_trace_without_the_scopes_reads_nothing(monkeypatch):
    """The parent's program has neither scope: every reader returns None
    and nothing raises."""
    ops = [(0, 1e6, "%fusion.9 = dot()", PATH + "attention/q_proj/dot:")]
    assert mla_trace.scope_times(_trace(ops), 0.02) is None
    assert mla_trace.scope_times(
        {"mark": None, "device": [], "spans": {}}, 0.02) is None
    run = {"cell": {"name": "mellum2_12b_c3.round",
                    "config": "mellum2_12b_c3"},
           "window_s": 0.02, "peaks": PEAKS}
    for name in ("mla_latent_ms", "moe_shared_ms", "mla_roofline"):
        assert run_cell.load_module(
            run_cell.HERE / "metrics" / f"{name}.py").read(run) is None
