"""``mixer_trace``: which events are a flash kernel's calls and which the
expert layer's, the kernels' operations and bytes from shapes, and the
roofline share of a made-up trace whose arithmetic can be done by hand."""

import mixer_trace

STEP = "jit_sl_train_step(1)"
PATH = "jit(sl_train_step)/transpose(jvp())/checkpoint/stage1/MellumBlock/"
SHAPES = {"rows": 2, "seq": 4096, "heads": 32, "kv_heads": 4,
          "head_dim": 128, "window": 1024}
PEAKS = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


def test_keys_seen_counts_the_band_and_the_triangle():
    assert mixer_trace.keys_seen(4, None) == 1 + 2 + 3 + 4
    assert mixer_trace.keys_seen(4, 2) == 1 + 2 + 2 + 2
    assert mixer_trace.keys_seen(4, 9) == mixer_trace.keys_seen(4, None)
    assert mixer_trace.keys_seen(4096, 1024) / 4096 == 896.125


def test_a_kernels_operations_do_not_depend_on_its_blocks():
    """2 x head_dim a seen pair and product; the backward pass owes five
    products between its two kernels."""
    fwd = mixer_trace.flash_flops("fwd", 2, 4096, 32, 128, 1024)
    assert fwd == 2 * 2 * 128 * 32 * 2 * mixer_trace.keys_seen(4096, 1024)
    bwd = sum(mixer_trace.flash_flops(k, 2, 4096, 32, 128, 1024)
              for k in ("bwd_dq", "bwd_dkv"))
    assert bwd == 2.5 * fwd
    full = mixer_trace.flash_flops("fwd", 2, 4096, 32, 128, None)
    assert 2.2 < full / fwd < 2.3          # the window sees 896 of 2,048


def test_the_operations_bound_every_call_at_the_cells_shapes():
    for kernel in mixer_trace.KERNELS:
        for scope in mixer_trace.ATTN_SCOPES:
            window = SHAPES["window"] if scope == "attn_window" else None
            flops = mixer_trace.flash_flops(kernel, 2, 4096, 32, 128, window)
            moved = mixer_trace.flash_bytes(kernel, 2, 4096, 32, 4, 128)
            assert flops / 197e12 > moved / 819e9
            assert mixer_trace.least_seconds(
                kernel, scope, SHAPES, PEAKS) == flops / 197e12


def test_classify_tells_a_kernel_call_from_what_reads_its_result():
    call = mixer_trace.classify(
        "%slt_flash_fwd.3 = (bf16[64,4096,128]) custom-call(%a, %b)",
        PATH + "attention/attn_window/slt_flash_fwd/pallas_call:")
    assert call == ("flash", "fwd", "attn_window")
    # a convert of the kernel's result names the kernel as its operand
    assert mixer_trace.classify(
        "%convert.16 = bf16[64,4096,128] convert(%slt_flash_bwd_dq.1)",
        PATH + "attention/convert_element_type:") is None
    # a reduce XLA files under the kernel's op_name is not a call of it
    assert mixer_trace.classify(
        "%reduce.7 = f32[64] reduce(%x)",
        PATH + "attention/attn_window/slt_flash_fwd/pallas_call:") is None
    assert mixer_trace.classify(
        "%slt_flash_bwd_dkv.2 = custom-call()",
        "jit(sl_train_step)/transpose(jvp(attn_full))/slt_flash_bwd_dkv:"
    ) == ("flash", "bwd_dkv", "attn_full")
    assert mixer_trace.classify(
        "%fusion.2 = gather()", PATH + "moe/moe_route/gather:") \
        == ("moe_route", None, None)
    # a grouped product carries no op_name at all
    assert mixer_trace.classify("%ragged-dot-none.4 = bf16[] fusion()",
                                "ragged-dot-none:") \
        == ("moe_experts", None, None)
    assert mixer_trace.classify("%fusion.9 = dot()",
                                PATH + "attention/q_proj/dot_general:") \
        is None


def _trace(ops, step_ns=10_000_000):
    mark = (0.0, 1_000.0, 7)
    lo = mark[1]
    return {"mark": mark, "spans": {}, "device": [{
        "name": "/device:TPU:0",
        "modules": [(lo + 10, lo + 10 + step_ns, STEP)],
        "ops": sorted((lo + 10 + s, lo + 10 + e, n, t)
                      for s, e, n, t in ops)}]}


def test_reduce_gives_own_times_a_step_and_the_roofline_share():
    least = mixer_trace.least_seconds("fwd", "attn_window", SHAPES, PEAKS)
    took = 4 * least                       # the kernel at a quarter
    ops = [
        (0, took * 1e9, "%slt_flash_fwd.1 = custom-call()",
         PATH + "attention/attn_window/slt_flash_fwd/pallas_call:"),
        (5e6, 6e6, "%fusion.1 = gather()", PATH + "moe/moe_route/gather:"),
        (6e6, 8e6, "%ragged-dot-none.1 = fusion()", "ragged-dot-none:"),
        (8e6, 8.5e6, "%fusion.3 = multiply()",
         PATH + "moe/moe_experts/experts/mul:"),
        (9e6, 9.5e6, "%fusion.9 = dot()", PATH + "attention/q_proj/dot:"),
    ]
    got = mixer_trace.reduce(_trace(ops), 0.02, SHAPES, PEAKS)
    assert got["steps"] == 1
    assert abs(got["ms"]["flash"] - took * 1e3) < 1e-6
    assert abs(got["ms"]["moe_route"] - 1.0) < 1e-9
    assert abs(got["ms"]["moe_experts"] - 2.5) < 1e-9
    assert got["flash_calls_a_step"] == {"fwd.attn_window": 1.0}
    assert abs(got["flash_roofline"] - 25.0) < 1e-6


def test_a_trace_without_the_scopes_reads_nothing():
    ops = [(0, 1e6, "%fusion.9 = dot()", PATH + "attention/q_proj/dot:")]
    assert mixer_trace.reduce(_trace(ops), 0.02, SHAPES, PEAKS) is None
    assert mixer_trace.reduce({"mark": None, "device": [], "spans": {}},
                              0.02, SHAPES, PEAKS) is None
    run = {"window_rounds": [{"counters": {"a": 1.0}}, {}]}
    assert mixer_trace.counter_mean(run, "moe_load_max_over_mean") is None
    assert mixer_trace.counter_mean(run, "a") == 1.0
