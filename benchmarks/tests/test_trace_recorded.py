"""The trace reduction on a small trace recorded on a TPU v5e
(``tools/trace_dump.py --record``): four runs of one jitted 512x512 bf16
matrix product with 2 ms host sleeps between them, under a clock mark."""

import pathlib

import trace_reduce as tr

TRACE = pathlib.Path(__file__).parent / "data" / "tiny_tpu.xplane.pb"


def test_planes_and_lines_are_where_the_reduction_looks():
    profile = tr.load(TRACE)
    planes = [p for p in profile.planes
              if p.name.startswith(tr.DEVICE_PREFIX)]
    assert [p.name for p in planes] == ["/device:TPU:0"]
    assert len(tr.line_events(planes[0], tr.MODULES_LINE)) == 4
    ops = tr.line_events(planes[0], tr.OPS_LINE)
    assert len(ops) == 12
    assert {name for _, _, name in ops} == {
        "convolution_tanh_fusion", "copy-start", "copy-done"}


def test_reduction_of_the_recorded_window():
    profile = tr.load(TRACE)
    mark_s = 27.017478334          # printed when the trace was recorded
    assert tr.clock_offset_ns(profile, mark_s) is not None
    got = tr.reduce(profile, window_s=0.05, mark_s=None, n_steps=4)
    # four executions of some 3 us inside a 50 ms window
    assert 8e-6 < got["busy_s"] < 40e-6
    assert got["window_s"] == 0.05
    assert 0.99 < got["idle_worst"] < 1.0
    assert got["step_module"].startswith("jit__lambda")
    assert 1e-6 < got["step_device_s"] < 20e-6
    ops = dict(got["breakdown"]["device_ops"])
    assert max(ops, key=ops.get) == "convolution_tanh_fusion"
    assert len(got["breakdown"]["device_ops"]) <= 10
    # no host spans given: every gap is unattributed, and busy + idle
    # make up the window
    gaps = dict(got["breakdown"]["idle_gaps"])
    assert set(gaps) == {"unattributed"}
    assert abs(gaps["unattributed"] + got["busy_s"] - 0.05) < 1e-9
