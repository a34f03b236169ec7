"""Compute performance-attribution plane (``runtime/perf.py``):
sampler gating, compile/retrace accounting, MFU math, HBM watermarks,
on-demand profiler arming, fleet/exporter surfacing, the sl_perf
report + regression gate, and the traced protocol-round attribution
identity (slow)."""

import json
import pathlib
import time
import urllib.error
import urllib.request

import pytest

from split_learning_tpu.config import ConfigError, from_dict
from split_learning_tpu.runtime.perf import (
    CompileWatch, MemoryWatch, PerfPlane, ProfileCapture,
    SampledStepTimer,
    DATASHEET_BF16_TFLOPS, flops_of_compiled, make_perf_plane,
    resolve_peak_tflops,
)
from split_learning_tpu.runtime.telemetry import (
    FleetMonitor, GaugeSet, TelemetryExporter, lint_prometheus,
    render_prometheus,
)
from split_learning_tpu.runtime.trace import (
    FaultCounters, GAUGE_NAMES, HistogramSet,
)


# --------------------------------------------------------------------------
# SampledStepTimer: sampler gating + attribution identity
# --------------------------------------------------------------------------

class TestSampledStepTimer:
    def test_fence_only_on_sampled_steps(self):
        fences = []
        st = SampledStepTimer(sample_every=4, fence=fences.append)
        st.start_round(0)
        for _ in range(12):
            st.note_step(time.perf_counter(), tree=("t",), n=1)
        assert len(fences) == 3          # steps 4, 8, 12
        assert st.steps == 12
        assert st.sampled_steps == 3

    def test_sample_every_one_fences_every_step(self):
        fences = []
        st = SampledStepTimer(sample_every=1, fence=fences.append)
        st.start_round(0)
        for _ in range(5):
            st.note_step(time.perf_counter(), tree=("t",))
        assert len(fences) == 5

    def test_no_tree_means_no_fence(self):
        fences = []
        st = SampledStepTimer(sample_every=1, fence=fences.append)
        st.start_round(0)
        st.note_step(time.perf_counter())
        assert fences == []

    def test_histograms_fed(self):
        hists = HistogramSet()
        st = SampledStepTimer(sample_every=2, hists=hists,
                              fence=lambda t: None)
        st.start_round(0)
        for _ in range(4):
            st.note_step(time.perf_counter(), tree=("t",))
        snap = hists.snapshot()
        assert snap["step_dispatch"]["count"] == 4
        assert snap["step_device"]["count"] == 2

    def test_device_estimate_scales_sampled_mean(self):
        st = SampledStepTimer(sample_every=2,
                              fence=lambda t: time.sleep(0.01))
        st.start_round(0)
        for _ in range(6):
            st.note_step(time.perf_counter(), tree=("t",))
        est = st.device_est_s()
        # 3 sampled fences of ~10 ms, scaled to 6 steps => ~60 ms
        assert 0.03 < est < 0.5

    def test_attribution_components_sum_to_wall(self):
        st = SampledStepTimer(sample_every=1, fence=lambda t: None)
        st.start_round(0)
        with st.host():
            time.sleep(0.02)
        t0 = time.perf_counter()
        time.sleep(0.02)
        st.note_step(t0, tree=("t",))
        att = st.attribution()
        assert att["host_s"] >= 0.015
        assert att["dispatch_s"] >= 0.015
        assert att["wall_s"] >= att["host_s"] + att["dispatch_s"] - 1e-3


# --------------------------------------------------------------------------
# CompileWatch: compiles, retraces, FLOPs, spans
# --------------------------------------------------------------------------

class TestCompileWatch:
    def _jit(self):
        import jax
        return jax.jit(lambda x: (x * 2.0).sum())

    def test_counts_compile_and_flops(self):
        import jax.numpy as jnp
        cw = CompileWatch()
        w = cw.wrap("op", self._jit())
        cw.note_round(0)
        w(jnp.ones((4, 4)))
        snap = cw.snapshot()
        assert snap["compiles"] == {"op": 1}
        assert snap["retraces"] == 0
        assert snap["compile_s_total"] > 0
        assert snap["round_flops"] > 0   # cost_analysis captured

    def test_retrace_after_round_zero_raises_counter(self):
        import jax.numpy as jnp
        faults = FaultCounters()
        cw = CompileWatch(faults=faults)
        w = cw.wrap("op", self._jit())
        cw.note_round(0)
        w(jnp.ones((4, 4)))
        cw.note_round(1)
        w(jnp.ones((4, 4)))          # cache hit: no retrace
        assert faults.snapshot().get("retraces") is None
        w(jnp.ones((5, 5)))          # new shape: retrace
        assert faults.snapshot()["retraces"] == 1
        assert cw.snapshot()["retraces"] == 1

    def test_late_join_cold_compile_is_not_a_retrace(self):
        # an elastic-join (or restarted) client's first round is 5:
        # its cold compiles there are warmup, not leaked retraces
        import jax.numpy as jnp
        faults = FaultCounters()
        cw = CompileWatch(faults=faults)
        w = cw.wrap("op", self._jit())
        cw.note_round(5)
        w(jnp.ones((4, 4)))          # cold compile at first round seen
        assert faults.snapshot().get("retraces") is None
        cw.note_round(6)
        w(jnp.ones((5, 5)))          # recompile past warmup: retrace
        assert faults.snapshot()["retraces"] == 1

    def test_runner_rebuild_fresh_op_is_not_a_retrace(self):
        # hyperparams changed mid-hold: the rebuilt runner's fresh ops
        # compile once more — warmup again, not a retrace
        import jax.numpy as jnp
        faults = FaultCounters()
        cw = CompileWatch(faults=faults)
        w = cw.wrap("op", self._jit())
        cw.note_round(0)
        w(jnp.ones((4, 4)))
        cw.note_round(1)
        w2 = cw.wrap("op", self._jit())   # fresh fn = rebuild
        w2(jnp.ones((4, 4)))
        assert faults.snapshot().get("retraces") is None
        w2(jnp.ones((5, 5)))         # NOW it's warm: retrace
        assert faults.snapshot()["retraces"] == 1

    def test_round_flops_accumulate_per_call(self):
        import jax.numpy as jnp
        cw = CompileWatch()
        w = cw.wrap("op", self._jit())
        cw.note_round(0)
        w(jnp.ones((4, 4)))
        one = cw.snapshot()["round_flops"]
        w(jnp.ones((4, 4)))
        w(jnp.ones((4, 4)))
        assert cw.snapshot()["round_flops"] == pytest.approx(3 * one)
        cw.note_round(1)             # round reset
        assert cw.snapshot()["round_flops"] == 0.0

    def test_compile_span_journaled(self):
        import jax.numpy as jnp

        class _Spy:
            def __init__(self):
                self.records = []

            def record(self, name, t0, t1, **attrs):
                self.records.append((name, attrs))

        spy = _Spy()
        cw = CompileWatch(tracer=spy)
        w = cw.wrap("bwd", self._jit())
        w(jnp.ones((2, 2)))
        assert spy.records and spy.records[0][0] == "compile"
        assert spy.records[0][1]["op"] == "bwd"

    def test_wrap_idempotent(self):
        cw = CompileWatch()
        f = self._jit()
        w1 = cw.wrap("op", f)
        assert cw.wrap("op", w1) is w1

    def test_flops_of_compiled(self):
        import jax
        import jax.numpy as jnp
        fn = jax.jit(lambda a: a @ a)
        flops = flops_of_compiled(fn, jnp.ones((8, 8)))
        assert flops and flops > 0


# --------------------------------------------------------------------------
# MemoryWatch / MFU / datasheet
# --------------------------------------------------------------------------

class TestMemoryAndMfu:
    def test_memory_sample_cpu_fallback(self):
        import jax.numpy as jnp
        gauges = GaugeSet()
        mw = MemoryWatch(gauges=gauges)
        keep = jnp.ones((256, 256))   # noqa: F841 — live footprint
        got = mw.sample()
        assert got is not None and got > 0
        assert gauges.get("hbm_peak_bytes") == got

    def test_plan_estimate_ratio(self):
        mw = MemoryWatch()
        mw.note_plan_estimate(1000)
        mw.peak_bytes = 500
        snap = mw.snapshot()
        assert snap["hbm_peak_vs_plan"] == 0.5

    def test_resolve_peak_datasheet_and_override(self):
        assert resolve_peak_tflops("TPU v5e") == \
            DATASHEET_BF16_TFLOPS["TPU v5e"]
        assert resolve_peak_tflops("cpu") is None
        assert resolve_peak_tflops("cpu", {"cpu": 0.25}) == 0.25
        assert resolve_peak_tflops("TPU v9", {"TPU v9": 1.5}) == 1.5

    def test_unknown_device_kind_is_an_error(self):
        """A device outside the table (and outside an explicit
        perf.datasheet override) must not get a default peak: the MFU
        it would produce is a wrong number under a device name."""
        with pytest.raises(ValueError, match="TPU v9"):
            resolve_peak_tflops("TPU v9")
        with pytest.raises(ValueError, match="TPU v9"):
            resolve_peak_tflops("TPU v9", {"cpu": 0.25})
        # the v5e chip the repo runs on reports this kind
        assert resolve_peak_tflops("TPU v5 lite") == 197.0

    def test_mfu_math_with_fake_datasheet_entry(self):
        """flops x rate / peak: pin the whole MFU pipeline with a fake
        1-TFLOP/s chip entry and hand-fed FLOPs."""
        import jax
        kind = jax.devices()[0].device_kind
        plane = PerfPlane("c1", sample_every=1,
                          datasheet={kind: 1.0})   # 1 TFLOP/s peak
        plane.start_round(0)
        plane.compile._flops["op"] = 1e9
        with plane.compile._lock:
            plane.compile.round_flops = 1e9       # 1 GFLOP this round
        rec = plane.end_round(samples=10, wall_s=0.5)
        # 1e9 FLOPs / 0.5 s = 2 GFLOP/s = 0.002 TFLOP/s -> MFU 0.002
        assert rec["tflops_per_sec"] == pytest.approx(0.002, rel=1e-3)
        assert rec["mfu"] == pytest.approx(0.002, rel=1e-3)
        assert rec["peak_tflops"] == 1.0

    def test_end_round_attribution_identity(self):
        plane = PerfPlane("c1", sample_every=1)
        plane.start_round(3)
        t0 = time.perf_counter()
        time.sleep(0.01)
        plane.note_step(t0, tree=None, n=4)
        time.sleep(0.02)
        rec = plane.end_round(samples=4)
        total = (rec["compute_s"] + rec["compile_s"] + rec["dispatch_s"]
                 + rec["host_s"] + rec["wait_s"])
        assert total == pytest.approx(rec["wall_s"], rel=0.05)
        assert rec["round"] == 3
        assert rec["v"] == 1

    def test_disabled_plane_is_inert(self):
        plane = PerfPlane("c1", enabled=False)
        plane.start_round(0)
        plane.note_step(time.perf_counter(), tree=("t",))
        with plane.host():
            pass
        assert plane.end_round() is None

    def test_compute_rate_withheld_without_a_fenced_step(self):
        # a short round (steps < sample-every) never fences, so there
        # is no device estimate — dispatch-only busy would inflate the
        # rate by orders of magnitude and flip the fleet monitor's
        # compute-slow vs wire-slow verdict
        gauges = GaugeSet()
        plane = PerfPlane("c1", sample_every=100, gauges=gauges)
        plane.start_round(0)
        for _ in range(3):
            plane.note_step(time.perf_counter(), tree=None, n=4)
        rec = plane.end_round(samples=12)
        assert "compute_samples_per_s" not in rec
        assert gauges.snapshot().get("compute_samples_per_s") is None

    def test_perf_enabled_gates_both_halves(self):
        # the switch loop.py's server half (MemoryWatch + kind=perf
        # records) shares with the client planes
        from split_learning_tpu.runtime.perf import perf_enabled
        assert perf_enabled(
            from_dict({"model": "KWT", "dataset": "SPEECHCOMMANDS",
                       "clients": [1]}))     # default: on
        assert not perf_enabled(
            from_dict({"model": "KWT", "dataset": "SPEECHCOMMANDS",
                       "clients": [1],
                       "perf": {"enabled": False}}))
        assert perf_enabled(object()) is False   # pre-plane config


# --------------------------------------------------------------------------
# config block
# --------------------------------------------------------------------------

class TestPerfConfig:
    def test_defaults_and_yaml_block(self):
        cfg = from_dict({"perf": {"sample-every": 8,
                                  "datasheet": {"cpu": 0.1}}})
        assert cfg.perf.sample_every == 8
        assert cfg.perf.datasheet == {"cpu": 0.1}
        plane = make_perf_plane(cfg, "c1")
        assert plane.enabled and plane.steps.sample_every == 8

    def test_bad_sample_every_rejected(self):
        with pytest.raises(ConfigError):
            from_dict({"perf": {"sample-every": 0}})

    def test_bad_datasheet_rejected(self):
        with pytest.raises(ConfigError):
            from_dict({"perf": {"datasheet": {"cpu": "fast"}}})

    def test_plane_tolerates_missing_block(self):
        class _Legacy:
            pass
        plane = make_perf_plane(_Legacy(), "c1")
        assert not plane.enabled

    def test_new_gauges_declared(self):
        for name in ("mfu", "step_seconds", "hbm_peak_bytes",
                     "compile_seconds_total", "compute_samples_per_s"):
            assert name in GAUGE_NAMES


# --------------------------------------------------------------------------
# ProfileCapture + exporter POST /profile
# --------------------------------------------------------------------------

class TestProfileCapture:
    def test_arm_start_step_stop_artifact(self, tmp_path):
        pc = ProfileCapture(tmp_path / "profile")
        assert not pc.armed
        info = pc.arm(2)
        assert info["armed"] and info["steps"] == 2
        assert pc.armed
        assert pc.maybe_start(5)
        assert pc.active and not pc.armed
        pc.note_step()
        assert pc.active
        pc.note_step()               # K steps reached: window closes
        assert not pc.active
        manifest = tmp_path / "profile" / "round5" / "capture.json"
        assert manifest.exists()
        rec = json.loads(manifest.read_text())
        assert rec["round"] == 5 and rec["steps"] == 2

    def test_unarmed_round_is_noop(self, tmp_path):
        pc = ProfileCapture(tmp_path)
        assert not pc.maybe_start(0)
        pc.note_step()
        pc.stop()                    # idempotent on a closed window
        assert list(tmp_path.glob("round*")) == []

    def test_round_end_forces_stop(self, tmp_path):
        pc = ProfileCapture(tmp_path)
        pc.arm(100)
        assert pc.maybe_start(1)
        pc.stop()                    # round ended before 100 steps
        assert not pc.active
        assert (tmp_path / "round1" / "capture.json").exists()

    def test_inproc_client_plane_ticks_server_capture(self, tmp_path):
        # the wiring that closes a steps=K window after K hot-loop
        # steps: the server registers its capture process-wide and an
        # in-process client's plane picks it up at construction
        from split_learning_tpu.runtime import perf as perf_mod
        from split_learning_tpu.runtime.bus import InProcTransport
        from split_learning_tpu.runtime.client import ProtocolClient
        from split_learning_tpu.runtime.server import ProtocolServer
        cfg = from_dict({
            "model": "KWT", "dataset": "SPEECHCOMMANDS",
            "clients": [1], "global-rounds": 1,
            "synthetic-size": 16, "log-path": str(tmp_path),
            "model-kwargs": {"embed_dim": 16, "num_heads": 2,
                             "mlp_dim": 32},
            "checkpoint": {"directory": str(tmp_path / "ckpt"),
                           "save": False},
            "observability": {"run-scoped": False},
            "perf": {"sample-every": 2},
        })
        bus = InProcTransport()
        server = ProtocolServer(cfg, transport=bus,
                                client_timeout=5.0)
        try:
            cap = server.ctx.perf_capture
            assert perf_mod.process_capture() is cap
            c = ProtocolClient(cfg, "w_1_0", 1, transport=bus)
            assert c.perf.capture is cap
            # K hot-loop ticks close an armed window (steps honored)
            cap.arm(2)
            assert cap.maybe_start(0)
            c.perf.note_step(time.perf_counter())
            assert cap.active
            c.perf.note_step(time.perf_counter())
            assert not cap.active
        finally:
            perf_mod.register_process_capture(None)

    def test_separate_process_client_gets_no_capture(self, tmp_path):
        # no server in this process (registration cleared): the plane
        # must NOT tick any capture — the round boundary closes it
        from split_learning_tpu.runtime import perf as perf_mod
        perf_mod.register_process_capture(None)
        assert perf_mod.process_capture() is None

    def test_exporter_post_profile_arms(self, tmp_path):
        pc = ProfileCapture(tmp_path)
        ex = TelemetryExporter(lambda: "", lambda: {},
                               profile_fn=pc.arm).start()
        try:
            req = urllib.request.Request(f"{ex.url}/profile?steps=3",
                                         method="POST")
            with urllib.request.urlopen(req, timeout=5) as resp:
                body = json.loads(resp.read().decode())
            assert body["armed"] and body["steps"] == 3
            assert pc.armed
            # bad steps -> 400, unknown path -> 404
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    f"{ex.url}/profile?steps=soon", method="POST"),
                    timeout=5)
            assert ei.value.code == 400
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(urllib.request.Request(
                    f"{ex.url}/nope", method="POST"), timeout=5)
        finally:
            ex.close()

    def test_exporter_post_profile_404_when_unwired(self):
        ex = TelemetryExporter(lambda: "", lambda: {}).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    f"{ex.url}/profile?steps=1", method="POST"),
                    timeout=5)
            assert ei.value.code == 404
        finally:
            ex.close()


# --------------------------------------------------------------------------
# /metrics + fleet surfacing
# --------------------------------------------------------------------------

class TestPerfMetricsSurface:
    def test_perf_gauges_render_and_lint(self):
        gauges = GaugeSet()
        faults = FaultCounters()
        gauges.set("mfu", 0.41)
        gauges.set("step_seconds", 0.012)
        gauges.set("hbm_peak_bytes", 1 << 30)
        gauges.set("compile_seconds_total", 17.5)
        faults.inc("retraces", 2)
        text = render_prometheus(faults=faults, gauges=gauges)
        for name in ("sl_mfu 0.41", "sl_step_seconds 0.012",
                     "sl_hbm_peak_bytes", "sl_compile_seconds_total",
                     "sl_retraces_total 2"):
            assert name in text
        assert lint_prometheus(text) == []

    def test_retraces_total_zero_by_default(self):
        text = render_prometheus(faults=FaultCounters())
        assert "sl_retraces_total 0" in text
        assert lint_prometheus(text) == []

    def _beat(self, mon, cid, seq, rate, gauges=None, latency=None):
        mon.note_heartbeat(cid, {
            "part": cid, "t": time.time() + seq * 0.01, "seq": seq,
            "samples_per_s": rate, "samples": 10,
            "gauges": gauges or {}, "latency": latency or {}})

    def test_fleet_snapshot_carries_perf_gauges(self):
        mon = FleetMonitor(interval=10.0, liveness_timeout=100.0)
        self._beat(mon, "c1", 1, 5.0,
                   gauges={"mfu": 0.3, "compute_samples_per_s": 7.0,
                           "hbm_peak_bytes": 42},
                   latency={"step_device": {"p95_ms": 12.5}})
        self._beat(mon, "c2", 1, 5.0)   # predates the perf plane
        snap = mon.snapshot()
        c1, c2 = snap["clients"]["c1"], snap["clients"]["c2"]
        assert c1["mfu"] == 0.3
        assert c1["compute_samples_per_s"] == 7.0
        assert c1["step_p95_ms"] == 12.5
        assert c2["mfu"] is None and c2["step_p95_ms"] is None
        # /metrics renders the per-client families and lints clean
        text = render_prometheus(fleet=mon)
        assert 'sl_client_mfu{client="c1"} 0.3' in text
        assert "sl_client_compute_samples_per_second" in text
        assert lint_prometheus(text) == []

    def test_straggler_why_compute_slow_vs_wire_slow(self):
        mon = FleetMonitor(interval=10.0, liveness_timeout=1000.0)
        now = time.time()
        # c_slowdev: overall slow AND device slow -> compute-slow
        self._beat(mon, "c_slowdev", 1, 1.0,
                   gauges={"compute_samples_per_s": 1.0})
        for cid in ("f1", "f2", "f3"):
            self._beat(mon, cid, 1, 10.0,
                       gauges={"compute_samples_per_s": 10.0})
        mon.advance(now=now + 0.1)
        why = [t["why"] for t in mon.transitions
               if t["client"] == "c_slowdev" and t["to"] == "straggler"]
        assert why and "compute-slow" in why[0]
        # c_wire: overall slow but device rate healthy -> wire-slow
        mon2 = FleetMonitor(interval=10.0, liveness_timeout=1000.0)
        self._beat(mon2, "c_wire", 1, 1.0,
                   gauges={"compute_samples_per_s": 10.0})
        for cid in ("f1", "f2", "f3"):
            self._beat(mon2, cid, 1, 10.0,
                       gauges={"compute_samples_per_s": 10.0})
        mon2.advance(now=now + 0.1)
        why = [t["why"] for t in mon2.transitions
               if t["client"] == "c_wire" and t["to"] == "straggler"]
        assert why and "wire-slow" in why[0]

    def test_sl_top_renders_perf_columns(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "sl_top", pathlib.Path(__file__).parent.parent
            / "tools" / "sl_top.py")
        sl_top = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sl_top)
        fleet = {"t": time.time(), "counts": {"healthy": 2},
                 "clients": {
                     "c1": {"state": "healthy", "round": 1,
                            "samples": 10, "samples_per_s": 5.0,
                            "straggler_score": 1.0, "mfu": 0.1234,
                            "step_p95_ms": 9.87, "age_s": 0.5},
                     "c_old": {"state": "healthy", "age_s": 0.5},
                 }, "transitions": []}
        out = sl_top.render_fleet(fleet, color=False)
        assert "MFU" in out and "STEP p95" in out
        assert "0.1234" in out and "9.87" in out
        # pre-perf client renders "-" not a crash
        line = [ln for ln in out.splitlines() if "c_old" in ln][0]
        assert "-" in line


# --------------------------------------------------------------------------
# slcheck perf analyzer (PF001)
# --------------------------------------------------------------------------

class TestPerfAnalyzer:
    def test_flags_unsampled_fence_in_hot_loop(self):
        from split_learning_tpu.analysis import perf_check
        src = (
            "def _train_whole(self):\n"
            "    for x in loader:\n"
            "        out = step(x)\n"
            "        jax.block_until_ready(out)\n")
        found = perf_check.scan_source(src, "planted.py",
                                       {"_train_whole": "loops"})
        assert [f.code for f in found] == ["PF001"]

    def test_flags_unsampled_memory_stats(self):
        from split_learning_tpu.analysis import perf_check
        src = (
            "def _train_first(self):\n"
            "    while True:\n"
            "        d.memory_stats()\n")
        found = perf_check.scan_source(src, "planted.py",
                                       {"_train_first": "loops"})
        assert [f.code for f in found] == ["PF001"]

    def test_sampler_gate_passes(self):
        from split_learning_tpu.analysis import perf_check
        src = (
            "def note_step(self):\n"
            "    for i in range(2):\n"
            "        if self.sampled:\n"
            "            jax.block_until_ready(out)\n")
        assert perf_check.scan_source(src, "x.py",
                                      {"note_step": "all"}) == []

    def test_else_branch_of_sampler_gate_is_not_gated(self):
        from split_learning_tpu.analysis import perf_check
        src = (
            "def note_step(self):\n"
            "    for i in range(2):\n"
            "        if self.sampled:\n"
            "            pass\n"
            "        else:\n"
            "            jax.block_until_ready(out)\n")
        found = perf_check.scan_source(src, "x.py",
                                       {"note_step": "all"})
        assert [f.code for f in found] == ["PF001"]

    def test_inverted_gate_body_flagged_else_passes(self):
        from split_learning_tpu.analysis import perf_check
        # `if not sampled:` body runs every UNSAMPLED step — a fence
        # there is the exact regression PF001 blocks; the else branch
        # runs when the sampler fired and is legitimately gated
        bad = (
            "def note_step(self):\n"
            "    for i in range(2):\n"
            "        if not self.sampled:\n"
            "            jax.block_until_ready(out)\n")
        found = perf_check.scan_source(bad, "x.py",
                                       {"note_step": "all"})
        assert [f.code for f in found] == ["PF001"]
        ok = (
            "def note_step(self):\n"
            "    for i in range(2):\n"
            "        if not self.sampled:\n"
            "            pass\n"
            "        else:\n"
            "            jax.block_until_ready(out)\n")
        assert perf_check.scan_source(ok, "x.py",
                                      {"note_step": "all"}) == []

    def test_sync_in_gate_condition_flagged(self):
        from split_learning_tpu.analysis import perf_check
        src = (
            "def note_step(self):\n"
            "    for i in range(2):\n"
            "        if self.sampled and jax.block_until_ready(out):\n"
            "            pass\n")
        found = perf_check.scan_source(src, "x.py",
                                       {"note_step": "all"})
        assert [f.code for f in found] == ["PF001"]

    def test_annotation_escape_hatch(self):
        from split_learning_tpu.analysis import perf_check
        src = (
            "def _train_whole(self):\n"
            "    for x in loader:\n"
            "        jax.block_until_ready(x)  "
            "# slcheck: sampled-gate\n")
        assert perf_check.scan_source(src, "x.py",
                                      {"_train_whole": "loops"}) == []

    def test_repo_runs_clean(self):
        from split_learning_tpu.analysis import perf_check
        root = pathlib.Path(__file__).resolve().parent.parent
        assert perf_check.run(root) == []

    def test_registered_in_cli(self):
        from split_learning_tpu.analysis.__main__ import ANALYZERS
        assert "perf" in ANALYZERS


# --------------------------------------------------------------------------
# tools/sl_perf.py: attribution report + regression gate
# --------------------------------------------------------------------------

def _sl_perf():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "sl_perf", pathlib.Path(__file__).parent.parent
        / "tools" / "sl_perf.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestSlPerf:
    def _payload(self, **over):
        base = {
            "metric": "vgg16_cifar10_train_samples_per_sec_per_chip",
            "value": 100.0, "unit": "samples/sec/chip",
            "extra": {"protocol_samples_per_sec": 6.0,
                      "cold_round_wall_s": 17.0,
                      "wire_mb_per_round": 4.0,
                      "split_ratio_vs_unsplit": 1.5,
                      "mfu": {"mfu_vs_datasheet": 0.3}},
        }
        base.update(over)
        return base

    def test_diff_detects_regression(self):
        sp = _sl_perf()
        prev = sp.stable_values(self._payload())
        cur = dict(prev, **{"extra.protocol_samples_per_sec": 4.0})
        diff = sp.diff_bench(prev, cur, threshold=0.15)
        assert diff["regressions"] == [
            "extra.protocol_samples_per_sec"]
        # lower-is-better direction: cold round got 30% slower
        cur2 = dict(prev, **{"extra.cold_round_wall_s": 23.0})
        diff2 = sp.diff_bench(prev, cur2, threshold=0.15)
        assert "extra.cold_round_wall_s" in diff2["regressions"]

    def test_diff_negative_within_noise_and_improvement_pass(self):
        sp = _sl_perf()
        prev = sp.stable_values(self._payload())
        # 10% worse protocol rate: inside the 15% noise threshold
        cur = dict(prev, **{"extra.protocol_samples_per_sec": 5.4,
                            "extra.cold_round_wall_s": 12.0,  # better
                            "value": 140.0})                  # better
        diff = sp.diff_bench(prev, cur, threshold=0.15)
        assert diff["regressions"] == []
        assert diff["keys"]["extra.protocol_samples_per_sec"][
            "regression"] is False

    def test_diff_skips_missing_keys(self):
        sp = _sl_perf()
        prev = sp.stable_values(self._payload())
        cur = {"value": 50.0}   # everything else never ran
        diff = sp.diff_bench(prev, cur, threshold=0.15)
        assert set(diff["keys"]) == {"value"}
        assert diff["regressions"] == ["value"]

    def test_load_bench_all_shapes(self, tmp_path):
        sp = _sl_perf()
        payload = self._payload()
        # (1) plain payload (the new bench.json artifact)
        p1 = tmp_path / "bench.json"
        p1.write_text(json.dumps(payload))
        # (2) driver wrapper with parsed set
        p2 = tmp_path / "wrapped.json"
        p2.write_text(json.dumps({"n": 1, "parsed": payload}))
        # (3) wrapper with the payload only in the stdout tail
        p3 = tmp_path / "tail.json"
        p3.write_text(json.dumps({
            "n": 2, "parsed": None,
            "tail": "noise\n" + json.dumps(payload) + "\n"}))
        # (4) FRONT-TRUNCATED tail (the BENCH_r04/r05 shape): only
        # regex scavenging recovers the stable keys
        p4 = tmp_path / "torn.json"
        p4.write_text(json.dumps({
            "n": 3, "parsed": None,
            "tail": json.dumps(payload)[40:]}))
        v1, v2, v3, v4 = (sp.load_bench(p) for p in (p1, p2, p3, p4))
        assert v1 == v2 == v3
        assert v1["extra.protocol_samples_per_sec"] == 6.0
        assert v4["extra.protocol_samples_per_sec"] == 6.0
        assert v4["extra.mfu.mfu_vs_datasheet"] == 0.3
        # (5) nothing recoverable (the rc=124 empty round)
        p5 = tmp_path / "dead.json"
        p5.write_text(json.dumps({"n": 4, "parsed": None,
                                  "tail": "cpuinfo noise"}))
        assert sp.load_bench(p5) is None

    def test_attribution_report_from_metrics(self, tmp_path):
        sp = _sl_perf()
        m = tmp_path / "metrics.jsonl"
        recs = [
            {"kind": "perf", "participant": "c1", "round": 0,
             "wall_s": 10.0, "compute_s": 6.0, "compile_s": 2.0,
             "dispatch_s": 1.0, "host_s": 0.5, "wait_s": 0.5,
             "steps": 8, "retraces": 0, "mfu": 0.25},
            {"kind": "round", "wall_s": 10.0},   # ignored
            {"kind": "perf", "participant": "c1", "round": 1,
             "wall_s": 8.0, "compute_s": 6.0, "compile_s": 0.0,
             "dispatch_s": 1.0, "host_s": 0.5, "wait_s": 0.5,
             "steps": 8, "retraces": 0, "mfu": 0.31},
        ]
        m.write_text("".join(json.dumps(r) + "\n" for r in recs))
        report = sp.attribution_report(sp.load_perf_records(tmp_path))
        assert len(report["rounds"]) == 2
        assert report["rounds"][0]["attributed_frac"] == 1.0
        assert [t["mfu"] for t in report["mfu_trend"]] == [0.25, 0.31]
        out = sp.render_report(report)
        assert "COMPILE" in out and "0.25" in out
        # no stage-stamped records -> no per-hop section
        assert "hops" not in report
        assert "per-hop" not in out

    def test_attribution_merges_stage_records_per_hop(self, tmp_path):
        """Stage-stamped kind=perf records — including the ones a
        stage-host process's inner clients write — roll up into one
        compute|wire|wait row per pipeline hop."""
        sp = _sl_perf()
        m = tmp_path / "metrics.jsonl"
        recs = [
            # hop 1: two first-stage clients in the server process
            {"kind": "perf", "participant": "client_1_0", "round": 0,
             "stage": 1, "wall_s": 10.0, "compute_s": 6.0,
             "compile_s": 0.0, "dispatch_s": 1.0, "host_s": 0.5,
             "wait_s": 2.5, "steps": 8, "samples": 64, "retraces": 0},
            {"kind": "perf", "participant": "client_1_1", "round": 0,
             "stage": 1, "wall_s": 9.0, "compute_s": 5.0,
             "compile_s": 0.0, "dispatch_s": 0.5, "host_s": 0.5,
             "wait_s": 3.0, "steps": 8, "samples": 64, "retraces": 0},
            # hop 2: the slot a StageHost runs remotely
            {"kind": "perf", "participant": "client_2_0", "round": 0,
             "stage": 2, "wall_s": 10.0, "compute_s": 4.0,
             "compile_s": 0.0, "dispatch_s": 2.0, "host_s": 1.0,
             "wait_s": 3.0, "steps": 8, "samples": 128,
             "retraces": 0},
            # pre-stage-stamp record: contributes to rounds, not hops
            {"kind": "perf", "participant": "legacy", "round": 0,
             "wall_s": 1.0, "compute_s": 1.0, "compile_s": 0.0,
             "dispatch_s": 0.0, "host_s": 0.0, "wait_s": 0.0,
             "steps": 1, "retraces": 0},
        ]
        m.write_text("".join(json.dumps(r) + "\n" for r in recs))
        report = sp.attribution_report(sp.load_perf_records(tmp_path))
        assert len(report["rounds"]) == 4
        hops = report["hops"]
        assert sorted(hops) == ["1", "2"]
        assert hops["1"]["n"] == 2
        assert hops["1"]["wall_s"] == 19.0
        assert hops["1"]["compute_s"] == 11.0
        # wire = dispatch + host, summed across the hop's records
        assert hops["1"]["wire_s"] == 2.5
        assert hops["1"]["wait_s"] == 5.5
        assert hops["1"]["samples"] == 128
        assert hops["2"] == {"n": 1, "wall_s": 10.0,
                             "compute_s": 4.0, "wire_s": 3.0,
                             "wait_s": 3.0, "samples": 128}
        out = sp.render_report(report)
        assert "per-hop attribution (stage pipeline):" in out
        assert "STAGE" in out and "WIRE" in out


# --------------------------------------------------------------------------
# bench.json artifact
# --------------------------------------------------------------------------

class TestBenchArtifact:
    def _bench(self, tmp_path, monkeypatch):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_mod", pathlib.Path(__file__).parent.parent
            / "bench.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        monkeypatch.setattr(mod, "PARTIAL",
                            tmp_path / ".bench_partial.json")
        monkeypatch.setattr(mod, "ARTIFACT_ROOT", tmp_path)
        return mod

    def test_flush_writes_schema_stamped_artifacts(self, tmp_path,
                                                   monkeypatch):
        mod = self._bench(tmp_path, monkeypatch)
        art = mod.Artifact(baseline=10.0)
        art.results["headline"] = {"samples_per_sec": 50.0,
                                   "batch": 32}
        art.flush()
        run_files = list(tmp_path.glob("artifacts/runs/*/bench.json"))
        assert len(run_files) == 1
        payload = json.loads(run_files[0].read_text())
        flat = json.loads((tmp_path / "bench.json").read_text())
        assert payload == flat
        assert payload["schema_version"] == mod.BENCH_SCHEMA_VERSION
        assert payload["run_id"] == art.run_id
        assert payload["value"] == 50.0
        # sl_perf reads the artifact directly
        sp = _sl_perf()
        assert sp.load_bench(run_files[0])["value"] == 50.0

    def test_flush_refreshes_in_place(self, tmp_path, monkeypatch):
        mod = self._bench(tmp_path, monkeypatch)
        art = mod.Artifact(baseline=10.0)
        art.flush()
        assert json.loads(
            (tmp_path / "bench.json").read_text())["value"] is None
        art.results["headline"] = {"samples_per_sec": 5.0, "batch": 8}
        art.flush()
        assert json.loads(
            (tmp_path / "bench.json").read_text())["value"] == 5.0
        # still exactly one run dir (same run id)
        assert len(list(tmp_path.glob("artifacts/runs/*"))) == 1


# --------------------------------------------------------------------------
# end-to-end: traced protocol round produces kind=perf records whose
# attribution sums to the round wall (slow)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_protocol_round_perf_attribution(tmp_path):
    import threading

    from split_learning_tpu.runtime.bus import InProcTransport
    from split_learning_tpu.runtime.client import ProtocolClient
    from split_learning_tpu.runtime.server import ProtocolServer

    cfg = from_dict({
        "model": "KWT", "dataset": "SPEECHCOMMANDS",
        "clients": [2, 1], "global-rounds": 2,
        "synthetic-size": 96, "val-max-batches": 1,
        "val-batch-size": 16, "compute-dtype": "float32",
        "model-kwargs": {"embed_dim": 16, "num_heads": 2,
                         "mlp_dim": 32},
        "log-path": str(tmp_path),
        "learning": {"batch-size": 8, "control-count": 2},
        "distribution": {"num-samples": 24},
        "topology": {"cut-layers": [2]},
        "checkpoint": {"directory": str(tmp_path / "ckpt"),
                       "save": False},
        "observability": {"run-scoped": False},
        "perf": {"sample-every": 2, "datasheet": {"cpu": 0.05}},
    })
    bus = InProcTransport()
    server = ProtocolServer(cfg, transport=bus, client_timeout=300.0)
    threads = []
    for stage, count in enumerate(cfg.clients, start=1):
        for i in range(count):
            c = ProtocolClient(cfg, f"perf_{stage}_{i}", stage,
                               transport=bus)
            t = threading.Thread(target=c.run, daemon=True)
            t.start()
            threads.append(t)
    result = server.serve()
    for t in threads:
        t.join(timeout=30)
    assert len(result.history) == 2

    perf_recs = []
    round_recs = []
    for line in (tmp_path / "metrics.jsonl").read_text().splitlines():
        rec = json.loads(line)
        if rec.get("kind") == "perf":
            perf_recs.append(rec)
        elif rec.get("kind") == "round":
            round_recs.append(rec)
    client_recs = [r for r in perf_recs if r.get("client")]
    # every client emitted one record per round
    assert len(client_recs) == 2 * 3
    for rec in client_recs:
        total = (rec["compute_s"] + rec["compile_s"]
                 + rec["dispatch_s"] + rec["host_s"] + rec["wait_s"])
        # the attribution identity: components sum to the wall
        assert total == pytest.approx(rec["wall_s"], rel=0.05)
        assert rec["hbm_peak_bytes"] > 0
    # stage-1 feeders ran steps and accrued FLOPs -> MFU (fake CPU
    # datasheet entry pins the denominator)
    feeders_r0 = [r for r in client_recs
                  if r["round_idx"] == 0 and r["steps"]]
    assert feeders_r0
    assert any("mfu" in r for r in feeders_r0)
    # round 0 paid compiles; a client record's wall stays within the
    # round's train span (the server-side round wall)
    r0_wall = round_recs[0]["wall_s"]
    for rec in (r for r in client_recs if r["round_idx"] == 0):
        assert rec["wall_s"] <= r0_wall * 1.05
        assert rec["compile_s"] > 0 or rec["steps"] == 0
    # server-side perf records carry the HBM watermark per round
    server_recs = [r for r in perf_recs
                   if r.get("participant") == "server"
                   and not r.get("client")]
    assert len(server_recs) == 2
    assert all(r.get("hbm_peak_bytes", 0) > 0 for r in server_recs)
