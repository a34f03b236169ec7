"""slcheck analyzer suite: the repo must run clean, and each analyzer
must catch its deliberately broken negative snippet (an illegal
protocol transition, a host sync in a jitted tick loop, a lock-order
inversion, ...)."""

import json
import pathlib
import textwrap

import pytest

from split_learning_tpu.analysis import concurrency as CL
from split_learning_tpu.analysis import jaxpr_audit as JX
from split_learning_tpu.analysis import model as M
from split_learning_tpu.analysis import protocol_check as PC
from split_learning_tpu.analysis.__main__ import main as slcheck_main
from split_learning_tpu.analysis.findings import Baseline, Finding

ROOT = pathlib.Path(__file__).resolve().parents[1]


def codes(findings):
    return {f.code for f in findings}


# --------------------------------------------------------------------------
# the repo itself must be clean (acceptance criterion)
# --------------------------------------------------------------------------

def test_repo_runs_clean_json(capsys):
    rc = slcheck_main(["--format", "json"])
    out = capsys.readouterr().out
    data = json.loads(out)
    assert rc == 0, out
    assert data["ok"], data["findings"]
    assert data["findings"] == []


def test_cli_baseline_suppresses(tmp_path, capsys):
    # a baselined fingerprint must flip the exit code back to 0
    f = Finding("PC001", "x.py", 3, "f", "boom")
    Baseline({f.fingerprint: "accepted"}, path=tmp_path / "b.json").save(
        [f])
    b = Baseline.load(tmp_path / "b.json")
    new, sup = b.split([f, Finding("PC001", "y.py", 1, "g", "other")])
    assert [x.path for x in sup] == ["x.py"]
    assert [x.path for x in new] == ["y.py"]


# --------------------------------------------------------------------------
# protocol conformance negatives
# --------------------------------------------------------------------------

def _role_check(tmp_path, snippet, role="client"):
    p = tmp_path / "snippet.py"
    p.write_text(textwrap.dedent(snippet))
    return PC._check_role_file(p, "snippet.py", role)


def test_client_sending_start_on_rpc_is_illegal(tmp_path):
    fs = _role_check(tmp_path, """
        class C:
            def bad_send(self):
                self.bus.publish(RPC_QUEUE, encode(Start(
                    start_layer=0, end_layer=-1, cluster=0,
                    params=None)))
            def bad_recv(self):
                raw = self.bus.get(RPC_QUEUE, timeout=1.0)
        """)
    assert "PC001" in codes(fs)    # client may not SEND Start
    assert "PC003" in codes(fs)    # client may not CONSUME rpc_queue


def test_server_gradient_send_is_illegal(tmp_path):
    fs = _role_check(tmp_path, """
        class S:
            def bad(self, cid):
                self.bus.publish(gradient_queue(1, cid),
                                 encode(Gradient(data_id="d",
                                                 data=None, trace=[])))
        """, role="server")
    assert "PC001" in codes(fs)


def test_unresolved_publish_needs_annotation(tmp_path):
    fs = _role_check(tmp_path, """
        class C:
            def relay(self, q, raw):
                self.bus.publish(mystery_queue(), raw)
        """)
    assert "PC002" in codes(fs)


def test_legal_sites_pass(tmp_path):
    fs = _role_check(tmp_path, """
        class C:
            def good(self):
                self.bus.publish(RPC_QUEUE, encode(Register(
                    client_id="c", stage=1)))
                out_qs = [intermediate_queue(1, 0)]
                for q in out_qs:
                    self.bus.publish(q, encode(EpochEnd(
                        client_id="c")))
                raw = self.bus.get(reply_queue(self.client_id))
        """)
    assert fs == []


def test_transport_origination_is_flagged(tmp_path):
    p = tmp_path / "bus.py"
    p.write_text(textwrap.dedent("""
        class T:
            def sneaky(self):
                self.inner.publish("rpc_queue", b"fake")
            def ok(self, queue, payload):
                self.inner.publish(queue, payload)
        """))
    fs = PC._check_transport_file(p, "bus.py")
    assert codes(fs) == {"PC008"}
    assert len(fs) == 1


def test_crc_order_violation_detected(tmp_path):
    p = tmp_path / "proto.py"
    p.write_text(textwrap.dedent("""
        def bad_decode(raw):
            arr = np.frombuffer(raw, np.float32)   # before any crc!
            if zlib.crc32(raw) != 0:
                raise ValueError
            return arr
        """))
    fs = PC._check_crc_order(p, "proto.py")
    assert codes(fs) == {"PC005"}


def test_codec_round_trip_clean():
    assert PC._check_codec() == []


# --------------------------------------------------------------------------
# trace validator
# --------------------------------------------------------------------------

def _ev(role, direction, kind, who=""):
    return M.Event(role=role, direction=direction, kind=kind,
                   participant=who or role)


def test_legal_round_validates_clean():
    events = [
        _ev("client", "send", "Register", "c1"),
        _ev("server", "recv", "Register"),
        _ev("server", "send", "Start"),
        _ev("client", "recv", "Start", "c1"),
        _ev("client", "send", "Ready", "c1"),
        _ev("server", "recv", "Ready"),
        _ev("server", "send", "Syn"),
        _ev("client", "recv", "Syn", "c1"),
        _ev("client", "send", "Notify", "c1"),
        _ev("server", "recv", "Notify"),
        _ev("server", "send", "Pause"),
        _ev("client", "recv", "Pause", "c1"),
        _ev("client", "send", "Update", "c1"),
        _ev("server", "recv", "Update"),
        _ev("server", "send", "Stop"),
        _ev("client", "recv", "Stop", "c1"),
    ]
    assert M.validate_events(events) == []


def test_illegal_transitions_flagged():
    # SYN before any START
    fs = M.validate_events([_ev("server", "send", "Syn")])
    assert codes(fs) == {"TV001"}
    # client uploading without a PAUSE
    fs = M.validate_events([
        _ev("client", "recv", "Start"),
        _ev("client", "send", "Ready"),
        _ev("client", "send", "Update"),
    ])
    assert codes(fs) == {"TV001"}
    # PAUSE before SYN on the server
    fs = M.validate_events([
        _ev("server", "send", "Start"),
        _ev("server", "send", "Pause"),
    ])
    assert codes(fs) == {"TV001"}


def test_log_replay_roundtrip():
    good = "\n".join([
        "2026-08-03 10:00:00,001 - c1.1a2b - INFO - [>>>] REGISTER "
        "stage=1",
        "2026-08-03 10:00:00,002 - server.9f - INFO - [<<<] REGISTER c1 "
        "stage=1",
        "2026-08-03 10:00:00,003 - server.9f - INFO - [>>>] START -> c1 "
        "layers=[0, -1]",
        "2026-08-03 10:00:00,004 - c1.1a2b - INFO - [<<<] START "
        "layers=[0, -1] cluster=0",
        "2026-08-03 10:00:00,005 - c1.1a2b - INFO - [>>>] READY",
        "2026-08-03 10:00:00,006 - server.9f - INFO - [>>>] SYN -> "
        "['c1']",
        "2026-08-03 10:00:00,007 - c1.1a2b - INFO - [<<<] SYN round=0",
        "2026-08-03 10:00:00,008 - c1.1a2b - INFO - [>>>] NOTIFY fwd=1",
        "2026-08-03 10:00:00,009 - server.9f - INFO - [<<<] NOTIFY c1",
        "2026-08-03 10:00:00,010 - server.9f - INFO - [>>>] PAUSE -> "
        "['c1']",
        "2026-08-03 10:00:00,011 - c1.1a2b - INFO - [<<<] PAUSE",
        "2026-08-03 10:00:00,012 - c1.1a2b - INFO - [>>>] UPDATE "
        "samples=8 ok=True",
        "2026-08-03 10:00:00,013 - server.9f - INFO - [<<<] UPDATE c1 "
        "samples=8 ok=True",
        "2026-08-03 10:00:00,014 - server.9f - INFO - [>>>] STOP -> all",
        "2026-08-03 10:00:00,015 - c1.1a2b - INFO - [<<<] STOP done",
    ])
    assert M.validate_log(good) == []
    bad = good.replace(
        "c1.1a2b - INFO - [>>>] READY",
        "c1.1a2b - INFO - [>>>] UPDATE samples=0 ok=True", 1)
    assert "TV001" in codes(M.validate_log(bad))


def test_real_round_log_validates_clean():
    """A genuine app.log from a full protocol round (written by the
    slow round tests / chaos runs) must replay clean.  Synthesizes a
    round via the real Logger to pin the format end to end."""
    import tempfile

    from split_learning_tpu.runtime.log import Logger
    with tempfile.TemporaryDirectory() as d:
        server = Logger(d, console=False, name="server")
        client = Logger(d, console=False, name="client_1_0")
        client.info("[>>>] REGISTER stage=1")
        server.received("REGISTER client_1_0 stage=1")
        server.sent("START -> client_1_0 layers=[0, -1]")
        client.info("[<<<] START layers=[0, -1] cluster=0")
        client.info("[>>>] READY")
        server.sent("SYN -> ['client_1_0']")
        client.info("[<<<] SYN round=0")
        client.info("[>>>] NOTIFY fwd=2 bwd=2")
        server.received("NOTIFY client_1_0")
        server.sent("PAUSE -> ['client_1_0']")
        client.info("[<<<] PAUSE")
        client.info("[>>>] UPDATE samples=8 ok=True")
        server.received("UPDATE client_1_0 samples=8 ok=True")
        server.sent("STOP -> all (training complete)")
        client.info("[<<<] STOP training complete")
        server.close()
        client.close()
        text = (pathlib.Path(d) / "app.log").read_text()
        events = M.events_from_log(text)
        assert len(events) == 15
        assert M.validate_log(text) == []


def test_data_stream_validator():
    import numpy as np

    from split_learning_tpu.runtime.protocol import Activation, Gradient
    act = lambda i: Activation(  # noqa: E731
        data_id=f"d{i}", data=np.ones((1,), np.float32),
        labels=np.zeros((1,), np.int64), trace=["c"], cluster=0)
    q = "intermediate_queue_1_0"
    assert M.validate_data_stream([act(0), act(1)], q) == []
    # duplicate delivery after the reliable layer is a contract breach
    fs = M.validate_data_stream([act(0), act(0)], q)
    assert codes(fs) == {"TV003"}
    # a gradient does not belong on the forward plane
    g = Gradient(data_id="g", data=None, trace=[])
    assert codes(M.validate_data_stream([g], q)) == {"TV003"}


# --------------------------------------------------------------------------
# jaxpr auditor negatives
# --------------------------------------------------------------------------

def _hot_tree(tmp_path, client_body, context_body="pass"):
    root = tmp_path
    rt = root / "split_learning_tpu" / "runtime"
    rt.mkdir(parents=True)
    (rt / "client.py").write_text(textwrap.dedent(client_body))
    (rt / "context.py").write_text(textwrap.dedent(f"""
        def _drive_columns(self):
            {context_body}
        """))
    return root


def test_host_sync_in_tick_loop_detected(tmp_path):
    root = _hot_tree(tmp_path, """
        class C:
            def _train_first(self):
                while True:
                    loss = r.fwd(x)
                    if not bool(jnp.isfinite(loss)):   # per-tick sync!
                        break
        """)
    fs = JX._audit_hot_loops(root)
    assert codes(fs) == {"JX001"}


def test_allow_sync_annotation_suppresses(tmp_path):
    root = _hot_tree(tmp_path, """
        class C:
            def _train_first(self):
                while True:
                    loss = r.fwd(x)
                    ok = bool(loss)  # slcheck: allow-sync
        """)
    assert JX._audit_hot_loops(root) == []


def test_jit_in_loop_detected(tmp_path):
    root = _hot_tree(tmp_path, """
        class C:
            def _train_middle(self):
                for x in data:
                    step = jax.jit(lambda v: v)
        """)
    assert "JX006" in codes(JX._audit_hot_loops(root))


def test_donated_reuse_detected(tmp_path):
    root = _hot_tree(tmp_path, """
        pass
        """, context_body="""
            out = step(params, opt, stats, x, labels, rngs)
            return params""")
    fs = JX._audit_donation(root)
    assert {f.code for f in fs} == {"JX005"}
    assert sum("params" in f.message for f in fs) == 1


def test_wire_upcast_detected_when_device_cast_removed(monkeypatch):
    import split_learning_tpu.runtime.client as client_mod
    monkeypatch.setattr(client_mod, "device_wire_dtype",
                        lambda d: None)
    fs = JX._audit_jaxprs(ROOT, "bfloat16")
    assert "JX002" in codes(fs)


def test_jaxpr_pass_clean_on_repo():
    assert JX._audit_jaxprs(ROOT, "bfloat16") == []


def test_update_buffer_without_donation_detected():
    """JX007: a jitted round-boundary op consuming an accumulator
    parameter without donating it is flagged."""
    src = ("import jax\n"
           "f = jax.jit(lambda acc, t: acc + t)\n"
           "def fused(acc, stat_acc, base):\n"
           "    return acc\n"
           "g = jax.jit(fused, donate_argnums=(0,))\n")
    fs = JX._scan_update_donation(src, "x.py")
    assert [f.code for f in fs] == ["JX007", "JX007"]
    assert "'acc'" in fs[0].message
    assert "stat_acc" in fs[1].message   # donated acc, forgot stat_acc


def test_update_donation_donated_site_passes():
    src = ("import jax\n"
           "f = jax.jit(lambda acc, t: acc + t, donate_argnums=(0,))\n")
    assert JX._scan_update_donation(src, "x.py") == []


def test_update_donation_clean_on_repo():
    assert JX._audit_update_donation(ROOT) == []


def test_update_jaxpr_clean_on_repo():
    """The fused sharded stage update: no host round-trips compiled in,
    and every leaf comes back in its declared START wire dtype (a bf16
    leaf must not fetch as fp32)."""
    assert JX._audit_update_jaxpr(ROOT) == []


# --------------------------------------------------------------------------
# concurrency lint negatives
# --------------------------------------------------------------------------

def _concurrency(tmp_path, snippet, monkeypatch):
    p = tmp_path / "snippet_bus.py"
    p.write_text(textwrap.dedent(snippet))
    monkeypatch.setattr(CL, "FILES", ("snippet_bus.py",))
    return CL.run(tmp_path)


def test_lock_order_inversion_detected(tmp_path, monkeypatch):
    fs = _concurrency(tmp_path, """
        import threading
        class A:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
            def m1(self):
                with self._a:
                    with self._b:
                        pass
            def m2(self):
                with self._b:
                    with self._a:
                        pass
        """, monkeypatch)
    assert "CL001" in codes(fs)
    assert any("cycle" in f.message for f in fs)


def test_blocking_under_lock_detected(tmp_path, monkeypatch):
    fs = _concurrency(tmp_path, """
        import threading, time
        class A:
            def __init__(self):
                self._a = threading.Lock()
            def m(self):
                with self._a:
                    time.sleep(1)
        """, monkeypatch)
    assert codes(fs) == {"CL002"}


def test_io_lock_annotation_allows_blocking(tmp_path, monkeypatch):
    fs = _concurrency(tmp_path, """
        import threading, time
        class A:
            def __init__(self):
                self._a = threading.Lock()  # slcheck: io-lock
            def m(self):
                with self._a:
                    self.sock.sendall(b"x")
        """, monkeypatch)
    assert fs == []


def test_thread_without_join_detected(tmp_path, monkeypatch):
    fs = _concurrency(tmp_path, """
        import threading
        class A:
            def __init__(self):
                self._t = threading.Thread(target=self.run)
                self._t.start()
        """, monkeypatch)
    assert codes(fs) == {"CL003"}


def test_inner_call_under_lock_detected(tmp_path, monkeypatch):
    fs = _concurrency(tmp_path, """
        import threading
        class A:
            def __init__(self):
                self._a = threading.Lock()
                self._t = threading.Thread(target=self.m)
                self._t.start()
            def m(self):
                with self._a:
                    self.inner.publish("q", b"")
            def stop(self):
                self._t.join()
        """, monkeypatch)
    assert codes(fs) == {"CL005"}


def test_io_lock_nested_under_state_lock_still_flagged(tmp_path,
                                                       monkeypatch):
    """An io-lock only exempts blocking when NOTHING else is held: a
    socket write inside `with io_lock:` nested under a state lock
    still blocks the state lock."""
    fs = _concurrency(tmp_path, """
        import threading
        class A:
            def __init__(self):
                self._state = threading.Lock()
                self._io = threading.Lock()  # slcheck: io-lock
            def m(self):
                with self._state:
                    with self._io:
                        self.sock.sendall(b"x")
        """, monkeypatch)
    assert "CL002" in codes(fs)
    assert any("_state" in f.message for f in fs)


def test_cond_wait_under_outer_lock_flagged(tmp_path, monkeypatch):
    fs = _concurrency(tmp_path, """
        import threading
        class A:
            def __init__(self):
                self._state = threading.Lock()
                self._c = threading.Condition()
            def m(self):
                with self._state:
                    with self._c:
                        self._c.wait_for(lambda: True)
        """, monkeypatch)
    assert any(f.code == "CL002" and "stays held" in f.message
               for f in fs)


def test_write_baseline_partial_run_keeps_other_suppressions(tmp_path):
    path = tmp_path / "b.json"
    keep = Finding("CL002", "bus.py", 1, "get", "accepted debt")
    Baseline({keep.fingerprint: "why"}, path=path).save([keep])
    new = Finding("PC001", "client.py", 2, "send", "fresh")
    b = Baseline.load(path)
    b.save([new], prune=False)         # partial analyzer run
    merged = Baseline.load(path)
    assert keep.fingerprint in merged.suppressions
    assert merged.suppressions[keep.fingerprint] == "why"
    assert new.fingerprint in merged.suppressions
    b2 = Baseline.load(path)
    b2.save([new], prune=True)         # full run prunes stale entries
    assert Baseline.load(path).suppressions == {
        new.fingerprint: "baselined by --write-baseline"}


def test_notify_outside_with_detected(tmp_path, monkeypatch):
    fs = _concurrency(tmp_path, """
        import threading
        class A:
            def __init__(self):
                self._c = threading.Condition()
            def m(self):
                self._c.notify_all()
        """, monkeypatch)
    assert codes(fs) == {"CL004"}


def test_repo_concurrency_clean():
    assert CL.run(ROOT) == []


# --------------------------------------------------------------------------
# instrumented-lock runtime mode (SLCHECK_LOCKS=1)
# --------------------------------------------------------------------------

def test_instrumented_locks_assert_order(monkeypatch):
    monkeypatch.setenv("SLCHECK_LOCKS", "1")
    from split_learning_tpu.analysis import locks
    a = locks.make_lock("async")
    b = locks.make_lock("inproc")
    with a:
        with b:          # outer -> inner: legal
            pass
    with pytest.raises(locks.LockOrderViolation):
        with b:
            with a:      # inner -> outer: inversion
                pass
    # the inversion above must not poison this thread's stack
    with a:
        with b:
            pass


def test_instrumented_transport_round_trip(monkeypatch):
    """A live transport stack under SLCHECK_LOCKS=1: the layered
    publish/get path must hold locks in LOCK_ORDER (the runtime twin
    of the static CL001 check)."""
    monkeypatch.setenv("SLCHECK_LOCKS", "1")
    from split_learning_tpu.runtime.bus import (
        InProcTransport, ReliableTransport,
    )
    bus = InProcTransport()
    sender = ReliableTransport(bus, sender="s",
                               patterns=("intermediate_queue*",),
                               redeliver_s=0.05, max_redeliver=5)
    recv = ReliableTransport(bus, sender="r",
                             patterns=("intermediate_queue*",),
                             redeliver_s=0.05, max_redeliver=5)
    msgs = [b"m%d" % i for i in range(20)]
    for m in msgs:
        sender.publish("intermediate_queue_0_0", m)
    got = [recv.get("intermediate_queue_0_0", timeout=10.0)
           for _ in msgs]
    assert got == msgs
    sender.stop(close_inner=False)
    recv.stop(close_inner=False)
    bus.close()


# --------------------------------------------------------------------------
# counter-name registry rule (CT001/CT002)
# --------------------------------------------------------------------------

def test_undeclared_counter_name_flagged():
    from split_learning_tpu.analysis import counters
    src = (
        "def repair(faults, hists):\n"
        "    faults.inc('drops')\n"              # declared: clean
        "    faults.inc('drosp')\n"              # typo: CT001
        "    hists.observe('frame_rtt', 0.1)\n"  # declared: clean
        "    hists.observe('frame_rtt_ms', 0.1)\n"   # typo: CT002
        "    faults.inc(derived_name)\n"         # non-literal: ignored
    )
    findings = counters.scan_source(src, "x.py")
    assert sorted(f.code for f in findings) == ["CT001", "CT002"]
    assert all(f.where == "repair" for f in findings)
    assert "drosp" in findings[0].message
    assert "FAULT_COUNTER_NAMES" in findings[0].message


def test_undeclared_gauge_name_flagged():
    from split_learning_tpu.analysis import counters
    src = (
        "def tick(gauges, ev):\n"
        "    gauges.set('round', 3)\n"          # declared: clean
        "    gauges.set('rnd', 3)\n"            # typo: CT003
        "    ev.set()\n"                        # no args: ignored
        "    arr.at[idx].set(0.0)\n"            # non-string: ignored
    )
    findings = counters.scan_source(src, "x.py")
    assert [f.code for f in findings] == ["CT003"]
    assert "rnd" in findings[0].message
    assert "GAUGE_NAMES" in findings[0].message


def test_counter_registry_clean_on_repo():
    from split_learning_tpu.analysis import counters
    from split_learning_tpu.analysis.__main__ import repo_root
    assert counters.run(repo_root()) == []


def test_heartbeat_legal_in_every_fsm_state():
    # heartbeats come from a background thread, orthogonal to the
    # lifecycle — every state must carry the self-loop, or the trace
    # validator would flag any interleaving chaos produces
    from split_learning_tpu.analysis.model import (
        CLIENT_FSM, SERVER_FSM, Event, validate_events,
    )
    for state, trans in SERVER_FSM.items():
        assert trans[("recv", "Heartbeat")] == state
    for state, trans in CLIENT_FSM.items():
        assert trans[("send", "Heartbeat")] == state
    events = [Event("client", "send", "Register", "c1"),
              Event("client", "send", "Heartbeat", "c1"),
              Event("client", "recv", "Start", "c1"),
              Event("client", "send", "Heartbeat", "c1"),
              Event("client", "send", "Ready", "c1"),
              Event("server", "recv", "Heartbeat", "server"),
              Event("server", "recv", "Register", "server")]
    assert validate_events(events) == []


# --------------------------------------------------------------------------
# codec analyzer (CD001-CD003)
# --------------------------------------------------------------------------

def test_unregistered_codec_counter_flagged():
    from split_learning_tpu.analysis import codec_check
    findings = codec_check.check_counters(
        registries=frozenset({"quant_nonfinite"}),
        codec_counters={"int8": ("quant_nonfinite",),
                        "topk": ("topk_dense_fallbackz",)})
    assert [f.code for f in findings] == ["CD001"]
    assert "topk_dense_fallbackz" in findings[0].message


def test_host_quant_in_hot_loop_flagged():
    from split_learning_tpu.analysis import codec_check
    src = (
        "def _train_first(self):\n"
        "    for batch in loader:\n"
        "        wire = _quant_int8(batch)\n"       # CD002
        "        publish(wire)\n"
        "def _send_update(self):\n"
        "    leaf = quantize_np(params, 64, 8)\n"   # no loop: legal
    )
    findings = codec_check.scan_source(src, "x.py")
    assert [f.code for f in findings] == ["CD002"]
    assert findings[0].where == "_train_first"
    assert "device" in findings[0].message


def test_codec_analyzer_clean_on_repo():
    from split_learning_tpu.analysis import codec_check
    from split_learning_tpu.analysis.__main__ import repo_root
    assert codec_check.run(repo_root(), trace=True) == []


def test_device_quant_audit_catches_host_fallback(monkeypatch):
    """CD003: a QuantCodec whose prepare pulls payloads to host (the
    regression the device kernels exist to prevent) fails the abstract
    trace."""
    import numpy as np

    from split_learning_tpu.analysis import codec_check
    from split_learning_tpu.runtime.codec import quant

    def host_prepare(self, tree, key=""):
        import jax
        return jax.tree_util.tree_map(
            lambda a: np.asarray(a) * 1.0, tree)   # host round-trip

    monkeypatch.setattr(quant.QuantCodec, "prepare", host_prepare)
    findings = codec_check.check_device_quant()
    assert findings and all(f.code == "CD003" for f in findings)


# --------------------------------------------------------------------------
# aggregation-path rule (AG001) + PartialAggregate protocol model
# --------------------------------------------------------------------------

def test_ag001_accumulation_flagged():
    from split_learning_tpu.analysis import agg_check
    src = (
        "def fold(updates, store):\n"
        "    trees = [u.params for u in updates]\n"        # AG001
        "    stats = [u.batch_stats for u in updates]\n"   # AG001
        "    held = []\n"
        "    for u in updates:\n"
        "        held.append(u.params)\n"                  # AG001
        "        store[u.client_id] = u.params\n"          # AG001
        "    got = [u for u in updates if u.params is not None]\n"
        "    return trees, stats, held, got\n"
    )
    findings = agg_check.check_source(src, "x.py")
    assert [f.code for f in findings] == ["AG001"] * 4
    assert {f.line for f in findings} == {2, 3, 6, 7}


def test_ag001_annotations_suppress():
    from split_learning_tpu.analysis import agg_check
    src = (
        "def oracle(updates, store):\n"
        "    trees = [u.params for u in updates]  "
        "# slcheck: agg-oracle\n"
        "    store[u.client_id] = u.params  # slcheck: agg-state\n"
    )
    assert agg_check.check_source(src, "x.py") == []


def test_ag001_registered_and_repo_clean():
    from split_learning_tpu.analysis import agg_check
    from split_learning_tpu.analysis.__main__ import ANALYZERS, repo_root
    assert "agg" in ANALYZERS
    assert agg_check.run(repo_root()) == []


# --------------------------------------------------------------------------
# async staleness-admission rule (AS001)
# --------------------------------------------------------------------------

def test_as001_unguarded_fold_flagged():
    from split_learning_tpu.analysis import async_check
    src = (
        "def pump(self, msg):\n"
        "    self._fold.add_update(msg)\n"                 # AS001
        "\n"
        "def drain(self, g, ent):\n"
        "    self._fold.add_partial(g.stage, g.key, ent)\n"  # AS001
        "\n"
        "self._fold.add_update(late_msg)\n"                # AS001 (no fn)
    )
    findings = async_check.check_source(src, "x.py")
    assert [f.code for f in findings] == ["AS001"] * 3
    assert {f.line for f in findings} == {2, 5, 7}


def test_as001_admission_window_suppresses():
    from split_learning_tpu.analysis import async_check
    src = (
        "def door(self, msg):\n"
        "    lag = self._cur_gen - msg.version\n"
        "    if lag <= self.cfg.learning.max_staleness:\n"
        "        self._fold.add_update(msg)\n"
        "\n"
        "def pump(self, msg):\n"
        "    self._admit_update(msg)\n"
        "    self._fold.add_update(msg)\n"     # enclosing fn holds the door
    )
    assert async_check.check_source(src, "x.py") == []


def test_as001_exempt_annotation_suppresses():
    from split_learning_tpu.analysis import async_check
    src = (
        "def l1_drain(self, fb, u):\n"
        "    fb['fold'].add_update(u)  # slcheck: async-exempt\n"
    )
    assert async_check.check_source(src, "x.py") == []


def test_as001_registered_and_repo_clean():
    from split_learning_tpu.analysis import async_check
    from split_learning_tpu.analysis.__main__ import ANALYZERS, repo_root
    assert "async" in ANALYZERS
    assert async_check.run(repo_root()) == []


def test_as001_server_fold_sites_enumerated():
    """The rule only bites if it watches the real file: every fold call
    site in runtime/server.py is either inside the admission door or
    carries the exemption."""
    import pathlib

    from split_learning_tpu.analysis import async_check
    src = pathlib.Path(
        async_check.FILES[0]).read_text()
    calls = src.count(".add_update(") + src.count(".add_partial(")
    assert calls >= 3   # _admit_update + L1 fallback + partial root


def test_partial_aggregate_in_protocol_model():
    # the tree's frame kind is first-class: model vocabulary, send/recv
    # rules for all three roles, and legal transitions where the
    # runtime produces them
    assert "PartialAggregate" in M.CONTROL_KINDS
    assert M.queue_family("aggregate_queue_0_3") == "aggregate"
    assert ("client", "aggregate", "Update") in M.SEND_RULES
    assert ("aggregator", "rpc", "PartialAggregate") in M.SEND_RULES
    assert ("server", "aggregate") in M.RECV_RULES
    events = [
        M.Event("server", "send", "Start", "server"),
        M.Event("server", "recv", "Ready", "server"),
        M.Event("server", "send", "Syn", "server"),
        M.Event("server", "recv", "Notify", "server"),
        M.Event("server", "send", "Pause", "server"),
        M.Event("server", "recv", "Update", "server"),       # fallback
        M.Event("server", "recv", "PartialAggregate", "server"),
        M.Event("server", "send", "Stop", "server"),
        M.Event("server", "recv", "PartialAggregate", "server"),
        M.Event("aggregator", "recv", "Update", "aggregator_0_0"),
        M.Event("aggregator", "recv", "Update", "aggregator_0_0"),
        M.Event("aggregator", "send", "PartialAggregate",
                "aggregator_0_0"),
    ]
    assert M.validate_events(events) == []


def test_aggregator_log_lines_resolve_to_aggregator_role():
    text = (
        "2026-08-03 10:00:00,000 - aggregator_0_1.abc - INFO - "
        "[<<<] UPDATE client_1_0 (L1 fold)\n"
        "2026-08-03 10:00:01,000 - aggregator_0_1.abc - INFO - "
        "[>>>] PARTIALAGGREGATE members=2/2\n")
    events = M.events_from_log(text)
    assert [e.role for e in events] == ["aggregator", "aggregator"]
    assert M.validate_events(events) == []


# --------------------------------------------------------------------------
# pallas lowering gate (PK001)
# --------------------------------------------------------------------------

def test_pallas_analyzer_clean_on_repo():
    # every enableable kernel traced with the kernel on must show its
    # pallas_call in the hot-path jaxpr (acceptance criterion)
    from split_learning_tpu.analysis import pallas_check
    from split_learning_tpu.analysis.__main__ import repo_root
    assert pallas_check.run(repo_root(), trace=True) == []


def test_pallas_gate_fires_on_pallas_free_program():
    import jax
    import numpy as np

    from split_learning_tpu.analysis import pallas_check
    jaxpr = jax.make_jaxpr(lambda x: x + 1)(np.ones((4,), np.float32))
    assert not pallas_check.contains_pallas_call(jaxpr)
    fs = pallas_check.check_lowering(jaxpr, "some/file.py", "quantize:int8")
    assert codes(fs) == {"PK001"}
    assert fs[0].where == "quantize:int8"


def test_pallas_gate_sees_call_through_jit_wrapping():
    import jax
    import numpy as np

    from split_learning_tpu.analysis import pallas_check
    from split_learning_tpu.ops.kernels.quant import quantize_tiles

    tiles = np.ones((3, 64), np.float32)
    jaxpr = jax.make_jaxpr(
        jax.jit(lambda t: quantize_tiles(t, bits=8)))(tiles)
    assert pallas_check.contains_pallas_call(jaxpr)
    assert pallas_check.check_lowering(jaxpr, "x.py", "quantize:int8") == []


def test_pallas_gate_fires_on_a_block_mosaic_refuses():
    """The lowering half has teeth: a ``pallas_call`` that is fine
    under the interpreter — one row of a (rows, 128) array per grid
    step, the layout the quantizer's scales used to have — is refused
    by the TPU lowering, on a host with no TPU."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from split_learning_tpu.analysis import pallas_check

    def copy_rows(x, interpret):
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        row = pl.BlockSpec((1, 128), lambda i: (i, 0))
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(x.shape[0],), in_specs=[row], out_specs=row,
            interpret=interpret)(x)

    x = jnp.arange(4 * 128, dtype=jnp.float32).reshape(4, 128)
    assert (copy_rows(x, True) == x).all()
    fs = pallas_check.check_tpu_lowering(
        "copy_rows", "x.py", lambda a: copy_rows(a, False),
        (jax.ShapeDtypeStruct(x.shape, x.dtype),))
    assert codes(fs) == {"PK001"}
    assert "does not lower for TPU" in fs[0].message


def test_pallas_analyzer_skipped_without_trace():
    from split_learning_tpu.analysis import pallas_check
    from split_learning_tpu.analysis.__main__ import repo_root
    assert pallas_check.run(repo_root(), trace=False) == []


# --------------------------------------------------------------------------
# blackbox analyzer (BB001-BB002) + BlackboxDump in the protocol model
# --------------------------------------------------------------------------

def test_bb001_uncovered_entry_point_flagged():
    from split_learning_tpu.analysis import blackbox_check
    src = ("import argparse\n"
           "def main(argv=None):\n"
           "    args = argparse.ArgumentParser().parse_args(argv)\n"
           "    return 0\n")
    fs = blackbox_check.check_entry(src, "runtime/fake.py")
    assert codes(fs) == {"BB001"}
    assert fs[0].line == 2  # anchored at def main
    assert "flight" in fs[0].message


def test_bb001_install_or_opt_out_passes():
    from split_learning_tpu.analysis import blackbox_check
    armed = ("from split_learning_tpu.runtime import blackbox\n"
             "def main():\n"
             "    blackbox.install_basic('p')\n")
    assert blackbox_check.check_entry(armed, "x.py") == []
    # an unrelated receiver's .install() must NOT satisfy the rule
    imposter = "def main():\n    handlers.install('p')\n"
    assert codes(blackbox_check.check_entry(imposter, "x.py")) == {"BB001"}
    opted = "# slcheck: no-blackbox\ndef main():\n    pass\n"
    assert blackbox_check.check_entry(opted, "x.py") == []


def test_bb002_silent_swallow_flagged():
    from split_learning_tpu.analysis import blackbox_check
    src = ("def pump(self):\n"
           "    try:\n"
           "        self.sock.recv(4)\n"
           "    except Exception:\n"
           "        pass\n")
    fs = blackbox_check.check_hot(src, "runtime/bus.py")
    assert codes(fs) == {"BB002"}


def test_bb002_evidence_or_opt_out_passes():
    from split_learning_tpu.analysis import blackbox_check
    evidenced = ("def pump(self):\n"
                 "    try:\n"
                 "        self.sock.recv(4)\n"
                 "    except Exception:\n"
                 "        self.faults.inc('recv_errors')\n")
    assert blackbox_check.check_hot(evidenced, "x.py") == []
    reraises = ("def pump(self):\n"
                "    try:\n"
                "        self.sock.recv(4)\n"
                "    except Exception:\n"
                "        raise\n")
    assert blackbox_check.check_hot(reraises, "x.py") == []
    opted = ("def close(self):\n"
             "    try:\n"
             "        self.sock.close()\n"
             "    except Exception:  # slcheck: no-blackbox\n"
             "        pass\n")
    assert blackbox_check.check_hot(opted, "x.py") == []
    narrow = ("def pump(self):\n"
              "    try:\n"
              "        self.sock.recv(4)\n"
              "    except OSError:\n"
              "        pass\n")
    assert blackbox_check.check_hot(narrow, "x.py") == []


def test_bb_registered_and_repo_clean():
    from split_learning_tpu.analysis import blackbox_check
    from split_learning_tpu.analysis.__main__ import ANALYZERS, repo_root
    assert "blackbox" in ANALYZERS
    assert blackbox_check.run(repo_root()) == []


def test_blackbox_dump_legal_in_every_fsm_state():
    # fleet snapshots fire the moment a death is noticed, whatever
    # round phase any participant is in — lifecycle-orthogonal like
    # Heartbeat, so every state needs the self-loop or chaos-run
    # traces through the validator would flag the fan-out
    from split_learning_tpu.analysis.model import (
        AGGREGATOR_FSM, CLIENT_FSM, SERVER_FSM, STAGEHOST_FSM,
        Event, validate_events,
    )
    for state, trans in SERVER_FSM.items():
        assert trans[("send", "BlackboxDump")] == state
    for fsm in (CLIENT_FSM, AGGREGATOR_FSM, STAGEHOST_FSM):
        for state, trans in fsm.items():
            assert trans[("recv", "BlackboxDump")] == state
    events = [Event("client", "send", "Register", "c1"),
              Event("client", "recv", "BlackboxDump", "c1"),
              Event("client", "recv", "Start", "c1"),
              Event("client", "recv", "BlackboxDump", "c1"),
              Event("server", "send", "BlackboxDump", "server")]
    assert validate_events(events) == []


def test_blackbox_dump_in_send_rules_and_samples():
    from split_learning_tpu.analysis import protocol_check as P
    from split_learning_tpu.analysis.model import CONTROL_KINDS, SEND_RULES
    assert "BlackboxDump" in CONTROL_KINDS
    assert ("server", "reply", "BlackboxDump") in SEND_RULES
    # the PC004 wire-conformance sample must round-trip
    from split_learning_tpu.runtime.protocol import decode, encode
    sample = P._sample_messages()["BlackboxDump"]
    msg = decode(encode(sample))
    assert msg == sample
