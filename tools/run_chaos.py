#!/usr/bin/env python
"""Chaos sweep: fault probabilities x seeds -> pass/fail matrix.

Each cell pushes a PROTOCOL message stream (TENSOR-framed Activations
fenced by an EpochEnd) through the production transport stack —
``ReliableTransport`` over a seeded ``ChaosTransport`` over the
in-process bus — and PASSes iff the receiver sees the exact sent
sequence, in order, with nothing extra, AND the decoded stream replays
clean through the protocol-model trace validator
(``split_learning_tpu/analysis/model.py``) — so every sweep cell also
proves protocol conformance, not just byte delivery.  ``--full`` cells
additionally replay the round's ``app.log`` through the control-plane
state machines.  Because every cell is reproducible from its (fault,
probability, seed) triple, a FAIL here is a ready-made regression
test: rerun with ``--only drop:0.4 --seeds 1 --seed-base <seed>`` and
debug.

    python tools/run_chaos.py                  # default grid, 5 seeds
    python tools/run_chaos.py --seeds 20 --messages 400   # longer soak
    python tools/run_chaos.py --full           # full tiny training
                                               # round per cell (slow;
                                               # needs jax/CPU)

Exit code is non-zero when any cell fails, so it slots into CI.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import threading
import time

# The cells test the host planes, most of them across several
# processes.  An accelerator belongs to one process, so the rig runs
# on the CPU backend by choice — this process and every child it
# spawns — whatever the machine has.
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, ".")  # run from the repo root

from split_learning_tpu.analysis.model import (  # noqa: E402
    validate_data_stream, validate_log,
)
from split_learning_tpu.config import ChaosConfig  # noqa: E402
from split_learning_tpu.runtime.bus import (  # noqa: E402
    InProcTransport, ReliableTransport,
)
from split_learning_tpu.runtime.chaos import ChaosTransport  # noqa: E402
from split_learning_tpu.runtime.trace import FaultCounters  # noqa: E402

QUEUE = "intermediate_queue_0_0"


def _protocol_stream(n: int) -> list[bytes]:
    """n TENSOR-framed Activations + the epoch fence, as wire bytes."""
    import numpy as np

    from split_learning_tpu.runtime import protocol as proto
    frames = [proto.encode(proto.Activation(
        data_id=f"d{i:06d}",
        data=np.full((8,), i % 7, np.float32),
        labels=np.asarray([i % 10], np.int64),
        trace=["feeder"], cluster=0)) for i in range(n)]
    frames.append(proto.encode(proto.EpochEnd(client_id="feeder")))
    return frames


def transport_cell(fault: str, prob: float, seed: int,
                   n_messages: int) -> tuple[bool, str]:
    """True iff the reliable layer fully masks this fault class."""
    kwargs = {f: 0.0 for f in ("drop", "duplicate", "reorder", "corrupt",
                               "delay")}
    if fault == "mixed":
        for f in kwargs:
            kwargs[f] = prob
    else:
        kwargs[fault] = prob
    cfg = ChaosConfig(enabled=True, seed=seed, delay_s=0.005,
                      queues=("intermediate_queue*",), **kwargs)
    bus = InProcTransport()
    fc = FaultCounters()
    # provision the redelivery budget for the injected loss regime: at
    # sustained ~2/3 per-attempt loss (mixed:0.4) the give-up odds are
    # loss^(attempts+1), so 40 attempts ≈ 5e-7/message.  The receiver's
    # gap timeout must exceed the sender's full retry horizon or a
    # skip-then-late-arrival turns into a loss.
    sender = ReliableTransport(
        ChaosTransport(bus, cfg, name="s", faults=fc), sender="s",
        patterns=("intermediate_queue*",), redeliver_s=0.05,
        max_redeliver=40, faults=fc)
    recv = ReliableTransport(bus, sender="r",
                             patterns=("intermediate_queue*",),
                             redeliver_s=0.05, max_redeliver=40,
                             gap_timeout_s=60.0, faults=fc)
    msgs = _protocol_stream(n_messages)
    t = threading.Thread(
        target=lambda: [sender.publish(QUEUE, m) for m in msgs],
        daemon=True)
    t.start()
    got = []
    for _ in msgs:
        m = recv.get(QUEUE, timeout=30.0)
        if m is None:
            break
        got.append(m)
    t.join(timeout=10)
    extra = recv.get(QUEUE, timeout=0.2)
    sender.stop(close_inner=False)
    recv.stop(close_inner=False)
    if got != msgs:
        return False, f"{len(got)}/{len(msgs)} exact"
    if extra is not None:
        return False, "phantom extra message"
    # protocol conformance: the post-transport stream must decode and
    # replay clean through the declarative data-plane model (right
    # kinds on this queue family, no duplicate data_id, no round
    # regression)
    from split_learning_tpu.runtime import protocol as proto
    try:
        decoded = [proto.decode(m) for m in got]
    except Exception as e:  # noqa: BLE001 — any decode failure fails the cell
        return False, f"undecodable frame: {type(e).__name__}"
    violations = validate_data_stream(decoded, QUEUE)
    if violations:
        return False, f"protocol: {violations[0].message}"
    snap = fc.snapshot()
    note = "+".join(f"{k[0]}{v}" for k, v in sorted(snap.items())
                    if k in ("drops", "duplicates", "reorders",
                             "corruptions", "delays"))
    return True, note or "quiet"


#: --codec: the wire compression stack the chaos cells run under
#: (int8 tiled activations + top-k EF gradients + int8-delta Updates)
#: — proving the error-feedback state and delta chain deterministic
#: UNDER faults, not just on a clean wire
CODEC_STACK = {"intermediate": "int8:64", "gradient": "topk:0.1",
               "rpc": "delta:int8"}


def full_round_cell(fault: str, prob: float, seed: int, tmp: str,
                    codec: bool = False) -> tuple[bool, str]:
    """Full 3-client round; PASS iff params match the fault-free run
    bit-for-bit (baseline computed once and cached on the function).
    ``codec=True`` runs BOTH the baseline and the chaotic cell with the
    compression stack enabled — bit-identity then proves the codecs'
    stateful parts (EF residuals, delta folds) are deterministic under
    drop/dup/reorder."""
    import numpy as np

    sys.path.insert(0, "tests")
    from test_chaos import _chaos, _round_cfg, _run_cell  # noqa: E402
    root = pathlib.Path(tmp)
    over = {"transport": {"codec": CODEC_STACK}} if codec else {}
    cache = "_base_codec" if codec else "_base"
    if not hasattr(full_round_cell, cache):
        cfg = _round_cfg(root, root / f"base{'_codec' if codec else ''}",
                         **over)
        setattr(full_round_cell, cache, _run_cell(cfg))
    base = getattr(full_round_cell, cache)
    kwargs = {f: 0.0 for f in ("drop", "duplicate", "reorder", "corrupt",
                               "delay")}
    if fault == "mixed":
        for f in kwargs:
            kwargs[f] = prob
    else:
        kwargs[fault] = prob
    cell_dir = root / f"{fault}_{prob}_{seed}"
    cfg = _round_cfg(root, cell_dir, **over)
    res = _run_cell(cfg, chaos_cfg=_chaos(seed=seed, delay_s=0.005,
                                          **kwargs), reliable=True)
    if not res.history[0].ok:
        return False, "round not ok"
    if res.history[0].num_samples != base.history[0].num_samples:
        return False, "sample count drifted"
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(base.params),
                    jax.tree_util.tree_leaves(res.params)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            return False, "params not bit-identical"
    # replay the round's recorded control-plane trace through the
    # protocol state machines: a chaos run must also PROVE protocol
    # conformance, not just converge to the right bits
    log = pathlib.Path(cell_dir) / "app.log"
    if log.exists():
        violations = validate_log(log.read_text(), source=str(log))
        if violations:
            return False, f"protocol: {violations[0].message}"
    # the distributed trace must survive chaos too: merge the cell's
    # span journals, schema-validate the Perfetto export, and require
    # a fully-connected span tree (every parent id resolves) — a chaos
    # fault that orphans spans would make chaotic rounds undebuggable
    # exactly when debugging matters.  trace.json is left in the cell
    # dir (CI uploads it as a workflow artifact).
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    import sl_trace
    files = sl_trace.find_span_files(cell_dir)
    if not files:
        return False, "no span journals (tracing disabled?)"
    spans = sl_trace.load_spans(files)
    errs = sl_trace.validate_spans(spans)
    if errs:
        return False, f"spans: {errs[0]}"
    orphans = sl_trace.orphan_spans(spans)
    if orphans:
        return False, f"{len(orphans)} orphan spans"
    trace = sl_trace.build_trace(spans)
    terr = sl_trace.validate_trace(trace)
    if terr:
        return False, f"trace: {terr[0]}"
    (pathlib.Path(cell_dir) / "trace.json").write_text(
        json.dumps(trace))
    report = sl_trace.critical_path(spans)
    if not report:
        return False, "no train span in merged trace"
    (pathlib.Path(cell_dir) / "critical_path.json").write_text(
        json.dumps(report, indent=2))
    return True, "bit-identical+conformant+traced"


def fleet_cell(tmp: str, seed: int = 7) -> tuple[bool, str]:
    """Live-telemetry chaos cell: a 3-client round (2 feeders + 1
    head) with one client's rpc traffic delay-injected, heartbeats at
    a short interval and the HTTP exporter on an ephemeral port.
    PASSes iff (a) the FleetMonitor marked the delayed client
    degraded/straggler mid-round AND the round still completed, (b)
    ``/metrics`` served parseable Prometheus text mid-round (format
    lint), and (c) ``sl_top``'s renderer produced the fleet table from
    the live ``/fleet`` snapshot.  Writes ``fleet.json`` (the final
    snapshot) into the cell dir for CI artifact upload."""
    import threading as _threading
    import urllib.request

    sys.path.insert(0, "tests")
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    import sl_top
    from test_chaos import _round_cfg  # noqa: E402

    from split_learning_tpu.runtime.client import ProtocolClient
    from split_learning_tpu.runtime.server import ProtocolServer
    from split_learning_tpu.runtime.telemetry import lint_prometheus

    interval = 0.25
    cell_dir = pathlib.Path(tmp) / "fleet"
    cfg = _round_cfg(pathlib.Path(tmp), cell_dir, observability={
        "heartbeat_interval": interval, "liveness_timeout": 8.0,
        "http_port": 0})
    slow = "client_1_1"
    # every rpc frame from the slow client (heartbeats included) held
    # ~8 intervals with p=0.6: fresh-beat gaps blow past the
    # straggler threshold, and the late arrivals land stale (the
    # dup/reorder-rejection path) before a fresh burst recovers it
    slow_chaos = ChaosConfig(enabled=True, seed=seed, delay=0.6,
                             delay_s=8 * interval,
                             queues=("rpc_queue",))
    bus = InProcTransport()
    fc = FaultCounters()
    server = ProtocolServer(cfg, transport=bus, client_timeout=300.0)
    url = server.exporter.url
    threads = []
    for stage, count in enumerate(cfg.clients, start=1):
        for i in range(count):
            cid = f"client_{stage}_{i}"
            t = (ChaosTransport(bus, slow_chaos, name=cid, faults=fc)
                 if cid == slow else bus)
            client = ProtocolClient(cfg, cid, stage, transport=t)
            th = _threading.Thread(target=client.run, daemon=True)
            th.start()
            threads.append(th)

    scrapes = {"ok": 0, "errs": [], "fleet": None}

    def poll_endpoint():
        while not done.is_set():
            try:
                with urllib.request.urlopen(f"{url}/metrics",
                                            timeout=2.0) as r:
                    errs = lint_prometheus(r.read().decode())
                if errs:
                    scrapes["errs"] = errs[:3]
                else:
                    scrapes["ok"] += 1
                scrapes["fleet"] = sl_top.fetch_fleet(url)
            except Exception:  # noqa: BLE001 — a truncated body /
                # json hiccup mid-teardown must not kill the poller
                # (only OSError would leave 'ok' forever 0)
                pass
            done.wait(0.5)

    done = _threading.Event()
    poller = _threading.Thread(target=poll_endpoint, daemon=True)
    poller.start()
    t0 = time.monotonic()
    try:
        res = server.serve()
    finally:
        done.set()
        poller.join(timeout=5)
    wall = time.monotonic() - t0
    for th in threads:
        th.join(timeout=30)
    # prefer the last LIVE /fleet scrape (proves the endpoint served
    # mid-round); the in-process snapshot is the fallback view
    fleet = scrapes["fleet"] or server.ctx.fleet.snapshot()
    (cell_dir / "fleet.json").write_text(json.dumps(fleet, indent=2))
    table = sl_top.render_fleet(fleet, color=False, source=url)
    (cell_dir / "fleet_table.txt").write_text(table + "\n")
    if not res.history or not res.history[0].ok:
        return False, "round not ok"
    if wall > 240:
        return False, f"round stalled ({wall:.0f}s)"
    flagged = {t["client"] for t in fleet.get("transitions", ())
               if t["to"] in ("degraded", "straggler")}
    if slow not in flagged:
        return False, f"{slow} never flagged (transitions: "\
                      f"{fleet.get('transitions')})"
    if any(t["client"] != slow and t["to"] == "lost"
           for t in fleet.get("transitions", ())):
        return False, "healthy client marked lost"
    if scrapes["errs"]:
        return False, f"/metrics lint: {scrapes['errs'][0]}"
    if scrapes["ok"] == 0:
        return False, "no successful mid-round /metrics scrape"
    if slow not in table:
        return False, "sl_top table missing the delayed client"
    straggled = any(t["client"] == slow and t["to"] == "straggler"
                    for t in fleet.get("transitions", ()))
    return True, ("straggler+recovered" if straggled
                  else "degraded") + f"+{scrapes['ok']}scrapes"


def async_cell(tmp: str, seed: int = 11) -> tuple[bool, str]:
    """Async-mode chaos cell (learning.mode: async): a 3-client round
    (2 aux-loss feeders + 1 head) under delay + drop + duplicate
    injection with the reliable layer on.  PASSes iff

    * the round completes without a barrier stall (bounded wall — the
      decoupled loops never park on gradient_queue, so an injected
      delay costs latency, not a deadlock);
    * the fold is DETERMINISTIC: a twin run with the same chaos seed
      produces bit-identical STAGE-1 aggregated params (each feeder's
      decoupled aux-step sequence depends only on its own data/rng —
      no wire cotangent to race on) and the exact same aggregation
      counter snapshot (dup drops included).  The head's shard is
      excluded: async deliberately trades the strict SDA arrival
      barrier for liveness, so the head steps in arrival order — the
      documented nondeterminism async buys its stall-freedom with;
    * stale rejections are counted EXACTLY: a directly-driven admission
      sweep over versions ``cur, cur-1, .., cur-max_staleness-1`` plus
      a duplicate must land exactly max_staleness admits, one reject,
      one dup drop — and the staleness weights must match
      ``staleness_decay ** lag`` to the bit.
    """
    import numpy as np

    sys.path.insert(0, "tests")
    from test_chaos import _chaos, _round_cfg, _run_cell  # noqa: E402

    over = dict(
        global_rounds=1,
        aggregation={"strategy": "fedavg", "sda_strict": False,
                     "sda_size": 1},
        learning={"mode": "async", "aux_head": "pooled-linear",
                  "max_staleness": 2, "staleness_decay": 0.5,
                  "async_quorum": 0, "batch_size": 4,
                  "control_count": 1, "optimizer": "adamw",
                  "learning_rate": 1e-3})
    chaos = _chaos(seed=seed, drop=0.10, duplicate=0.10, delay=0.15,
                   delay_s=0.02)

    def run(tag):
        fc = FaultCounters()
        cfg = _round_cfg(pathlib.Path(tmp),
                         pathlib.Path(tmp) / f"async_{tag}", **over)
        t0 = time.monotonic()
        res = _run_cell(cfg, chaos_cfg=chaos, reliable=True, faults=fc)
        return res, fc.snapshot(), time.monotonic() - t0

    res_a, snap_a, wall_a = run("a")
    res_b, snap_b, wall_b = run("b")
    if not (res_a.history and res_a.history[0].ok
            and res_b.history and res_b.history[0].ok):
        return False, "round not ok"
    if max(wall_a, wall_b) > 240:
        return False, f"barrier stall ({max(wall_a, wall_b):.0f}s)"
    import jax

    from split_learning_tpu.models import build_model, shard_params
    cfg_a = _round_cfg(pathlib.Path(tmp),
                       pathlib.Path(tmp) / "async_spec", **over)
    specs = build_model(cfg_a.model_key,
                        **(cfg_a.model_kwargs or {})).specs
    cut = cfg_a.topology.cut_layers[0]
    s1_a = shard_params(res_a.params, specs, 0, cut)
    s1_b = shard_params(res_b.params, specs, 0, cut)
    if not s1_a:
        return False, "no stage-1 keys in aggregated params"
    for a, b in zip(jax.tree_util.tree_leaves(s1_a),
                    jax.tree_util.tree_leaves(s1_b)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            return False, "async stage-1 fold not deterministic"
    if res_a.history[0].num_samples != res_b.history[0].num_samples:
        return False, "sample count drifted"
    agg_keys = ("agg_stale_updates", "agg_stale_admits",
                "agg_dup_drops")
    counts_a = {k: snap_a.get(k, 0) for k in agg_keys}
    if counts_a != {k: snap_b.get(k, 0) for k in agg_keys}:
        return False, f"agg counters drifted: {counts_a} vs twin"

    # exact staleness accounting, driven directly (no timing): versions
    # cur .. cur-(max_staleness+1) plus a duplicate of the last admit
    from split_learning_tpu.runtime.bus import InProcTransport
    from split_learning_tpu.runtime.aggregate import StreamingFold
    from split_learning_tpu.runtime.protocol import Update
    from split_learning_tpu.runtime.server import ProtocolContext
    cfg = _round_cfg(pathlib.Path(tmp), pathlib.Path(tmp) / "admit",
                     **over)
    ctx = ProtocolContext(cfg, InProcTransport())
    ctx._cur_gen = 5
    ctx._fold = StreamingFold({1: ["c0"]}, faults=ctx.faults)

    def upd(cid, ver):
        return Update(client_id=cid, stage=1, cluster=0,
                      params={"layer1": {"w": np.ones(4, np.float32)}},
                      num_samples=8, round_idx=ver, version=ver)
    for ver in (5, 4, 3, 2):          # lag 0 fresh, 1+2 admit, 3 reject
        ctx._admit_update(upd(f"c{5 - ver}", ver))
    ctx._admit_update(upd("c1", 4))   # post-fold duplicate
    snap = ctx.faults.snapshot()
    got = {k: snap.get(k, 0) for k in agg_keys}
    want = {"agg_stale_updates": 1, "agg_stale_admits": 2,
            "agg_dup_drops": 1}
    if got != want:
        return False, f"admission counts {got} != {want}"
    # weight math: 8 + 8*0.5 + 8*0.25 folded over all-ones trees
    result = ctx._fold.finish()
    w = np.asarray(result.params["layer1"]["w"])
    if not np.allclose(w, 1.0):
        return False, f"staleness-weighted fold wrong: {w[:2]}"
    expect_w = 8 + 8 * 0.5 + 8 * 0.25
    st = ctx._fold._stages[1]
    if abs(st.total_w - expect_w) > 1e-9:
        return False, f"fold weight {st.total_w} != {expect_w}"
    return True, (f"deterministic+admitted "
                  f"({counts_a.get('agg_dup_drops', 0)} dup drops, "
                  f"{wall_a:.0f}s/{wall_b:.0f}s)")


def tree_remote_cell(tmp: str) -> tuple[bool, str]:
    """Multi-process aggregator-tree cell (aggregation.remote): a real
    TCP broker, THREE aggregator subprocesses spawned by the server
    (``aggregation.nodes: 3``), a 3-client deterministic round — and
    one aggregator process SIGKILLed mid-round, right after its group
    assignment lands.  PASSes iff

    * the round completes without a barrier stall (the killed node's
      groups degrade to the server's counted direct-to-root fallback
      drain — detected via the spawned process's exit, the same path
      FleetMonitor ``lost`` drives for adopted nodes);
    * the kind=agg record counts the node death and the fault record
      counts ``agg_l1_fallbacks`` ≥ 1 with every member still folded
      or explicitly abandoned;
    * the surviving nodes' ``kind=agg_node`` records and the tree
      topology land as artifacts (``agg_tree.json``).
    """
    import json
    import threading as _threading

    sys.path.insert(0, "tests")
    from test_chaos import _round_cfg  # noqa: E402

    from split_learning_tpu.runtime.bus import Broker
    from split_learning_tpu.runtime.chaos import make_runtime_transport
    from split_learning_tpu.runtime.client import ProtocolClient
    from split_learning_tpu.runtime.server import ProtocolServer

    cell_dir = pathlib.Path(tmp) / "tree_remote"
    cell_dir.mkdir(parents=True, exist_ok=True)
    broker = Broker("127.0.0.1", 0)
    killed = {}
    try:
        cfg = _round_cfg(
            pathlib.Path(tmp), cell_dir,
            transport={"kind": "tcp", "host": "127.0.0.1",
                       "port": broker.port},
            aggregation={"strategy": "sda", "sda_size": 2,
                         "sda_strict": True, "fan_in": 2, "levels": 2,
                         "remote": True, "nodes": 3},
            observability={"heartbeat_interval": 0.5,
                           "liveness_timeout": 15.0})
        server = ProtocolServer(cfg, client_timeout=300.0)
        threads = []
        for stage, count in enumerate(cfg.clients, start=1):
            for i in range(count):
                cid = f"client_{stage}_{i}"
                client = ProtocolClient(
                    cfg, cid, stage,
                    transport=make_runtime_transport(cfg, cid))
                th = _threading.Thread(target=client.run, daemon=True)
                th.start()
                threads.append(th)

        def killer():
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                for nid, glist in sorted(
                        server.ctx._l1_remote.items()):
                    if not glist:
                        continue
                    proc = (server.ctx._agg_nodes.get(nid)
                            or {}).get("proc")
                    if proc is not None and proc.poll() is None:
                        proc.kill()     # SIGKILL: no cleanup, no flush
                        killed["nid"] = nid
                        killed["groups"] = [g.idx for g in glist]
                        return
                time.sleep(0.05)

        kt = _threading.Thread(target=killer, daemon=True)
        kt.start()
        t0 = time.monotonic()
        res = server.serve()
        wall = time.monotonic() - t0
        kt.join(timeout=5)
        for th in threads:
            th.join(timeout=30)
        topo = {"agg_tree": server.ctx._agg_topology,
                "killed": killed,
                "fleet": (server.ctx.fleet.snapshot()
                          if server.ctx.fleet is not None else {})}
        (cell_dir / "agg_tree.json").write_text(
            json.dumps(topo, indent=2, default=str))
    finally:
        broker.close()
    if not res.history or not res.history[0].ok:
        return False, "round not ok"
    if wall > 240:
        return False, f"round stalled ({wall:.0f}s)"
    if not killed:
        return False, "no aggregator process killed (assignment never " \
                      "observed)"
    recs = []
    for p in cell_dir.rglob("metrics.jsonl"):
        if p.is_symlink():
            continue
        for line in p.read_text().splitlines():
            try:
                recs.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    agg = [r for r in recs if r.get("kind") == "agg"]
    if not agg:
        return False, "no kind=agg record"
    if agg[-1].get("node_deaths", 0) < 1:
        return False, "node death not counted on the agg record"
    if agg[-1].get("remote_nodes", 0) < 3:
        return False, f"expected 3 remote nodes, saw " \
                      f"{agg[-1].get('remote_nodes')}"
    faults = [r for r in recs if r.get("kind") == "faults"]
    snap = faults[-1] if faults else {}
    fallbacks = snap.get("agg_l1_fallbacks", 0)
    if not fallbacks:
        return False, "agg_l1_fallbacks never counted"
    node_recs = [r for r in recs if r.get("kind") == "agg_node"]
    if not node_recs:
        return False, "no kind=agg_node records from surviving nodes"
    abandoned = snap.get("agg_fallback_abandons", 0)
    return True, (f"killed {killed['nid']} "
                  f"(groups {killed['groups']}), "
                  f"fallbacks={fallbacks} abandoned={abandoned} "
                  f"survivor_folds={sum(r.get('folded', 0) for r in node_recs)} "
                  f"[{wall:.0f}s]")


def overlap_cell(tmp: str, seed: int = 13) -> tuple[bool, str]:
    """Sync-overlap chaos cell (learning.sync-overlap): a 3-client
    sync round with the round-boundary overlap ON, under drop +
    duplicate + delay injection with the reliable layer masking.
    PASSes iff

    * the rounds complete without a barrier stall (bounded wall — the
      overlap's speculative ticks hand any control frame back to the
      lifecycle loop in arrival order, so nothing can park);
    * the aggregated params are BIT-IDENTICAL to a fault-free,
      overlap-OFF twin: the speculation (prefetch + stale-seed
      forwards, spliced or discarded with rng/loader state restored)
      must be invisible to training semantics even while chaos
      reorders the wire around it;
    * the overlap actually ran (kind=overlap records in the cell's
      metrics).
    """
    import numpy as np

    sys.path.insert(0, "tests")
    from test_chaos import _chaos, _round_cfg, _run_cell  # noqa: E402

    chaos = _chaos(seed=seed, drop=0.10, duplicate=0.10, delay=0.15,
                   delay_s=0.02)
    fc = FaultCounters()
    cfg_c = _round_cfg(pathlib.Path(tmp),
                       pathlib.Path(tmp) / "overlap_chaos",
                       global_rounds=2,
                       learning={"sync_overlap": True})
    t0 = time.monotonic()
    res_c = _run_cell(cfg_c, chaos_cfg=chaos, reliable=True, faults=fc)
    wall = time.monotonic() - t0
    cfg_b = _round_cfg(pathlib.Path(tmp),
                       pathlib.Path(tmp) / "overlap_base",
                       global_rounds=2)
    res_b = _run_cell(cfg_b)
    if not (res_c.history and all(h.ok for h in res_c.history)
            and res_b.history and all(h.ok for h in res_b.history)):
        return False, "round not ok"
    if wall > 240:
        return False, f"barrier stall ({wall:.0f}s)"
    import jax
    la = jax.tree_util.tree_leaves(res_c.params)
    lb = jax.tree_util.tree_leaves(res_b.params)
    if len(la) != len(lb) or any(
            np.asarray(a).tobytes() != np.asarray(b).tobytes()
            for a, b in zip(la, lb)):
        return False, "overlap+chaos fold not bit-identical"
    if [h.num_samples for h in res_c.history] \
            != [h.num_samples for h in res_b.history]:
        return False, "sample counts drifted"
    import glob as _glob
    import json as _json
    n_ovl = 0
    for p in _glob.glob(str(pathlib.Path(tmp) / "overlap_chaos"
                            / "**" / "metrics.jsonl"), recursive=True):
        for line in open(p):
            try:
                rec = _json.loads(line)
            except _json.JSONDecodeError:
                continue
            if rec.get("kind") == "overlap":
                n_ovl += 1
    if not n_ovl:
        return False, "no overlap activity recorded"
    return True, (f"bit-identical through chaos "
                  f"({n_ovl} overlap ticks records, {wall:.0f}s)")


def sched_cell(tmp: str, seed: int = 17) -> tuple[bool, str]:
    """Closed-loop scheduler chaos cell (scheduler.enabled): a
    heterogeneous 6-client round (synthetic-client substrate,
    ``runtime/simfleet.py``, against the real server/telemetry/
    aggregation planes) with ONE injected compute-straggler (device
    rate 10x slow) and ONE wire-straggler (wire time ~6x compute),
    with duplicate+reorder chaos on the rpc queue.  PASSes iff

    * every round completes (the scheduler must never stall a round);
    * BOTH stragglers are attributed correctly and demoted with their
      knobs retuned: the compute-straggler gets the wider staleness
      window + quorum exemption, the wire-straggler the heavier
      intermediate codec (asserted from the decision journal's
      attribution + knob details);
    * the decisions journal validates (``validate_journal``: every
      control action fully attributable — SC001's runtime twin);
    * /fleet carries the scheduler view (cluster map + per-client
      SCHED column source) and sl_top renders it.

    Writes ``sched.json`` (decisions + final fleet snapshot) into the
    cell dir for CI artifact upload."""
    from split_learning_tpu.config import from_dict
    from split_learning_tpu.runtime.log import Logger
    from split_learning_tpu.runtime.scheduler import validate_journal
    from split_learning_tpu.runtime.server import ProtocolServer
    from split_learning_tpu.runtime.simfleet import (
        SimClientSpec, SyntheticFleet,
    )

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    import sl_top

    cell_dir = pathlib.Path(tmp) / "sched"
    cell_dir.mkdir(parents=True, exist_ok=True)
    n1, heads = 6, 1
    cfg = from_dict({
        "model": "KWT", "dataset": "SPEECHCOMMANDS",
        "clients": [n1, heads], "global_rounds": 3,
        "synthetic_size": 48, "val_max_batches": 1,
        "val_batch_size": 16,
        "model_kwargs": {"embed_dim": 16, "num_heads": 2,
                         "mlp_dim": 32},
        "log_path": str(cell_dir),
        "learning": {"batch_size": 4},
        "topology": {"cut_layers": [2]},
        "checkpoint": {"save": False, "validate": False,
                       "directory": str(cell_dir / "ckpt")},
        "observability": {"heartbeat_interval": 0.25,
                          "liveness_timeout": 30.0, "http_port": 0},
        # evict-after high: this cell proves DEMOTION + attribution
        # (eviction has its own coverage in tests/test_scheduler.py)
        "scheduler": {"enabled": True, "warmup_rounds": 1,
                      "evict_after": 10, "barrier_grace_s": 0.5},
    })
    # one compute-straggler, one wire-straggler, four healthy + a head
    n_layers, speed, samples = 4, 100.0, 32
    update_bytes = 64 << 10
    specs = []
    for i in range(n1):
        sp, wire = speed, 0.0
        if i == 0:
            sp = speed / 10.0
        elif i == 1:
            wire = update_bytes / (6.0 * samples / speed)
        specs.append(SimClientSpec(
            cid=f"sim_1_{i:05d}", stage=1, compute_speed=sp,
            wire_bytes_per_s=wire, samples=samples,
            profile={"exe_time": [(1.0 / sp) / n_layers] * n_layers,
                     "size_data": [float(update_bytes)] * n_layers,
                     "speed": sp, "network": 0.0}))
    specs.append(SimClientSpec(cid="sim_2_00000", stage=2,
                               compute_speed=speed, samples=samples))
    compute_slow, wire_slow = "sim_1_00000", "sim_1_00001"

    bus = InProcTransport()
    fc = FaultCounters()
    # duplicate + reorder chaos on the rpc queue: the scheduler's
    # inputs (heartbeats, update-piggybacked telemetry) must survive
    # the staleness guard's rejections without misattributing anyone
    chaos = ChaosConfig(enabled=True, seed=seed, duplicate=0.2,
                        reorder=0.2, queues=("rpc_queue",))
    fleet_bus = ChaosTransport(bus, chaos, name="simfleet", faults=fc)
    server = ProtocolServer(cfg, transport=bus,
                            logger=Logger.for_run(cfg, "server",
                                                  console=False),
                            client_timeout=120.0)
    fleet = SyntheticFleet(fleet_bus, specs,
                           heartbeat_interval=0.25,
                           time_scale=1.0).start()
    t0 = time.monotonic()
    try:
        res = server.serve()
    finally:
        fleet.stop()
    wall = time.monotonic() - t0
    ctx = server.ctx
    decisions = list(ctx.scheduler.decisions)
    fsnap = ctx.scheduler.annotate_fleet(ctx.fleet.snapshot())
    topo = fsnap["scheduler"]
    (cell_dir / "sched.json").write_text(json.dumps(
        {"decisions": decisions, "fleet": fsnap, "wall_s": wall},
        indent=2, default=str))
    if not res.history or not all(r.ok for r in res.history):
        return False, "round not ok"
    if wall > 240:
        return False, f"round stalled ({wall:.0f}s)"
    errs = validate_journal(decisions)
    if errs:
        return False, f"journal invalid: {errs[0]}"
    demotes = {d["client"]: d["detail"] for d in decisions
               if d["action"] == "demote"}
    if compute_slow not in demotes:
        return False, f"{compute_slow} never demoted"
    if wire_slow not in demotes:
        return False, f"{wire_slow} never demoted"
    det_c, det_w = demotes[compute_slow], demotes[wire_slow]
    if det_c.get("attribution") != "compute" \
            or "staleness_bonus" not in det_c.get("knobs", {}):
        return False, (f"compute-straggler misattributed: {det_c}")
    if det_w.get("attribution") != "wire" \
            or "intermediate" not in det_w.get("knobs",
                                               {}).get("codec", {}):
        return False, f"wire-straggler misattributed: {det_w}"
    table = sl_top.render_fleet(fsnap, color=False, source="sched")
    (cell_dir / "sched_table.txt").write_text(table + "\n")
    # the SCHED column shows each client's LAST action (a later mid-
    # round drop may have overwritten demote@rN) — require both
    # stragglers to carry one, and the demotions to render in the
    # decisions tail
    if not all("@r" in (topo["actions"].get(c) or "")
               for c in (compute_slow, wire_slow)):
        return False, "sl_top SCHED column missing the stragglers"
    if "demote" not in table:
        return False, "sl_top decisions tail missing the demotions"
    healthy_demoted = [c for c in demotes
                       if c not in (compute_slow, wire_slow)]
    if healthy_demoted:
        return False, f"healthy clients demoted: {healthy_demoted}"
    return True, (f"both stragglers attributed+demoted, "
                  f"{len(decisions)} journaled decisions, "
                  f"{fc.snapshot().get('duplicates', 0)} dup "
                  f"{fc.snapshot().get('reorders', 0)} reorder "
                  f"injected [{wall:.0f}s]")


def fleet_scale_cell(tmp: str, seed: int = 23) -> tuple[bool, str]:
    """Hierarchical digest roll-up chaos cell
    (observability.digest-interval): a 24-client synthetic fleet whose
    heartbeats route through TWO in-proc aggregator-node digest
    workers, with duplicate+reorder chaos on the digest and rpc
    queues, and ONE node stopped mid-run.  PASSes iff

    * every round completes (the roll-up must never stall a round);
    * the digest path actually carried the fleet: the server folded
      FleetDigest frames (digest block in /fleet with exact state
      counts covering the routed clients);
    * the killed node's clients fall back to DIRECT heartbeats,
      counted exactly (``digest_fallbacks`` == clients routed to it);
    * NO client ever transitions to ``lost`` (the fallback drains the
      dead node's parked beats — a phantom `lost` flap is the failure
      mode this cell exists to catch);
    * chaos was real: duplicated digest/heartbeat frames were
      rejected by the (t, seq) staleness guards, never double-folded.

    Writes ``fleet_digest.json`` (final snapshot + fallback counts)
    into the cell dir for CI artifact upload."""
    import threading

    from split_learning_tpu.config import from_dict
    from split_learning_tpu.runtime.aggnode import AggregatorNode
    from split_learning_tpu.runtime.log import Logger
    from split_learning_tpu.runtime.server import ProtocolServer
    from split_learning_tpu.runtime.simfleet import (
        SyntheticFleet, hetero_fleet,
    )

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    import sl_top

    cell_dir = pathlib.Path(tmp) / "fleet_scale"
    cell_dir.mkdir(parents=True, exist_ok=True)
    n1, heads = 24, 1
    cfg = from_dict({
        "model": "KWT", "dataset": "SPEECHCOMMANDS",
        "clients": [n1, heads], "global_rounds": 4,
        "synthetic_size": 48, "val_max_batches": 1,
        "val_batch_size": 16,
        "model_kwargs": {"embed_dim": 16, "num_heads": 2,
                         "mlp_dim": 32},
        "log_path": str(cell_dir),
        "learning": {"batch_size": 4},
        "topology": {"cut_layers": [2]},
        "checkpoint": {"save": False, "validate": False,
                       "directory": str(cell_dir / "ckpt")},
        "observability": {"heartbeat_interval": 0.2,
                          "liveness_timeout": 2.0,
                          "digest_interval": 0.3,
                          "watchlist_size": 8,
                          "max_client_series": 16,
                          "http_port": 0},
    })
    bus = InProcTransport()
    fc = FaultCounters()
    # dup + reorder on the roll-up path: duplicated heartbeats must be
    # rejected by the node monitors' staleness guard, duplicated
    # FleetDigest frames by the server's — never double-folded
    chaos = ChaosConfig(enabled=True, seed=seed, duplicate=0.2,
                        reorder=0.2,
                        queues=("digest_queue_*", "rpc_queue"))
    fleet_bus = ChaosTransport(bus, chaos, name="simfleet", faults=fc)
    # server FIRST: its startup queue purge would eat AggHello frames
    # published before it exists (the spawned-subprocess ordering)
    server = ProtocolServer(cfg, transport=bus,
                            logger=Logger.for_run(cfg, "server",
                                                  console=False),
                            client_timeout=120.0)
    # node publishes (FleetDigest frames included) ride the same
    # dup/reorder chaos: a duplicated digest must be rejected by the
    # server's (t, seq) guard, never double-folded
    nodes = [AggregatorNode(
        cfg, f"tel_node_{i}",
        transport=ChaosTransport(bus, chaos, name=f"tel_node_{i}",
                                 faults=fc),
        fold_transport=bus, digest_transport=bus)
        for i in range(2)]
    node_threads = [threading.Thread(target=n.run, daemon=True)
                    for n in nodes]
    for t in node_threads:
        t.start()
    specs = hetero_fleet(n1, heads, compute_speed=100.0, samples=32,
                         seed=seed)
    fleet = SyntheticFleet(fleet_bus, specs, heartbeat_interval=0.2,
                           time_scale=1.0).start()
    ctx = server.ctx
    state = {"route_before": {}, "killed": None}

    def killer():
        # let round 1 establish the routes, then stop one node that
        # actually serves clients (its digest thread dies with it)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            routed = dict(ctx._digest_route)
            if len(set(routed.values())) >= 2:
                break
            time.sleep(0.1)
        time.sleep(1.0)
        routed = dict(ctx._digest_route)
        state["route_before"] = routed
        victims = sorted(set(routed.values()))
        if victims:
            state["killed"] = victims[0]
            for n in nodes:
                if n.node_id == victims[0]:
                    n.stop()

    kt = threading.Thread(target=killer, daemon=True)
    kt.start()
    t0 = time.monotonic()
    try:
        res = server.serve()
    finally:
        fleet.stop()
        for n in nodes:
            n.stop()
    wall = time.monotonic() - t0
    snap = ctx.fleet.snapshot(series=False)
    faults = ctx.faults.snapshot()
    killed = state["killed"]
    expected_fallbacks = sum(
        1 for nid in state["route_before"].values() if nid == killed)
    out = {
        "wall_s": round(wall, 3), "killed_node": killed,
        "route_before": state["route_before"],
        "digest_fallbacks": faults.get("digest_fallbacks", 0),
        "expected_fallbacks": expected_fallbacks,
        "stale_digests": faults.get("stale_digests", 0),
        "stale_heartbeats": sum(
            n.faults.snapshot().get("stale_heartbeats", 0)
            for n in nodes),
        "fleet": snap,
    }
    (cell_dir / "fleet_digest.json").write_text(
        json.dumps(out, indent=2, default=str))
    table = sl_top.render_fleet(snap, color=False,
                                source="fleet-scale", top=10)
    (cell_dir / "fleet_digest_table.txt").write_text(table + "\n")
    if not res.history or not all(r.ok for r in res.history):
        return False, "round not ok"
    if killed is None:
        return False, "no digest routes established (roll-up inert)"
    if not state["route_before"]:
        return False, "no clients were routed through digest nodes"
    if faults.get("digest_fallbacks", 0) != expected_fallbacks:
        return False, (f"fallback count {faults.get('digest_fallbacks')}"
                       f" != {expected_fallbacks} clients routed to "
                       f"{killed}")
    phantom = [t for t in snap.get("transitions", ())
               if t.get("to") == "lost"
               and str(t.get("client", "")).startswith("sim_")]
    if phantom:
        return False, f"phantom lost transition(s): {phantom[:3]}"
    dig_nodes = (snap.get("digest") or {}).get("nodes") or {}
    if not dig_nodes:
        return False, "no FleetDigest ever folded at the server"
    if killed in dig_nodes:
        return False, f"dead node {killed} still in the digest fold"
    if out["stale_heartbeats"] <= 0 or out["stale_digests"] <= 0:
        return False, ("chaos injected nothing the guards rejected "
                       f"(beats={out['stale_heartbeats']} "
                       f"digests={out['stale_digests']})")
    return True, (f"{len(state['route_before'])} routed, "
                  f"{expected_fallbacks} fell back on {killed} death, "
                  f"{out['stale_heartbeats']} dup beats + "
                  f"{out['stale_digests']} dup digests rejected, "
                  f"0 phantom lost [{wall:.0f}s]")


def broker_shard_cell(tmp: str, seed: int = 29) -> tuple[bool, str]:
    """Sharded broker plane chaos cell (broker.shards): a 3-client
    deterministic round over TWO real broker shard processes with the
    reliable layer on and drop+dup+reorder injected on the data-plane
    queues — and the shard owning the forward data queue SIGKILLed
    mid-round (its queued frames die with it), then respawned on the
    same port.  PASSes iff

    * the round completes without a barrier stall (per-shard reconnect
      backoff + at-least-once redelivery absorb the restart; the
      surviving shard's traffic never stalls);
    * aggregation is BIT-IDENTICAL to a fault-free twin run over a
      fresh 2-shard plane (chaos off, no kill) — the exactness bar
      every chaos cell in this suite holds;
    * fault counts are exact where exactness is provable: zero
      ``lost``, zero ``gave_up`` (nothing may be silently dropped),
      with ``reconnects`` >= 1 (the kill was real) and
      ``redeliveries`` >= 1 (the at-least-once envelope repaired real
      loss), all recorded in the artifact;
    * the killed shard actually carried data-plane traffic before the
      kill (a kill on an idle shard proves nothing).

    Writes ``broker_shard.json`` (kill choreography, per-shard stats
    frames, fault counters) into the cell dir for CI artifact upload.
    """
    import threading as _threading

    import numpy as np

    sys.path.insert(0, "tests")
    from test_chaos import _round_cfg  # noqa: E402

    from split_learning_tpu.broker import spawn_shard
    from split_learning_tpu.runtime.bus import (
        broker_stats, collect_broker_stats, find_port_block, shard_for,
    )
    from split_learning_tpu.runtime.chaos import make_runtime_transport
    from split_learning_tpu.runtime.client import ProtocolClient
    from split_learning_tpu.runtime.server import ProtocolServer

    cell_dir = pathlib.Path(tmp) / "broker_shard"
    cell_dir.mkdir(parents=True, exist_ok=True)
    shards = 2

    def spawn_plane():
        base = find_port_block(shards)
        procs = {i: spawn_shard("127.0.0.1", base + i, shard_index=i,
                                python_only=True)
                 for i in range(shards)}
        deadline = time.monotonic() + 120
        for i in range(shards):
            while time.monotonic() < deadline:
                try:
                    broker_stats("127.0.0.1", base + i, timeout=1.0)
                    break
                except Exception:  # noqa: BLE001 — still booting
                    time.sleep(0.25)
        return base, procs

    def run_round(tag, base, chaos_on):
        over = dict(
            global_rounds=3,   # enough round time for a MID-round kill
            transport={"kind": "tcp", "host": "127.0.0.1",
                       "port": base, "reliable": True,
                       # reply_* upgraded too: a kill landing on the
                       # START fan-out must be repaired by redelivery,
                       # not by waiting out the ready barrier (the
                       # README failure-model table's documented
                       # upgrade for control frames)
                       "reliable_queues": [
                           "intermediate_queue*", "gradient_queue*",
                           "rpc_queue", "aggregate_queue*",
                           "reply_*"],
                       "async_send": False},
            broker={"shards": shards})
        if chaos_on:
            over["chaos"] = {"enabled": True, "seed": seed,
                             "drop": 0.05, "duplicate": 0.1,
                             "reorder": 0.1}
        cfg = _round_cfg(pathlib_tmp, cell_dir / tag, **over)
        fc = FaultCounters()
        server = ProtocolServer(
            cfg, transport=make_runtime_transport(cfg, "server",
                                                  faults=fc),
            client_timeout=300.0)
        threads = []
        for stage, count in enumerate(cfg.clients, start=1):
            for i in range(count):
                cid = f"client_{stage}_{i}"
                client = ProtocolClient(
                    cfg, cid, stage,
                    transport=make_runtime_transport(cfg, cid,
                                                     faults=fc))
                th = _threading.Thread(target=client.run, daemon=True)
                th.start()
                threads.append(th)
        t0 = time.monotonic()
        res = server.serve()
        wall = time.monotonic() - t0
        for th in threads:
            th.join(timeout=30)
        return res, fc, wall

    pathlib_tmp = pathlib.Path(tmp)
    # fault-free twin on its own fresh plane
    base_b, procs_b = spawn_plane()
    try:
        res_base, _, _ = run_round("twin", base_b, chaos_on=False)
    finally:
        for p in procs_b.values():
            p.kill()
    if not res_base.history or not res_base.history[0].ok:
        return False, "fault-free twin round not ok"

    # chaotic run: drop/dup/reorder + mid-round shard SIGKILL+respawn
    base, procs = spawn_plane()
    victim = shard_for("intermediate_queue_0_0", shards)
    kill_info: dict = {}

    def killer():
        deadline = time.monotonic() + 200
        # prefer killing while frames sit queued-but-unconsumed (their
        # loss is what redelivery must repair); parked-GET delivery
        # bypasses the store, so depth>=1 is intermittent — past the
        # soft deadline a busy victim is killed regardless (the drop
        # chaos keeps the redelivery assertion independent).  The
        # trigger threshold is LOW and the poll tight: a warm-cache
        # round is sub-second, and a kill that waits too long lands in
        # the teardown instead of the round.
        soft = time.monotonic() + 15
        while time.monotonic() < deadline:
            try:
                s = broker_stats("127.0.0.1", base + victim,
                                 timeout=1.0)
            except Exception:  # noqa: BLE001 — booting / mid-kill
                time.sleep(0.1)
                continue
            if s.get("published", 0) >= 4 and (
                    s.get("depth", 0) >= 1
                    or time.monotonic() >= soft
                    or s.get("published", 0) >= 12):
                procs[victim].kill()   # SIGKILL: queued frames die
                procs[victim].wait()
                kill_info["published_at_kill"] = s["published"]
                kill_info["depth_at_kill"] = s["depth"]
                kill_info["t_kill"] = time.monotonic()
                time.sleep(1.0)        # real downtime window
                procs[victim] = spawn_shard(
                    "127.0.0.1", base + victim, shard_index=victim,
                    python_only=True)
                kill_info["downtime_s"] = round(
                    time.monotonic() - kill_info["t_kill"], 3)
                return
            time.sleep(0.01)

    kt = _threading.Thread(target=killer, daemon=True)
    kt.start()
    try:
        res, fc, wall = run_round("chaos", base, chaos_on=True)
        kt.join(timeout=10)
        stats = collect_broker_stats("127.0.0.1", base, shards)
    finally:
        for p in procs.values():
            p.kill()
    snap = fc.snapshot()
    out = {
        "shards": shards, "base_port": base, "victim_shard": victim,
        "kill": {k: v for k, v in kill_info.items() if k != "t_kill"},
        "wall_s": round(wall, 3),
        "faults": snap,
        "shard_stats": stats,
    }
    (cell_dir / "broker_shard.json").write_text(
        json.dumps(out, indent=2, default=str))
    if not res.history or not all(r.ok for r in res.history):
        return False, "round not ok"
    if wall > 240:
        return False, f"round stalled ({wall:.0f}s)"
    if "published_at_kill" not in kill_info:
        return False, "victim shard never qualified for the kill " \
                      "(no mid-round traffic observed)"
    if snap.get("reconnects", 0) < 1:
        return False, f"no reconnects counted: {snap}"
    if snap.get("redeliveries", 0) < 1:
        return False, f"no redeliveries counted: {snap}"
    if snap.get("lost", 0) != 0:
        return False, f"phantom lost: {snap.get('lost')}"
    if snap.get("gave_up", 0) != 0:
        return False, f"redelivery gave up: {snap.get('gave_up')}"
    if [r.num_samples for r in res.history] \
            != [r.num_samples for r in res_base.history]:
        return False, "sample count drifted"
    import jax
    la = jax.tree_util.tree_leaves(res_base.params)
    lb = jax.tree_util.tree_leaves(res.params)
    if len(la) != len(lb) or any(
            np.asarray(a).tobytes() != np.asarray(b).tobytes()
            for a, b in zip(la, lb)):
        return False, "aggregation not bit-identical to the twin"
    return True, (f"shard {victim} killed+respawned "
                  f"(depth {kill_info.get('depth_at_kill')} at kill), "
                  f"{snap.get('reconnects')} reconnects "
                  f"{snap.get('redeliveries')} redeliveries "
                  f"{snap.get('dedup_hits', 0)} dedups, 0 lost "
                  f"[{wall:.0f}s]")


def mpmd_cell(tmp: str) -> tuple[bool, str]:
    """Cross-host MPMD stage-pipeline chaos cell (pipeline.remote): a
    3-stage deterministic round whose two later stages run on TWO
    server-spawned StageHost subprocesses over a real 2-shard TCP
    broker plane — and the stage host owning the stage-2 slot is
    SIGKILLed the moment the round attempt arms the stage watch
    (mid-round by construction).  PASSes iff

    * the round completes via the counted slot re-assignment (the
      dead host's slot moves to the survivor UNDER THE SAME client
      id, the attempt re-runs behind a bumped generation fence);
    * aggregation is BIT-IDENTICAL to a fault-free single-process
      twin (same client ids -> same per-client seeds -> same fold);
    * the fallback counts are exact: ``stage_host_deaths == 1`` and
      ``stage_reassigns == 1`` (one slot moved), and the survivor
      ends the round owning the victim's slot.

    Writes ``mpmd.json`` (assignment choreography, kill timing, fault
    counters) into the cell dir; the stage hosts' own log/metrics
    sidecars land under the cell's log dirs for CI artifact upload.
    """
    import threading as _threading

    import numpy as np

    sys.path.insert(0, "tests")
    from test_chaos import _round_cfg  # noqa: E402

    from split_learning_tpu.broker import spawn_shard
    from split_learning_tpu.runtime.bus import (
        broker_stats, find_port_block, ShardedTcpTransport,
    )
    from split_learning_tpu.runtime.client import ProtocolClient
    from split_learning_tpu.runtime.server import ProtocolServer

    cell_dir = pathlib.Path(tmp) / "mpmd"
    cell_dir.mkdir(parents=True, exist_ok=True)
    shards = 2

    def spawn_plane():
        base = find_port_block(shards)
        procs = [spawn_shard("127.0.0.1", base + i, shard_index=i,
                             python_only=True)
                 for i in range(shards)]
        deadline = time.monotonic() + 120
        for i in range(shards):
            while time.monotonic() < deadline:
                try:
                    broker_stats("127.0.0.1", base + i, timeout=1.0)
                    break
                except Exception:  # noqa: BLE001 — still booting
                    time.sleep(0.25)
        return base, procs

    def run_round(tag, base, n_hosts):
        """(result, ctx, wall, killed) — stage-1 feeders as threads;
        later stages in-process (n_hosts=0, the twin) or on spawned
        stage hosts, with the scripted mid-round SIGKILL when hosts
        are in play."""
        over = dict(
            clients=[2, 1, 1],
            topology={"cut_layers": [2, 4]},
            # dropout OFF: the middle stage relays activations on
            # receipt (arrival order), so with >= 3 stages the
            # bit-identity recipe additionally needs rng-insensitive
            # forwards — the 2-stage recipe's strict-SDA head never
            # exposed the middle relay's rng-to-batch assignment race
            model_kwargs={"dropout_rate": 0.0},
            transport={"kind": "tcp", "host": "127.0.0.1",
                       "port": base, "async_send": False},
            broker={"shards": shards})
        if n_hosts:
            over["pipeline"] = {"remote": True, "hosts": n_hosts,
                                "retries": 2}
        cfg = _round_cfg(pathlib.Path(tmp), cell_dir / tag, **over)
        server = ProtocolServer(
            cfg, transport=ShardedTcpTransport("127.0.0.1", base,
                                               shards),
            client_timeout=300.0)
        ctx = server.ctx
        threads = []
        stages = range(1, 2) if n_hosts else range(1, 4)
        for stage in stages:
            for i in range(cfg.clients[stage - 1]):
                cid = f"client_{stage}_{i}"
                client = ProtocolClient(
                    cfg, cid, stage,
                    transport=ShardedTcpTransport("127.0.0.1", base,
                                                  shards))
                th = _threading.Thread(target=client.run, daemon=True)
                th.start()
                threads.append(th)
        killed: list = []
        if n_hosts:
            def killer():
                deadline = time.monotonic() + 200
                while time.monotonic() < deadline:
                    # the stage watch arms exactly while a round
                    # attempt is in flight — a kill here is mid-round
                    # by construction, after the barrier committed to
                    # the standing assignment
                    if ctx._stage_watch:
                        hid = next(
                            (h for h in sorted(ctx._stage_assignments)
                             if ctx._stage_assignments[h]), None)
                        if hid:
                            slots = [
                                s["client_id"] for s in
                                ctx._stage_assignments[hid]]
                            proc = (ctx._stage_hosts.get(hid)
                                    or {}).get("proc")
                            if proc is not None:
                                proc.kill()   # SIGKILL
                                killed.append(
                                    {"host": hid, "slots": slots,
                                     "t": round(time.monotonic(), 3)})
                                return
                    time.sleep(0.005)
            kt = _threading.Thread(target=killer, daemon=True)
            kt.start()
        t0 = time.monotonic()
        res = server.serve()
        wall = time.monotonic() - t0
        for th in threads:
            th.join(timeout=30)
        return res, ctx, wall, (killed[0] if killed else None)

    # fault-free single-process twin on its own fresh plane
    base_b, procs_b = spawn_plane()
    try:
        res_base, _, _, _ = run_round("twin", base_b, 0)
    finally:
        for p in procs_b:
            p.kill()
    if not res_base.history or not res_base.history[0].ok:
        return False, "fault-free twin round not ok"

    # MPMD run: 2 stage hosts, scripted mid-round SIGKILL
    base, procs = spawn_plane()
    try:
        res, ctx2, wall, killed = run_round("chaos", base, 2)
    finally:
        for p in procs:
            p.kill()
    snap = ctx2.faults.snapshot()
    out = {
        "shards": shards, "base_port": base, "hosts": 2,
        "wall_s": round(wall, 3),
        "kill": killed,
        "final_assignments": {
            h: [s["client_id"] for s in sl]
            for h, sl in ctx2._stage_assignments.items()},
        "faults": snap,
    }
    (cell_dir / "mpmd.json").write_text(
        json.dumps(out, indent=2, default=str))
    if killed is None:
        return False, "no stage host qualified for the kill"
    if not res.history or not res.history[0].ok:
        return False, "round not ok after stage-host kill"
    if wall > 240:
        return False, f"round stalled ({wall:.0f}s)"
    if snap.get("stage_host_deaths") != 1:
        return False, f"deaths != 1: {snap}"
    if snap.get("stage_reassigns") != len(killed["slots"]):
        return False, f"reassigns != {len(killed['slots'])}: {snap}"
    moved = killed["slots"]
    survivor_slots = [
        cid for h, sl in ctx2._stage_assignments.items()
        if h != killed["host"] for cid in
        [s["client_id"] for s in sl]]
    if not all(cid in survivor_slots for cid in moved):
        return False, (f"moved slots {moved} not on a survivor: "
                       f"{out['final_assignments']}")
    if [r.num_samples for r in res.history] \
            != [r.num_samples for r in res_base.history]:
        return False, "sample count drifted"
    import jax
    la = jax.tree_util.tree_leaves(res_base.params)
    lb = jax.tree_util.tree_leaves(res.params)
    if len(la) != len(lb) or any(
            np.asarray(a).tobytes() != np.asarray(b).tobytes()
            for a, b in zip(la, lb)):
        return False, "aggregation not bit-identical to the twin"
    return True, (f"host {killed['host']} SIGKILLed mid-round, "
                  f"slot(s) {moved} re-assigned, fold bit-identical "
                  f"(1 death, {len(moved)} reassign) [{wall:.0f}s]")


def postmortem_cell(tmp: str) -> tuple[bool, str]:
    """Flight-recorder / postmortem chaos cell (runtime/blackbox.py +
    tools/sl_postmortem.py): the mpmd choreography — a 3-stage round
    with the later stages on 2 server-spawned StageHost subprocesses
    over a real 2-shard TCP broker plane — with the blackbox recorder
    armed in EVERY process, and the stage host owning a slot SIGKILLed
    mid-round.  SIGKILL is the oracle: the victim writes nothing, so
    the verdict can only come from the surviving fleet's dumps (the
    server's ring records the death with role + round, the fan-out
    snapshots the survivors, the broker sweep pulls the shard rings).
    PASSes iff

    * the round still completes via the counted slot re-assignment;
    * ``sl_postmortem`` over the cell's dumps names the KILLED host as
      the victim, role ``stage_host``, first abnormal event
      ``child_exit``/``participant_lost`` in the round that was in
      flight, reported by the server;
    * a fault-free twin of the same round, same recorder armed, yields
      the clean "no abnormal termination" verdict.

    Writes ``postmortem.json`` + ``postmortem_twin.json`` and the raw
    ``blackbox-*.json`` dumps into the cell dir for CI upload.
    """
    import threading as _threading

    sys.path.insert(0, "tests")
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    import sl_postmortem  # noqa: E402
    from test_chaos import _round_cfg  # noqa: E402

    from split_learning_tpu.broker import spawn_shard
    from split_learning_tpu.runtime import blackbox
    from split_learning_tpu.runtime.bus import (
        broker_stats, find_port_block, ShardedTcpTransport,
    )
    from split_learning_tpu.runtime.client import ProtocolClient
    from split_learning_tpu.runtime.server import ProtocolServer

    cell_dir = pathlib.Path(tmp) / "postmortem"
    cell_dir.mkdir(parents=True, exist_ok=True)
    shards = 2

    def spawn_plane():
        base = find_port_block(shards)
        procs = [spawn_shard("127.0.0.1", base + i, shard_index=i,
                             python_only=True)
                 for i in range(shards)]
        deadline = time.monotonic() + 120
        for i in range(shards):
            while time.monotonic() < deadline:
                try:
                    broker_stats("127.0.0.1", base + i, timeout=1.0)
                    break
                except Exception:  # noqa: BLE001 — still booting
                    time.sleep(0.25)
        return base, procs

    def run_round(tag, base, n_hosts):
        dump_dir = cell_dir / tag
        dump_dir.mkdir(parents=True, exist_ok=True)
        over = dict(
            clients=[2, 1, 1],
            topology={"cut_layers": [2, 4]},
            model_kwargs={"dropout_rate": 0.0},
            transport={"kind": "tcp", "host": "127.0.0.1",
                       "port": base, "async_send": False},
            broker={"shards": shards},
            observability={"blackbox": {
                "enabled": True, "dump_dir": str(dump_dir)}})
        if n_hosts:
            over["pipeline"] = {"remote": True, "hosts": n_hosts,
                                "retries": 2}
        cfg = _round_cfg(pathlib.Path(tmp), dump_dir / "logs", **over)
        # arm THIS process's recorder as the server role (spawned
        # stage hosts arm themselves from the same config in main())
        blackbox._reset_for_tests()
        blackbox.configure(cfg, "server", role="server")
        server = ProtocolServer(
            cfg, transport=ShardedTcpTransport("127.0.0.1", base,
                                               shards),
            client_timeout=300.0)
        ctx = server.ctx
        threads = []
        stages = range(1, 2) if n_hosts else range(1, 4)
        for stage in stages:
            for i in range(cfg.clients[stage - 1]):
                cid = f"client_{stage}_{i}"
                client = ProtocolClient(
                    cfg, cid, stage,
                    transport=ShardedTcpTransport("127.0.0.1", base,
                                                  shards))
                th = _threading.Thread(target=client.run, daemon=True)
                th.start()
                threads.append(th)
        killed: list = []
        if n_hosts:
            def killer():
                deadline = time.monotonic() + 200
                while time.monotonic() < deadline:
                    if ctx._stage_watch:
                        hid = next(
                            (h for h in sorted(ctx._stage_assignments)
                             if ctx._stage_assignments[h]), None)
                        if hid:
                            proc = (ctx._stage_hosts.get(hid)
                                    or {}).get("proc")
                            if proc is not None:
                                rnd = getattr(ctx, "_cur_round", 0)
                                proc.kill()   # SIGKILL: writes NOTHING
                                killed.append({"host": hid,
                                               "round": rnd})
                                return
                    time.sleep(0.005)
            kt = _threading.Thread(target=killer, daemon=True)
            kt.start()
        res = server.serve()
        for th in threads:
            th.join(timeout=30)
        # give the fire-and-forget broker blackbox sweep a beat to
        # land its shard dumps before the assembler scans the dir
        time.sleep(1.5)
        blackbox.dump("cell_end")
        return res, (killed[0] if killed else None), dump_dir

    # fault-free twin: same recorder armed, nothing dies -> the
    # assembler must come back CLEAN (the no-false-positive half)
    base_b, procs_b = spawn_plane()
    try:
        res_twin, _, twin_dir = run_round("twin", base_b, 0)
    finally:
        for p in procs_b:
            p.kill()
    if not res_twin.history or not res_twin.history[0].ok:
        return False, "fault-free twin round not ok"
    doc_twin = sl_postmortem.assemble(twin_dir)
    (cell_dir / "postmortem_twin.json").write_text(
        json.dumps(doc_twin, indent=2, default=str))
    if doc_twin["verdict"]["abnormal"]:
        return False, (f"twin verdict not clean: "
                       f"{doc_twin['verdict']}")

    # chaos run: 2 stage hosts, one SIGKILLed mid-round
    base, procs = spawn_plane()
    try:
        res, killed, chaos_dir = run_round("chaos", base, 2)
    finally:
        for p in procs:
            p.kill()
    if killed is None:
        return False, "no stage host qualified for the kill"
    if not res.history or not res.history[0].ok:
        return False, "round not ok after stage-host kill"
    doc = sl_postmortem.assemble(chaos_dir)
    (cell_dir / "postmortem.json").write_text(
        json.dumps(doc, indent=2, default=str))
    v = doc["verdict"]
    if not v["abnormal"]:
        return False, "kill not detected: verdict came back clean"
    if v["victim"] != killed["host"]:
        return False, (f"victim {v['victim']} != killed "
                       f"{killed['host']}")
    if v["role"] != "stage_host":
        return False, f"role {v['role']} != stage_host"
    if v["cause"]["kind"] not in ("child_exit", "participant_lost"):
        return False, f"cause {v['cause']['kind']} unexpected"
    if v["round"] != killed["round"]:
        return False, (f"round {v['round']} != in-flight "
                       f"{killed['round']}")
    if v["reported_by"] != "server":
        return False, f"reported by {v['reported_by']}, not server"
    if len(doc["dumps"]) < 2:
        return False, f"only {len(doc['dumps'])} dump(s) collected"
    print(sl_postmortem.render(doc))
    return True, (f"{killed['host']} SIGKILLed mid-round; verdict "
                  f"names it ({v['role']}, {v['cause']['kind']}, "
                  f"round {v['round']}) from {len(doc['dumps'])} "
                  f"survivor dumps; twin clean")


def kernels_cell(tmp: str, seed: int = 19) -> tuple[bool, str]:
    """Pallas kernel-plane chaos cell (kernels.*): a 3-client round
    with the FULL wire compression stack AND every fused kernel
    enabled (``kernels: {quantize, dequantize, stage_update}``, the
    sharded mesh update backend underneath), under drop + duplicate +
    delay injection with the reliable layer masking.  PASSes iff

    * the round completes without a barrier stall;
    * the aggregated params are BIT-IDENTICAL to a fault-free,
      KERNELS-OFF twin on the same codec stack: the single-pass Pallas
      kernels must be invisible to training semantics — same codes,
      same scales, same fused update, down to the last bit — even
      while chaos reorders the quantized wire around them (the live
      twin of the PK001 lowering gate and tests/test_kernels.py);
    * the kernel plan was actually installed for the run (the process
      plan the self-describing decode path follows).
    """
    import numpy as np

    sys.path.insert(0, "tests")
    from test_chaos import _chaos, _round_cfg, _run_cell  # noqa: E402

    from split_learning_tpu.ops import kernels as kplane

    kernels_on = {"quantize": True, "dequantize": True,
                  "stage_update": True}
    common = dict(transport={"codec": dict(CODEC_STACK)},
                  aggregation={"sharded": True})
    chaos = _chaos(seed=seed, drop=0.10, duplicate=0.10, delay=0.15,
                   delay_s=0.02)
    # fault-free kernels-off twin first (the plan default is all-off)
    cfg_b = _round_cfg(pathlib.Path(tmp),
                       pathlib.Path(tmp) / "kernels_base", **common)
    res_b = _run_cell(cfg_b)
    fc = FaultCounters()
    cfg_k = _round_cfg(pathlib.Path(tmp),
                       pathlib.Path(tmp) / "kernels_chaos",
                       kernels=kernels_on, **common)
    t0 = time.monotonic()
    res_k = _run_cell(cfg_k, chaos_cfg=chaos, reliable=True, faults=fc)
    wall = time.monotonic() - t0
    plan = kplane.plan()
    if not (plan.quantize and plan.dequantize and plan.stage_update):
        return False, f"kernel plan never installed: {plan}"
    if not (res_k.history and res_k.history[0].ok
            and res_b.history and res_b.history[0].ok):
        return False, "round not ok"
    if wall > 240:
        return False, f"barrier stall ({wall:.0f}s)"
    if res_k.history[0].num_samples != res_b.history[0].num_samples:
        return False, "sample count drifted"
    import jax
    la = jax.tree_util.tree_leaves(res_b.params)
    lb = jax.tree_util.tree_leaves(res_k.params)
    if len(la) != len(lb) or any(
            np.asarray(a).tobytes() != np.asarray(b).tobytes()
            for a, b in zip(la, lb)):
        return False, "kernels+chaos fold not bit-identical"
    snap = fc.snapshot()
    injected = sum(snap.get(k, 0) for k in ("drops", "duplicates",
                                            "delays"))
    if not injected:
        return False, "chaos injected nothing"
    return True, (f"bit-identical with all kernels on "
                  f"({injected} faults injected, {wall:.0f}s)")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Sweep fault probabilities over seeds; print a "
                    "pass/fail matrix.")
    ap.add_argument("--seeds", type=int, default=5)
    ap.add_argument("--seed-base", type=int, default=100)
    ap.add_argument("--messages", type=int, default=150)
    ap.add_argument("--probs", default="0.05,0.2,0.4",
                    help="comma-separated probabilities")
    ap.add_argument("--only", default=None,
                    help="restrict to one cell, e.g. drop:0.4")
    ap.add_argument("--full", action="store_true",
                    help="full tiny training round per cell (slow)")
    ap.add_argument("--codec", action="store_true",
                    help="with --full: run cells with the wire "
                         "compression stack (int8 activations + top-k "
                         "EF gradients + delta Updates) — proves the "
                         "codec state deterministic under faults")
    ap.add_argument("--artifacts-dir", default=None,
                    help="with --full: run cells under this directory "
                         "so spans-*.jsonl / metrics.jsonl / "
                         "trace.json survive for CI artifact upload")
    ap.add_argument("--fleet", action="store_true",
                    help="run ONLY the live-telemetry fleet cell: a "
                         "3-client round with one rpc-delayed client; "
                         "asserts the FleetMonitor flags it, /metrics "
                         "lints mid-round, and sl_top renders the "
                         "/fleet snapshot (writes fleet.json)")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="run ONLY the async-mode cell: a 3-client "
                         "aux-loss round under delay+drop+dup must "
                         "complete with no barrier stall, fold "
                         "deterministically (twin-seed bit-identity), "
                         "and count stale rejections exactly")
    ap.add_argument("--tree-remote", dest="tree_remote",
                    action="store_true",
                    help="run ONLY the multi-process aggregator-tree "
                         "cell: 3 aggregator subprocesses over a real "
                         "TCP broker serve a 3-client round's tree; "
                         "one is SIGKILLed mid-round and the round "
                         "must complete via the counted direct-to-"
                         "root fallback drain")
    ap.add_argument("--sched", dest="sched_mode",
                    action="store_true",
                    help="run ONLY the closed-loop scheduler cell: a "
                         "heterogeneous 6-client synthetic round with "
                         "one compute- and one wire-straggler under "
                         "rpc dup+reorder chaos; both must be "
                         "attributed correctly and demoted with their "
                         "knobs retuned, the round must complete, and "
                         "the kind=sched decisions journal must "
                         "validate (writes sched.json)")
    ap.add_argument("--fleet-scale", dest="fleet_scale",
                    action="store_true",
                    help="run ONLY the hierarchical digest roll-up "
                         "cell: 24 synthetic clients' heartbeats roll "
                         "up through 2 aggregator-node digest workers "
                         "under dup+reorder chaos; one node is killed "
                         "and its clients must fall back to direct "
                         "heartbeats, counted, with no phantom lost "
                         "flap (writes fleet_digest.json)")
    ap.add_argument("--broker-shard", dest="broker_shard",
                    action="store_true",
                    help="run ONLY the sharded broker plane cell: a "
                         "3-client round over 2 real broker shard "
                         "processes with drop+dup+reorder chaos; the "
                         "data-plane shard is SIGKILLed mid-round and "
                         "respawned, and the round must complete "
                         "bit-identical to a fault-free twin with "
                         "exact fault counts (reconnects/redeliveries "
                         "counted, zero lost) — writes "
                         "broker_shard.json")
    ap.add_argument("--mpmd", dest="mpmd_mode", action="store_true",
                    help="run ONLY the cross-host MPMD stage-pipeline "
                         "cell: a 3-stage round with the later stages "
                         "on 2 spawned StageHost subprocesses over a "
                         "real 2-shard TCP broker; one stage host is "
                         "SIGKILLed mid-round and the round must "
                         "complete via the counted slot re-assignment, "
                         "bit-identical to a fault-free single-process "
                         "twin (writes mpmd.json)")
    ap.add_argument("--postmortem", dest="postmortem_mode",
                    action="store_true",
                    help="run ONLY the flight-recorder cell: the mpmd "
                         "choreography with the blackbox recorder "
                         "armed fleet-wide; a stage host is SIGKILLed "
                         "mid-round and sl_postmortem over the "
                         "surviving dumps must name the killed host, "
                         "its role and the in-flight round, while a "
                         "fault-free twin's report comes back clean "
                         "(writes postmortem.json + blackbox-*.json)")
    ap.add_argument("--kernels", dest="kernels_mode",
                    action="store_true",
                    help="run ONLY the Pallas kernel-plane cell: a "
                         "3-client round with the full codec stack and "
                         "every fused kernel enabled (quantize/"
                         "dequantize/stage_update over the sharded "
                         "mesh backend) under drop+dup+delay must stay "
                         "bit-identical to a fault-free kernels-off "
                         "twin on the same stack")
    ap.add_argument("--overlap", dest="overlap_mode",
                    action="store_true",
                    help="run ONLY the sync-overlap cell: a 3-client "
                         "sync round with learning.sync-overlap on "
                         "under drop+dup+delay must stay bit-identical "
                         "to a fault-free overlap-off twin with no "
                         "barrier stall")
    args = ap.parse_args(argv)

    if args.tree_remote:
        if args.artifacts_dir:
            tmp = args.artifacts_dir
            pathlib.Path(tmp).mkdir(parents=True, exist_ok=True)
        else:
            import tempfile
            tmp = tempfile.mkdtemp(prefix="chaos_tree_remote_")
        t0 = time.monotonic()
        ok, note = tree_remote_cell(tmp)
        dt = time.monotonic() - t0
        print(f"tree-remote cell: {'PASS' if ok else 'FAIL'} ({note}) "
              f"[{dt:.1f}s, artifacts in {tmp}]")
        return 0 if ok else 1

    if args.mpmd_mode:
        if args.artifacts_dir:
            tmp = args.artifacts_dir
            pathlib.Path(tmp).mkdir(parents=True, exist_ok=True)
        else:
            import tempfile
            tmp = tempfile.mkdtemp(prefix="chaos_mpmd_")
        t0 = time.monotonic()
        ok, note = mpmd_cell(tmp)
        dt = time.monotonic() - t0
        print(f"mpmd cell: {'PASS' if ok else 'FAIL'} ({note}) "
              f"[{dt:.1f}s, artifacts in {tmp}]")
        return 0 if ok else 1

    if args.postmortem_mode:
        if args.artifacts_dir:
            tmp = args.artifacts_dir
            pathlib.Path(tmp).mkdir(parents=True, exist_ok=True)
        else:
            import tempfile
            tmp = tempfile.mkdtemp(prefix="chaos_postmortem_")
        t0 = time.monotonic()
        ok, note = postmortem_cell(tmp)
        dt = time.monotonic() - t0
        print(f"postmortem cell: {'PASS' if ok else 'FAIL'} ({note}) "
              f"[{dt:.1f}s, artifacts in {tmp}]")
        return 0 if ok else 1

    if args.broker_shard:
        if args.artifacts_dir:
            tmp = args.artifacts_dir
            pathlib.Path(tmp).mkdir(parents=True, exist_ok=True)
        else:
            import tempfile
            tmp = tempfile.mkdtemp(prefix="chaos_broker_shard_")
        t0 = time.monotonic()
        ok, note = broker_shard_cell(tmp)
        dt = time.monotonic() - t0
        print(f"broker-shard cell: {'PASS' if ok else 'FAIL'} ({note}) "
              f"[{dt:.1f}s, artifacts in {tmp}]")
        return 0 if ok else 1

    if args.fleet_scale:
        if args.artifacts_dir:
            tmp = args.artifacts_dir
            pathlib.Path(tmp).mkdir(parents=True, exist_ok=True)
        else:
            import tempfile
            tmp = tempfile.mkdtemp(prefix="chaos_fleet_scale_")
        t0 = time.monotonic()
        ok, note = fleet_scale_cell(tmp)
        dt = time.monotonic() - t0
        print(f"fleet-scale cell: {'PASS' if ok else 'FAIL'} ({note}) "
              f"[{dt:.1f}s, artifacts in {tmp}]")
        return 0 if ok else 1

    if args.sched_mode:
        if args.artifacts_dir:
            tmp = args.artifacts_dir
            pathlib.Path(tmp).mkdir(parents=True, exist_ok=True)
        else:
            import tempfile
            tmp = tempfile.mkdtemp(prefix="chaos_sched_")
        t0 = time.monotonic()
        ok, note = sched_cell(tmp)
        dt = time.monotonic() - t0
        print(f"sched cell: {'PASS' if ok else 'FAIL'} ({note}) "
              f"[{dt:.1f}s, artifacts in {tmp}]")
        return 0 if ok else 1

    if args.kernels_mode:
        if args.artifacts_dir:
            tmp = args.artifacts_dir
            pathlib.Path(tmp).mkdir(parents=True, exist_ok=True)
        else:
            import tempfile
            tmp = tempfile.mkdtemp(prefix="chaos_kernels_")
        t0 = time.monotonic()
        ok, note = kernels_cell(tmp)
        dt = time.monotonic() - t0
        print(f"kernels cell: {'PASS' if ok else 'FAIL'} ({note}) "
              f"[{dt:.1f}s, artifacts in {tmp}]")
        return 0 if ok else 1

    if args.overlap_mode:
        if args.artifacts_dir:
            tmp = args.artifacts_dir
            pathlib.Path(tmp).mkdir(parents=True, exist_ok=True)
        else:
            import tempfile
            tmp = tempfile.mkdtemp(prefix="chaos_overlap_")
        t0 = time.monotonic()
        ok, note = overlap_cell(tmp)
        dt = time.monotonic() - t0
        print(f"overlap cell: {'PASS' if ok else 'FAIL'} ({note}) "
              f"[{dt:.1f}s, artifacts in {tmp}]")
        return 0 if ok else 1

    if args.async_mode:
        if args.artifacts_dir:
            tmp = args.artifacts_dir
            pathlib.Path(tmp).mkdir(parents=True, exist_ok=True)
        else:
            import tempfile
            tmp = tempfile.mkdtemp(prefix="chaos_async_")
        t0 = time.monotonic()
        ok, note = async_cell(tmp)
        dt = time.monotonic() - t0
        print(f"async cell: {'PASS' if ok else 'FAIL'} ({note}) "
              f"[{dt:.1f}s, artifacts in {tmp}]")
        return 0 if ok else 1

    if args.fleet:
        if args.artifacts_dir:
            tmp = args.artifacts_dir
            pathlib.Path(tmp).mkdir(parents=True, exist_ok=True)
        else:
            import tempfile
            tmp = tempfile.mkdtemp(prefix="chaos_fleet_")
        t0 = time.monotonic()
        ok, note = fleet_cell(tmp)
        dt = time.monotonic() - t0
        print(f"fleet cell: {'PASS' if ok else 'FAIL'} ({note}) "
              f"[{dt:.1f}s, artifacts in {tmp}]")
        return 0 if ok else 1

    faults = ["drop", "duplicate", "reorder", "corrupt", "delay",
              "mixed"]
    probs = [float(p) for p in args.probs.split(",")]
    cells = [(f, p) for f in faults for p in probs]
    if args.only:
        f, _, p = args.only.partition(":")
        cells = [(f, float(p))]

    tmp = None
    if args.full:
        if args.artifacts_dir:
            tmp = args.artifacts_dir
            pathlib.Path(tmp).mkdir(parents=True, exist_ok=True)
        else:
            import tempfile
            tmp = tempfile.mkdtemp(prefix="chaos_sweep_")

    width = max(len(f) for f, _ in cells) + 6
    print(f"{'cell':<{width}} " + " ".join(
        f"seed{args.seed_base + i:<4}" for i in range(args.seeds)))
    failures = 0
    for fault, prob in cells:
        row = []
        for i in range(args.seeds):
            seed = args.seed_base + i
            t0 = time.monotonic()
            if args.full:
                ok, note = full_round_cell(fault, prob, seed, tmp,
                                           codec=args.codec)
            else:
                ok, note = transport_cell(fault, prob, seed,
                                          args.messages)
            dt = time.monotonic() - t0
            row.append("PASS" if ok else f"FAIL({note})")
            if not ok:
                failures += 1
                print(f"  FAIL {fault}:{prob} seed={seed} -> {note} "
                      f"({dt:.1f}s)", file=sys.stderr)
        print(f"{fault + ':' + str(prob):<{width}} " + " ".join(
            f"{r:<8}" for r in row))
    print(f"\n{len(cells) * args.seeds - failures}/"
          f"{len(cells) * args.seeds} cells passed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
