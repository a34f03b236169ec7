"""Standalone aggregator-node process (``aggregation.remote``).

PR 9's aggregator tree ran its L1 folds as server THREADS — the fold
fan-in was constant but every partial still folded inside one process,
and the tree died with the server.  This module promotes the tree's
interior nodes to **standalone processes** connected over the existing
TCP broker (``tools/sl_aggregator.py`` /
``python -m split_learning_tpu.aggregator``):

* the node builds its transport with
  :func:`~split_learning_tpu.runtime.chaos.make_runtime_transport`, so
  the Reliable/Chaos/Async stacks compose exactly as they do for a
  client — a chaos sweep faults the aggregate plane of a remote tree
  the same way it faults a thread-mode one;
* it announces itself with an
  :class:`~split_learning_tpu.runtime.protocol.AggHello` on the rpc
  queue and then heartbeats like any client
  (:class:`~split_learning_tpu.runtime.telemetry.TelemetryEmitter`
  with ``kind="agg_node"``) — liveness is the HEARTBEAT/FleetMonitor
  plane, and a node the monitor marks ``lost`` (or whose spawned
  process exits) triggers the server's counted direct-to-root
  fallback drain, not a barrier stall;
* per train_cluster invocation the server sends one
  :class:`~split_learning_tpu.runtime.protocol.AggAssign` naming the
  node's groups (any level — an L2 group folds its children's
  PartialAggregates).  The node's fold worker drives one
  :class:`~split_learning_tpu.runtime.aggregate.L1Aggregator` PER
  GROUP — the same object the thread mode runs, minus the thread —
  multiplexed over a single dedicated broker connection (zero-timeout
  gets round-robin across the group queues), so a node serving
  hundreds of groups costs two connections, not hundreds;
* flushes cascade level-ascending on
  :class:`~split_learning_tpu.runtime.protocol.AggFlush` (or the
  assignment deadline): level-1 groups flush first so interior groups
  can still fold the children's partials before their own forced
  flush;
* per assignment the node emits one ``kind=agg_node`` metrics record
  (folded count, ingress/egress bytes, fold wall) and mirrors the
  numbers into gauges that ride its heartbeats — ``/fleet`` and
  ``sl_top`` can name a slow aggregator the way they name a slow
  client.
"""

from __future__ import annotations

import argparse
import threading
import time

from split_learning_tpu.config import Config, from_yaml
from split_learning_tpu.runtime import aggregate as agg_plane
from split_learning_tpu.runtime import blackbox
from split_learning_tpu.runtime.log import Logger
from split_learning_tpu.runtime.protocol import (
    AggAssign, AggFlush, AggHello, BlackboxDump, FleetDigest,
    FrameAssembler, Heartbeat, Stop, digest_queue, encode, reply_queue,
    RPC_QUEUE,
)

#: seconds an interior group keeps polling for its children's partials
#: after the flush cascade released the level below it
FLUSH_GRACE_S = 2.0


class DigestWorker(threading.Thread):
    """Hierarchical heartbeat roll-up (``observability.digest-interval``):
    drains the node's :func:`digest_queue` — where the server routed
    its assigned clients' HEARTBEAT frames via START ``extra.digest``
    — into a node-local :class:`~split_learning_tpu.runtime.telemetry
    .FleetMonitor` (the SAME state machine the server runs, so the
    rolled-up per-state counts are exact vs a flat oracle), and
    publishes one :class:`FleetDigest` frame per interval on the rpc
    queue.  Root ingest is thereby O(nodes + top-K), not O(clients).

    Owns its transport (``digest_bus``): a blocking control-loop get
    and a zero-timeout fold sweep must never share a TCP socket with
    this drain (the same ownership rule as the fold worker's)."""

    #: heartbeat frames drained per sweep before the publish check
    DRAIN_BATCH = 512

    def __init__(self, node: "AggregatorNode", interval: float):
        super().__init__(daemon=True, name=f"{node.node_id}-digest")
        from split_learning_tpu.runtime.telemetry import FleetMonitor
        self.node = node
        self.interval = max(float(interval), 1e-3)
        self.queue = digest_queue(node.node_id)
        obs = node.cfg.observability
        # the node-local monitor mirrors the server's thresholds so
        # digest states are exactly what a flat FleetMonitor fed the
        # same heartbeats would report
        self.monitor = FleetMonitor(
            interval=obs.heartbeat_interval,
            liveness_timeout=obs.liveness_timeout,
            log=None, faults=node.faults)
        self._asm = FrameAssembler(faults=node.faults)
        # NOT named _stop: threading.Thread's join() path calls an
        # internal _stop() on 3.10 — shadowing it with an Event breaks
        # every join of this thread
        self._halt = threading.Event()
        self._seq = 0

    def stop(self) -> None:
        self._halt.set()

    def run(self) -> None:
        next_pub = time.monotonic() + self.interval
        while not self._halt.is_set():
            drained = self._drain()
            self.monitor.note_pump()
            if time.monotonic() >= next_pub:
                next_pub += self.interval
                try:
                    self.publish_digest()
                except Exception as e:  # noqa: BLE001 — transport
                    # gone: the server's node-death fallback re-points
                    # the clients; this thread just winds down
                    self.node.log.warning(f"digest publish failed: {e}")
                    return
            if not drained:
                self._halt.wait(min(self.interval / 4, 0.05))

    def _drain(self) -> bool:
        drained = False
        for _ in range(self.DRAIN_BATCH):
            raw = self.node.digest_bus.get(self.queue, timeout=0.0)
            if raw is None:
                break
            drained = True
            try:
                msg = self._asm.feed(raw)
            except Exception:  # noqa: BLE001 — one corrupt heartbeat
                self.node.faults.inc("corrupt_rejected")
                continue
            if isinstance(msg, Heartbeat):
                self.monitor.note_heartbeat(msg.client_id,
                                            msg.telemetry)
        return drained

    def publish_digest(self) -> None:
        """Advance the local state machine and ship one digest (also
        called once at teardown so the last interval isn't lost)."""
        t0 = time.time()
        self.monitor.advance()
        self._seq += 1
        digest = self.monitor.build_digest(self.node.node_id,
                                           self._seq)
        self.node.bus.publish(RPC_QUEUE, encode(FleetDigest(
            node_id=self.node.node_id, digest=digest)))
        self.node.gauges.set("fleet_digest_clients",
                             digest.get("clients", 0))
        self.node.tracer.record(
            "agg.digest", t0, time.time(), always=True, seq=self._seq,
            clients=digest.get("clients", 0))


class AssignmentWorker(threading.Thread):
    """One invocation's fold worker: drives the assignment's
    L1Aggregator objects (any level) over a dedicated transport,
    publishing each group's partial the moment it completes."""

    def __init__(self, node: "AggregatorNode", assign: AggAssign):
        super().__init__(daemon=True,
                         name=f"{node.node_id}-fold-g{assign.gen}")
        self.node = node
        self.gen = assign.gen
        self.round_idx = assign.round_idx
        self.flush = threading.Event()
        spec = None
        if assign.codec:
            from split_learning_tpu.runtime.codec.specs import parse_spec
            spec = parse_spec(assign.codec)
        bases = assign.bases or {}
        deadline = time.monotonic() + float(assign.deadline_s)
        self.workers: list[agg_plane.L1Aggregator] = []
        for d in assign.groups or []:
            g = agg_plane.AggGroup.from_dict(d)
            out_q = (RPC_QUEUE if g.parent is None
                     else agg_plane.aggregate_queue(assign.cluster,
                                                    g.parent))
            self.workers.append(agg_plane.L1Aggregator(
                node.fold_bus, cluster=assign.cluster, group=g,
                members=g.members, gen=assign.gen, deadline=deadline,
                log=node.log, faults=node.faults,
                chunk_bytes=assign.chunk_bytes, out_queue=out_q,
                codec=spec, base=bases.get(g.stage),
                base_gen=assign.gen if spec is not None
                and spec.kind == "delta" else None))

    def run(self) -> None:
        t0 = time.perf_counter()
        tw0 = time.time()
        try:
            self._fold_loop()
            tw1 = time.time()
            self.node.tracer.record(
                "agg.fold", tw0, tw1, always=True, gen=self.gen,
                round=self.round_idx, groups=len(self.workers))
            self._flush_cascade()
            self.node.tracer.record(
                "agg.flush", tw1, time.time(), always=True,
                gen=self.gen, round=self.round_idx,
                flushed=sum(1 for w in self.workers if w.flushed))
        except Exception as e:  # noqa: BLE001 — a dead transport mid-
            # round means the node is effectively dead for this gen;
            # the server's fallback drain recovers the groups
            self.node.log.warning(
                f"fold worker gen={self.gen} died: {e}")
            return
        self._report(time.perf_counter() - t0)

    def _pending(self) -> list:
        return [w for w in self.workers if not w.flushed]

    def _fold_loop(self) -> None:
        bus = self.node.fold_bus
        while not self.flush.is_set():
            live = self._pending()
            if not live:
                return
            if all(time.monotonic() >= w.deadline for w in live):
                return
            progress = False
            for w in live:
                raw = bus.get(w.queue, timeout=0.0)
                if raw is None:
                    continue
                progress = True
                w.feed_raw(raw)
                if w.complete:
                    w.publish()
            if not progress:
                self.flush.wait(0.004)

    def _flush_cascade(self) -> None:
        """Forced flush, level-ascending: flushing an interior group
        before its children have published would silently drop whole
        subtrees, so each level flushes and the next gets a bounded
        grace to drain the partials that flush produced."""
        bus = self.node.fold_bus
        levels = sorted({w.group.level for w in self._pending()})
        for i, lv in enumerate(levels):
            for w in self._pending():
                if w.group.level == lv:
                    w.publish()
            rest = [w for w in self._pending() if w.group.level > lv]
            if not rest:
                return
            grace = time.monotonic() + FLUSH_GRACE_S
            while time.monotonic() < grace:
                progress = False
                for w in list(rest):
                    if w.flushed:
                        continue
                    raw = bus.get(w.queue, timeout=0.0)
                    if raw is None:
                        continue
                    progress = True
                    w.feed_raw(raw)
                    if w.complete:
                        w.publish()
                if all(w.flushed for w in rest):
                    break
                if not progress:
                    time.sleep(0.004)
        for w in self._pending():
            w.publish()

    def _report(self, fold_s: float) -> None:
        node = self.node
        folded = sum(len(w.seen) for w in self.workers)
        ingress = sum(w.ingress_bytes for w in self.workers)
        egress = sum(w.egress_bytes for w in self.workers)
        node.gauges.set("agg_node_folded", folded)
        node.gauges.set("agg_node_ingress_bytes", ingress)
        node.gauges.set("agg_node_egress_bytes", egress)
        node.gauges.set("agg_node_fold_s", round(fold_s, 6))
        node.gauges.set("agg_node_groups", len(self.workers))
        node.log.metric(
            kind="agg_node", node=node.node_id, gen=self.gen,
            round_idx=self.round_idx, groups=len(self.workers),
            folded=folded, ingress_bytes=ingress, egress_bytes=egress,
            fold_s=round(fold_s, 6),
            incomplete=sum(1 for w in self.workers if not w.complete))
        # round boundary for this node: make the gen's spans durable
        # now, not at whatever flush_every batch boundary comes next
        node.tracer.flush()


class AggregatorNode:
    """The node process: adoption hello, heartbeats, assignment loop.

    ``transport``/``fold_transport`` default to fresh
    ``make_runtime_transport`` stacks (two broker connections: the
    control loop's blocking get must not starve the fold worker's
    zero-timeout sweeps); tests pass a shared in-proc bus for both.
    """

    def __init__(self, cfg: Config, node_id: str, transport=None,
                 fold_transport=None, digest_transport=None,
                 logger: Logger | None = None):
        self.cfg = cfg
        self.node_id = node_id
        from split_learning_tpu.runtime.trace import FaultCounters
        self.faults = FaultCounters()
        obs = getattr(cfg, "observability", None)
        digest_interval = (obs.digest_interval
                           if obs is not None else 0.0)
        # close-at-teardown only covers stacks this node CREATED: an
        # injected transport (tests, in-proc cells) is shared — the
        # same ownership rule as L1Aggregator's owns_bus
        self._owns_buses = transport is None
        if transport is None:
            from split_learning_tpu.runtime.chaos import (
                make_runtime_transport,
            )
            transport = make_runtime_transport(cfg, node_id,
                                               faults=self.faults)
            if fold_transport is None:
                fold_transport = make_runtime_transport(
                    cfg, f"{node_id}.fold", faults=self.faults)
            if digest_transport is None and digest_interval > 0:
                digest_transport = make_runtime_transport(
                    cfg, f"{node_id}.digest", faults=self.faults)
        self.bus = transport
        self.fold_bus = (fold_transport if fold_transport is not None
                         else transport)
        self.digest_bus = (digest_transport
                           if digest_transport is not None
                           else transport)
        self.log = logger or Logger.for_run(cfg, node_id, console=False)
        # span-plane membership: the node's fold/flush/digest phases
        # journal into spans-{node_id}.jsonl so sl_trace merges the
        # aggregator tier into the fleet timeline (the trace id is
        # adopted per-assignment from AggAssign-carrying runs' config;
        # absent that, the journal still merges by wall clock)
        from split_learning_tpu.runtime.spans import make_tracer
        self.tracer = make_tracer(cfg, node_id)
        self._asm = FrameAssembler(faults=self.faults)
        self._stop = threading.Event()
        from split_learning_tpu.runtime.telemetry import (
            GaugeSet, TelemetryEmitter,
        )
        self.gauges = GaugeSet()
        interval = obs.heartbeat_interval if obs is not None else 0.0
        self.emitter = TelemetryEmitter(
            node_id, self._beat, interval=interval, faults=self.faults,
            gauges=self.gauges, kind="agg_node")
        # hierarchical heartbeat roll-up: one FleetDigest per
        # observability.digest-interval over the clients whose
        # heartbeats the server routed to this node's digest queue
        self.digester = (DigestWorker(self, digest_interval)
                         if digest_interval > 0 else None)

    def _beat(self, snapshot: dict) -> None:
        self.bus.publish(RPC_QUEUE, encode(Heartbeat(
            client_id=self.node_id, telemetry=snapshot)))

    def stop(self) -> None:
        self._stop.set()

    def run(self) -> None:
        self.bus.publish(RPC_QUEUE, encode(AggHello(
            node_id=self.node_id)))
        self.log.sent("AGGHELLO")
        self.emitter.start()
        if self.digester is not None:
            self.digester.start()
        worker: AssignmentWorker | None = None
        try:
            while not self._stop.is_set():
                raw = self.bus.get(reply_queue(self.node_id),
                                   timeout=0.25)
                if raw is None:
                    continue
                try:
                    msg = self._asm.feed(raw)
                except Exception as e:  # noqa: BLE001 — one corrupt
                    # frame costs one message, not the node
                    self.faults.inc("corrupt_rejected")
                    self.log.warning(f"dropping undecodable frame: {e}")
                    continue
                if msg is None:
                    continue
                if isinstance(msg, Stop):
                    self.log.received(f"STOP ({msg.reason})")
                    break
                if isinstance(msg, BlackboxDump):
                    # server-initiated fleet snapshot: flush this
                    # node's flight recorder alongside everyone else's
                    blackbox.record("dump_request", reason=msg.reason)
                    blackbox.dump(msg.reason or "fleet_snapshot")
                    continue
                if isinstance(msg, AggAssign):
                    self.log.received(
                        f"AGGASSIGN gen={msg.gen} "
                        f"groups={len(msg.groups or [])}")
                    if worker is not None and worker.is_alive():
                        # a new assignment supersedes the old round:
                        # flush it out rather than strand its groups.
                        # The old worker MUST be gone before the new
                        # one starts — both would otherwise drive the
                        # same fold transport from two threads (the
                        # exact concurrent-socket use thread-mode L1s
                        # avoid by owning their own stacks).  The
                        # cascade is bounded (FLUSH_GRACE_S per level
                        # + publish time), so 60 s only fails on a
                        # stuck transport — then folding the new gen
                        # is impossible anyway: drop the assignment
                        # and let the server's fallback drain recover.
                        worker.flush.set()
                        worker.join(timeout=60.0)
                        if worker.is_alive():
                            self.log.warning(
                                f"fold worker gen={worker.gen} still "
                                f"running; dropping assignment "
                                f"gen={msg.gen} (server fallback "
                                "will drain the groups)")
                            continue
                    worker = AssignmentWorker(self, msg)
                    worker.start()
                elif isinstance(msg, AggFlush):
                    self.log.received(f"AGGFLUSH gen={msg.gen}")
                    if worker is not None and worker.gen == msg.gen:
                        worker.flush.set()
        finally:
            if worker is not None and worker.is_alive():
                worker.flush.set()
                worker.join(timeout=10.0)
            if self.digester is not None:
                self.digester.stop()
                self.digester.join(timeout=5.0)
                try:
                    # last interval's heartbeats must not vanish with
                    # the node: one final digest before teardown
                    self.digester.publish_digest()
                except Exception:  # noqa: BLE001 — transport already
                    pass           # gone; the server's fallback covers
            self.emitter.stop()
            self.tracer.close()
            if self._owns_buses:
                for bus in {
                        id(self.bus): self.bus,
                        id(self.fold_bus): self.fold_bus,
                        id(self.digest_bus): self.digest_bus}.values():
                    try:
                        bus.close()
                    except Exception:  # noqa: BLE001 — teardown
                        pass           # best-effort

            self.log.close()


def write_node_config(cfg: Config, path) -> None:
    """Persist a config for spawned aggregator subprocesses.  JSON is
    a YAML subset, so ``from_yaml`` reads it back; tuples become lists
    (``_freeze`` re-tuples them on load)."""
    import json

    from split_learning_tpu.config import to_dict
    with open(path, "w") as f:
        json.dump(to_dict(cfg), f, default=list)


def spawn_node(config_path, node_id: str):
    """Spawn one aggregator subprocess (tcp transport).  The node is
    host-only, so its JAX_PLATFORMS is cpu whatever the parent's is (a
    child that inherited the parent's accelerator platform would
    contend for a chip the parent holds); it inherits stdio so its
    tracebacks surface in CI logs."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, "-m", "split_learning_tpu.aggregator",
         "--config", str(config_path), "--node-id", node_id], env=env)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Standalone split-learning aggregator node "
                    "(aggregation.remote).")
    ap.add_argument("--config", default="config.yaml")
    ap.add_argument("--node-id", default="aggregator_node_0")
    args = ap.parse_args(argv)
    cfg = from_yaml(args.config)
    blackbox.install(cfg, args.node_id, role="agg_node")
    node = AggregatorNode(cfg, args.node_id)
    node.run()


if __name__ == "__main__":
    main()
