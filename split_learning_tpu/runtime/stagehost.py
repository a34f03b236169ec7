"""Standalone MPMD stage-host process (``pipeline.remote``).

The pipeline's later stages (the consumers of
``intermediate_queue_*`` activations, producers of
``gradient_queue_*`` cotangents) have so far lived in the same
process group the deployment harness started — the data-plane half of
multi-host was the last structural gap after PR 12 moved the
*aggregation* tree out of process and PR 15 sharded the broker.
Following the MPMD pipeline-parallelism blueprint (each stage its own
program on its own host, activations streamed over the network), this
module promotes later-stage clients to **standalone stage-host
processes** connected over the existing (sharded) TCP broker
(``tools/sl_stagehost.py`` / ``python -m split_learning_tpu.stagehost``):

* the host builds its transport with
  :func:`~split_learning_tpu.runtime.chaos.make_runtime_transport` and
  announces itself with a
  :class:`~split_learning_tpu.runtime.protocol.StageHello` on the rpc
  queue (re-sent until adopted), then heartbeats like any client
  (``kind="stage_host"``) — liveness is the HEARTBEAT/FleetMonitor
  plane, and a host the monitor marks ``lost`` (or whose spawned
  process exits) triggers the server's counted slot re-assignment,
  not a barrier stall;
* the server replies with a
  :class:`~split_learning_tpu.runtime.protocol.StageAssign` naming the
  later-stage client slots this host runs.  Each slot spins one inner
  :class:`~split_learning_tpu.runtime.client.ProtocolClient` thread
  under the ASSIGNED ``client_id`` — the inner client REGISTERs and
  then speaks the ordinary choreography, so the Reliable/Chaos/Async/
  codec transport stack, the generation fences and the PR 10 async
  plane (aux heads + bounded staleness, which absorbs inter-host
  jitter) all compose unchanged;
* a MID-ROUND re-assignment (another host died) arrives as a further
  StageAssign: the dead host's slots are adopted under the SAME
  client ids, so the per-client ShardRunner seed — and therefore the
  re-run round's fold — is bit-identical to the fault-free twin;
* the host's own heartbeats carry the per-hop view ``sl_top`` renders
  as ROLE=stage rows: slot count, summed samples/s EWMA, the inner
  hot loops' step histogram (teed into the host's set, so step p95
  rides the host beat) and the summed ingest backlog
  (``queue_depth``).  The inner clients additionally emit their own
  ``kind=perf`` records per round, which ``sl_perf`` merges into the
  per-hop compute|wire|wait attribution table.
"""

from __future__ import annotations

import argparse
import threading
import time

from split_learning_tpu.config import Config, from_yaml
from split_learning_tpu.runtime import blackbox
from split_learning_tpu.runtime.log import Logger
from split_learning_tpu.runtime.protocol import (
    BlackboxDump, FrameAssembler, Heartbeat, StageAssign, StageHello,
    Stop, encode, reply_queue, RPC_QUEUE,
)

#: seconds between StageHello re-sends while not yet adopted (the
#: server's startup purge may race a fast host's first hello — the
#: same re-REGISTER discipline clients use)
HELLO_RESEND_S = 2.0


class _TeeHists:
    """Forwards histogram observations to two sets: the inner client's
    own (its heartbeats keep their per-client step digests) and the
    host's (so the HOST beat carries a merged step histogram across
    its slots — the ``sl_top`` stage row's step p95)."""

    def __init__(self, own, host):
        self._own = own
        self._host = host

    def observe(self, name: str, value: float) -> None:
        self._own.observe(name, value)
        self._host.observe(name, value)

    def __getattr__(self, attr):
        # digests/snapshots read the inner client's own set
        return getattr(self._own, attr)


class SlotWorker(threading.Thread):
    """One assigned later-stage client slot: an ordinary
    :class:`ProtocolClient` under the assigned ``client_id``, on its
    own transport stack, driven to completion on this thread."""

    def __init__(self, host: "StageHost", slot: dict):
        cid = slot["client_id"]
        super().__init__(daemon=True, name=f"{host.host_id}-{cid}")
        self.host = host
        self.slot = dict(slot)
        self.client_id = cid
        self.client = host._make_client(self.slot)
        # tee the hot loop's step observations into the host's set
        self.client.hists = _TeeHists(self.client.hists, host.hists)

    def run(self) -> None:
        t0 = time.time()
        ok = True
        try:
            self.client.run()
        except Exception as e:  # noqa: BLE001 — a dead transport or a
            # fault unwinding the slot's hot loop means this slot is
            # done; the server's liveness plane (the inner client's
            # heartbeats died with it) and re-run machinery recover
            ok = False
            self.host.log.warning(
                f"slot {self.client_id} died: {e}")
        self.host.tracer.record(
            "stage.slot", t0, time.time(), always=True,
            client=self.client_id, stage=int(self.slot.get("stage", 0)),
            ok=ok)


class StageHost:
    """The host process: adoption hello, heartbeats, assignment loop.

    ``transport`` defaults to a fresh ``make_runtime_transport`` stack;
    tests inject a shared in-proc bus (and usually a ``make_client``
    factory wiring the inner clients onto the same bus)."""

    def __init__(self, cfg: Config, host_id: str, transport=None,
                 make_client=None, logger: Logger | None = None):
        self.cfg = cfg
        self.host_id = host_id
        from split_learning_tpu.runtime.trace import (
            FaultCounters, HistogramSet,
        )
        self.faults = FaultCounters()
        self.hists = HistogramSet()
        self._owns_bus = transport is None
        if transport is None:
            from split_learning_tpu.runtime.chaos import (
                make_runtime_transport,
            )
            transport = make_runtime_transport(cfg, host_id,
                                               faults=self.faults)
        self.bus = transport
        self._make_client = make_client or self._default_client
        self.log = logger or Logger.for_run(cfg, host_id, console=False)
        # span-plane membership: adoption, each StageAssign apply and
        # each slot's whole lifetime journal into
        # spans-{host_id}.jsonl, so sl_trace's merged fleet timeline
        # covers the stage tier (the inner clients keep their own
        # journals — this is the HOST's view)
        from split_learning_tpu.runtime.spans import make_tracer
        self.tracer = make_tracer(cfg, host_id)
        self._t_hello: float | None = None
        self._asm = FrameAssembler(faults=self.faults)
        # NOT named _stop: see aggnode.DigestWorker — threading
        # internals shadow that name on some interpreter versions
        self._halt = threading.Event()
        self.adopted = threading.Event()
        self.workers: dict[str, SlotWorker] = {}
        from split_learning_tpu.runtime.telemetry import (
            GaugeSet, TelemetryEmitter,
        )
        self.gauges = GaugeSet()
        obs = getattr(cfg, "observability", None)
        interval = obs.heartbeat_interval if obs is not None else 0.0
        self.emitter = TelemetryEmitter(
            host_id, self._beat, interval=interval, faults=self.faults,
            hists=self.hists, gauges=self.gauges,
            samples_fn=self._total_samples, kind="stage_host")

    # -- inner clients -------------------------------------------------------

    def _default_client(self, slot: dict):
        from split_learning_tpu.runtime.client import ProtocolClient
        return ProtocolClient(self.cfg, slot["client_id"],
                              int(slot["stage"]),
                              cluster=slot.get("cluster"))

    def _total_samples(self) -> int:
        return sum(w.client.num_samples for w in self.workers.values())

    def _refresh_gauges(self) -> None:
        self.gauges.set("stage_slots", len(self.workers))
        depth = 0.0
        for w in self.workers.values():
            depth += w.client.gauges.get("queue_depth", 0.0) or 0.0
        self.gauges.set("queue_depth", depth)

    def _beat(self, snapshot: dict) -> None:
        self._refresh_gauges()
        snapshot["gauges"] = self.gauges.snapshot()
        # the host's stage view: the (lowest) stage its slots run —
        # display only; per-stage measured rates come from the inner
        # clients' own stage-tagged heartbeats
        stages = sorted({int(w.slot.get("stage", 0))
                         for w in self.workers.values()})
        if stages:
            snapshot["stage"] = stages[0]
        self.bus.publish(RPC_QUEUE, encode(Heartbeat(
            client_id=self.host_id, telemetry=snapshot)))

    def _apply_assign(self, msg: StageAssign) -> None:
        slots = msg.slots or []
        t0 = time.time()
        self.log.received(
            f"STAGEASSIGN gen={msg.gen} slots={len(slots)}")
        if not self.adopted.is_set() and self._t_hello is not None:
            # hello -> first assignment: the adoption handshake
            self.tracer.record("stage.adopt", self._t_hello, t0,
                               always=True, gen=msg.gen)
        self.adopted.set()
        for slot in slots:
            cid = slot["client_id"]
            old = self.workers.get(cid)
            if old is not None and old.is_alive():
                # idempotent re-send of a slot this host already runs
                continue
            try:
                worker = SlotWorker(self, slot)
            except Exception as e:  # noqa: BLE001 — a slot that cannot
                # build (bad stage index, dead transport) must not kill
                # the host's other slots; the server's liveness plane
                # notices the missing client
                self.log.warning(
                    f"slot {cid} failed to start: {e}")
                continue
            self.workers[cid] = worker
            worker.start()
        self._refresh_gauges()
        self.tracer.record("stage.assign", t0, time.time(),
                           always=True, gen=msg.gen, round=msg.round_idx,
                           slots=len(slots))
        self.tracer.flush()

    # -- lifecycle -----------------------------------------------------------

    def stop(self) -> None:
        self._halt.set()

    def run(self) -> None:
        self._hello()
        self.emitter.start()
        next_hello = time.monotonic() + HELLO_RESEND_S
        try:
            while not self._halt.is_set():
                raw = self.bus.get(reply_queue(self.host_id),
                                   timeout=0.25)
                if raw is None:
                    if not self.adopted.is_set() \
                            and time.monotonic() >= next_hello:
                        self._hello()
                        next_hello = time.monotonic() + HELLO_RESEND_S
                    continue
                try:
                    msg = self._asm.feed(raw)
                except Exception as e:  # noqa: BLE001 — one corrupt
                    # frame costs one message, not the host
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
                    # host's flight recorder alongside everyone else's
                    blackbox.record("dump_request", reason=msg.reason)
                    blackbox.dump(msg.reason or "fleet_snapshot")
                    continue
                if isinstance(msg, StageAssign):
                    self._apply_assign(msg)
        finally:
            # the inner clients receive their own STOPs from the
            # server's fan-out (they are registrations like any
            # client's); give them a bounded drain
            for w in self.workers.values():
                w.join(timeout=10.0)
            self.emitter.stop()
            self.tracer.close()
            if self._owns_bus:
                try:
                    self.bus.close()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
            self.log.close()

    def _hello(self) -> None:
        if self._t_hello is None:
            self._t_hello = time.time()
        self.bus.publish(RPC_QUEUE, encode(StageHello(
            host_id=self.host_id, capacity=len(self.workers))))
        self.log.sent("STAGEHELLO")


def write_host_config(cfg: Config, path) -> None:
    """Persist a config for spawned stage-host subprocesses (JSON is a
    YAML subset; ``from_yaml`` reads it back — same contract as
    ``aggnode.write_node_config``)."""
    import json

    from split_learning_tpu.config import to_dict
    with open(path, "w") as f:
        json.dump(to_dict(cfg), f, default=list)


def spawn_stage_host(config_path, host_id: str, cpu: int | None = None):
    """Spawn one stage-host subprocess (tcp transport).  ``cpu`` pins
    the child to one core via ``taskset``-free sched_setaffinity
    inheritance (the child re-pins itself from ``SLT_PIN_CPU``) — the
    bench's NUMA proxy.  stdio is inherited so tracebacks surface in
    CI.

    The child runs on the CPU backend whatever the parent runs on: an
    accelerator belongs to one process, the spawning server may hold
    it, and a child that inherited ``JAX_PLATFORMS=tpu`` would fail or
    hang waiting for it.  Until per-process chip pinning is settled
    (ROADMAP S0/S3) the multi-process protocol path is CPU-only."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if cpu is not None:
        env["SLT_PIN_CPU"] = str(cpu)
    return subprocess.Popen(
        [sys.executable, "-m", "split_learning_tpu.stagehost",
         "--config", str(config_path), "--host-id", host_id], env=env)


def main(argv=None):
    import os
    ap = argparse.ArgumentParser(
        description="Standalone split-learning stage host "
                    "(pipeline.remote).")
    ap.add_argument("--config", default="config.yaml")
    ap.add_argument("--host-id", default="stage_host_0")
    args = ap.parse_args(argv)
    pin = os.environ.get("SLT_PIN_CPU")
    if pin is not None and hasattr(os, "sched_setaffinity"):
        try:
            os.sched_setaffinity(0, {int(pin)})
        except (OSError, ValueError):
            pass   # a bad pin must not stop the host from serving
    from split_learning_tpu.platform import (
        apply_compile_cache, apply_platform_env,
    )
    apply_platform_env()
    apply_compile_cache()
    cfg = from_yaml(args.config)
    blackbox.install(cfg, args.host_id, role="stage_host")
    host = StageHost(cfg, args.host_id)
    import jax
    host.log.info(f"stage host {args.host_id}: jax backend "
                  f"{jax.default_backend()}")
    host.run()


if __name__ == "__main__":
    main()
