"""Multi-process protocol server: the reference's ``server.py`` +
``src/Server.py`` FSM over real transports.

The server is a :class:`ProtocolContext` — a
:class:`~split_learning_tpu.runtime.context.TrainContext` whose
``train_cluster`` drives REMOTE clients through the control protocol
instead of running the compiled mesh step locally.  Because it satisfies
the same interface, all six round strategies
(:mod:`split_learning_tpu.runtime.strategies`) work unchanged over a
live deployment — the reference needed a full server fork per algorithm
(SURVEY.md §2.3).

Round choreography parity (``/root/reference/src/Server.py``):
registration barrier (``:111-135``) → planning (``:300-382``) → per-round
START with shard weights (``:214-298``) → READY barrier (replacing the
25 s sleep at ``:289``) → SYN (``:290-296``) → NOTIFY collection → PAUSE
fan-out (``:137-153``) → UPDATE collection (``:155-170``) → strategy
aggregation → validation + checkpoint (``:182-196``, via the shared round
loop in :mod:`split_learning_tpu.runtime.loop`).

Failure-detection improvement over the reference (SURVEY.md §5.3: a
crashed client hangs the round forever): every barrier carries a
deadline; clients that miss it are dropped from the round with a logged
warning instead of wedging the server.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import os
import threading
import time
import zlib
from typing import Any, Callable

import numpy as np

from split_learning_tpu.config import Config, from_yaml
from split_learning_tpu.models import shard_params
from split_learning_tpu.parallel.mesh import stage_ranges
from split_learning_tpu.runtime.bus import Broker, Transport
from split_learning_tpu.runtime.context import MeshContext
from split_learning_tpu.runtime.log import Logger
from split_learning_tpu.runtime.loop import TrainResult, run_training
from split_learning_tpu.runtime.plan import (
    ClusterPlan, Registration, plan_clusters,
)
from split_learning_tpu.runtime import aggregate as agg_plane
from split_learning_tpu.runtime import blackbox
from split_learning_tpu.runtime.protocol import (
    AggAssign, AggFlush, AggHello, BlackboxDump, DigestRoute,
    FleetDigest, FrameAssembler, Heartbeat, Notify, PartialAggregate,
    Pause, Ready, Register, StageAssign, StageHello, Start, Stop, Syn,
    Update, digest_queue, encode, encode_parts, reply_queue, RPC_QUEUE,
)
from split_learning_tpu.runtime.spans import unpack_ctx
from split_learning_tpu.runtime.telemetry import FleetMonitor, GaugeSet


class RoundTimeout(RuntimeError):
    pass


class _StageHostLost(RuntimeError):
    """Raised from inside a round attempt's barriers when an assigned
    stage host (pipeline.remote) died — its spawned process exited or
    the FleetMonitor marked it ``lost``.  Caught by ``train_cluster``'s
    retry wrapper: the dead host's slots are re-assigned to a survivor
    under the SAME client ids and the attempt re-runs behind a bumped
    generation fence (every barrier frame is gen-fenced, so the aborted
    attempt's stragglers drop on arrival and the re-run's fold is
    bit-identical to a fault-free round)."""

    def __init__(self, host_id: str):
        super().__init__(f"stage host {host_id} lost mid-round")
        self.host_id = host_id


class ProtocolContext(MeshContext):
    """Server-side TrainContext that trains via remote protocol clients.

    Validation / init reuse the in-process implementations (the server
    holds the full model for reassembly + test passes, exactly like the
    reference's ``src/val/get_val.py``).
    """

    clients_hold_state = True   # remote shards persist between rounds
    # the in-process device-resident fast path MUST NOT hijack protocol
    # rounds — training happens on remote clients, not the server's mesh
    train_cluster_resident = None

    def __init__(self, cfg: Config, transport: Transport,
                 logger: Logger | None = None,
                 client_timeout: float = 600.0,
                 ready_timeout: float | None = None):
        super().__init__(cfg)
        if self._parallel_axis() is not None:
            # fail fast like require_profiles: protocol clients build
            # plain unsharded ShardRunners — silently dropping the
            # configured TP/SP/EP axis would train in a different regime
            # than the YAML states (and OOM at real model scale)
            name, n = self._parallel_axis()
            raise ValueError(
                f"topology.{name}-parallel={n} is only supported by the "
                "in-process mesh backend (python -m split_learning_tpu"
                ".run); the multi-process protocol deployment does not "
                "shard client models yet")
        self.bus = transport
        from split_learning_tpu.runtime.spans import make_tracer
        from split_learning_tpu.runtime.trace import (
            HistogramSet, default_fault_counters, default_wire_counters,
        )
        self.faults = getattr(transport, "faults", None) \
            or default_fault_counters
        self.wire = getattr(transport, "wire", None) \
            or default_wire_counters
        self.tracer = getattr(transport, "tracer", None) \
            or make_tracer(cfg, "server")
        self.hists = getattr(transport, "hists", None) or HistogramSet()
        self._fault_base: dict = {}   # snapshot at the last round log
        self._assembler = FrameAssembler()   # chunked UPDATE reassembly
        self.log = logger or Logger.for_run(cfg, "server",
                                            console=False)
        # live fleet telemetry (runtime/telemetry.py): per-client
        # health state machine + time series fed by HEARTBEAT frames
        # (and the snapshot piggybacked on every Update).  The round
        # barriers consult it so a `lost` client is dropped after
        # observability.liveness-timeout instead of stalling to the
        # full client_timeout.  None when heartbeats are disabled.
        self.gauges = GaugeSet()
        obs = getattr(cfg, "observability", None)
        self.fleet = None
        if obs is not None and obs.heartbeat_interval > 0:
            self.fleet = FleetMonitor(
                interval=obs.heartbeat_interval,
                liveness_timeout=obs.liveness_timeout,
                log=self.log, gauges=self.gauges, faults=self.faults,
                watchlist_size=obs.watchlist_size)
        # hierarchical heartbeat roll-up (observability.digest-interval
        # > 0): clients are routed to an adopted aggregator node's
        # digest queue via START extra.digest and the node's
        # FleetDigest frames replace their individual heartbeats at
        # this pump.  _digest_route maps client -> node; a dead node's
        # clients are re-pointed to direct heartbeats (DigestRoute
        # frames) and its queue drained here, counted, so the fallback
        # can never mint a phantom `lost`.
        self._digest_interval = (obs.digest_interval
                                 if obs is not None else 0.0)
        self._digest_route: dict[str, str] = {}
        self._digest_dead: set = set()
        self._dead_drains: dict[str, FrameAssembler] = {}
        self._digest_check_t = 0.0
        self.client_timeout = client_timeout
        # registration/READY happen before any jit work on the client, so
        # they can run on a much shorter deadline than the training
        # barriers (NOTIFY/UPDATE), which cover compile + a full round
        self.ready_timeout = (client_timeout if ready_timeout is None
                              else ready_timeout)
        self._registrations: dict[str, Registration] = {}
        self._ready: set = set()
        self._notified: set = set()
        self._updates: list[Update] = []
        # delta-encoded Updates (transport.codec rpc family): versioned
        # per-client shadow copies of the shards this server sent, so a
        # delta UPDATE folds back into a full tree before aggregation
        from split_learning_tpu.runtime.codec import parse_codec_map
        self._delta_shadow = None
        if parse_codec_map(getattr(cfg.transport, "codec",
                                   None)).get("rpc") is not None:
            from split_learning_tpu.runtime.codec.delta import DeltaShadow
            self._delta_shadow = DeltaShadow(faults=self.faults)
        if self.fleet is not None:
            # a `lost` client's delta shadow is a full shard copy
            # pinned in host memory; before this hook only the elastic
            # prune reclaimed it — a lost-but-never-pruned client (or
            # a non-elastic deployment) leaked its shadow forever
            self.fleet.on_lost = self._on_client_lost
        # streaming aggregation plane (runtime/aggregate.py, ROADMAP
        # item 4): fold each UPDATE into a running per-stage weighted
        # sum the moment it decodes, so the UPDATE barrier holds O(1)
        # parameter trees instead of O(clients).  Only strategies whose
        # aggregation consumes the whole update list at once stream;
        # the others (relay/periodic/fedasync read individual
        # u.params) keep barrier semantics untouched.
        self._agg = cfg.aggregation
        self._streaming = (self._agg.streaming and self._agg.strategy
                           in agg_plane.FOLD_STRATEGIES)
        self._fold_backend = (agg_plane.make_fold_backend(cfg)
                              if self._streaming else None)
        self._fold: agg_plane.StreamingFold | None = None
        self._group_of: dict = {}      # client_id -> AggGroup (tree on)
        self._l1: list = []            # this invocation's L1Aggregators
        self._l1_fallback: dict = {}   # group idx -> fallback drain state
        # multi-process aggregator tree (aggregation.remote,
        # runtime/aggnode.py): adopted node registry (AggHello /
        # spawned Popen handles), the current invocation's node ->
        # groups assignment, nodes already declared dead this
        # invocation, and the full tree plan by group idx
        self._agg_nodes: dict = {}     # node_id -> {t, proc?}
        # cross-host MPMD stage pipeline (pipeline.remote,
        # runtime/stagehost.py): adopted stage-host registry (StageHello
        # / spawned Popen handles) and the standing host -> later-stage
        # client-slot assignment.  _stage_watch arms the barrier-side
        # death check only INSIDE a train_cluster attempt — a host dying
        # between rounds is handled by the next attempt's recovery, not
        # by an exception out of an idle pump.
        self._stage_hosts: dict = {}        # host_id -> {t, proc?, dead?}
        self._stage_assignments: dict = {}  # host_id -> [slot dicts]
        self._stage_watch = False
        self._l1_remote: dict = {}     # node_id -> [AggGroup]
        self._dead_nodes: set = set()
        self._tree_groups: dict = {}   # group idx -> AggGroup
        self._tree_roots: list = []    # parentless groups (root children)
        self._tree_narrowed: dict = {}   # group idx -> responsive members
        self._cur_cluster = 0
        self._agg_topology: dict | None = None   # /fleet view
        # partial-sum codec (transport.codec: partial): the spec, and
        # the per-stage START-base trees the delta mode reconstructs
        # against (both endpoints hold the generation's base)
        from split_learning_tpu.runtime.codec import parse_codec_map
        self._partial_codec = parse_codec_map(
            getattr(cfg.transport, "codec", None)).get("partial")
        self._partial_bases: dict = {}
        self._partial_base_gen: int | None = None
        # members of a dead L1's group whose UPDATE frames the L1
        # consumed before dying — unrecoverable, so the UPDATE barrier
        # stops waiting for them (counted agg_fallback_abandons)
        self._agg_gone: set = set()
        self._l1_logs: dict = {}       # agg_id -> cached Logger (the
        # L1's [<<<]/[>>>] markers carry the aggregator participant
        # name, so --validate-log replays the AGGREGATOR_FSM on real
        # runs instead of vacuously)
        # FedAvgM velocity, keyed cluster_id -> {path: vel}: each
        # cluster's fold is its own optimizer stream — a shared dict
        # would feed cluster B the velocity cluster A wrote THIS round
        self._agg_velocity: dict = {}
        # elastic membership (topology.elastic-join): ids the CURRENT
        # plans were computed from; per-ROUND alive/silent bookkeeping
        # (sequential strategies run several train_cluster invocations
        # per round — a slow client must not accrue several misses in
        # one round); consecutive missed ROUNDS per client (a fresh
        # REGISTER forgives); clients whose next START must carry
        # params whatever the strategy's wire economy says (joiners,
        # and everyone after a re-plan moved the cuts)
        self._planned_ids: set = set()
        self._round_alive: set = set()
        self._round_silent: set = set()
        self._missed: dict[str, int] = {}
        self._needs_params: set = set()
        self._replan_failed_for: set | None = None
        # fence: messages are stamped with a per-train_cluster-invocation
        # generation (NOT the round index — sequential strategies run
        # several invocations with the same round_idx, and a straggler
        # from sub-call k must not satisfy sub-call k+1's barriers)
        self._gen = 0
        self._cur_gen = 0
        # async decoupled mode (learning.mode: async): the generation IS
        # the global model version.  Instead of the hard gen fence, the
        # UPDATE pump admits contributions through a bounded-staleness
        # window (_admit_update) and the UPDATE barrier cuts a new
        # version at learning.async-quorum fresh contributions.
        self._async = cfg.learning.mode == "async"
        # (client_id, version) pairs already folded — the dedup that
        # keeps an at-least-once redelivery of a post-fold Update from
        # double-counting samples (and a stale resend from re-folding
        # across invocations); pruned past the admission window
        self._folded_versions: set = set()
        # late-READY SYN: True between the SYN fan-out and the end of
        # an async invocation, so a straggler's late READY still gets
        # its SYN instead of idling out the whole round
        self._syn_live = False
        self._syn_round = 0
        # per-client responsive-set fence overrides captured at the
        # SYN fan-out, reused for late-READY joiners
        self._syn_overrides: dict = {}
        # closed-loop resource-aware scheduler (runtime/scheduler.py,
        # scheduler.enabled): round-boundary decision loop consuming
        # the fleet-telemetry plane — online clustering, straggler
        # demotion/eviction with per-client knob retunes, measured-
        # throughput cut re-planning.  _sched_gone mirrors _agg_gone:
        # clients a barrier stopped waiting for by scheduler policy
        # (mid-round drop), reset per invocation; _stage_of maps the
        # invocation's active clients to stages so a mid-round drop
        # can release the streaming fold's reorder window.
        self.scheduler = None
        self._sched_gone: set = set()
        self._sched_watched: set = set()
        self._stage_of: dict = {}
        sch = getattr(cfg, "scheduler", None)
        if sch is not None and sch.enabled:
            from split_learning_tpu.runtime.scheduler import Scheduler
            self.scheduler = Scheduler(cfg, log=self.log,
                                       faults=self.faults,
                                       gauges=self.gauges)
        # flight-recorder fleet snapshots (runtime/blackbox.py): one
        # BlackboxDump fan-out per distinct dead participant, globally
        # rate-limited so a death CASCADE yields one snapshot naming
        # the first victim instead of a dump storm
        self._bb_snapped: set = set()
        self._bb_last_snap = 0.0

    # -- rpc pump ------------------------------------------------------------

    def _pump_one(self, timeout: float) -> bool:
        raw = self.bus.get(RPC_QUEUE, timeout=timeout)
        if raw is None:
            if self.fleet is not None:
                # liveness ages are only trustworthy at a DRAINED
                # queue: after an unpumped phase (validation) the
                # backlog still holds everyone's beats, and opening
                # the gate on the first frame would flash spurious
                # `lost` states before the drain finishes — see
                # FleetMonitor.note_pump
                self.fleet.note_pump()
            # drained pump = a quiet moment: the right time for the
            # (throttled) digest-node death check, so a dead node's
            # clients are re-pointed within _DIGEST_CHECK_S whatever
            # phase the round is in
            self._check_digest_nodes()
            return False
        t_wall = time.time()
        t0 = time.perf_counter()
        try:
            msg = self._assembler.feed(raw)
        except Exception as e:  # noqa: BLE001 — corrupt frame: a flipped
            # bit on rpc_queue must cost one message, not the server
            self.faults.inc("corrupt_rejected")
            self.log.warning(f"dropping undecodable rpc frame: {e}")
            self.wire.add_decode(time.perf_counter() - t0)
            return True
        dt = time.perf_counter() - t0
        self.wire.add_decode(dt)
        self.hists.observe("decode", dt)
        if msg is None:
            return True   # chunk of a still-partial frame
        ctx = unpack_ctx(getattr(msg, "_ctx", None))
        if ctx is not None:
            # consume span linked to the client's publish span: the
            # UPDATE upload gets a flow edge like any data-plane frame
            _, sender_span, t_send = ctx
            rtt = max(0.0, t_wall - t_send)
            self.hists.observe("frame_rtt", rtt)
            self.tracer.record(
                "consume", t_wall, t_wall + dt, parent=sender_span,
                queue=RPC_QUEUE, kind=type(msg).__name__,
                nbytes=len(raw), rtt_ms=round(rtt * 1e3, 3),
                round=getattr(msg, "round_idx", None))
        if isinstance(msg, Heartbeat):
            # liveness + telemetry only — never logged (one frame per
            # interval per client would drown the protocol trace).
            # note_heartbeat applies the seq/send-time staleness guard,
            # so a duplicated/reordered beat can't resurrect a lost
            # client or extend its liveness.
            if self.fleet is not None:
                self.fleet.note_heartbeat(
                    msg.client_id, msg.telemetry,
                    via=self._digest_route.get(msg.client_id))
            return True
        if isinstance(msg, FleetDigest):
            # one aggregator node's rolled-up heartbeat summary
            # (observability.digest-interval) — the O(nodes) ingest
            # that replaces O(clients) individual beats.  note_digest
            # applies the same (t, seq) staleness guard as heartbeats
            # (duplicates counted stale_digests), and the frame itself
            # proves the node's process is alive.  A digest from a
            # node already failed over is stale BY DEFINITION: its
            # clients were re-pointed to direct heartbeats, and
            # re-installing the standing digest (a reordered frame
            # published before the death) would double-count them
            # forever — _check_digest_nodes never revisits dead nodes.
            if self.fleet is not None:
                if msg.node_id in self._digest_dead:
                    self.faults.inc("stale_digests")
                else:
                    self.fleet.note_frame(msg.node_id)
                    self.fleet.note_digest(msg.node_id, msg.digest)
            return True
        if self.fleet is not None:
            cid = getattr(msg, "client_id", None)
            if cid is not None:
                # any rpc frame proves a live process (clients with
                # heartbeats disabled still register liveness); the
                # piggybacked Update snapshot counts as a full beat —
                # consumed even when the Update itself is stale-gen,
                # liveness is not round-fenced
                if isinstance(msg, Update) and msg.telemetry:
                    self.fleet.note_heartbeat(
                        cid, msg.telemetry,
                        via=self._digest_route.get(cid))
                else:
                    self.fleet.note_frame(
                        cid, via=self._digest_route.get(cid))
        if isinstance(msg, Register):
            if (self.cfg.topology.elastic_join
                    and not 1 <= msg.stage <= self.cfg.num_stages):
                # elastic: a stored out-of-range registration would
                # poison every later re-planning pass, so drop it.
                # Non-elastic keeps the old fail-fast: it counts toward
                # the barrier and planning immediately raises naming
                # the misconfigured client.
                self.log.warning(
                    f"ignoring REGISTER {msg.client_id}: stage "
                    f"{msg.stage} outside 1..{self.cfg.num_stages}")
                return True
            # keyed by client_id: clients re-REGISTER until STARTed (the
            # server's startup purge may race a fast client's first one)
            if msg.client_id not in self._registrations:
                self.log.received(f"REGISTER {msg.client_id} "
                                  f"stage={msg.stage}")
            self._registrations[msg.client_id] = Registration(
                client_id=msg.client_id, stage=msg.stage,
                cluster=msg.cluster, profile=msg.profile)
            # a REGISTER proves a live process: forgive barrier misses
            # (a crashed-and-restarted client re-joins by re-registering)
            self._missed.pop(msg.client_id, None)
        elif isinstance(msg, Ready):
            # fenced like Notify/Update: a late READY from a dropped
            # invocation must not let the server SYN a client that is
            # still unwinding the old round
            if msg.round_idx != self._cur_gen:
                self.log.warning(f"stale READY {msg.client_id} "
                                 f"gen={msg.round_idx} (dropped)")
            else:
                late = (self._syn_live
                        and msg.client_id not in self._ready)
                self._ready.add(msg.client_id)
                if late:
                    # async pipelining: the SYN fan-out already went
                    # out (the READY barrier collapsed to the
                    # responsive set) — a straggler that finishes its
                    # previous round's late upload and re-READYs still
                    # joins THIS round instead of idling to the next.
                    # It gets the same responsive-set fence overrides
                    # the fan-out carried: the static START values may
                    # name feeders dropped at the barrier, whose
                    # fences would stall its strict drain / burn the
                    # async drain grace every round.
                    q, feeders = self._syn_overrides.get(
                        msg.client_id, (None, None))
                    self.bus.publish(
                        reply_queue(msg.client_id),
                        encode(Syn(self._syn_round,
                                   sda_fence_quorum=q,
                                   sda_feeders=feeders)))
                    self.log.sent(f"SYN -> {msg.client_id} "
                                  "(late READY)")
        elif isinstance(msg, Notify):
            if msg.round_idx != self._cur_gen:
                self.log.warning(f"stale NOTIFY {msg.client_id} "
                                 f"gen={msg.round_idx} (dropped)")
            else:
                self._notified.add(msg.client_id)
                self.log.received(f"NOTIFY {msg.client_id}")
        elif isinstance(msg, Update):
            # generation fence (sync) / bounded-staleness admission
            # window (async) + (client_id, version) dedup — one door
            # for every fold-bound Update
            self._admit_update(msg)
        elif isinstance(msg, PartialAggregate):
            # one aggregator's folded group landing at the root
            if msg.round_idx != self._cur_gen:
                self.faults.inc("agg_stale_drops")
                self.log.warning(
                    f"stale PARTIALAGGREGATE {msg.aggregator_id} "
                    f"gen={msg.round_idx} (dropped)")
            else:
                self._fold_partial(msg, nbytes=self._assembler.last_bytes)
        elif isinstance(msg, AggHello):
            # a standalone aggregator process offering itself for
            # adoption (aggregation.remote); liveness afterwards rides
            # its heartbeats through the FleetMonitor like a client's
            ent = self._agg_nodes.setdefault(msg.node_id, {})
            if "t" not in ent:
                self.log.received(f"AGGHELLO {msg.node_id}")
            ent["t"] = time.time()
            if self.fleet is not None:
                self.fleet.note_frame(msg.node_id)
        elif isinstance(msg, StageHello):
            # a standalone stage-host process offering itself for
            # adoption (pipeline.remote); liveness afterwards rides its
            # heartbeats through the FleetMonitor like a client's.  A
            # host helloing again AFTER assignment (slow adoption ack,
            # or a restarted process under the same id) gets its
            # standing slots re-sent — the host side is idempotent.
            ent = self._stage_hosts.setdefault(msg.host_id, {})
            if "t" not in ent:
                self.log.received(f"STAGEHELLO {msg.host_id}")
            ent["t"] = time.time()
            if self.fleet is not None:
                self.fleet.note_frame(msg.host_id)
            if self._stage_assignments.get(msg.host_id):
                self._send_stage_assign(msg.host_id)
        return True

    def _admit_update(self, msg: Update) -> None:
        """The one admission door for client Updates.

        * dedup first: a resent (at-least-once redelivered) Update for
          a ``(client_id, version)`` already folded is dropped BEFORE
          any sample accounting — the weight-less skip path in
          ``aggregate_cluster`` must never see the same contribution
          twice (PR 6 double-count fix);
        * sync (``learning.mode: sync``): only the current generation
          folds — the hard fence, unchanged semantics;
        * async: an Update seeded from version ``v`` is admitted while
          ``server_version - v <= learning.max-staleness`` and folded
          with weight scaled by ``staleness-decay ** lag`` under a
          ``client@vN`` extras key (a straggler contributes late
          instead of stalling the fleet); anything older is
          rejected-and-counted (``agg_stale_updates``).
        """
        lrn = self.cfg.learning
        ver = msg.version if msg.version is not None else msg.round_idx
        key = (msg.client_id, ver)
        if key in self._folded_versions:
            self.faults.inc("agg_dup_drops")
            self.log.warning(f"duplicate UPDATE {msg.client_id} "
                             f"v{ver} (already folded; dropped)")
            return
        lag = self._cur_gen - ver
        if lag == 0 and msg.round_idx == self._cur_gen:
            self._fold_update(msg)
            if self._fold is not None:
                # streaming fold: the weights fold into the running
                # sum NOW (a shallow copy keeps the tree alive in
                # the fold's reorder window) and the barrier list
                # holds a weight-stripped record — O(1) full trees
                # at the UPDATE barrier instead of O(clients)
                self._fold.add_update(copy.copy(msg))
                msg.params = None
                msg.batch_stats = None
            self._folded_versions.add(key)
            self._updates.append(msg)
            if self._async and self.fleet is not None:
                # version lag is an async-mode signal: in sync mode the
                # generation bumps per INVOCATION (sequential clusters
                # would read as phantom lag and flap the straggler state)
                self.fleet.note_client_version(msg.client_id, ver)
            self.log.received(f"UPDATE {msg.client_id} "
                              f"samples={msg.num_samples} ok={msg.ok}")
            return
        # per-client staleness window: a scheduler-demoted compute-slow
        # client folds through a WIDER admission window than the global
        # config grants (runtime/scheduler.py _act_demote)
        max_st = lrn.max_staleness
        if self.scheduler is not None:
            max_st += self.scheduler.staleness_bonus_for(msg.client_id)
        if (self._async and self._fold is not None
                and 0 < lag <= max_st):
            # bounded-staleness admission: fold with decayed weight,
            # keyed off the canonical window so the same client's
            # FRESH contribution this round still occupies its slot
            self._fold_update(msg)
            scale = lrn.staleness_decay ** lag
            self._fold.add_update(copy.copy(msg), scale=scale,
                                  key=f"{msg.client_id}@v{ver}")
            msg.params = None
            msg.batch_stats = None
            self._folded_versions.add(key)
            self._updates.append(msg)
            self.faults.inc("agg_stale_admits")
            if self.fleet is not None:   # stale admits only exist async
                self.fleet.note_client_version(msg.client_id, ver)
            self.log.received(
                f"UPDATE {msg.client_id} v{ver} lag={lag} "
                f"(stale-admitted, weight x{scale:g})")
            return
        self.faults.inc("agg_stale_updates")
        self.log.warning(f"stale UPDATE {msg.client_id} v{ver} "
                         f"lag={lag} (rejected)")

    def _fold_update(self, msg: Update) -> None:
        """Reconstruct a delta-encoded UPDATE in place (``base +
        dequant(delta)`` against the versioned shadow).  When the
        version chain is broken (shadow missing/moved — redelivery
        gap, server state loss) the delta is unusable: the update is
        kept WEIGHT-LESS (the barrier must not stall on it; aggregation
        skips param-less updates) and the client is marked for a full
        re-seed, so the next round repairs the chain.  Full frames
        (delta_base None) pass through and are counted — they ARE the
        resync path."""
        if msg.delta_base is None:
            if self._delta_shadow is not None and msg.params is not None:
                self.faults.inc("delta_full_frames")
            return
        full = (None if self._delta_shadow is None
                else self._delta_shadow.fold(msg.client_id,
                                             msg.delta_base, msg.params))
        if full is None:
            self.log.warning(
                f"delta UPDATE {msg.client_id} against unknown base "
                f"v{msg.delta_base}: weights dropped; full-frame "
                "resync next round")
            self._needs_params.add(msg.client_id)
            msg.params = None
            msg.batch_stats = None
        else:
            msg.params = full
        msg.delta_base = None   # downstream sees a plain (full) update

    def _on_client_lost(self, cid: str) -> None:
        """FleetMonitor ``lost`` transition hook: reclaim the client's
        delta shadow (a full shard copy pinned in host memory).  A
        rejoiner full-frames its next UPDATE anyway — the chain repairs
        itself, only the memory was leaking."""
        if self._delta_shadow is not None:
            self._delta_shadow.clear(cid)
            self.gauges.set("agg_shadow_bytes",
                            self._delta_shadow.nbytes())
        # the FleetMonitor tracks every heartbeating participant, not
        # just clients — name the role the postmortem should report
        role = ("agg_node" if cid in self._agg_nodes
                else "stage_host" if cid in self._stage_hosts
                else "client")
        self._fleet_snapshot(cid, role, "participant_lost")

    # -- flight-recorder fleet snapshot (runtime/blackbox.py) ----------------

    #: minimum wall-clock gap between fleet snapshots: a cascade of
    #: deaths (one kill tipping over its dependents) produces ONE
    #: snapshot naming the first victim — the proximate cause the
    #: postmortem wants — instead of a dump storm
    BB_SNAPSHOT_MIN_S = 5.0

    def _death_kind(self, victim: str, registry: dict) -> str:
        """``child_exit`` when the victim is a subprocess this server
        spawned and its Popen handle reports an exit code, else
        ``participant_lost`` (heartbeats aged out — externally-started
        process, or a SIGKILL that left no exit notification)."""
        proc = (registry.get(victim) or {}).get("proc")
        if proc is not None and proc.poll() is not None:
            return "child_exit"
        return "participant_lost"

    def _fleet_snapshot(self, victim: str, role: str,
                        kind: str) -> None:
        """Record a participant death in the server's ring and trigger
        the fleet-wide flight-recorder snapshot: dump the server's own
        ring, fan a :class:`BlackboxDump` out to every surviving
        participant's reply queue, and sweep the broker shards' rings
        over their control queues — so the postmortem assembler finds
        every process's last seconds in one artifacts directory even
        though the victim itself (SIGKILL) wrote nothing."""
        if not blackbox.enabled():
            return
        blackbox.record(kind, participant=victim, role=role,
                        round=int(getattr(self, "_cur_round", 0)),
                        gen=self._cur_gen)
        now = time.monotonic()
        if victim in self._bb_snapped \
                or now - self._bb_last_snap < self.BB_SNAPSHOT_MIN_S:
            return
        self._bb_snapped.add(victim)
        self._bb_last_snap = now
        reason = f"{kind}:{victim}"
        # own ring FIRST — it holds the death event this snapshot is
        # named after, and a fan-out failure must not lose it
        blackbox.dump(reason)
        targets = (set(self._registrations) | set(self._agg_nodes)
                   | set(self._stage_hosts))
        targets.discard(victim)
        for pid in sorted(targets):
            if self.fleet is not None \
                    and self.fleet.state(pid) == "lost":
                continue   # its queue has no consumer; skip, don't park
            try:
                self.bus.publish(reply_queue(pid), encode(BlackboxDump(
                    participant=pid, reason=reason,
                    t_req=time.time())))  # slcheck: wire=BlackboxDump
            except Exception:  # noqa: BLE001 — snapshot is best-effort
                blackbox.record("error", where="bb_fanout",
                                participant=pid)
        self.log.warning(f"flight-recorder fleet snapshot: {reason} "
                         f"({len(targets)} participants asked to dump)")
        if self.cfg.transport.kind == "tcp":
            # shard sweep dials TCP: off-pump so barrier latency stays
            # flat while the shards answer
            threading.Thread(target=self._sweep_broker_blackbox,
                             args=(reason,), daemon=True,
                             name="bb-sweep").start()

    def _sweep_broker_blackbox(self, reason: str) -> None:
        """Pull each broker shard's ring over ``__broker__.blackbox``
        and persist it next to this server's own dumps (the shard
        replies with bytes; the REQUESTER owns the dump directory)."""
        from split_learning_tpu.runtime.bus import broker_blackbox
        host, port = self.cfg.transport.host, self.cfg.transport.port
        for i in range(self.cfg.broker.shards):
            try:
                d = broker_blackbox(host, port + i, timeout=2.0)
            except Exception:  # noqa: BLE001 — dead/foreign shard
                blackbox.record("error", where="bb_broker_sweep",
                                shard=i)
                continue
            d.setdefault("snap_reason", reason)
            d.setdefault("participant", f"broker-shard_{i}")
            blackbox.write_dump_dict(d)

    def _fold_partial(self, msg: PartialAggregate,
                      nbytes: int = 0) -> None:
        """Fold one PartialAggregate at its group's canonical position
        and book its members: each one gets a weight-less Update record
        (barrier membership, ok flag, elastic liveness) and its
        piggybacked telemetry feeds the fleet monitor — clients behind
        an aggregator stay individually visible everywhere but the
        fold.  A codec'd payload (transport.codec: partial) is
        reconstructed to f32 sums first; one that cannot be (missing
        delta base) is dropped and counted — the fallback machinery,
        not a silently wrong fold, owns that group's fate."""
        if self._fold is None:
            self.log.warning(
                f"PARTIALAGGREGATE {msg.aggregator_id} outside a "
                "streaming invocation (dropped)")
            return
        self._agg_ingress_bytes = (
            getattr(self, "_agg_ingress_bytes", 0) + int(nbytes))
        if msg.codec or msg.members_z:
            from split_learning_tpu.runtime.codec.partial import (
                PartialCodecError, decode_partial_msg,
            )
            try:
                decode_partial_msg(msg, bases=self._partial_bases,
                                   base_gen=self._partial_base_gen)
            except PartialCodecError as e:
                self.faults.inc("partial_codec_errors")
                self.log.warning(
                    f"PARTIALAGGREGATE {msg.aggregator_id}: "
                    f"undecodable codec'd payload ({e}); dropped")
                return
        # gen-fenced upstream (the pump drops stale PartialAggregates
        # before this); tree members are never stale-admitted
        self._fold.add_partial(  # slcheck: async-exempt
            msg.stage, agg_plane.group_key(msg.group), msg.sums,
            msg.weight, msg.dtypes, stat_sums=msg.stat_sums,
            stat_weight=msg.stat_weight, stat_dtypes=msg.stat_dtypes,
            n_samples=msg.n_samples)
        for m in msg.members or []:
            cid = m.get("client_id")
            if cid is None:
                continue
            if self.fleet is not None and m.get("telemetry"):
                self.fleet.note_heartbeat(
                    cid, m["telemetry"],
                    via=self._digest_route.get(cid))
            # num_samples=0: the group's stage-1 samples already rode
            # the partial's n_samples — a per-member recount would
            # double the round total
            self._updates.append(Update(
                client_id=cid, stage=int(m.get("stage", msg.stage)),
                cluster=msg.cluster, params=None, num_samples=0,
                ok=bool(m.get("ok", True)), round_idx=msg.round_idx))
        self.log.received(
            f"PARTIALAGGREGATE {msg.aggregator_id} "
            f"members={len(msg.members or [])} weight={msg.weight:g}")

    def _node_dead(self, node_id: str) -> bool:
        """A remote aggregator node is dead when its spawned process
        exited or the FleetMonitor marked it ``lost`` (no heartbeat
        within observability.liveness-timeout) — the satellite fix for
        the thread-liveness assumption: ``_poll_l1`` used to detect a
        dead L1 via ``Thread.is_alive``, which a remote process has no
        equivalent of."""
        ent = self._agg_nodes.get(node_id) or {}
        proc = ent.get("proc")
        if proc is not None and proc.poll() is not None:
            return True
        return (self.fleet is not None
                and self.fleet.state(node_id) == "lost")

    # -- cross-host MPMD stage pipeline (pipeline.remote) --------------------

    def _host_dead(self, host_id: str) -> bool:
        """Same liveness rule as :meth:`_node_dead`, for stage hosts:
        the spawned child exited, OR the FleetMonitor aged the host's
        heartbeats to ``lost`` (externally-started hosts have no Popen
        handle — the telemetry plane is their only death signal)."""
        ent = self._stage_hosts.get(host_id) or {}
        if ent.get("dead"):
            return True
        proc = ent.get("proc")
        if proc is not None and proc.poll() is not None:
            return True
        return (self.fleet is not None
                and self.fleet.state(host_id) == "lost")

    def _send_stage_assign(self, host_id: str) -> None:
        self.bus.publish(reply_queue(host_id), encode(StageAssign(
            host_id=host_id, gen=self._cur_gen,
            round_idx=getattr(self, "_cur_round", 0),
            slots=[dict(s) for s in
                   self._stage_assignments.get(host_id, [])])))
        self.log.sent(
            f"STAGEASSIGN {host_id} "
            f"slots={len(self._stage_assignments.get(host_id, []))}")

    def assign_stage_slots(self) -> None:
        """Deal the pipeline's later-stage client slots round-robin
        across the adopted stage hosts and publish each host its
        StageAssign.  Runs BEFORE the registration barrier: the slots'
        inner clients ARE later-stage registrations, so the barrier
        cannot complete until the hosts have spun them up."""
        from split_learning_tpu.runtime.plan import pipeline_slots
        slots = pipeline_slots(self.cfg)
        hosts = [h for h in sorted(self._stage_hosts)
                 if "t" in self._stage_hosts[h]
                 and not self._host_dead(h)]
        if not hosts:
            self.log.warning(
                "pipeline.remote: no stage host adopted — "
                "later-stage slots unassigned")
            return
        self._stage_assignments = {h: [] for h in hosts}
        for j, slot in enumerate(slots):
            self._stage_assignments[hosts[j % len(hosts)]].append(slot)
        for h in hosts:
            self._send_stage_assign(h)

    def _check_stage_hosts(self) -> None:
        """Barrier-side death check (armed only inside a round
        attempt): the first assigned host found dead aborts the attempt
        via :class:`_StageHostLost` — the retry wrapper re-assigns and
        re-runs rather than letting the barrier eat its full deadline
        waiting for clients whose process is gone."""
        for host_id in sorted(self._stage_assignments):
            if self._stage_assignments[host_id] \
                    and self._host_dead(host_id):
                raise _StageHostLost(host_id)

    def _recover_stage_host(self, host_id: str) -> None:
        """Counted re-assignment after a stage-host death: the dead
        host's slots move to the surviving hosts round-robin UNDER THE
        SAME CLIENT IDS (the per-client ShardRunner seed is a client-id
        hash, so the re-run round's fold stays bit-identical to the
        fault-free twin), and each touched survivor gets a fresh
        StageAssign.  One ``stage_host_deaths`` per death, one
        ``stage_reassigns`` per moved slot — the chaos cell's exact
        expected counts."""
        self.faults.inc("stage_host_deaths")
        self._fleet_snapshot(host_id, "stage_host",
                             self._death_kind(host_id,
                                              self._stage_hosts))
        ent = self._stage_hosts.setdefault(host_id, {})
        ent["dead"] = True
        dead_slots = self._stage_assignments.pop(host_id, [])
        survivors = [h for h in sorted(self._stage_assignments)
                     if not self._host_dead(h)]
        if not survivors:
            raise RoundTimeout(
                f"stage host {host_id} died and no live stage host "
                "remains to adopt its "
                f"{len(dead_slots)} slot(s)")
        touched = set()
        for j, slot in enumerate(dead_slots):
            tgt = survivors[j % len(survivors)]
            self._stage_assignments[tgt].append(slot)
            self.faults.inc("stage_reassigns")
            touched.add(tgt)
        self.log.warning(
            f"stage host {host_id} lost: re-assigned "
            f"{len(dead_slots)} slot(s) to {sorted(touched)}")
        for tgt in sorted(touched):
            self._send_stage_assign(tgt)

    # -- hierarchical heartbeat roll-up (observability.digest-interval) ------

    #: wall-clock cadence of the digest-node death check (cheap: a
    #: dict walk over the routed nodes, work only on a death)
    _DIGEST_CHECK_S = 0.5

    def _digest_route_for(self, cid: str) -> str | None:
        """The digest queue this client's heartbeats should roll up
        through (START ``extra.digest``), or None for direct rpc
        beats.  Assignment is a stable hash over the live adopted
        nodes, so successive STARTs keep a client on the same node
        (its node-local state machine keeps its history)."""
        if self._digest_interval <= 0 or self.fleet is None:
            return None
        nodes = [n for n in sorted(self._agg_nodes)
                 if n not in self._digest_dead
                 and not self._node_dead(n)]
        if not nodes:
            return None
        nid = nodes[zlib.crc32(cid.encode()) % len(nodes)]
        self._digest_route[cid] = nid
        # any standing direct entry stops aging at this monitor — the
        # node's state machine covers the client from here (its next
        # beats land on the node's queue, not ours)
        self.fleet.route_via(cid, nid)
        return digest_queue(nid)

    def _check_digest_nodes(self, now: float | None = None) -> None:
        """Digest-node death fallback: a routed node whose process
        exited (or whose own heartbeats went FleetMonitor-``lost``)
        gets its digest queue drained HERE — the heartbeats parked
        there are liveness proof, not losses — and each of its clients
        is re-pointed to direct rpc beats with a counted
        ``digest_fallbacks``.  The node's standing digest is dropped
        from the fold (its clients now count via their own beats), so
        the degradation can neither double-count nor mint a phantom
        ``lost``."""
        if not self._digest_route and not self._dead_drains:
            return
        now = time.monotonic() if now is None else now
        if now - self._digest_check_t < self._DIGEST_CHECK_S:
            return
        self._digest_check_t = now
        if self.fleet is not None:
            # _node_dead reads the monitor's state: advance first so a
            # silent node's `lost` is current at this check, not the
            # last barrier's
            self.fleet.advance()
        # keep draining dead nodes' queues: a client mid-compile only
        # reads its reply queue (the DigestRoute) at the next control
        # point, so its beats keep landing on the dead queue for a
        # while — every one of them is liveness proof this monitor
        # must see, or the fallback itself would mint the phantom
        # `lost` it exists to prevent.  Quiesces to one empty
        # zero-timeout get per dead node per check.
        for nid, asm in self._dead_drains.items():
            self._drain_dead_queue(nid, asm)
        routed: dict[str, list] = {}
        for cid, nid in self._digest_route.items():
            routed.setdefault(nid, []).append(cid)
        for nid, cids in routed.items():
            if nid in self._digest_dead or not self._node_dead(nid):
                continue
            self._digest_dead.add(nid)
            asm = self._dead_drains[nid] = FrameAssembler(
                faults=self.faults)
            drained = self._drain_dead_queue(nid, asm)
            if self.fleet is not None:
                self.fleet.drop_digest(nid)
            for cid in sorted(cids):
                self.faults.inc("digest_fallbacks")
                self._digest_route.pop(cid, None)
                self.bus.publish(
                    reply_queue(cid),
                    encode(DigestRoute(client_id=cid, queue=None)))  # slcheck: wire=DigestRoute
                if self.fleet is not None:
                    # the client was beating into the dead node's
                    # queue, not lying silent — a fresh liveness grace
                    # covers the re-route gap
                    self.fleet.note_frame(cid)
            self.log.warning(
                f"digest node {nid} is dead; re-pointed {len(cids)} "
                f"client(s) to direct heartbeats ({drained} queued "
                "beat(s) recovered)")

    def _drain_dead_queue(self, nid: str, asm: FrameAssembler) -> int:
        """Fold the heartbeats parked on a dead digest node's queue
        straight into this monitor (via=None: the senders are falling
        back to direct reporting)."""
        drained = 0
        q = digest_queue(nid)
        while True:
            raw = self.bus.get(q, timeout=0.0)
            if raw is None:
                return drained
            try:
                m = asm.feed(raw)
            except Exception:  # noqa: BLE001 — one corrupt beat
                self.faults.inc("corrupt_rejected")
                continue
            if isinstance(m, Heartbeat) and self.fleet is not None:
                self.fleet.note_heartbeat(m.client_id, m.telemetry)
                drained += 1

    def _spawn_l1_threads(self, plan, groups, narrowed: dict) -> None:
        """Thread-mode aggregators (the default): one L1Aggregator
        thread per group, any level.  Over TCP each gets its own
        transport stack (a blocked get serializes a TcpTransport's
        socket); in-proc they share the bus."""
        l1_deadline = time.monotonic() + self.client_timeout
        for g in groups:
            agg_id = f"aggregator_{plan.cluster_id}_{g.idx}"
            l1_bus, owns = self.bus, False
            if self.cfg.transport.kind == "tcp":
                from split_learning_tpu.runtime.chaos import (
                    make_runtime_transport,
                )
                l1_bus = make_runtime_transport(
                    self.cfg, agg_id, faults=self.faults)
                owns = True
            l1_log = self._l1_logs.get(agg_id)
            if l1_log is None:
                l1_log = self._l1_logs[agg_id] = Logger.for_run(
                    self.cfg, agg_id, console=False)
            out_q = (RPC_QUEUE if g.parent is None
                     else agg_plane.aggregate_queue(plan.cluster_id,
                                                    g.parent))
            t = agg_plane.L1Aggregator(
                l1_bus, cluster=plan.cluster_id, group=g,
                members=narrowed[g.idx], gen=self._cur_gen,
                deadline=l1_deadline, log=l1_log,
                faults=self.faults,
                chunk_bytes=self.cfg.transport.chunk_mb << 20,
                owns_bus=owns, out_queue=out_q,
                codec=self._partial_codec,
                base=self._partial_bases.get(g.stage),
                base_gen=self._partial_base_gen)
            t.start()
            self._l1.append(t)

    def _dispatch_remote(self, plan, groups, narrowed: dict,
                         node_ids: list, round_idx: int) -> None:
        """Assign the tree's groups round-robin across the adopted
        aggregator processes and send each node ONE AggAssign naming
        its groups (and the delta-codec base trees, when configured).
        The node folds exactly what a thread-mode L1 would — same
        L1Aggregator objects, same queues — so the choreography and
        determinism contracts carry over unchanged."""
        codec_s = None
        if self._partial_codec is not None:
            from split_learning_tpu.runtime.codec.partial import (
                spec_string,
            )
            codec_s = spec_string(self._partial_codec)
        self._l1_remote = {nid: [] for nid in node_ids}
        ordered = sorted(groups, key=lambda g: (g.level, g.idx))
        for i, g in enumerate(ordered):
            self._l1_remote[node_ids[i % len(node_ids)]].append(g)
        for nid, glist in self._l1_remote.items():
            wire_groups = []
            for g in glist:
                d = g.as_dict()
                d["members"] = list(narrowed[g.idx])
                wire_groups.append(d)
            assign = AggAssign(
                node_id=nid, cluster=plan.cluster_id,
                gen=self._cur_gen, round_idx=round_idx,
                groups=wire_groups, deadline_s=self.client_timeout,
                codec=codec_s,
                bases=(dict(self._partial_bases)
                       if self._partial_bases else None),
                chunk_bytes=self.cfg.transport.chunk_mb << 20)
            for part in encode_parts(
                    assign, self.cfg.transport.chunk_mb << 20):
                self.bus.publish(reply_queue(nid), part)  # slcheck: wire=AggAssign
            self.log.sent(f"AGGASSIGN -> {nid} "
                          f"groups={len(wire_groups)}")

    #: liveness grace on a fallback drain: a dead L1 may have consumed
    #: a member's UPDATE frames before dying — those are unrecoverable,
    #: and the member (already in its post-round wait) will never
    #: resend, so the barrier must not wait client_timeout for it.
    #: The clock resets on every recovered frame, so an actively
    #: draining queue never expires; only a drained-and-silent one
    #: abandons its missing members (same bound as _finish_l1).
    L1_FALLBACK_GRACE_S = 30.0

    def _poll_l1(self) -> None:
        """Aggregator-tree health check, run every UPDATE-barrier pump
        iteration: a dead aggregator — a thread that is no longer
        alive, or a REMOTE node whose spawned process exited or whose
        heartbeats went FleetMonitor-``lost`` — degrades its groups to
        direct-to-root: the server drains the orphaned queues itself
        and folds each group at its canonical position, so tree rounds
        stay deterministic through aggregator loss instead of stalling
        a barrier."""
        for t in self._l1:
            if t.flushed:
                continue
            fb = self._l1_fallback.get(t.group.idx)
            if fb is None:
                if t.is_alive():
                    continue
                self.faults.inc("agg_l1_fallbacks")
                self.log.warning(
                    f"aggregator {t.agg_id} died mid-round; draining "
                    f"group {t.group.idx} direct-to-root")
                fb = self._start_fallback(t.group, t.cluster,
                                          set(t.members))
            self._step_fallback(fb)
        for nid, glist in self._l1_remote.items():
            if nid in self._dead_nodes:
                for g in glist:
                    fb = self._l1_fallback.get(g.idx)
                    if fb is not None:
                        self._step_fallback(fb)
                continue
            if not self._node_dead(nid):
                continue
            self._dead_nodes.add(nid)
            self.faults.inc("agg_node_deaths")
            self._fleet_snapshot(nid, "agg_node",
                                 self._death_kind(nid, self._agg_nodes))
            self.log.warning(
                f"aggregator node {nid} is dead (process exit or "
                f"fleet-lost); draining its {len(glist)} group(s) "
                "direct-to-root")
            for g in glist:
                if g.parent is None and self._fold is not None \
                        and self._fold.has_key(g.stage, g.key):
                    continue   # its partial already landed at the root
                self.faults.inc("agg_l1_fallbacks")
                members = set(self._tree_narrowed.get(g.idx,
                                                      g.members))
                fb = self._start_fallback(g, self._cur_cluster,
                                          members)
                self._step_fallback(fb)

    def _start_fallback(self, group, cluster: int,
                        members: set) -> dict:
        fb = self._l1_fallback[group.idx] = {
            "group": group, "cluster": cluster,
            "members": set(members),
            "fold": agg_plane.StreamingFold(
                {group.stage: sorted(members)}, faults=self.faults),
            "asm": FrameAssembler(faults=self.faults),
            "seen": set(), "meta": [],
            # parentless groups book members/sums straight into the
            # root fold; groups under an interior parent publish a
            # substitute PartialAggregate into the parent's queue
            # instead (the parent's dedup absorbs the race where the
            # aggregator had actually flushed before being declared
            # dead) — booking BOTH ways would double-count members
            "book_direct": group.parent is None,
            "deadline": (time.monotonic()
                         + self.L1_FALLBACK_GRACE_S),
            "flushed": False}
        return fb

    def _children_draining(self, group) -> bool:
        """True while any CHILD group of an interior ``group`` has an
        unflushed fallback of its own: the child's drain will publish
        a substitute partial into THIS group's queue, so flushing (or
        abandoning) the parent now would strand members the child is
        actively recovering.  Bounded — every child fallback's own
        grace deadline abandons it eventually."""
        if group.level == 1:
            return False
        return any(f["group"].parent == group.idx and not f["flushed"]
                   for f in self._l1_fallback.values())

    def _step_fallback(self, fb: dict) -> None:
        if not fb["flushed"]:
            self._drain_fallback(fb)
        if not fb["flushed"] and time.monotonic() >= fb["deadline"]:
            if self._children_draining(fb["group"]):
                fb["deadline"] = (time.monotonic()
                                  + self.L1_FALLBACK_GRACE_S)
                return
            gone_keys = fb["members"] - fb["seen"]
            gone = self._member_clients(fb["group"], gone_keys)
            for _ in sorted(gone):
                self.faults.inc("agg_fallback_abandons")
            if gone_keys:
                self.log.warning(
                    f"fallback group {fb['group'].idx}: abandoning "
                    f"{sorted(gone_keys)} (dead aggregator consumed "
                    f"their frames; folding "
                    f"{len(fb['seen'])}/{len(fb['members'])} members)")
            self._agg_gone |= gone
            self._flush_fallback(fb)

    def _member_clients(self, group, keys) -> set:
        """The CLIENT ids behind a set of member keys — the ids
        themselves at level 1, the flattened (narrowed) client
        membership of the named child groups above it.  What the
        UPDATE barrier stops waiting for when a fallback abandons."""
        if group.level == 1:
            return set(keys)
        out: set = set()
        by_key = {g.key: g for g in self._tree_groups.values()}
        for key in keys:
            child = by_key.get(key)
            if child is not None:
                out |= self._member_clients(
                    child, self._tree_narrowed.get(child.idx,
                                                   child.members))
        return out

    def _drain_fallback(self, fb: dict) -> None:
        g = fb["group"]
        for m in agg_plane.drain_group_queue(
                self.bus, fb["cluster"], g.idx, self._cur_gen,
                fb["asm"], self.faults, log=self.log):
            if isinstance(m, Update):
                self._drain_fallback_update(fb, g, m)
            else:
                self._drain_fallback_partial(fb, g, m)
        if not fb["flushed"] and fb["seen"] >= fb["members"]:
            self._flush_fallback(fb)

    def _drain_fallback_update(self, fb: dict, g, u: Update) -> None:
        if g.level != 1 or u.client_id in fb["seen"]:
            self.faults.inc("agg_dup_drops")
            return
        fb["seen"].add(u.client_id)
        fb["deadline"] = time.monotonic() + self.L1_FALLBACK_GRACE_S
        self._fold_update(u)   # delta reconstruction, like the pump
        # drain_group_queue already gen-fenced this frame
        fb["fold"].add_update(copy.copy(u))  # slcheck: async-exempt
        fb["meta"].append({"client_id": u.client_id, "stage": u.stage,
                           "num_samples": u.num_samples, "ok": u.ok,
                           "telemetry": u.telemetry})
        u.params = None
        u.batch_stats = None
        if self.fleet is not None and u.telemetry:
            self.fleet.note_heartbeat(
                u.client_id, u.telemetry,
                via=self._digest_route.get(u.client_id))
        if fb["book_direct"]:
            self._updates.append(u)
        self.log.received(f"UPDATE {u.client_id} (fallback drain)")

    def _drain_fallback_partial(self, fb: dict, g,
                                m: PartialAggregate) -> None:
        """A dead INTERIOR group's queue holds its children's
        partials: recover them into the fallback sub-fold, keyed and
        dedup'd exactly as the dead aggregator would have."""
        key = agg_plane.group_key(m.group)
        if g.level == 1 or key in fb["seen"]:
            self.faults.inc("agg_dup_drops")
            return
        if m.codec or m.members_z:
            from split_learning_tpu.runtime.codec.partial import (
                PartialCodecError, decode_partial_msg,
            )
            try:
                decode_partial_msg(m, bases=self._partial_bases,
                                   base_gen=self._partial_base_gen)
            except PartialCodecError as e:
                self.faults.inc("partial_codec_errors")
                self.log.warning(f"fallback drain: undecodable "
                                 f"partial ({e}); dropped")
                return
        fb["seen"].add(key)
        fb["deadline"] = time.monotonic() + self.L1_FALLBACK_GRACE_S
        fb["fold"].add_partial(  # slcheck: async-exempt
            m.stage, key, m.sums, m.weight, m.dtypes,
            stat_sums=m.stat_sums, stat_weight=m.stat_weight,
            stat_dtypes=m.stat_dtypes, n_samples=m.n_samples)
        fb["meta"].extend(m.members or [])
        for mm in m.members or []:
            cid = mm.get("client_id")
            if cid is None:
                continue
            if self.fleet is not None and mm.get("telemetry"):
                self.fleet.note_heartbeat(
                    cid, mm["telemetry"],
                    via=self._digest_route.get(cid))
            if fb["book_direct"]:
                self._updates.append(Update(
                    client_id=cid, stage=int(mm.get("stage", m.stage)),
                    cluster=m.cluster, params=None, num_samples=0,
                    ok=bool(mm.get("ok", True)),
                    round_idx=m.round_idx))
        self.log.received(
            f"PARTIALAGGREGATE {m.aggregator_id} (fallback drain)")

    def _flush_fallback(self, fb: dict) -> None:
        """Close a fallback group: its sub-fold's partial sums land
        where the dead aggregator's would have — folded at the
        group's canonical position in the root fold when parentless,
        published as a substitute PartialAggregate into the parent's
        queue otherwise (same summation shape either way)."""
        g = fb["group"]
        stages, n = fb["fold"].partial()
        ent = stages.get(g.stage)
        if fb["book_direct"]:
            if ent:
                # members already gen-fenced at the drain
                self._fold.add_partial(  # slcheck: async-exempt
                    g.stage, g.key, ent["sums"], ent["weight"],
                    ent["dtypes"], stat_sums=ent["stat_sums"],
                    stat_weight=ent["stat_weight"],
                    stat_dtypes=ent["stat_dtypes"], n_samples=n)
            else:
                self._fold.drop(g.stage, g.key)
        else:
            ent = ent or {}
            msg = PartialAggregate(
                aggregator_id=f"aggregator_{fb['cluster']}_{g.idx}",
                cluster=fb["cluster"], group=g.idx, stage=g.stage,
                round_idx=self._cur_gen, sums=ent.get("sums"),
                weight=float(ent.get("weight") or 0.0),
                dtypes=ent.get("dtypes"),
                stat_sums=ent.get("stat_sums"),
                stat_weight=float(ent.get("stat_weight") or 0.0),
                stat_dtypes=ent.get("stat_dtypes"), n_samples=n,
                members=fb["meta"], level=g.level)
            q = agg_plane.aggregate_queue(fb["cluster"], g.parent)
            chunk = self.cfg.transport.chunk_mb << 20
            for part in encode_parts(msg, chunk):
                self.bus.publish(q, part)  # slcheck: wire=PartialAggregate
            self.log.sent(
                f"PARTIALAGGREGATE (fallback substitute for group "
                f"{g.idx} -> group {g.parent})")
        fb["flushed"] = True

    def _finish_l1(self) -> None:
        """Post-barrier aggregator-tree resolution, LEVEL-ASCENDING:
        live unflushed aggregators are told to flush (the server gave
        up on their stragglers) level by level, so an interior group
        still folds the partials the level below it just produced;
        remote nodes get one AggFlush each and cascade internally
        (runtime/aggnode.py); dead aggregators fall back to the
        direct-to-root drain; every fallback closes into the root
        fold.  Bounded — an aggregator that can neither flush nor die
        within the grace window is abandoned (its group key is
        dropped at finish)."""
        for lv in sorted({t.group.level for t in self._l1}):
            level_ts = [t for t in self._l1 if t.group.level == lv]
            for t in level_ts:
                if t.is_alive() and not t.flushed:
                    t.request_flush()

            def lv_done(ts=level_ts) -> bool:
                self._poll_l1()
                return all(
                    t.flushed or self._l1_fallback.get(
                        t.group.idx, {}).get("flushed")
                    for t in ts)
            deadline = time.monotonic() + 15.0
            while not lv_done() and time.monotonic() < deadline:
                self._pump_one(timeout=0.05)
        for nid in self._l1_remote:
            if nid not in self._dead_nodes:
                self.bus.publish(
                    reply_queue(nid),
                    encode(AggFlush(node_id=nid, gen=self._cur_gen)))
        if self._l1_remote:
            self.log.sent(f"AGGFLUSH -> {sorted(self._l1_remote)}")
        want = [(g.stage, g.key) for g in self._tree_roots] \
            or [(t.group.stage, t.group.key) for t in self._l1]

        def landed() -> bool:
            self._poll_l1()
            return all(self._fold.has_key(s, k) for s, k in want)

        if not landed():
            self._pump_until(
                landed, "aggregator flushes",
                deadline=time.monotonic() + 30.0)
        # forced close, LEVEL-ASCENDING: a child's flush publishes its
        # substitute into the parent's queue, so the parent (stepped
        # right after, flushed later in the same ordering) still folds
        # it instead of closing empty a microsecond earlier
        for fb in sorted(self._l1_fallback.values(),
                         key=lambda f: (f["group"].level,
                                        f["group"].idx)):
            if not fb["flushed"]:
                self._flush_fallback(fb)
            parent_idx = fb["group"].parent
            if parent_idx is not None:
                pfb = self._l1_fallback.get(parent_idx)
                if pfb is not None and not pfb["flushed"]:
                    self._drain_fallback(pfb)
        for t in self._l1:
            t.join(timeout=5.0)

    def _pump_until(self, pred: Callable[[], bool],
                    what: str | Callable[[], str],
                    deadline: float | None = None,
                    waiting: Callable[[], set] | None = None,
                    poll: Callable[[], None] | None = None,
                    sched_drop: bool = False) -> bool:
        """Drain rpc_queue until ``pred()``; False if the deadline passes.

        ``what`` may be a callable so the timeout warning names who is
        missing AT the deadline (an eager f-string would snapshot the
        missing set before any response arrived).

        ``waiting`` (when given) names the clients the barrier still
        needs: once EVERY one of them is FleetMonitor-``lost`` (no
        heartbeat for ``observability.liveness-timeout``), the wait
        gives up early — a dead client costs the round the liveness
        timeout, not the full barrier deadline.  A slow-but-alive
        straggler is never dropped by the monitor itself; with
        ``sched_drop`` (the NOTIFY/UPDATE barriers, when the
        scheduler is enabled) the scheduler's mid-round policy MAY
        stop waiting for a health-state-straggler past
        ``scheduler.barrier-grace-s`` — each such drop is journaled
        (``kind=sched``) and counted, and the caller's predicate
        consults ``_sched_gone`` so the barrier actually releases."""
        deadline = (time.monotonic() + self.client_timeout
                    if deadline is None else deadline)
        t_begin = time.monotonic()
        t_checked = 0.0
        t_stage = 0.0
        while not pred():
            if poll is not None:
                poll()   # e.g. L1 aggregator health -> fallback drain
                if pred():
                    return True
            now = time.monotonic()
            # stage-host death check (pipeline.remote, armed only
            # inside a round attempt): raises _StageHostLost so the
            # retry wrapper re-assigns and re-runs instead of this
            # barrier eating its deadline on a dead host's clients
            if (self._stage_watch
                    and now - t_stage >= self._WAIT_CHECK_S):
                t_stage = now
                if self.fleet is not None:
                    self.fleet.advance()
                self._check_stage_hosts()
            remain = deadline - now
            if remain <= 0:
                w = what() if callable(what) else what
                self.faults.inc("timeouts")
                self.log.warning(f"timeout waiting for {w}")
                return False
            # the liveness/scheduler checks walk the whole fleet
            # (advance + waiting-set rebuild are O(clients)); at 10k
            # clients running them per FRAME is an O(n^2) round wall,
            # so they are throttled to a coarse wall-clock cadence —
            # more than fine-grained enough for 45 s liveness
            # timeouts and multi-second scheduler graces
            if (waiting is not None and self.fleet is not None
                    and now - t_checked >= self._WAIT_CHECK_S):
                t_checked = now
                self._check_digest_nodes(now)
                lost = self.fleet.advance()
                missing = set(waiting())
                if missing and missing <= lost:
                    self.faults.inc("fleet_lost_drops", len(missing))
                    self.log.warning(
                        f"dropping lost client(s) {sorted(missing)}: "
                        f"no heartbeat within "
                        f"{self.fleet.liveness_timeout:g}s — barrier "
                        "released early")
                    return False
                if (sched_drop and missing
                        and self.scheduler is not None):
                    drop = self.scheduler.barrier_drop(
                        missing, self.fleet.states(),
                        waited_s=now - t_begin,
                        round_idx=getattr(self, "_cur_round",
                                          self._cur_gen))
                    if drop:
                        self._sched_release(drop)
                        continue   # re-check pred: barrier shrank
            if self._pump_one(timeout=min(remain, 0.25)):
                # drain what is already queued before re-evaluating
                # the barrier predicate: pred/waiting are O(clients),
                # and one evaluation per BATCH instead of per frame
                # is what keeps a 10k-client registration storm or
                # UPDATE wave linear in fleet size
                for _ in range(self._PUMP_BATCH - 1):
                    if not self._pump_one(timeout=0.0):
                        break
        return True

    #: wall-clock cadence of the O(clients) liveness/scheduler barrier
    #: checks inside _pump_until
    _WAIT_CHECK_S = 0.1
    #: frames drained per barrier-predicate evaluation
    _PUMP_BATCH = 256

    def _sched_release(self, drop: set) -> None:
        """Apply a scheduler mid-round drop: the barrier predicates
        stop counting these clients (``_sched_gone``) and the
        streaming fold's reorder window stops holding their slots —
        the same release discipline as a READY-barrier drop, so the
        fold order (and hence the aggregate) stays canonical over the
        clients that actually contributed."""
        self._sched_gone |= drop
        if self._fold is not None:
            for cid in sorted(drop):
                s = self._stage_of.get(cid)
                if s is not None and not self._fold.has_key(s, cid):
                    self._fold.drop(s, cid)

    # -- registration barrier ------------------------------------------------

    @property
    def registrations(self) -> list[Registration]:
        return list(self._registrations.values())

    def wait_for_registrations(self) -> list[Registration]:
        """Block until every configured client has registered
        (``src/Server.py:111-135``).

        Under ``topology.elastic-join`` the barrier counts PER STAGE:
        an elastic spare registering during startup must not mask a
        missing configured client (a raw total would release early),
        and extras beyond the configured counts are welcome — the
        initial plan simply includes them.
        """
        # full client_timeout here, NOT ready_timeout: registration covers
        # client process startup (jax import, transport connect) and a
        # miss is fatal rather than an elastic drop
        need = list(self.cfg.clients)

        def by_stage() -> list[int]:
            # out-of-range stages are deliberately kept registered in
            # non-elastic mode for fail-fast planning; they must not
            # crash (stage > len) or miscount (stage 0) the timeout
            # message that reports them
            counts = [0] * len(need)
            for r in self._registrations.values():
                if 1 <= r.stage <= len(need):
                    counts[r.stage - 1] += 1
            return counts

        if self.cfg.topology.elastic_join:
            enough = lambda: all(  # noqa: E731
                c >= n for c, n in zip(by_stage(), need))
            what = lambda: (  # noqa: E731
                f"per-stage registrations {by_stage()}/{need}")
        else:
            total = sum(need)
            enough = lambda: len(self._registrations) >= total  # noqa
            what = f"{total} registrations"
        self._pump_until(enough, what,
                         deadline=time.monotonic() + self.client_timeout)
        if not enough():
            raise RoundTimeout(
                f"registrations incomplete within {self.client_timeout}s:"
                f" per-stage {by_stage()} of {need}")
        self._planned_ids = set(self._registrations)
        return self.registrations

    _DEAD_AFTER = 2   # consecutive silent ROUNDS before pruning

    def refresh_plans(self, plans):
        """Elastic membership between rounds (topology.elastic-join).

        Extension beyond the reference (its client set is frozen at the
        registration barrier, ``src/Server.py:111-135``; a late client
        can never join and a dead one stalls every barrier forever):
        fold the finished round's alive/silent bookkeeping, drain
        between-round mail, then re-plan when the live set moved.
        Joiners (and everyone, when the re-plan moves the cuts) are
        marked so their next START carries shard weights even under a
        hold-weights strategy like FLEX.  When a full re-plan is
        impossible (e.g. a fixed distribution matrix pinned to the
        original membership), dead clients are still pruned surgically
        from the current plans so later rounds stop paying their
        barrier deadlines — only joining needs the planner.
        """
        if not self.cfg.topology.elastic_join:
            return None
        # fold the round: one miss per silent ROUND, not per invocation
        for cid in self._round_silent - self._round_alive:
            self._missed[cid] = self._missed.get(cid, 0) + 1
        for cid in self._round_alive:
            self._missed.pop(cid, None)
        self._round_alive = set()
        self._round_silent = set()
        while self._pump_one(timeout=0.0):
            pass
        dead = {c for c, n in self._missed.items()
                if n >= self._DEAD_AFTER}
        live = set(self._registrations) - dead
        if live == self._planned_ids:
            return None
        joined = sorted(live - self._planned_ids)
        pruned = sorted(self._planned_ids - live)
        regs = [r for c, r in self._registrations.items() if c in live]
        try:
            new_plans = plan_clusters(self.cfg, regs, exact_counts=False)
        except ValueError as e:
            if live != self._replan_failed_for:
                self.log.warning(f"elastic re-plan impossible: {e}")
                self._replan_failed_for = set(live)
            new_plans = self._prune_plans(plans, set(pruned))
            if new_plans is None:
                return None   # nothing safely removable; keep plans
            joined = []       # joining DOES need the planner
            live = self._planned_ids - set(pruned)
        else:
            self._replan_failed_for = None
            # a held shard survives only if the client keeps the SAME
            # layer range: compare per client (a re-plan can move a
            # client between clusters with different cuts even when no
            # single cluster's cuts changed) — joiners fall out of the
            # same comparison (no old range)
            old_rng = self._client_ranges(plans)
            new_rng = self._client_ranges(new_plans)
            self._needs_params |= {cid for cid, rng in new_rng.items()
                                   if old_rng.get(cid) != rng}
        for cid in pruned:
            self.bus.publish(reply_queue(cid), encode(Stop(
                reason="pruned: missed consecutive round barriers")))
            if self._delta_shadow is not None:
                # a pruned client's shadow is a full shard copy pinned
                # in server memory; under membership churn that leaks
                # without bound (a rejoiner full-frames anyway)
                self._delta_shadow.clear(cid)
            if self.fleet is not None:
                # stop scoring the pruned client (its zero rate would
                # drag the fleet median down for the survivors)
                self.fleet.forget(cid)
            self._digest_route.pop(cid, None)
        self.log.info(f"elastic re-plan: joined={joined} "
                      f"pruned={pruned}", "cyan")
        self._planned_ids = live
        return new_plans

    def _client_ranges(self, plans) -> dict:
        """client_id -> the (start, end) layer range it owns."""
        out = {}
        for p in plans:
            ranges = stage_ranges(len(self.specs), p.cuts)
            for s in range(1, p.n_stages + 1):
                for cid in p.clients[s - 1]:
                    out[cid] = ranges[s - 1]
        return out

    @staticmethod
    def _prune_plans(plans, pruned: set):
        """Remove ``pruned`` clients from existing plans without
        re-planning; None when any cluster would lose a whole stage
        (shared feasibility invariant: runtime/plan.py, also the
        scheduler's eviction path)."""
        from split_learning_tpu.runtime.plan import prune_plan_members
        return prune_plan_members(plans, pruned)

    def schedule_plans(self, plans, round_idx: int):
        """Closed-loop scheduler pass at a round boundary
        (``scheduler.enabled``; called by the round loop right after
        the elastic refresh).  Drains between-round mail so the fleet
        snapshot is current, runs the decision pass, then applies the
        transport side effects the scheduler itself must not own:
        STOP fan-out + shadow/telemetry reclaim for evictions (the
        same steps as the elastic prune), and ``_needs_params``
        marking for every client whose layer range a re-plan moved.
        Returns the replacement plans, or None when nothing changed."""
        if self.scheduler is None:
            return None
        fleet = {"clients": {}}
        if self.fleet is not None:
            while self._pump_one(timeout=0.0):
                pass
            self.fleet.advance()
            fleet = self.fleet.snapshot()
        profiles = {cid: (r.profile or {})
                    for cid, r in self._registrations.items()}
        out = self.scheduler.plan_round(plans, round_idx, fleet,
                                        profiles)
        if out.fan_in is not None and out.fan_in != self._agg.fan_in:
            # adopted fan-in retune: the next train_cluster plans its
            # tree at the new width (the journal already carries the
            # kind=sched "retune" record; this is just the application)
            import dataclasses as _dc
            self._agg = _dc.replace(self._agg, fan_in=int(out.fan_in))
            self.log.info(
                f"scheduler: aggregation fan-in retuned to "
                f"{out.fan_in}", "cyan")
        for cid in sorted(out.evict):
            # the elastic-drop path's teardown: STOP, drop the
            # registration (or the next elastic refresh would re-plan
            # the evicted client straight back in), reclaim the delta
            # shadow, stop fleet-scoring, forget the barrier ledger.
            # A recovered client rejoins by re-REGISTERing through
            # the elastic planner.
            self.bus.publish(reply_queue(cid), encode(Stop(
                reason="scheduler: evicted (persistent straggler)")))
            self._registrations.pop(cid, None)
            self._missed.pop(cid, None)
            if self._delta_shadow is not None:
                self._delta_shadow.clear(cid)
            if self.fleet is not None:
                self.fleet.forget(cid)
            self._digest_route.pop(cid, None)
            self._planned_ids.discard(cid)
        if self.fleet is not None:
            # scheduler attention pins the watchlist: a knob-carrying
            # (demoted/exempted) client keeps its exact server-side
            # view even when it climbs out of the digests' top-K —
            # the scheduler needs to SEE the recovery to revoke the
            # knobs.  Pins released on promotion next boundary.
            watched = self.scheduler.attention()
            for cid in watched - self._sched_watched:
                self.fleet.watch(cid)
            for cid in self._sched_watched - watched:
                self.fleet.watch(cid, pinned=False)
            self._sched_watched = watched
        if out.plans is None:
            return None
        old_rng = self._client_ranges(plans)
        new_rng = self._client_ranges(out.plans)
        # a re-plan that moved the cuts invalidates held shards: every
        # client whose layer range changed gets params on its next
        # START whatever the strategy's wire economy says
        self._needs_params |= {cid for cid, rng in new_rng.items()
                               if old_rng.get(cid) != rng}
        for plan in out.plans:
            self.log.info(
                f"Cluster {plan.cluster_id} (scheduler): "
                f"cuts={plan.cuts} "
                f"clients={[len(ids) for ids in plan.clients]}",
                "cyan")
        return out.plans

    # -- the remote round ----------------------------------------------------

    def train_cluster(self, plan: ClusterPlan, params, stats,
                      **kw) -> list[Update]:
        """One remote round for one cluster — see
        :meth:`_train_cluster_once` for the choreography.

        This wrapper adds the pipeline.remote death-retry loop: with
        stage-host slots assigned, a host death mid-attempt surfaces
        as :class:`_StageHostLost` from a barrier's pump; the wrapper
        re-assigns the dead host's slots to survivors (same client
        ids) and re-runs the attempt.  The re-run bumps the generation
        fence, so every straggler frame from the aborted attempt drops
        on arrival and the re-run fold is bit-identical to a
        fault-free round — surviving clients mid-round receive the
        fresh START, requeue-and-abort (``_redeliver_start``), and
        rejoin.  ``pipeline.retries`` caps attempts; exhaustion fails
        the round loudly."""
        if not self._stage_assignments:
            return self._train_cluster_once(plan, params, stats, **kw)
        retries = int(getattr(self.cfg.pipeline, "retries", 0))
        attempt = 0
        while True:
            self._stage_watch = True
            try:
                return self._train_cluster_once(plan, params, stats,
                                                **kw)
            except _StageHostLost as e:
                attempt += 1
                if attempt > retries:
                    raise RoundTimeout(
                        f"stage host {e.host_id} died and "
                        f"pipeline.retries={retries} re-assignment "
                        "attempt(s) are exhausted") from e
                self.log.warning(
                    f"round attempt aborted ({e}); re-assigning and "
                    f"re-running (attempt {attempt}/{retries})")
                self._recover_stage_host(e.host_id)
            finally:
                self._stage_watch = False

    def _train_cluster_once(self, plan: ClusterPlan, params, stats, *,
                      round_idx: int = 0, epochs: int = 1,
                      client_subset: list | None = None,
                      per_client_params: dict | None = None,
                      lr: float | None = None,
                      sync_all_later_stages: bool = False,
                      send_params: bool | dict = True,
                      send_weights: bool | dict = True) -> list[Update]:
        """One remote round for one cluster.

        FLEX wire economy (``other/FLEX/src/Server.py:140-143``):
        ``send_params`` False (bool, or {stage: bool}) sends START
        without weights (clients keep their local shard — client-side
        persistence between rounds); ``send_weights`` (same shape) rides
        the PAUSE so clients on non-aggregation rounds reply UPDATE
        without a state_dict (sample counts still flow; no weight bytes
        move).
        """
        stage1 = [c for c in plan.stage1_clients
                  if client_subset is None or c in client_subset]
        if not stage1:
            return []
        active = [(cid, 1) for cid in stage1]
        for s in range(2, plan.n_stages + 1):
            active += [(cid, s) for cid in plan.clients[s - 1]]

        ranges = stage_ranges(len(self.specs), plan.cuts)
        learning = dataclasses.asdict(self.cfg.learning)
        if lr is not None:
            learning["learning_rate"] = lr
        self._ready.clear()
        self._notified.clear()
        self._updates = []
        self._gen += 1
        self._cur_gen = self._gen
        self._cur_round = round_idx
        self._syn_live = False
        # async: the generation is the global model version — prune the
        # (client, version) dedup ledger past the admission window and
        # tell the fleet monitor where "now" is (version-lag scoring)
        self._folded_versions = {
            (c, v) for c, v in self._folded_versions
            if self._cur_gen - v
            <= self.cfg.learning.max_staleness + 1
            + (self.scheduler.max_staleness_bonus
               if self.scheduler is not None else 0)}
        if self._async and self.fleet is not None:
            # async only: in sync mode the generation is an invocation
            # counter, not a model version — feeding it to the monitor
            # would fabricate version lag for sequential clusters
            self.fleet.note_version(self._cur_gen)

        # streaming fold for this invocation: contributions fold in
        # canonical per-stage key order — sorted client ids, or L1
        # group keys when the aggregator tree (aggregation.fan-in) is
        # interposed.  Built BEFORE the START fan-out so the first
        # UPDATE to land already has somewhere to fold.
        groups = None
        self._group_of = {}
        self._l1 = []
        self._l1_fallback = {}
        self._l1_remote = {}
        self._dead_nodes = set()
        self._tree_groups = {}
        self._tree_roots = []
        self._agg_gone = set()
        self._sched_gone = set()
        self._stage_of = dict(active)
        self._agg_ingress_bytes = 0
        if self._streaming:
            fan_in = self._agg.fan_in
            expected: dict[int, list] = {}
            if fan_in and len(active) > fan_in:
                groups = agg_plane.plan_tree(active, fan_in,
                                             self._agg.levels)
                self._tree_groups = {g.idx: g for g in groups}
                self._tree_roots = agg_plane.root_groups(groups)
                self._group_of = {cid: g for g in groups
                                  if g.level == 1 for cid in g.members}
                for g in self._tree_roots:
                    expected.setdefault(g.stage, []).append(g.key)
            else:
                for cid, s in sorted(active):
                    expected.setdefault(s, []).append(cid)
            self._fold = agg_plane.StreamingFold(
                expected, backend=self._fold_backend,
                faults=self.faults, hists=self.hists)
            # partial-sum delta codec: pin this generation's per-stage
            # START base — the tree encodes (group mean - base) and
            # every receiver (interior node or this root) adds it back
            self._partial_bases = {}
            self._partial_base_gen = None
            if groups is not None and self._partial_codec is not None \
                    and self._partial_codec.kind == "delta":
                for s in range(1, plan.n_stages + 1):
                    a, b = ranges[s - 1]
                    self._partial_bases[s] = _np_tree(
                        shard_params(params, self.specs, a, b))
                self._partial_base_gen = self._cur_gen

        # 2LS fixed 1:1 edge<->head pairing: when in_clusters in-groups
        # each have their own head, the forward data plane runs over
        # pair-indexed queues instead of the shared cluster queue
        # (other/2LS/src/train/VGG16.py:23).  Requires a 2-stage plan
        # with exactly one head per in-cluster; otherwise the shared
        # queue's natural load balancing stays.
        pair_of: dict = {}
        n_in = self.cfg.topology.in_clusters
        if n_in > 1 and plan.n_stages == 2:
            from split_learning_tpu.runtime.context import client_groups
            groups = client_groups(len(stage1), min(n_in, len(stage1)))
            heads = plan.clients[1]
            if len(heads) == len(groups):
                for g, idxs in enumerate(groups):
                    for i in idxs:
                        pair_of[stage1[i]] = g
                    pair_of[heads[g]] = g
            else:
                self.log.warning(
                    f"in_clusters={n_in} but {len(heads)} heads for "
                    f"{len(groups)} in-groups: keeping shared queues")

        # window never wider than the feeders a head actually HEARS:
        # origins are trace[0] (the stage-1 feeders = DCSL "devices"),
        # and with 2LS pairing each head's queue receives only its own
        # group — a wider sda_size could never assemble a
        # distinct-origin window and every batch would crawl through
        # the idle-flush path
        if plan.n_stages >= 2:
            if pair_of:
                group_sizes = {}
                for cid in stage1:
                    g = pair_of.get(cid)
                    group_sizes[g] = group_sizes.get(g, 0) + 1
                n_feeders = min(group_sizes.values())
            else:
                n_feeders = len(stage1)
        else:
            n_feeders = 1
        sda = (min(self.cfg.aggregation.sda_size, n_feeders)
               if sync_all_later_stages else 1)

        # DCSL dispatch topology (other/DCSL/src/Scheduler.py:21-26,
        # :110-133): with SDA active, feeding clients scatter successive
        # batches round-robin across the next stage's PER-DEVICE queues
        # (per-device ``intermediate_queue_..._p{client_id}``) instead of
        # the shared cluster queue, and every later-stage device consumes
        # its own queue.
        # snapshot BEFORE the sda_route mutation below: strict-SDA
        # feeder sets must reflect the 2LS edge<->head pairing only —
        # the per-device routing entries are not a feeder partition
        pair_groups = dict(pair_of)
        sda_route = sda > 1 and plan.n_stages >= 2 and not pair_of
        if sda_route:
            for s in range(2, plan.n_stages + 1):
                for cid in plan.clients[s - 1]:
                    pair_of[cid] = cid

        # round-phase spans: sequential on the server thread, parented
        # under the round loop's "train" span, so the critical-path
        # walker can cross from the server timeline into client
        # timelines at the consume spans recorded inside each barrier
        fanout_span = self.tracer.start("start_fanout",
                                        round=round_idx,
                                        cluster=plan.cluster_id)
        fanout_t0 = time.time()
        shadow_refresh_s = 0.0
        # stage-ascending order (``active`` is built stage 1 first):
        # stage-1 clients' STARTs leave the socket before any later
        # stage's are even encoded, so the pipeline's feeders start
        # streaming while the rest of the fan-out is still encoding —
        # the fan-out half of the per-shard streaming discipline.
        # Per-stage shard trees are cached across clients: 10k stage-1
        # clients share one layer range, and re-slicing the same base
        # per client was a multi-ms/START tax at fleet scale (the
        # trees are read-only views of the same host arrays — exactly
        # the sharing the delta shadow already relies on).
        shard_cache: dict = {}
        for cid, s in active:
            a, b = ranges[s - 1]
            sp = (send_params.get(s, True)
                  if isinstance(send_params, dict) else bool(send_params))
            if cid in self._needs_params:
                # elastic joiner (no local shard yet) or a re-plan moved
                # the cuts: a weight-less START would crash the client's
                # shard reuse whatever the strategy's wire economy says
                sp = True
                self._needs_params.discard(cid)
            if sp:
                base = (per_client_params or {}).get(cid, params)
                key = (a, b) if base is params else None
                cached = shard_cache.get(key) \
                    if key is not None else None
                if cached is None:
                    shard_p = _np_tree(shard_params(base, self.specs,
                                                    a, b))
                    shard_s = _np_tree(shard_params(stats or {},
                                                    self.specs, a, b))
                    if key is not None:
                        shard_cache[key] = (shard_p, shard_s)
                else:
                    shard_p, shard_s = cached
            else:
                shard_p = shard_s = None
            # delta codec: keep a versioned shadow of EXACTLY what this
            # START carries, and advertise the version we hold — the
            # client sends a delta only against a matching base (a
            # weight-less START advertises the standing shadow).
            # Aggregator-tree members get NO advertisement: an L1
            # holds no shadow to reconstruct a delta against, so tree
            # rounds always full-frame (and the standing shadow is
            # reclaimed — it could never be used again)
            delta_ver = None
            group = self._group_of.get(cid)
            if self._delta_shadow is not None:
                if group is not None:
                    self._delta_shadow.clear(cid)
                elif sp:
                    # the shadow stores VIEWS of the same host arrays
                    # the sharded update fetched (one device->host
                    # fetch per stage, _np_tree/shard_params slice
                    # without copying) — no fp32 re-materialization
                    t_sh = time.perf_counter()
                    self._delta_shadow.note_sent(cid, self._cur_gen,
                                                 shard_p)
                    shadow_refresh_s += time.perf_counter() - t_sh
                    delta_ver = self._cur_gen
                else:
                    delta_ver = self._delta_shadow.version_for(cid)
            label_counts = None
            if s == 1:
                label_counts = np.asarray(
                    plan.label_counts[plan.stage1_clients.index(cid)])
            end_layer = -1 if s == plan.n_stages else b
            # per-shard START streaming: a big shard frame splits into
            # crc'd SLTC chunks published as they are cut, so the
            # client's FrameAssembler starts receiving shard bytes
            # while the tail of the frame is still encoding (and
            # later-stage STARTs haven't been touched yet)
            start_parts = encode_parts(Start(
                start_layer=a, end_layer=end_layer,
                cluster=plan.cluster_id, params=shard_p,
                batch_stats=shard_s, learning=learning,
                label_counts=label_counts, round_idx=round_idx,
                extra={"epochs": epochs, "sda_size": sda,
                       # strict barriers work at ANY depth: stage-1
                       # feeders fence their epochs (EpochEnd) and
                       # middle stages propagate the marker downstream
                       # after the activations it fences, so the head's
                       # dead-barrier rule sees root-origin fences even
                       # through a deep pipeline
                       "sda_strict": self.cfg.aggregation.sda_strict,
                       # copies of each (origin, epoch) fence this
                       # client must collect before acting on it (head:
                       # record; middle: relay downstream): every
                       # previous-stage device sends/relays one copy,
                       # and only the LAST copy's per-queue FIFO
                       # position proves all activations it fences have
                       # arrived — a single early copy can overtake
                       # batches routed via a slower previous-stage
                       # device.  Stage 2 hears each feeder directly
                       # (one copy).
                       "sda_fence_quorum": (
                           1 if s <= 2
                           else max(1, len(plan.clients[s - 2]))),
                       # the strict head must know its FULL feeder set:
                       # draining leftovers is only safe once every
                       # feeder that could still extend a window has
                       # fenced its epoch — "everyone currently
                       # buffered is done" is not enough (a quiet
                       # feeder may still be mid-batch).  CONSUMERS
                       # only (stages >= 2): feeders are producers,
                       # never drain against the set — and shipping a
                       # 10k-id list inside every stage-1 START was
                       # the O(n^2) half of a fleet-scale fan-out
                       "sda_feeders": (
                           None if s == 1 else
                           ([c for c in stage1
                             if pair_groups.get(c)
                             == pair_groups.get(cid)]
                            if pair_groups else list(stage1))),
                       "n_stages": plan.n_stages,
                       "pair": pair_of.get(cid),
                       "sda_peers": (list(plan.clients[s])
                                     if sda_route and s < plan.n_stages
                                     else None),
                       "refresh": self.cfg.distribution.refresh,
                       # clients adopt the server's run-scoped trace id
                       # so all participants' spans merge onto ONE
                       # trace, across processes
                       "trace_id": self.tracer.trace_id,
                       "delta_base_version": delta_ver,
                       # aggregator tree: publish the round UPDATE to
                       # this group's aggregate queue instead of rpc
                       "agg_group": (group.idx if group is not None
                                     else None),
                       # scheduler-granted per-client knob retunes
                       # (runtime/scheduler.py): e.g. a heavier
                       # activation codec for a wire-slow straggler.
                       # None for undemoted clients and with the
                       # scheduler off — the client's config applies.
                       "sched": (self.scheduler.knobs_for(cid)
                                 if self.scheduler is not None
                                 else None),
                       # hierarchical heartbeat roll-up: the digest
                       # queue this client's beats publish to (its
                       # aggregator node folds them into FleetDigest
                       # frames), None = direct rpc heartbeats
                       "digest": self._digest_route_for(cid),
                       "gen": self._cur_gen}),
                self.cfg.transport.chunk_mb << 20)
            for part in start_parts:
                self.bus.publish(reply_queue(cid), part)  # slcheck: wire=Start
            self.log.sent(f"START -> {cid} layers=[{a}, {end_layer}]"
                          + ("" if sp else " (no weights)"))
        fanout_span.end()
        # round-boundary fan-out wall: with the previous invocation's
        # kind=agg update window this bounds the serial weight-update
        # bubble (finish + re-shard + encode + publish) the clients'
        # sync-overlap ticks hide
        self.log.metric(kind="update", gen=self._cur_gen,
                        round_idx=round_idx, cluster=plan.cluster_id,
                        fanout_s=round(time.time() - fanout_t0, 6),
                        fanout_t0=round(fanout_t0, 6),
                        fanout_t1=round(time.time(), 6),
                        shadow_refresh_s=round(shadow_refresh_s, 6),
                        n_starts=len(active))
        # also surfaced on this invocation's kind=agg record below —
        # note the boundary: this is the cost of the fan-out that
        # OPENED this invocation (delivering the previous fold's
        # params), so the agg record shows the adjacent boundary's
        # shadow-write cost; kind=update above is the exact per-round
        # attribution
        self._fanout_shadow_s = shadow_refresh_s
        if self._delta_shadow is not None:
            # shadow memory audit: bytes pinned by per-client base
            # copies, refreshed whenever the set can have changed
            self.gauges.set("agg_shadow_bytes",
                            self._delta_shadow.nbytes())

        ids = {cid for cid, _ in active}
        with self.tracer.span("ready_wait", round=round_idx):
            ready_ok = self._pump_until(
                lambda: ids <= self._ready,
                lambda: f"READY from {ids - self._ready}",
                deadline=time.monotonic() + self.ready_timeout,
                waiting=lambda: ids - self._ready)
        if not ready_ok:
            ids &= self._ready  # drop unresponsive clients mid-round
        if self._fold is not None and groups is None:
            # flat streaming: stop the reorder window waiting for
            # clients dropped at the READY barrier
            for cid, s in active:
                if cid not in ids:
                    self._fold.drop(s, cid)
        if groups is not None:
            # aggregator tree: dispatch the tree's interior nodes now,
            # with LEVEL-1 membership narrowed to the responsive set
            # (a client dropped at READY will never publish; its
            # aggregator must not hold the group's flush for it).
            # Interior groups keep every child key — child workers
            # always publish, an empty group immediately.
            narrowed = {
                g.idx: ([m for m in g.members if m in ids]
                        if g.level == 1 else list(g.members))
                for g in groups}
            self._tree_narrowed = narrowed
            self._cur_cluster = plan.cluster_id
            node_ids = [n for n in sorted(self._agg_nodes)
                        if not self._node_dead(n)]
            if self._agg.remote and not node_ids:
                self.log.warning(
                    "aggregation.remote: no live aggregator nodes "
                    "adopted — falling back to thread-mode L1s")
            if self._agg.remote and node_ids:
                self._dispatch_remote(plan, groups, narrowed, node_ids,
                                      round_idx)
            else:
                self._spawn_l1_threads(plan, groups, narrowed)
            self._agg_topology = {
                "fan_in": self._agg.fan_in,
                "levels": self._agg.levels,
                "remote": bool(self._l1_remote),
                "gen": self._cur_gen,
                "groups": [{
                    "idx": g.idx, "stage": g.stage, "level": g.level,
                    "parent": g.parent,
                    "members": len(narrowed[g.idx]),
                    "node": next((n for n, gl in
                                  self._l1_remote.items()
                                  if any(x.idx == g.idx for x in gl)),
                                 None)}
                    for g in groups],
            }
            self.log.info(
                f"aggregator tree: {len(groups)} group(s), fan-in "
                f"{self._agg.fan_in}, levels {self._agg.levels}"
                + (f", remote across {len(self._l1_remote)} node(s)"
                   if self._l1_remote else " (threads)"), "cyan")
        stage_of = dict(active)
        syn_span = self.tracer.start("syn_fanout", round=round_idx)
        # strict-SDA liveness under client loss (ADVICE r5): the
        # fence quorum / feeder set sent in START counted the
        # STATIC plan, but a previous-stage client dropped at the
        # READY barrier will never send its fence copies — the
        # static quorum could never be met and the strict drain
        # would stall to round timeout.  Recompute both from the
        # RESPONSIVE set and rebroadcast them with SYN.  Computed for
        # EVERY active client (not just the responsive set): a late
        # READY joiner's pump-sent SYN reuses its entry.
        self._syn_overrides = {}
        # stage-1 clients never consume a feeder set (they produce);
        # building a per-client O(stage1) list for each of them was
        # the other O(n^2) term of a fleet-scale round open — they
        # get (quorum=1, no override) in O(1)
        responsive_s1 = [c for c in stage1 if c in ids]
        for cid, s in active:
            if s == 1:
                self._syn_overrides[cid] = (1, None)
                continue
            quorum = (1 if s <= 2 else max(1, sum(
                1 for c in plan.clients[s - 2] if c in ids)))
            feeders = [c for c in responsive_s1
                       if not pair_groups
                       or pair_groups.get(c) == pair_groups.get(cid)]
            self._syn_overrides[cid] = (quorum, feeders)
        for cid in ids:
            quorum, feeders = self._syn_overrides[cid]
            self.bus.publish(reply_queue(cid), encode(Syn(
                round_idx, sda_fence_quorum=quorum,
                sda_feeders=feeders)))
        self.log.sent(f"SYN -> {sorted(ids)}")
        syn_span.end()
        # async: keep the SYN window open — a straggler's late READY
        # (it was still uploading its previous round) gets its SYN from
        # the pump and joins this round late instead of idling it out
        self._syn_live = self._async
        self._syn_round = round_idx

        s1_ids = set(stage1) & ids
        quorum_n = self.cfg.learning.async_quorum
        # scheduler demotions lower a compute-slow straggler's quorum
        # share: exempt clients don't count toward quorum denominators
        # (their contribution folds late through the widened staleness
        # window instead of holding the round)
        exempt = ({c for c in ids if self.scheduler.quorum_exempt(c)}
                  if self.scheduler is not None else set())
        deadline = time.monotonic() + self.client_timeout
        with self.tracer.span("notify_wait", round=round_idx):
            if self._async and quorum_n:
                # async quorum: the round moves on once enough feeders
                # exhausted their data — a high-RTT feeder finishes its
                # contribution late (stale-admitted next cut) instead
                # of stalling the fleet
                # exempt clients shrink the denominator, floored at 1
                # so a FULLY-exempt stage still owes one NOTIFY — but
                # a genuinely EMPTY stage keeps the old instant-pass
                # (need 0): flooring that case would hang the barrier
                # for the full client_timeout on a set that can never
                # respond
                s1_need = min(max(1, len(s1_ids - exempt))
                              if s1_ids else 0,
                              max(1, quorum_n))
                self._pump_until(
                    lambda: len(self._notified & s1_ids) >= s1_need,
                    f"NOTIFY quorum {s1_need}/{len(s1_ids)}",
                    deadline=deadline,
                    waiting=lambda: s1_ids - self._notified)
            else:
                self._pump_until(
                    lambda: s1_ids - self._sched_gone
                    <= self._notified,
                    "NOTIFY from stage-1 clients",
                    deadline=deadline,
                    waiting=lambda: (s1_ids - self._notified
                                     - self._sched_gone),
                    sched_drop=True)
        pause_span = self.tracer.start("pause_fanout", round=round_idx)
        # late-READY joiners (async) get their PAUSE too — they are
        # training and must upload like everyone else
        pause_ids = set(ids) | (self._ready & {c for c, _ in active})
        for cid in pause_ids:
            if isinstance(send_weights, dict):
                flag = bool(send_weights.get(stage_of[cid], True))
            else:
                flag = bool(send_weights)
            self.bus.publish(reply_queue(cid),
                             encode(Pause(send_weights=flag)))
        self.log.sent(f"PAUSE -> {sorted(pause_ids)}")
        pause_span.end()

        # _agg_gone: members a dead L1 consumed-then-lost — their
        # UPDATE can never arrive, so the barrier stops counting them.
        # fresh_ids folds INCREMENTALLY: re-scanning the whole updates
        # list per predicate evaluation is an O(n^2) barrier over a
        # 10k-client UPDATE wave.
        fresh_seen: set = set()
        fresh_idx = [0]

        def fresh_ids() -> set:
            ups = self._updates
            for u in ups[fresh_idx[0]:]:
                if (u.version if u.version is not None
                        else u.round_idx) == self._cur_gen:
                    fresh_seen.add(u.client_id)
            fresh_idx[0] = len(ups)
            return fresh_seen
        if self._async and quorum_n:
            # bounded-staleness version cut: a new global version cuts
            # once async-quorum FRESH contributions folded; stragglers
            # contribute late through the admission window instead of
            # holding the barrier.  Scheduler-exempt clients shrink
            # the denominator — a demoted compute-slow client's share
            # of the quorum is zero.
            # same floor discipline as the NOTIFY quorum: fully-exempt
            # still owes one fresh fold, genuinely-empty passes
            need = min(max(1, quorum_n),
                       max(1, len(ids - exempt)) if ids else 0)
            got = lambda: len((fresh_ids() & ids)  # noqa: E731
                              | ((self._agg_gone | self._sched_gone)
                                 & ids)) >= need
            missing = lambda: (ids - fresh_ids()  # noqa: E731
                               - self._agg_gone - self._sched_gone)
            what = lambda: (f"UPDATE quorum {need}/{len(ids)} "  # noqa
                            f"(missing {sorted(missing())})")
        else:
            # fresh_ids, NOT the raw barrier list: in async mode a
            # straggler's stale-admitted PREVIOUS-version Update also
            # rides self._updates, and counting it would cut the round
            # without the client's fresh contribution (in sync the two
            # sets are identical — only current-gen Updates fold)
            got = lambda: (fresh_ids() | self._agg_gone  # noqa: E731
                           | self._sched_gone) >= ids
            missing = lambda: (ids - fresh_ids()  # noqa: E731
                               - self._agg_gone - self._sched_gone)
            what = lambda: "UPDATE from " + str(missing())  # noqa
        with self.tracer.span("update_wait", round=round_idx):
            self._pump_until(
                got, what,
                deadline=time.monotonic() + self.client_timeout,
                waiting=missing,
                poll=(self._poll_l1 if self._l1 or self._l1_remote
                      else None),
                sched_drop=True)
        self._syn_live = False
        if self._l1 or self._l1_remote:
            self._finish_l1()
        updates = list(self._updates)
        self._updates = []
        if self._fold is not None:
            # the overlapped fold already consumed (and freed) every
            # tree; what is left is the O(1) divide + optimizer step.
            # The aggregate span carries the overlapped fold wall so
            # sl_trace/sl_perf attribute the phase honestly.
            fold, self._fold = self._fold, None
            m = float(self._agg.server_momentum)
            self._update_t0 = time.time()
            with self.tracer.span(
                    "aggregate", round=round_idx,
                    cluster=plan.cluster_id,
                    overlapped_fold_s=round(fold.fold_s, 6)):
                # fused sharded update (aggregation.update-sharded):
                # each stage's divide+momentum+cast runs as one
                # donated program, all stages dispatched before any
                # fetch — stage k's single device->host fetch overlaps
                # stage k+1's device compute.  The on_stage hook marks
                # each stage's completion on the aggregate span so
                # sl_trace shows the per-shard pipeline.
                result = fold.finish(
                    base=params if m else None, momentum=m,
                    velocity=(self._agg_velocity.setdefault(
                        plan.cluster_id, {}) if m else None),
                    fused=self._agg.update_sharded,
                    on_stage=lambda s, p, st: self.tracer.record(
                        "update_stage", time.time(), time.time(),
                        round=round_idx, stage=s))
            self._update_t1 = time.time()
            updates = agg_plane.UpdateBatch(updates)
            updates.fold = result
            self.log.metric(
                kind="agg", gen=self._cur_gen, round_idx=round_idx,
                cluster=plan.cluster_id,
                backend=(self._fold_backend.name
                         if self._fold_backend is not None else "host"),
                fan_in=(self._agg.fan_in if groups is not None else 0),
                levels=(self._agg.levels if groups is not None else 0),
                remote_nodes=len(self._l1_remote),
                node_deaths=len(self._dead_nodes),
                # rpc-wire bytes of the PartialAggregate frames that
                # landed at this root (chunked streams fully counted)
                # — the ingress the partial codec exists to shrink
                root_ingress_bytes=self._agg_ingress_bytes,
                partial_codec=(None if self._partial_codec is None
                               else self._partial_codec.kind),
                fold_s=result.fold_s, folded=result.folded,
                partials=result.partials,
                window_hwm=result.window_hwm,
                peak_tree_copies=result.peak_tree_copies,
                n_samples=result.n_samples,
                # round-boundary update wall (divide + FedAvgM + cast
                # + per-stage fetch) — the serial bubble the sharded
                # update shrinks and the clients' sync-overlap hides.
                # Wall-clock t0/t1 let the bench intersect this window
                # with client overlap activity on the same host clock.
                update_sharded=bool(self._agg.update_sharded),
                update_s=result.update_s,
                update_t0=round(self._update_t0, 6),
                update_t1=round(self._update_t1, 6),
                stage_update_ms=result.stage_update_ms,
                shadow_refresh_s=round(
                    getattr(self, "_fanout_shadow_s", 0.0), 6))
            self.log.info(
                f"streamed aggregate: folded={result.folded} "
                f"(partials={result.partials}) fold={result.fold_s:.3f}s"
                f" peak_tree_copies={result.peak_tree_copies:g}",
                "cyan")
            self._l1 = []
            self._l1_fallback = {}
            self._l1_remote = {}
        # elastic liveness bookkeeping, folded per ROUND at the next
        # refresh_plans: any UPDATE during the round marks a client
        # alive even if it sat out other invocations of a sequential
        # strategy (topology.elastic-join)
        responded = {u.client_id for u in updates}
        self._round_alive |= responded
        self._round_silent |= {cid for cid, _ in active} - responded
        # wire audit: CUMULATIVE transport-wide publish bytes by queue
        # kind (reply_* = server control/weights down; rpc = client
        # control/weights up; data = activation/gradient plane).  On the
        # shared in-process bus this covers every participant; over TCP
        # each process's transport counts its own publishes.  Consumers
        # should diff successive records — values never reset.
        totals = {"reply": 0, "rpc": 0, "data": 0}
        for q, n in self.bus.bytes_out_snapshot().items():
            kind = ("reply" if q.startswith("reply_")
                    else "rpc" if q == RPC_QUEUE else "data")
            totals[kind] += n
        # per-process wire counters ride the same record (bytes in/out
        # by plane, encode/decode seconds, async sender high-water
        # mark) and the end-of-round log line, so the wire's cost is
        # auditable next to its volume
        wsnap = {k: v for k, v in self.wire.snapshot().items() if v}
        self.log.metric(kind="wire", gen=self._cur_gen,
                        round_idx=round_idx, cluster=plan.cluster_id,
                        cumulative_reply_bytes=totals["reply"],
                        cumulative_rpc_bytes=totals["rpc"],
                        cumulative_data_bytes=totals["data"],
                        **wsnap)
        if wsnap:
            self.log.info(
                "round wire (cumulative): "
                f"out={wsnap.get('bytes_out_total', 0)}B "
                f"in={wsnap.get('bytes_in_total', 0)}B "
                f"encode={wsnap.get('encode_s', 0):.3f}s "
                f"decode={wsnap.get('decode_s', 0):.3f}s "
                f"sendq_hwm={wsnap.get('send_queue_hwm', 0)}")
        # failure/recovery observability: CUMULATIVE fault counters
        # (drops, timeouts, redeliveries, dedup_hits, reconnects, ...)
        # from this process's transport stack — chaos runs must be
        # auditable, not silently self-healing.  Same diff-successive-
        # records contract as the wire bytes above.  Logged only when
        # something actually happened, so clean runs stay clean.
        snap = {k: v for k, v in self.faults.snapshot().items() if v}
        if snap:
            if snap != self._fault_base:
                self.log.info(
                    "round faults (cumulative): "
                    + " ".join(f"{k}={v}"
                               for k, v in sorted(snap.items())),
                    "yellow")
                self._fault_base = snap
            self.log.metric(kind="faults", gen=self._cur_gen,
                            round_idx=round_idx,
                            cluster=plan.cluster_id, **snap)
        # latency percentiles: this process's histograms (frame RTT,
        # step, encode/decode) merged with the process-wide transport
        # clocks (broker queue-wait, reliable-envelope RTT), which have
        # no per-participant registry in reach.  Cumulative — diff
        # successive records like every counter above.
        from split_learning_tpu.runtime.trace import default_histograms
        hsnap = {**default_histograms.snapshot(),
                 **self.hists.snapshot()}
        if hsnap and hsnap != getattr(self, "_hist_base", None):
            self._hist_base = hsnap
            self.log.metric(kind="latency", gen=self._cur_gen,
                            round_idx=round_idx,
                            cluster=plan.cluster_id, **hsnap)
        # fleet health at round end: one kind=fleet metrics record (the
        # per-client states, rates, straggler scores AND the latest
        # counter snapshots each heartbeat flushed — so a client that
        # crashed mid-round still has its counters on disk) plus a
        # one-line summary.  Same per-invocation cadence as the wire/
        # fault records above.
        if self.fleet is not None:
            # drain queued-but-unpumped heartbeats first so the record
            # reflects what clients SENT, not when we last listened
            while self._pump_one(timeout=0.0):
                pass
            self.fleet.advance()
            fsnap = self.fleet.snapshot()
            if self.scheduler is not None:
                # mirror the /fleet scheduler view into the journaled
                # record so sl_top --journal renders the same
                # CLUSTER/SCHED columns as the live endpoint
                self.scheduler.annotate_fleet(fsnap)
            self.log.metric(kind="fleet", gen=self._cur_gen,
                            round_idx=round_idx,
                            cluster=plan.cluster_id, fleet=fsnap)
            counts = fsnap["counts"]
            unhealthy = {c: v["state"]
                         for c, v in fsnap["clients"].items()
                         if v["state"] != "healthy"}
            line = ("fleet: " + " ".join(
                f"{s}={n}" for s, n in counts.items() if n))
            if unhealthy:
                line += " (" + " ".join(
                    f"{c}:{s}" for c, s in sorted(unhealthy.items())) \
                    + ")"
            self.log.info(line, "yellow" if unhealthy else "cyan")
        # a finished invocation's spans must be durable before the next
        # one (or a crash) — the journal buffers between flushes
        self.tracer.flush()
        return updates

    def stop_all(self, reason: str = "training complete"):
        for reg in self.registrations:
            self.bus.publish(reply_queue(reg.client_id),
                             encode(Stop(reason=reason)))
        for nid in self._agg_nodes:
            self.bus.publish(reply_queue(nid),
                             encode(Stop(reason=reason)))
        for hid in self._stage_hosts:
            self.bus.publish(reply_queue(hid),
                             encode(Stop(reason=reason)))
        # the STOP fan-out must actually leave this process before the
        # caller tears the broker down
        flush = getattr(self.bus, "flush", None)
        if flush is not None:
            flush(timeout=10.0)
        self.log.sent(f"STOP -> all ({reason})")
        for l1_log in self._l1_logs.values():
            l1_log.close()
        self._l1_logs = {}
        self.tracer.close()


def _np_tree(tree: Any) -> Any:
    import jax
    return jax.tree_util.tree_map(np.asarray, tree)


class ProtocolServer:
    """Top-level server process (reference ``server.py:20-30``)."""

    def __init__(self, cfg: Config, transport: Transport | None = None,
                 logger: Logger | None = None,
                 client_timeout: float = 600.0,
                 ready_timeout: float | None = None):
        self.cfg = cfg
        self.log = logger or Logger.for_run(cfg, "server",
                                            console=True)
        if transport is None:
            from split_learning_tpu.runtime.chaos import (
                make_runtime_transport,
            )
            transport = make_runtime_transport(cfg, "server")
        bus = transport
        bus.purge()   # queue hygiene at startup (src/Utils.py:8-32)
        self.ctx = ProtocolContext(cfg, bus, logger=self.log,
                                   client_timeout=client_timeout,
                                   ready_timeout=ready_timeout)
        # aggregation.nodes: spawn the aggregator subprocesses this
        # deployment wants (tcp only — validated at config load); the
        # nodes connect to the broker, AggHello into the rpc pump, and
        # are adopted before planning (serve() waits for them)
        self._spawned_nodes: list = []
        if cfg.aggregation.remote and cfg.aggregation.nodes:
            import pathlib

            from split_learning_tpu.runtime.aggnode import (
                spawn_node, write_node_config,
            )
            cfg_path = pathlib.Path(
                getattr(self.log, "output_dir", None)
                or cfg.log_path) / "aggregator_config.json"
            write_node_config(cfg, cfg_path)
            for i in range(cfg.aggregation.nodes):
                nid = f"aggregator_node_{i}"
                proc = spawn_node(cfg_path, nid)
                self.ctx._agg_nodes.setdefault(nid, {})["proc"] = proc
                self._spawned_nodes.append(proc)
            self.log.info(
                f"spawned {cfg.aggregation.nodes} aggregator "
                "node(s)", "cyan")
        # pipeline.hosts: spawn the stage-host subprocesses this
        # deployment wants (tcp only — validated at config load); the
        # hosts connect to the broker, StageHello into the rpc pump,
        # and are adopted + assigned before the registration barrier
        # (their inner clients ARE the later-stage registrations)
        self._spawned_hosts: list = []
        if cfg.pipeline.remote and cfg.pipeline.hosts:
            import pathlib

            from split_learning_tpu.runtime.stagehost import (
                spawn_stage_host, write_host_config,
            )
            cfg_path = pathlib.Path(
                getattr(self.log, "output_dir", None)
                or cfg.log_path) / "stagehost_config.json"
            write_host_config(cfg, cfg_path)
            ncpu = os.cpu_count() or 1
            for i in range(cfg.pipeline.hosts):
                hid = f"stage_host_{i}"
                # pin_cpus: one core per host, core 0 left to the
                # server + feeders — placement-stable measurement
                cpu = ((i + 1) % ncpu
                       if cfg.pipeline.pin_cpus and ncpu > 1 else None)
                proc = spawn_stage_host(cfg_path, hid, cpu=cpu)
                self.ctx._stage_hosts.setdefault(hid, {})["proc"] = proc
                self._spawned_hosts.append(proc)
            self.log.info(
                f"spawned {cfg.pipeline.hosts} stage host(s)", "cyan")
        # real-time export (observability.http-port): /metrics serves
        # Prometheus text, /fleet the JSON health snapshot — what
        # tools/sl_top.py polls for the live terminal view.  Render
        # callbacks advance the monitor first so a mid-wait scrape
        # sees current health states, not the last pump's.
        self.exporter = None
        obs = getattr(cfg, "observability", None)
        # on-demand profiler hook (runtime/perf.py): POST /profile
        # arms a jax.profiler window the round loop opens at the next
        # round boundary; artifact lands under the run-scoped
        # profile/ directory.  Attached to the context so run_training
        # drives the window whatever backend is underneath.
        from split_learning_tpu.runtime.perf import (
            ProfileCapture, profile_output_dir, register_process_capture,
        )
        self.ctx.perf_capture = ProfileCapture(
            profile_output_dir(cfg, self.log), log=self.log)
        # in-process cells (client threads sharing this process) tick
        # this capture from their hot loops, closing a steps=K window
        # after K steps; separate client processes can't — there the
        # round boundary closes it (see register_process_capture)
        register_process_capture(self.ctx.perf_capture)
        # broker-plane self-telemetry (broker.shards): each shard's
        # event loop serves a stats frame on its control queue; the
        # server sweeps the plane at most every broker.stats-interval
        # seconds, mirrors plane-wide sums into the broker_* gauges
        # (so /metrics carries them) and hands the per-shard rows to
        # /fleet, where sl_top renders them as ROLE=broker rows
        self._broker_stats_cache: dict = {"t": 0.0, "stats": None,
                                          "busy": False}

        def _refresh_broker_stats() -> None:
            from split_learning_tpu.runtime.bus import (
                collect_broker_stats,
            )
            cache = self._broker_stats_cache
            try:
                stats = collect_broker_stats(
                    cfg.transport.host, cfg.transport.port,
                    cfg.broker.shards)
                cache["stats"], cache["t"] = stats, time.monotonic()
                live = [s for s in stats if "error" not in s]
                g = self.ctx.gauges
                g.set("broker_shards_up", len(live))
                for gauge, key in (
                        ("broker_conns", "conns"),
                        ("broker_queues", "queues"),
                        ("broker_depth", "depth"),
                        ("broker_depth_hwm", "depth_hwm"),
                        ("broker_parked_gets", "parked_gets"),
                        ("broker_bytes_in", "bytes_in"),
                        ("broker_bytes_out", "bytes_out")):
                    g.set(gauge, sum(s.get(key, 0) for s in live))
            finally:
                cache["busy"] = False

        def _broker_stats() -> list | None:
            """Cached shard-stats rows; a stale cache triggers an
            ASYNC refresh and serves the previous sweep — dialing the
            shards inline would add their connect latency to every
            /fleet scrape (observed as scraper-side timeouts while a
            compile starves the exporter threads)."""
            if (cfg.transport.kind != "tcp"
                    or cfg.broker.stats_interval <= 0):
                return None
            cache = self._broker_stats_cache
            now = time.monotonic()
            if (now - cache["t"] >= cfg.broker.stats_interval
                    and not cache["busy"]):
                cache["busy"] = True
                threading.Thread(target=_refresh_broker_stats,
                                 daemon=True,
                                 name="broker-stats").start()
            return cache["stats"]

        self._broker_stats = _broker_stats
        if obs is not None and obs.http_port is not None:
            from split_learning_tpu.runtime.telemetry import (
                TelemetryExporter, render_prometheus,
            )
            ctx = self.ctx

            def _metrics() -> str:
                if ctx.fleet is not None:
                    ctx.fleet.advance()
                _broker_stats()   # refresh the broker_* gauges
                return render_prometheus(
                    fleet=ctx.fleet, faults=ctx.faults, wire=ctx.wire,
                    hists=ctx.hists, gauges=ctx.gauges,
                    max_client_series=obs.max_client_series)

            def _fleet(query: dict | None = None) -> dict:
                query = query or {}
                if ctx.fleet is None:
                    snap = {"clients": {}, "counts": {},
                            "transitions": []}
                else:
                    ctx.fleet.advance()
                    # default shape: full detail (series included)
                    # while the tracked population is small; summary
                    # (no ring-buffer series) past the series cap.
                    # ?full=1 forces the old shape, ?page=N /
                    # ?client=id fetch per-client detail on demand.
                    full = str(query.get("full", "")) \
                        in ("1", "true", "yes")
                    client = query.get("client")
                    page = None
                    try:
                        if query.get("page") is not None:
                            page = int(query["page"])
                    except (TypeError, ValueError):
                        page = None
                    big = (ctx.fleet.tracked_clients()
                           > obs.max_client_series)
                    snap = ctx.fleet.snapshot(
                        series=full or not big,
                        page=page, client=client)
                # aggregator-tree topology (aggregation.fan-in /
                # levels / remote): which node serves which group, so
                # straggler attribution can NAME a slow L1 instead of
                # pointing at "the aggregate phase"
                if ctx._agg_topology is not None:
                    snap["agg_tree"] = ctx._agg_topology
                # closed-loop scheduler view (runtime/scheduler.py):
                # the current online-cluster map and last re-plan
                # decision, plus per-client CLUSTER/SCHED fields so
                # straggler attribution can name WHY a client was
                # evicted/demoted (sl_top renders both columns)
                if ctx.scheduler is not None:
                    ctx.scheduler.annotate_fleet(snap)
                # sharded broker plane: per-shard stats rows (sl_top
                # ROLE=broker) — cached, so scrapes don't hammer the
                # shards' control queues
                brokers = _broker_stats()
                if brokers is not None:
                    snap["brokers"] = brokers
                return snap

            self.exporter = TelemetryExporter(
                _metrics, _fleet, port=int(obs.http_port),
                profile_fn=self.ctx.perf_capture.arm).start()
            self.log.info("telemetry: serving /metrics, /fleet and "
                          f"POST /profile on {self.exporter.url}",
                          "cyan")

    def serve(self) -> TrainResult:
        from split_learning_tpu.parallel.multihost import (
            ensure_initialized,
        )
        ensure_initialized()
        if self.cfg.pipeline.remote:
            # adopt stage hosts and deal the later-stage slots BEFORE
            # the registration barrier: the slots' inner clients are
            # the later-stage registrations the barrier counts, so no
            # host = the barrier can never release.  Zero adopted
            # hosts is therefore fatal, not a warning.
            ctx = self.ctx
            want = max(int(self.cfg.pipeline.hosts), 1)

            def helloed() -> int:
                return sum(1 for e in ctx._stage_hosts.values()
                           if "t" in e)
            ctx._pump_until(
                lambda: helloed() >= want,
                lambda: (f"stage host adoption "
                         f"({helloed()}/{want} helloed)"),
                deadline=time.monotonic() + 60.0)
            if not helloed():
                raise RoundTimeout(
                    "pipeline.remote: no stage host announced itself "
                    "within 60s — start hosts with `python -m "
                    "split_learning_tpu.stagehost` or set "
                    "pipeline.hosts")
            self.log.info(
                f"stage hosts adopted: {helloed()}/{want}", "cyan")
            ctx.assign_stage_slots()
        regs = self.ctx.wait_for_registrations()
        if self.cfg.aggregation.remote:
            # adopt aggregator nodes before the first round: spawned
            # subprocesses are still importing; externally-started
            # ones may hello any time.  A miss is a warning, not a
            # failure — the tree falls back to thread-mode L1s.
            ctx = self.ctx
            want = max(int(self.cfg.aggregation.nodes), 1)

            def adopted() -> int:
                return sum(1 for e in ctx._agg_nodes.values()
                           if "t" in e)
            ctx._pump_until(
                lambda: adopted() >= want,
                lambda: (f"aggregator node adoption "
                         f"({adopted()}/{want} helloed)"),
                deadline=time.monotonic() + 60.0)
            self.log.info(
                f"aggregator nodes adopted: {adopted()}/{want}",
                "cyan")
        # elastic deployments may have spares beyond the configured
        # counts at startup; plan whoever is there
        with self.ctx.tracer.span("plan"):
            plans = plan_clusters(
                self.cfg, regs,
                exact_counts=not self.cfg.topology.elastic_join)
        try:
            result = run_training(self.cfg, self.ctx, plans, self.log)
        finally:
            self.ctx.stop_all()
            from split_learning_tpu.runtime.perf import (
                process_capture, register_process_capture,
            )
            # only clear our own registration: a newer server in this
            # process may already have registered its capture
            if process_capture() is self.ctx.perf_capture:
                register_process_capture(None)
            if self.exporter is not None:
                self.exporter.close()
            for proc in self._spawned_nodes + self._spawned_hosts:
                # STOP already fanned out (stop_all); give each child
                # a moment to exit cleanly, then make sure
                try:
                    proc.wait(timeout=5.0)
                except Exception:  # noqa: BLE001 — still running
                    proc.terminate()
                    try:
                        proc.wait(timeout=5.0)
                    except Exception:  # noqa: BLE001 — last resort
                        proc.kill()
        return result


def main(argv=None):
    from split_learning_tpu.platform import (
        apply_compile_cache, apply_platform_env,
    )
    apply_platform_env()
    apply_compile_cache()
    ap = argparse.ArgumentParser(
        description="Split-learning protocol server (reference server.py "
                    "parity).")
    ap.add_argument("--config", default="config.yaml")
    ap.add_argument("--broker", action="store_true",
                    help="also host the TCP broker in this process "
                         "(broker.shards > 1 hosts every shard of "
                         "the plane on consecutive ports)")
    ap.add_argument("--client_timeout", type=float, default=600.0)
    ap.add_argument("--ready_timeout", type=float, default=None,
                    help="registration/READY barrier deadline "
                         "(default: --client_timeout)")
    args = ap.parse_args(argv)
    cfg = from_yaml(args.config)
    blackbox.install(cfg, "server", role="server")
    brokers = []
    if args.broker and cfg.transport.kind == "tcp":
        # each shard is its own O(1)-thread event loop; hosting N of
        # them in-process keeps the single-command dev deployment
        # working with broker.shards > 1 (production runs them as
        # separate processes: python -m split_learning_tpu.broker
        # --shards N)
        brokers = [Broker(cfg.transport.host, cfg.transport.port + i,
                          shard_id=f"shard_{i}")
                   for i in range(cfg.broker.shards)]
    try:
        server = ProtocolServer(cfg, client_timeout=args.client_timeout,
                                ready_timeout=args.ready_timeout)
        result = server.serve()
        for rec in result.history:
            acc = (f" val_acc={rec.val_accuracy:.4f}"
                   if rec.val_accuracy is not None else "")
            print(f"round {rec.round_idx}: ok={rec.ok} "
                  f"samples={rec.num_samples}{acc}")
    finally:
        for broker in brokers:
            broker.close()


if __name__ == "__main__":
    main()
