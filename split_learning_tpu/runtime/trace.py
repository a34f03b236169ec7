"""Runtime tracing: per-phase step timing, counters and histograms.

The reference's only observability is tqdm bars, per-batch loss prints,
and a one-shot message-size probe (SURVEY.md §5.1); its in-message
``trace`` field is routing state, not tracing.  Here:

* :class:`StepTimer` — named wall-clock phase accumulators with
  ``jax.block_until_ready`` fencing, dumped as a metrics dict (feeds the
  metrics.jsonl sidecar, ``runtime/log.py``);
* :class:`FaultCounters` — thread-safe failure/recovery counters
  (``drops``, ``timeouts``, ``redeliveries``, ``dedup_hits``,
  ``reconnects``, ...) shared by the transport stack
  (``runtime/bus.py`` reliability layer, ``runtime/chaos.py`` fault
  injection, TCP reconnect) and surfaced by the protocol server into
  ``metrics.jsonl`` and its end-of-round log line, so chaos runs are
  observable instead of silently self-healing;
* :class:`LatencyHistogram` / :class:`HistogramSet` — fixed-bucket
  (log-spaced) latency histograms for frame RTT, broker queue wait,
  step time and encode/decode, surfaced as ``kind: latency``
  metrics.jsonl records next to the counters;
* :data:`FAULT_COUNTER_NAMES` / :data:`HISTOGRAM_NAMES` /
  :data:`GAUGE_NAMES` — the declared name registries the ``counters``
  slcheck analyzer holds every ``.inc``/``.observe``/``.set`` call
  site to (typo'd names silently mint dead keys otherwise).  The
  ``GaugeSet`` type the gauge registry covers lives in
  ``runtime/telemetry.py`` with the rest of the live telemetry plane.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import math
import threading
import time

import jax

from split_learning_tpu.runtime import blackbox

#: Declared registry of every FaultCounters name the runtime may
#: increment.  ``FaultCounters.inc`` with a string literal outside this
#: set is a typo that would silently mint a new key (and a dashboard
#: nobody reads) — the ``counters`` slcheck analyzer
#: (``analysis/counters.py``) enforces membership statically.
FAULT_COUNTER_NAMES = frozenset({
    # chaos injection (runtime/chaos.py)
    "drops", "duplicates", "reorders", "corruptions", "delays",
    "crashes", "late_drops",
    # reliable delivery (runtime/bus.py ReliableTransport)
    "redeliveries", "dedup_hits", "resequenced", "lost", "gave_up",
    "daemon_errors", "ack_send_failures", "corrupt_rejected",
    # transport plumbing
    "reconnects", "timeouts", "async_send_errors", "prefetch_errors",
    # live telemetry plane (runtime/telemetry.py): heartbeat publishes
    # that failed, duplicate/reordered heartbeats the fleet monitor
    # rejected as stale, and barrier waits cut short because every
    # missing client was health-state `lost`
    "heartbeat_errors", "stale_heartbeats", "fleet_lost_drops",
    # hierarchical digest roll-up (runtime/sketch.py +
    # observability.digest-interval): duplicate/reordered FleetDigest
    # frames the server's seq guard rejected, and clients re-pointed
    # to direct heartbeats because their digest node died (one inc per
    # re-pointed client — the chaos cell's exact fallback count)
    "stale_digests", "digest_fallbacks",
    # wire codecs (runtime/codec/): non-finite payloads crossing the
    # quantizer, top-k leaves too small to sparsify, and the delta
    # codec's fold/full-frame/version-gap outcomes
    "quant_nonfinite", "topk_dense_fallbacks",
    "delta_folds", "delta_full_frames", "delta_resyncs",
    # performance-attribution plane (runtime/perf.py CompileWatch):
    # compiles observed after round 0 — the live twin of slcheck's
    # static retrace rule; rendered as sl_retraces_total on /metrics
    "retraces",
    # streaming aggregation plane (runtime/aggregate.py): duplicate
    # contributions the fold refused to double-weight, stale-generation
    # frames dropped at an L1 or the fallback drain, L1 aggregators
    # that died mid-round and degraded to the direct-to-root drain,
    # and group members abandoned because a dead L1 consumed their
    # UPDATE frames before dying (one inc per member)
    "agg_dup_drops", "agg_stale_drops", "agg_l1_fallbacks",
    "agg_fallback_abandons",
    # multi-process aggregator tree (runtime/aggnode.py): frames whose
    # ASSEMBLED chunked size broke the broker-cap twin in
    # FrameAssembler, partial frames a node/root could not decode
    # through the partial codec (missing/mismatched delta base), and
    # remote aggregator nodes declared dead (child exit or
    # FleetMonitor lost) whose groups fell back to the root drain
    "oversize_frames", "partial_codec_errors", "agg_node_deaths",
    # sync-mode round-boundary overlap (runtime/client.py
    # _sync_overlap_ticks): speculative caches the next START consumed
    # (spliced) vs invalidated-and-unwound (discarded)
    "overlap_splices", "overlap_discards",
    # async bounded-staleness admission window (runtime/server.py
    # _admit_update): contributions folded late with a decayed weight
    # (server_version - version <= learning.max-staleness), and
    # contributions past the window rejected and dropped
    "agg_stale_admits", "agg_stale_updates",
    # closed-loop scheduler (runtime/scheduler.py): clients evicted
    # through the elastic path, demoted with retuned knobs, adopted
    # cut re-plans, straggler clients a NOTIFY/UPDATE barrier dropped
    # mid-round after the scheduler grace, clients moved between
    # online clusters, and knob frames a client rejected (bad spec)
    "sched_evictions", "sched_demotions", "sched_replans",
    "sched_barrier_drops", "sched_cluster_moves",
    "sched_knob_rejects",
    # scheduler-driven aggregator fan-in retuning (ROADMAP item 1, 1M
    # tier): adopted aggregation.fan-in changes driven by measured
    # kind=agg_node fold walls
    "sched_fanin_retunes",
    # MPMD cross-host stage pipeline (runtime/stagehost.py +
    # pipeline.remote): stage hosts declared dead mid-round (child
    # exit or FleetMonitor lost), and later-stage client slots moved
    # to a surviving host (one inc per slot — the chaos cell's exact
    # fallback count), after which the invocation re-runs under a
    # fresh generation
    "stage_host_deaths", "stage_reassigns",
})

#: Declared registry of latency-histogram names (same contract as
#: FAULT_COUNTER_NAMES, enforced on ``.observe("name", ...)`` sites).
HISTOGRAM_NAMES = frozenset({
    "frame_rtt",       # publish wire-context t_send -> consume decode
    "queue_wait",      # broker enqueue -> dequeue (InProcTransport)
    "transport_rtt",   # reliable envelope t_send -> receiver pop
    "step",            # one hot-loop training step (bwd+apply / window)
    "encode",          # frame encode (device fetch + TENSOR framing)
    "decode",          # frame decode (assembler feed)
    # performance-attribution plane (runtime/perf.py StepTimer):
    # per-step dispatch wall (every step) and dispatch+device wall
    # (sampled steps only — the fenced ones)
    "step_dispatch", "step_device",
    # streaming aggregation plane (runtime/aggregate.py): wall of one
    # contribution's fold into the running sum (per Update / partial)
    "agg_fold",
})

#: Declared registry of gauge names (``runtime/telemetry.py GaugeSet``;
#: same contract as the two registries above, enforced on
#: ``.set("name", ...)`` sites by the ``counters`` analyzer CT003).
#: Unlike the counters/histograms, gauges are LAST-VALUE semantics:
#: each set overwrites, snapshots report the current value.
GAUGE_NAMES = frozenset({
    # client-side (set by the hot loops + heartbeat emitter)
    "round",           # current round index (set at SYN)
    "epoch",           # current local epoch within the round
    "inflight",        # stage-1 1F1B in-flight window depth
    "samples_per_s",   # EWMA training throughput (emitter tick)
    # performance-attribution plane (runtime/perf.py): model-FLOPs
    # utilization vs the datasheet peak, last sampled step's wall,
    # peak device bytes, cumulative compile wall, and samples/s over
    # device-busy time (distinguishes slow-compute from slow-wire)
    "mfu", "step_seconds", "hbm_peak_bytes", "compile_seconds_total",
    "compute_samples_per_s",
    # server-side (set by the FleetMonitor on every advance)
    "fleet_size", "fleet_healthy", "fleet_degraded",
    "fleet_straggler", "fleet_lost",
    # streaming aggregation plane (runtime/aggregate.py): host bytes
    # pinned by the delta codec's per-client shadow trees — the memory
    # the `lost`-client prune and elastic prune reclaim
    "agg_shadow_bytes",
    # standalone aggregator nodes (runtime/aggnode.py), set per round
    # and ridden on the node's heartbeats so /fleet and sl_top can
    # attribute a slow L1: contributions folded, wire bytes in/out of
    # the node's fold worker, and the round's fold wall
    "agg_node_folded", "agg_node_ingress_bytes",
    "agg_node_egress_bytes", "agg_node_fold_s", "agg_node_groups",
    # closed-loop scheduler (runtime/scheduler.py): wall milliseconds
    # of the last round-boundary decision pass (the control-plane cost
    # the 10k-client bench key pins flat), and the live online-cluster
    # count
    "sched_decision_ms", "sched_clusters",
    # hierarchical digest roll-up (runtime/sketch.py DIGEST_GAUGE_NAMES
    # — CT004 holds that registry to this one): nodes currently
    # reporting digests, clients covered by those digests, and the
    # server watchlist's size (the bounded exact-state population)
    "fleet_digest_nodes", "fleet_digest_clients", "fleet_watchlist",
    # sharded broker plane (runtime/bus.py Broker stats frames, polled
    # by the server's /fleet "brokers" sweep): shard processes
    # answering their stats control queue, and the plane-wide sums of
    # their connection counts, live queues, stored depth (+ high
    # water), parked GET continuations and wire bytes
    "broker_shards_up", "broker_conns", "broker_queues",
    "broker_depth", "broker_depth_hwm", "broker_parked_gets",
    "broker_bytes_in", "broker_bytes_out",
    # MPMD stage pipeline (runtime/client.py later-stage hot loops +
    # runtime/stagehost.py): a later-stage client's local ingest
    # backlog (buffered SDA window batches at the head, awaiting-
    # gradient in-flight entries at a middle stage), and the slot
    # count a stage host is currently running — both ride heartbeats
    # so sl_top can name a backed-up hop
    "queue_depth", "stage_slots",
    # flight recorder (runtime/blackbox.py): ring depth and seconds
    # since the participant's last dump, ridden on heartbeats so
    # /fleet and sl_top's BLACKBOX column can show per-participant
    # capture state (-1 age = never dumped)
    "blackbox_ring_depth", "blackbox_last_dump_age_s",
})


class FaultCounters:
    """Monotonic named counters; values never reset during a run, so
    consumers diff successive snapshots (same contract as the server's
    cumulative wire-byte metrics)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: collections.Counter = collections.Counter()

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] += n
        # flight-recorder feed (runtime/blackbox.py): every counter
        # increment is a "something abnormal was absorbed" event — the
        # per-process ring keeps the last N with timestamps, which is
        # the ordering the monotonic totals erase
        if blackbox.enabled():
            blackbox.record("fault", name=name, n=n)

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._counts)

    def total(self) -> int:
        with self._lock:
            return sum(self._counts.values())


#: process-wide default registry: every transport wrapper created without
#: an explicit ``faults=`` lands here, so one process's server sees its
#: clients' counters too in single-process (inproc) deployments
default_fault_counters = FaultCounters()


class WireCounters:
    """Thread-safe wire-traffic counters (same monotonic contract as
    :class:`FaultCounters`: values never reset, consumers diff
    successive snapshots): bytes sent/received per queue, cumulative
    encode/decode seconds, and the async sender-queue high-water mark.
    Fed by the transport stack (``runtime/bus.py AsyncTransport``) and
    the protocol codec call sites; surfaced into ``metrics.jsonl`` by
    the server's end-of-round summary and each client's round-end
    record."""

    #: queue-name prefixes classified as data-plane traffic
    _DATA_PREFIXES = ("intermediate_queue", "gradient_queue")

    def __init__(self):
        self._lock = threading.Lock()
        self._bytes_out: collections.Counter = collections.Counter()
        self._bytes_in: collections.Counter = collections.Counter()
        self._raw_bytes_out: collections.Counter = collections.Counter()
        self._msgs_out = 0
        self._msgs_in = 0
        self._encode_s = 0.0
        self._encode_n = 0
        self._decode_s = 0.0
        self._decode_n = 0
        self._send_queue_hwm = 0

    def count_out(self, queue: str, nbytes: int) -> None:
        with self._lock:
            self._bytes_out[queue] += nbytes
            self._msgs_out += 1

    def count_raw(self, queue: str, nbytes: int) -> None:
        """Pre-codec dense-equivalent bytes of a payload published on
        ``queue`` (what the plain wire-dtype path would have moved):
        the denominator of the wire compression ratio.  Only codec
        paths count here, so zero means no codec was active."""
        with self._lock:
            self._raw_bytes_out[queue] += nbytes

    def count_in(self, queue: str, nbytes: int) -> None:
        with self._lock:
            self._bytes_in[queue] += nbytes
            self._msgs_in += 1

    def add_encode(self, seconds: float) -> None:
        with self._lock:
            self._encode_s += seconds
            self._encode_n += 1

    def add_decode(self, seconds: float) -> None:
        with self._lock:
            self._decode_s += seconds
            self._decode_n += 1

    def note_send_depth(self, depth: int) -> None:
        with self._lock:
            if depth > self._send_queue_hwm:
                self._send_queue_hwm = depth

    def per_queue(self) -> dict:
        with self._lock:
            return {"bytes_out": dict(self._bytes_out),
                    "bytes_in": dict(self._bytes_in)}

    def _data_bytes(self, counter) -> int:
        return sum(n for q, n in counter.items()
                   if q.startswith(self._DATA_PREFIXES))

    def snapshot(self) -> dict:
        """Flat record for metrics.jsonl (zero-valued fields included —
        callers prune)."""
        with self._lock:
            return {
                "bytes_out_total": sum(self._bytes_out.values()),
                "bytes_in_total": sum(self._bytes_in.values()),
                "raw_bytes_out": sum(self._raw_bytes_out.values()),
                "data_bytes_out": self._data_bytes(self._bytes_out),
                "data_bytes_in": self._data_bytes(self._bytes_in),
                "data_raw_bytes_out": self._data_bytes(
                    self._raw_bytes_out),
                "msgs_out": self._msgs_out,
                "msgs_in": self._msgs_in,
                "encode_s": round(self._encode_s, 6),
                "encode_n": self._encode_n,
                "decode_s": round(self._decode_s, 6),
                "decode_n": self._decode_n,
                "send_queue_hwm": self._send_queue_hwm,
            }


#: process-wide default, mirroring ``default_fault_counters``
default_wire_counters = WireCounters()


class LatencyHistogram:
    """Fixed-bucket latency histogram: log-spaced bounds from 1 µs to
    ~64 s (factor 2^0.25 per bucket, so a reported percentile is within
    ~19% of the true value), O(log buckets) per observe, thread-safe.
    Monotonic like the counters above: never reset, consumers diff
    successive snapshots."""

    #: geometric bucket upper bounds (seconds); one overflow bucket past
    #: the last bound
    BOUNDS = tuple(1e-6 * (2 ** (i / 4)) for i in range(104))

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = [0] * (len(self.BOUNDS) + 1)
        self._n = 0
        self._sum = 0.0
        self._max = 0.0

    def observe(self, seconds: float) -> None:
        if not (seconds >= 0.0):     # NaN/negative: clock went backward
            seconds = 0.0
        i = bisect.bisect_left(self.BOUNDS, seconds)
        with self._lock:
            self._counts[i] += 1
            self._n += 1
            self._sum += seconds
            if seconds > self._max:
                self._max = seconds

    def _bucket_value(self, i: int) -> float:
        """Representative value: geometric mean of the bucket's edges."""
        hi = self.BOUNDS[min(i, len(self.BOUNDS) - 1)]
        lo = self.BOUNDS[i - 1] if i > 0 else hi / (2 ** 0.25)
        return math.sqrt(lo * hi)

    def percentile(self, q: float) -> float:
        """Approximate q-th percentile (q in [0, 100]) in seconds."""
        with self._lock:
            n = self._n
            if n == 0:
                return 0.0
            rank = max(1, math.ceil(n * q / 100.0))
            cum = 0
            for i, c in enumerate(self._counts):
                cum += c
                if cum >= rank:
                    return min(self._bucket_value(i), self._max)
            return self._max

    def snapshot(self) -> dict:
        with self._lock:
            n = self._n
            if n == 0:
                return {}
            mean = self._sum / n
        return {"count": n, "mean_ms": round(mean * 1e3, 4),
                "p50_ms": round(self.percentile(50) * 1e3, 4),
                "p95_ms": round(self.percentile(95) * 1e3, 4),
                "p99_ms": round(self.percentile(99) * 1e3, 4),
                "max_ms": round(self._max * 1e3, 4)}


class HistogramSet:
    """Named latency histograms, created on first observe.  Names must
    come from :data:`HISTOGRAM_NAMES` (statically enforced by the
    ``counters`` analyzer); snapshots flow into metrics.jsonl as
    ``kind: latency`` records next to the counter records."""

    def __init__(self):
        self._lock = threading.Lock()
        self._hists: dict[str, LatencyHistogram] = {}

    def hist(self, name: str) -> LatencyHistogram:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = LatencyHistogram()
            return h

    def observe(self, name: str, seconds: float) -> None:
        self.hist(name).observe(seconds)

    def snapshot(self) -> dict:
        with self._lock:
            hists = list(self._hists.items())
        return {name: snap for name, h in hists
                if (snap := h.snapshot())}


#: process-wide default: layers with no per-participant registry in
#: reach (the in-process broker's queue-wait clock, the reliable
#: receiver's envelope RTT) observe here, mirroring
#: ``default_fault_counters``
default_histograms = HistogramSet()


class StepTimer:
    """Accumulates wall-clock per named phase; device-fenced."""

    def __init__(self):
        self.totals: dict = collections.defaultdict(float)
        self.counts: dict = collections.defaultdict(int)

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time a phase.  The context yields a ``fence`` callable: pass
        it the pytree produced INSIDE the block and it is blocked on
        before the clock stops, so async dispatch doesn't hide device
        time::

            with timer.phase("step") as fence:
                out = step(...)
                fence(out)
        """
        pending = []
        t0 = time.perf_counter()
        try:
            yield pending.append
        finally:
            for tree in pending:
                jax.block_until_ready(tree)
            self.totals[name] += time.perf_counter() - t0
            self.counts[name] += 1

    def record(self, name: str, seconds: float):
        self.totals[name] += seconds
        self.counts[name] += 1

    def summary(self) -> dict:
        return {
            name: {"total_s": round(self.totals[name], 6),
                   "count": self.counts[name],
                   "mean_s": round(self.totals[name]
                                   / max(self.counts[name], 1), 6)}
            for name in sorted(self.totals)
        }

    def reset(self):
        self.totals.clear()
        self.counts.clear()
