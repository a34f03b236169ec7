"""Streaming sharded aggregation plane (ROADMAP item 4).

The server used to materialize one full parameter tree per client and
FedAvg-fold them all at once at the UPDATE barrier
(``runtime/server.py:_fold_update`` collecting, then
``runtime/strategies.py:aggregate_cluster`` folding) — aggregate wall
and host memory grew linearly with fleet width while every client idled
behind the slowest one.  This module rebuilds that data plane as a
streaming, hierarchical, optionally mesh-sharded fold:

* :class:`StreamingFold` — an incremental weighted-sum accumulator.
  Each Update folds into a per-stage running sum the moment the server
  decodes it, so the barrier holds O(1) parameter trees instead of
  O(clients) and per-client fold cost is constant.  **Determinism
  contract**: contributions fold in the canonical ``(stage,
  client_id)`` order whatever order frames arrive — a small reorder
  window holds early arrivals until their predecessors land (or are
  dropped), so the float summation sequence is exactly the barrier
  oracle's (``aggregate_cluster`` over the client-id-sorted list) and
  the result is **bit-identical** to it, chaos dup/reorder/drop
  included.  Window memory is O(arrival skew): zero when updates land
  in client order, and never worse than the old barrier's O(clients).

* :class:`L1Aggregator` — the aggregator tree (``aggregation.fan-in``):
  clients publish their Update to a per-group ``aggregate_queue_*``
  instead of ``rpc_queue``; an L1 aggregator folds its ≤ fan-in members
  into one :class:`~split_learning_tpu.runtime.protocol.PartialAggregate`
  (per-stage weighted SUMS + total weight, so the root continues the
  fold without re-dividing) published to the server.  Per-node fan-in
  stays constant at 100+ clients.  An L1 that dies mid-round degrades
  to direct-to-root: the server drains the orphaned group queue itself
  (counted ``agg_l1_fallbacks``) and folds the members at the group's
  canonical position, so tree rounds stay deterministic.  Note the
  tree changes the summation SHAPE (``(a+b)+(c+d)`` vs the flat
  ``((a+b)+c)+d``), so tree mode is deterministic-but-not-bit-identical
  to the flat fold — the documented trade for constant fan-in.

* :class:`MeshFoldBackend` — the running sum, the FedAvg divide and the
  server-side optimizer step run as jitted elementwise ops on arrays
  sharded across the server's device mesh (leaf axis 0 over an ``agg``
  axis, the shard/gather-fn pattern), instead of replicated host
  pytrees; accumulator buffers are donated so the fold updates in
  place.  :class:`HostFoldBackend` is the numpy twin — both replicate
  ``ops/fedavg.py:_avg_leaves`` op for op, so host and mesh folds are
  bit-identical on CPU.

* server-side optimizer (``aggregation.server-momentum``, FedAvgM):
  ``v = m·v + (base - avg); new = base - v`` applied leafwise inside
  the fold's finalize — with ``m = 0`` (default) this is plain FedAvg.
  Velocity lives in the backend's (sharded) representation between
  rounds.

* **sharded weight-update plane** (``aggregation.update-sharded``,
  "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
  Training", arxiv 2004.13336): the whole round-boundary update —
  FedAvg divide, FedAvgM step, wire-dtype cast for START — runs as ONE
  fused program per stage (:meth:`MeshFoldBackend.stage_update`:
  jitted, accumulator/velocity buffers donated, every leaf sharded
  along axis 0 over the ``agg`` axis via the shared
  :func:`~split_learning_tpu.parallel.axes.leaf_axis0_spec` rule),
  with a single device->host fetch per stage.  ``finish(on_stage=...)``
  dispatches every stage's program before fetching any, then streams
  each stage's host trees to the callback in stage order — stage k's
  fetch + START encode overlap stage k+1's device compute, the
  per-shard pipelining that (with the clients' ``learning.sync-overlap``
  ticks) hides the round-boundary update wall.

* **multi-level, multi-process tree** (``aggregation.levels`` /
  ``aggregation.remote``): :func:`plan_tree` generalizes the fan-in
  grouping recursively — interior groups fold their children's
  PartialAggregates (sums of sums with total weight, so any depth
  divides exactly once at the root), and every group's input is
  simply ``aggregate_queue(cluster, idx)`` (indices globally unique
  across levels).  :class:`L1Aggregator` serves any level; with
  ``aggregation.remote`` the same fold logic runs inside standalone
  aggregator processes (``runtime/aggnode.py``,
  ``tools/sl_aggregator.py``) adopted over the broker, with liveness
  via the HEARTBEAT/FleetMonitor plane and the counted direct-to-root
  fallback drain on node death.  The partial-sum wire optionally
  compresses through the ``partial`` codec family
  (``runtime/codec/partial.py``).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any

import numpy as np

from split_learning_tpu.ops.fedavg import (
    is_int_dtype as _is_int_dtype, unflatten_items as _unflatten,
    walk_items as _flat_items,
)
from split_learning_tpu.runtime.protocol import (
    FrameAssembler, PartialAggregate, Update, aggregate_queue,
    encode_parts, RPC_QUEUE,
)

#: strategies whose per-invocation aggregation consumes the WHOLE update
#: list at once (``aggregate_cluster(ups)``) — the only shape a
#: streaming fold can replace.  relay / periodic / fedasync read
#: individual ``u.params`` (per-client persistence, subset merges), so
#: they keep the barrier semantics and streaming stays off.
FOLD_STRATEGIES = frozenset({"fedavg", "sda", "cluster_relay"})


class UpdateBatch(list):
    """``train_cluster``'s return value when a streaming fold ran: the
    (weight-stripped) Update list plus the precomputed fold result that
    ``aggregate_cluster`` consumes instead of re-folding."""
    fold: "FoldResult | None" = None


@dataclasses.dataclass
class FoldResult:
    params: Any
    stats: Any
    n_samples: int
    fold_s: float = 0.0            # wall spent folding (overlapped)
    peak_tree_copies: float = 0.0  # window HWM in full-tree equivalents
    window_hwm: int = 0            # most simultaneous held contributions
    folded: int = 0                # contributions folded
    partials: int = 0              # PartialAggregate contributions
    update_s: float = 0.0          # round-boundary update wall (divide +
    # momentum + cast + device->host fetch), the serial bubble the
    # sharded update + sync overlap exist to shrink/hide
    stage_update_ms: dict = dataclasses.field(default_factory=dict)
    # per-stage update wall (ms), keyed by stage — the per-shard
    # streaming granularity


# --------------------------------------------------------------------------
# fold backends
# --------------------------------------------------------------------------
# Both replicate ops/fedavg.py:_avg_leaves op for op:
#   t   = nan_to_num(leaf.astype(f32)) * w
#   acc = t | acc + t          (canonical order)
#   avg = acc / total_w        (int leaves: round first)
# so a streamed fold is bit-identical to the barrier fold, and the mesh
# backend is bit-identical to the host one on CPU (elementwise IEEE ops).

def _mom_path_set(st: "_StageFold", base_flat, momentum: float) -> set:
    """Paths the FedAvgM step applies to: float leaves present in the
    base tree (int leaves and paths outside the base keep plain
    FedAvg) — the single definition both backends' fused stage update
    and the legacy per-leaf path share."""
    if not momentum or base_flat is None:
        return set()
    return {p for p in st.acc
            if p in base_flat and not _is_int_dtype(st.dtype[p])}


def _stage_velocity(st: "_StageFold", base_flat, velocity,
                    mom_paths: set) -> dict:
    """This stage's usable velocity entries (an elastic re-plan can
    leave a path's velocity shaped for another tensor — restart those
    from zero, exactly like the legacy per-leaf path did)."""
    out = {}
    for p in mom_paths:
        vel = (velocity or {}).get(p)
        if vel is not None and np.shape(vel) != np.shape(base_flat[p]):
            vel = None
        out[p] = vel
    return out


class HostFoldBackend:
    """Numpy accumulate/divide — the single-host default."""

    name = "host"

    def contrib(self, leaf, w) -> np.ndarray:
        return np.nan_to_num(np.asarray(leaf, dtype=np.float32)) * w

    def ingest(self, sums_leaf) -> np.ndarray:
        """Adopt a PartialAggregate's precomputed f32 sum leaf.

        ``nan_to_num`` like :meth:`contrib`: a partial's sums arrive
        over the wire (f32 overflow at an L1, a corrupt-but-crc-lucky
        frame) and are the one fold input the contribution path's
        sanitizer never saw — a no-op on every finite value, so clean
        runs keep their bit-identity contracts."""
        return np.nan_to_num(np.asarray(sums_leaf, dtype=np.float32))

    def add(self, acc, t):
        return acc + t

    def finalize(self, acc, total_w: float, dtype) -> np.ndarray:
        avg = acc / np.float32(total_w)
        if _is_int_dtype(dtype):
            return np.round(avg).astype(dtype)
        return avg.astype(dtype)

    def momentum_step(self, base, avg32, vel, m: float):
        """FedAvgM: returns (new_param_f32, new_velocity)."""
        b = np.asarray(base, dtype=np.float32)
        v = m * vel + (b - avg32) if vel is not None else (b - avg32)
        return b - v, v

    def stage_update(self, st: "_StageFold", base_flat, velocity,
                     momentum: float):
        """Fused per-stage round-boundary update, host twin: FedAvg
        divide + FedAvgM step + cast back to the START wire dtype for
        EVERY leaf of one stage, as one call.  Returns an opaque
        pending handle for :meth:`stage_fetch` (eager here; the mesh
        backend dispatches async so stage k+1's compute overlaps
        stage k's fetch/encode)."""
        mom_paths = _mom_path_set(st, base_flat, momentum)
        vels = _stage_velocity(st, base_flat, velocity, mom_paths)
        params: dict = {}
        new_vel: dict = {}
        for path, acc in st.acc.items():
            dt = st.dtype[path]
            if path in mom_paths:
                avg32 = self.finalize(acc, st.total_w,
                                      np.dtype(np.float32))
                new32, nv = self.momentum_step(base_flat[path], avg32,
                                               vels[path], momentum)
                new_vel[path] = nv
                params[path] = np.asarray(new32).astype(dt)
            else:
                params[path] = self.finalize(acc, st.total_w, dt)
        stats = {p: self.finalize(a, st.stat_total_w, st.stat_dtype[p])
                 for p, a in st.stat_acc.items()}
        return params, stats, new_vel

    def stage_fetch(self, pending):
        return pending

    def to_host(self, x) -> np.ndarray:
        return np.asarray(x)

    def nbytes(self, x) -> int:
        return np.asarray(x).nbytes


class MeshFoldBackend:
    """Accumulate/divide/optimizer as jitted ops on arrays sharded over
    the server's device mesh (``aggregation.sharded``).

    Each leaf shards along axis 0 over a 1-D ``agg`` mesh axis when the
    axis divides evenly (replicated otherwise — small leaves are not
    worth a ragged layout).  The add donates the accumulator buffer, so
    per-client fold cost is one sharded elementwise add with no fresh
    allocation; only ``finalize`` gathers to host.
    """

    name = "mesh"

    def __init__(self, devices=None, kernels=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from split_learning_tpu.ops import kernels as kplane
        # Pallas kernel plan for the fused stage update (kernels:
        # config block; None = the process-wide plan), captured at
        # construction so one backend's programs are self-consistent
        self._kplan = kplane.as_plan(kernels)
        self._jax = jax
        devs = list(devices) if devices is not None else jax.devices()
        self.n_devices = len(devs)
        self.mesh = Mesh(np.asarray(devs), ("agg",))
        self._NS, self._P = NamedSharding, PartitionSpec
        self._contrib = jax.jit(
            lambda x, w: jnp.nan_to_num(x.astype(jnp.float32)) * w)
        # `acc` naming is load-bearing: the JX007 audit
        # (analysis/jaxpr_audit.py) statically requires every jitted
        # op consuming a running-accumulator parameter to donate it
        self._add = jax.jit(lambda acc, t: acc + t, donate_argnums=(0,))
        self._div = jax.jit(lambda a, tw: a / tw)
        self._div_round = jax.jit(lambda a, tw: jnp.round(a / tw))
        # FedAvgM inner step: v' = m v + (b - a); p' = b - v'
        def _mom(b, a, v, m):
            nv = m * v + (b - a)
            return b - nv, nv
        self._mom = jax.jit(_mom)
        # fused per-stage round-boundary update programs, keyed by the
        # stage's static structure signature (paths/shapes/dtypes +
        # which paths take the momentum step) — see _fused_update.
        # Bounded like client._OPS_CACHE: elastic re-plans mint fresh
        # signatures, and each entry pins a compiled XLA executable.
        self._fused_cache: dict = {}
        self._fused_cache_max = 32

    def _sharding(self, shape):
        from split_learning_tpu.parallel.axes import leaf_axis0_spec
        spec = leaf_axis0_spec(tuple(shape), self.n_devices, "agg")
        return self._NS(self.mesh, spec)

    # -- fused sharded stage update (aggregation.update-sharded) ---------

    def _fused_update(self, sig, dtypes, stat_dtypes, mom_paths):
        """One jitted program for one stage's ENTIRE round-boundary
        update: FedAvg divide, FedAvgM momentum step, and the cast
        back to each leaf's START wire dtype — every leaf sharded
        along axis 0 over the ``agg`` mesh axis (the ZeRO-style
        leaf-axis-0 rule), accumulator and velocity buffers DONATED so
        the update happens in place.  The elementwise op sequence
        matches the host twin exactly, so mesh and host stay
        bit-identical on CPU."""
        prog = self._fused_cache.get(sig)
        if prog is not None:
            return prog
        jax = self._jax
        import jax.numpy as jnp
        kplan = self._kplan
        if kplan.stage_update:
            from split_learning_tpu.ops.kernels import update as kupd

        def fused(acc, stat_acc, base, vel, tw, stat_tw, m):
            params, stats, nvel = {}, {}, {}
            for path in sorted(acc):
                dt = dtypes[path]
                if kplan.stage_update and kupd.kernel_ok(acc[path]):
                    # single-pass Pallas finish (same op order as the
                    # jnp chain below — mesh/host stay bit-identical)
                    if path in mom_paths:
                        p, nv = kupd.momentum_leaf(
                            acc[path], base[path], vel[path], tw, m,
                            dt)
                        nvel[path] = nv
                        params[path] = p
                    else:
                        params[path] = kupd.finalize_leaf(
                            acc[path], tw, dt, rnd=_is_int_dtype(dt))
                    continue
                a32 = acc[path] / tw
                if path in mom_paths:
                    nv = m * vel[path] + (base[path] - a32)
                    nvel[path] = nv
                    params[path] = (base[path] - nv).astype(dt)
                elif _is_int_dtype(dt):
                    params[path] = jnp.round(a32).astype(dt)
                else:
                    params[path] = a32.astype(dt)
            for path in sorted(stat_acc):
                dt = stat_dtypes[path]
                if kplan.stage_update and kupd.kernel_ok(
                        stat_acc[path]):
                    stats[path] = kupd.finalize_leaf(
                        stat_acc[path], stat_tw, dt,
                        rnd=_is_int_dtype(dt))
                    continue
                s32 = stat_acc[path] / stat_tw
                stats[path] = (jnp.round(s32).astype(dt)
                               if _is_int_dtype(dt)
                               else s32.astype(dt))
            return params, stats, nvel

        # donate the consumed accumulators and the replaced velocity;
        # base is read-only (it seeds the NEXT round's shadow compare)
        from split_learning_tpu.runtime.memo import bounded_setdefault
        return bounded_setdefault(
            self._fused_cache, self._fused_cache_max, sig,
            lambda: jax.jit(fused, donate_argnums=(0, 1, 3)))

    def stage_update(self, st: "_StageFold", base_flat, velocity,
                     momentum: float):
        """Dispatch one stage's fused sharded update; returns a pending
        handle whose :meth:`stage_fetch` does the stage's ONE
        device->host fetch.  Dispatch is async — the caller can
        dispatch every stage first and then fetch in stage order, so
        stage k's fetch/encode overlaps stage k+1's device compute
        (the per-shard streaming the START fan-out consumes)."""
        mom_paths = frozenset(_mom_path_set(st, base_flat, momentum))
        vels = _stage_velocity(st, base_flat, velocity, mom_paths)
        dtypes = dict(st.dtype)
        stat_dtypes = dict(st.stat_dtype)
        sig = (tuple(sorted((p, tuple(np.shape(a)), str(dtypes[p]))
                            for p, a in st.acc.items())),
               tuple(sorted((p, tuple(np.shape(a)),
                             str(stat_dtypes[p]))
                            for p, a in st.stat_acc.items())),
               tuple(sorted(mom_paths)))
        prog = self._fused_update(sig, dtypes, stat_dtypes, mom_paths)
        base_dev = {p: self._put(np.asarray(base_flat[p], np.float32))
                    for p in mom_paths}
        vel_dev = {}
        for p in mom_paths:
            v = vels[p]
            if v is None:
                vel_dev[p] = self._put(
                    np.zeros(np.shape(base_flat[p]), np.float32))
            elif isinstance(v, np.ndarray):
                vel_dev[p] = self._put(v)
            else:
                vel_dev[p] = v   # already device-resident (sharded)
        import warnings
        with warnings.catch_warnings():
            # int leaves accumulate in f32 and cast to int on output —
            # their donated buffer can't alias the narrower result, and
            # XLA says so once per compile; expected, not actionable
            warnings.filterwarnings(
                "ignore", message=".*donated buffers were not usable.*")
            params, stats, nvel = prog(
                dict(st.acc), dict(st.stat_acc), base_dev, vel_dev,
                np.float32(st.total_w), np.float32(st.stat_total_w),
                np.float32(momentum))
        st.acc = {}          # donated — the buffers are gone
        st.stat_acc = {}
        return params, stats, nvel

    def stage_fetch(self, pending):
        """The stage's single device->host fetch (params + stats in one
        transfer); the new velocity stays device-resident between
        rounds (the backend's sharded representation)."""
        params, stats, nvel = pending
        host_p, host_s = self._jax.device_get((params, stats))
        return host_p, host_s, nvel

    def _put(self, a: np.ndarray):
        return self._jax.device_put(a, self._sharding(a.shape))

    def contrib(self, leaf, w):
        a = np.asarray(leaf)
        return self._contrib(self._put(a), np.float32(w))

    def ingest(self, sums_leaf):
        # nan_to_num for wire-borne partial sums, like the host twin
        return self._put(np.nan_to_num(
            np.asarray(sums_leaf, dtype=np.float32)))

    def add(self, acc, t):
        return self._add(acc, t)

    def finalize(self, acc, total_w: float, dtype) -> np.ndarray:
        fn = self._div_round if _is_int_dtype(dtype) else self._div
        out = fn(acc, np.float32(total_w))
        return np.asarray(self._jax.device_get(out)).astype(dtype)

    def momentum_step(self, base, avg32, vel, m: float):
        b = self._put(np.asarray(base, dtype=np.float32))
        a = avg32 if not isinstance(avg32, np.ndarray) else self._put(avg32)
        if vel is None:
            vel = self._put(np.zeros(np.shape(base), np.float32))
        return self._mom(b, a, vel, np.float32(m))

    def to_host(self, x) -> np.ndarray:
        return np.asarray(self._jax.device_get(x))

    def nbytes(self, x) -> int:
        return int(np.prod(np.shape(x), dtype=np.int64)
                   * np.dtype(np.float32).itemsize)


def make_fold_backend(cfg) -> HostFoldBackend | MeshFoldBackend:
    if getattr(cfg.aggregation, "sharded", False):
        return MeshFoldBackend(kernels=getattr(cfg, "kernels", None))
    return HostFoldBackend()


# --------------------------------------------------------------------------
# tree flatten helpers: the canonical walk/unflatten live in
# ops/fedavg.py (imported above as _flat_items/_unflatten) — ONE copy
# of the dict-pytree semantics, shared with the TreeFold oracle, so
# the bit-identity contract cannot be broken by the two folds
# disagreeing about what a leaf is.
# --------------------------------------------------------------------------

def _tree_nbytes(tree) -> int:
    return sum(np.asarray(leaf).nbytes for _, leaf in _flat_items(tree))


# --------------------------------------------------------------------------
# the streaming fold
# --------------------------------------------------------------------------

class _StageFold:
    """Per-stage canonical-order fold state."""

    def __init__(self, order: list):
        self.order = list(order)          # canonical fold order (keys)
        self.order_set = set(self.order)
        self.next = 0                     # next canonical position
        self.pending: dict = {}           # key -> held contribution
        self.extras: dict = {}            # keys outside the plan
        self.folded: set = set()
        self.gone: set = set()            # dropped; stop waiting for them
        self.acc: dict = {}               # path -> backend accumulator
        self.dtype: dict = {}             # path -> original np dtype
        self.total_w: float = 0.0
        self.stat_acc: dict = {}
        self.stat_dtype: dict = {}
        self.stat_total_w: float = 0.0


class StreamingFold:
    """Incremental per-stage weighted FedAvg with a canonical-order
    reorder window (module docstring has the determinism contract).

    ``expected`` maps stage -> the ordered list of contribution keys
    (client ids, or group keys at an aggregator-tree root).  Duplicate
    contributions for a key are dropped and counted (``agg_dup_drops``)
    — at-least-once delivery must not double-weight a client.
    Thread-safe (the rpc pump and L1 threads may race an exporter).
    """

    def __init__(self, expected: dict, *, backend=None, faults=None,
                 hists=None):
        self.backend = backend if backend is not None else HostFoldBackend()
        if faults is None:
            from split_learning_tpu.runtime.trace import (
                default_fault_counters,
            )
            faults = default_fault_counters
        self.faults = faults
        self.hists = hists
        self._lock = threading.Lock()
        self._stages = {int(s): _StageFold(keys)
                        for s, keys in expected.items()}
        self.n_samples = 0
        self.fold_s = 0.0
        self.folded = 0
        self.partials = 0
        self._held_bytes = 0
        self._held_hwm_bytes = 0
        self.window_hwm = 0
        self._finished = None

    # -- ingest --------------------------------------------------------------

    def add_update(self, u: Update, *, scale: float = 1.0,
                   key: str | None = None) -> None:
        """Fold one client Update (params may be None — a weight-less
        update occupies its canonical slot, counts stage-1 samples, and
        contributes nothing, exactly like the barrier oracle skips it).

        ``scale`` multiplies the FedAvg weight — the async mode's
        staleness decay (``staleness_decay ** version_lag``); 1.0 (the
        sync default) keeps the integer weight path bit-identical to
        the barrier oracle.  ``key`` overrides the fold key: a
        stale-admitted contribution folds under ``client@vN`` so it
        can never collide with (or dup-drop) the same client's fresh
        contribution in the canonical window — it lands in the extras
        set and folds deterministically (sorted) at finish."""
        if getattr(u, "delta_base", None) is not None:
            raise ValueError(
                f"delta-encoded Update from {u.client_id} reached the "
                "streaming fold un-reconstructed")
        self._enqueue(int(u.stage), key or u.client_id, ("u", u, scale),
                      0 if u.params is None else _tree_nbytes(u.params))

    def add_partial(self, stage: int, key: str, sums, weight: float,
                    dtypes, stat_sums=None, stat_weight: float = 0.0,
                    stat_dtypes=None, n_samples: int = 0) -> None:
        """Fold one L1 aggregator's per-stage partial SUMS at the
        group's canonical position."""
        item = ("p", dict(sums=sums, weight=weight, dtypes=dtypes,
                          stat_sums=stat_sums, stat_weight=stat_weight,
                          stat_dtypes=stat_dtypes, n_samples=n_samples))
        self._enqueue(int(stage), key, item,
                      _tree_nbytes(sums) if sums else 0)

    def has_key(self, stage: int, key) -> bool:
        """True once the key is accounted for at this stage: folded,
        held in the window, an extra, or declared gone."""
        with self._lock:
            st = self._stages.get(int(stage))
            return st is not None and (
                key in st.folded or key in st.pending
                or key in st.extras or key in st.gone)

    def drop(self, stage: int, key: str) -> None:
        """The key will never contribute (client dropped at a barrier):
        stop holding the window for it."""
        with self._lock:
            st = self._stages.get(int(stage))
            if st is None:
                return
            st.gone.add(key)
            self._drain(st)

    def _enqueue(self, stage: int, key, item, nbytes: int) -> None:
        with self._lock:
            st = self._stages.get(stage)
            if st is None:
                # a stage outside the plan: fold deterministically at
                # finish (sorted), never silently dropped
                st = self._stages[stage] = _StageFold([])
            if key in st.folded or key in st.pending or key in st.extras:
                self.faults.inc("agg_dup_drops")
                return
            if key not in st.order_set or key in st.gone:
                # outside the plan, or a key the window already gave up
                # on (dropped at a barrier, then revived — e.g. an
                # async late-READY rejoin): the canonical window has
                # passed its slot, so fold it deterministically
                # (sorted) at finish instead of parking it in a
                # pending slot the drain will never reach
                st.extras[key] = item
            else:
                st.pending[key] = item
            self._held_bytes += nbytes
            self._held_hwm_bytes = max(self._held_hwm_bytes,
                                       self._held_bytes)
            self.window_hwm = max(
                self.window_hwm,
                sum(len(s.pending) + len(s.extras)
                    for s in self._stages.values()))
            self._drain(st)

    # -- canonical-order drain ----------------------------------------------

    def _drain(self, st: _StageFold) -> None:
        while st.next < len(st.order):
            k = st.order[st.next]
            item = st.pending.pop(k, None)
            if item is None:
                if k in st.gone or k in st.folded:
                    st.next += 1
                    continue
                return   # window holds until the predecessor lands
            self._fold_item(st, k, item)
            st.next += 1

    def _fold_item(self, st: _StageFold, key, item) -> None:
        t0 = time.perf_counter()
        kind, payload = item[0], item[1]
        if kind == "u":
            scale = item[2] if len(item) > 2 else 1.0
            self._fold_update_item(st, payload, scale)
        else:
            self._fold_partial_item(st, payload)
        st.folded.add(key)
        self.folded += 1
        dt = time.perf_counter() - t0
        self.fold_s += dt
        if self.hists is not None:
            self.hists.observe("agg_fold", dt)

    def _fold_update_item(self, st: _StageFold, u: Update,
                          scale: float = 1.0) -> None:
        if u.stage == 1:
            self.n_samples += u.num_samples
        if u.params is None:
            return
        self._held_bytes -= _tree_nbytes(u.params)
        # sync path keeps the INT weight so the float summation is
        # bit-identical to the barrier oracle; the async staleness
        # decay scales it only when it actually decays
        w = max(1, u.num_samples)
        if scale != 1.0:
            w = w * float(scale)
        st.total_w += w
        be = self.backend
        for path, leaf in _flat_items(u.params):
            c = be.contrib(leaf, w)
            if path in st.acc:
                st.acc[path] = be.add(st.acc[path], c)
            else:
                st.acc[path] = c
                st.dtype[path] = np.asarray(leaf).dtype
        if u.batch_stats:
            st.stat_total_w += w
            for path, leaf in _flat_items(u.batch_stats):
                c = be.contrib(leaf, w)
                if path in st.stat_acc:
                    st.stat_acc[path] = be.add(st.stat_acc[path], c)
                else:
                    st.stat_acc[path] = c
                    st.stat_dtype[path] = np.asarray(leaf).dtype

    def _fold_partial_item(self, st: _StageFold, p: dict) -> None:
        self.partials += 1
        self.n_samples += int(p.get("n_samples") or 0)
        be = self.backend
        for acc, dty, sums_key, dt_key, w_key in (
                (st.acc, st.dtype, "sums", "dtypes", "weight"),
                (st.stat_acc, st.stat_dtype, "stat_sums", "stat_dtypes",
                 "stat_weight")):
            sums = p.get(sums_key)
            if not sums:
                continue
            if sums_key == "sums":
                self._held_bytes -= _tree_nbytes(sums)
                st.total_w += float(p[w_key])
            else:
                st.stat_total_w += float(p[w_key])
            dtypes = {path: np.dtype(d)
                      for path, d in _flat_items(p.get(dt_key) or {})}
            for path, leaf in _flat_items(sums):
                t = be.ingest(leaf)
                if path in acc:
                    acc[path] = be.add(acc[path], t)
                else:
                    acc[path] = t
                    dty[path] = dtypes.get(path, np.dtype(np.float32))

    def _drain_all(self) -> None:
        for st in self._stages.values():
            st.gone |= set(st.order)      # stop waiting; fold arrivals
            self._drain(st)
            for k in sorted(st.extras, key=str):
                self._fold_item(st, k, st.extras.pop(k))

    # -- results -------------------------------------------------------------

    def partial(self) -> tuple[dict, int]:
        """L1 flush: per-stage weighted SUMS (host np) + metadata, no
        divide — the root continues the fold.  Terminal."""
        with self._lock:
            self._drain_all()
            out: dict = {}
            be = self.backend
            for s in sorted(self._stages):
                st = self._stages[s]
                if not st.acc and not st.stat_acc and not st.total_w:
                    continue
                out[s] = {
                    "sums": _unflatten({p: be.to_host(a)
                                        for p, a in st.acc.items()}),
                    "weight": st.total_w,
                    "dtypes": _unflatten({p: str(d)
                                          for p, d in st.dtype.items()}),
                    "stat_sums": _unflatten(
                        {p: be.to_host(a)
                         for p, a in st.stat_acc.items()}),
                    "stat_weight": st.stat_total_w,
                    "stat_dtypes": _unflatten(
                        {p: str(d) for p, d in st.stat_dtype.items()}),
                }
            return out, self.n_samples

    def finish(self, base=None, momentum: float = 0.0,
               velocity: dict | None = None, *, fused: bool = True,
               on_stage=None) -> FoldResult:
        """The round-boundary update: FedAvg divide (+ optional server
        momentum vs ``base``) + cast back to each leaf's START wire
        dtype, in canonical stage order; idempotent (returns the first
        result).

        ``fused`` (``aggregation.update-sharded``, default) runs each
        stage's whole update as ONE backend program — on the mesh
        backend a jitted, donated, leaf-axis-0-sharded program whose
        result comes back in a single device->host fetch; every
        stage's program is dispatched before any stage is fetched, so
        stage k's fetch (and whatever the caller's ``on_stage``
        does with it — shadow refresh, START encode) overlaps stage
        k+1's device compute.  ``fused=False`` keeps the legacy
        per-leaf path as the bit-parity oracle.

        ``on_stage(stage, stage_params, stage_stats)`` (when given) is
        called per stage, in ascending stage order, the moment that
        stage's host trees exist — the per-shard streaming hook the
        server's START fan-out consumes."""
        with self._lock:
            if self._finished is not None:
                return self._finished
            self._drain_all()
            be = self.backend
            t0 = time.perf_counter()
            params: dict = {}
            stats: dict = {}
            stage_ms: dict = {}
            base_flat = (dict(_flat_items(base))
                         if (momentum and base is not None) else None)
            order = [s for s in sorted(self._stages)
                     if self._stages[s].acc or self._stages[s].stat_acc]
            if fused:
                # all stages dispatch BEFORE any stage fetches; sound
                # because stage param paths are disjoint (stage
                # concatenation of absolute layer keys) — no stage's
                # velocity read depends on another stage's write
                pending = [(s, be.stage_update(self._stages[s],
                                               base_flat, velocity,
                                               momentum))
                           for s in order]
                for s, pend in pending:
                    t_s = time.perf_counter()
                    flat_p, flat_s, new_vel = be.stage_fetch(pend)
                    if velocity is not None:
                        velocity.update(new_vel)
                    stage_p = _unflatten(flat_p)
                    stage_s = _unflatten(flat_s)
                    params.update(stage_p)
                    stats.update(stage_s)
                    stage_ms[s] = round(
                        (time.perf_counter() - t_s) * 1e3, 3)
                    if on_stage is not None:
                        on_stage(s, stage_p, stage_s)
            else:
                for s in order:
                    t_s = time.perf_counter()
                    st = self._stages[s]
                    flat: dict = {}
                    for path, acc in st.acc.items():
                        dt = st.dtype[path]
                        if base_flat is not None and path in base_flat \
                                and not _is_int_dtype(dt):
                            # server momentum (FedAvgM): average in
                            # f32, optimizer step in the backend, one
                            # dtype cast at the end
                            avg32 = be.finalize(acc, st.total_w,
                                                np.dtype(np.float32))
                            vel = (velocity or {}).get(path)
                            if vel is not None and np.shape(vel) != \
                                    np.shape(base_flat[path]):
                                # an elastic re-plan moved this path's
                                # layer range: the old velocity is
                                # another tensor's momentum — restart
                                # from zero
                                vel = None
                            new32, nv = be.momentum_step(
                                base_flat[path], avg32, vel, momentum)
                            if velocity is not None:
                                velocity[path] = nv
                            flat[path] = be.to_host(new32).astype(dt)
                        else:
                            flat[path] = be.finalize(acc, st.total_w,
                                                     dt)
                    stage_p = _unflatten(flat)
                    stage_s = {}
                    if st.stat_acc:
                        stage_s = _unflatten(
                            {p: be.finalize(a, st.stat_total_w,
                                            st.stat_dtype[p])
                             for p, a in st.stat_acc.items()})
                    params.update(stage_p)
                    stats.update(stage_s)
                    stage_ms[s] = round(
                        (time.perf_counter() - t_s) * 1e3, 3)
                    if on_stage is not None:
                        on_stage(s, stage_p, stage_s)
            update_s = time.perf_counter() - t0
            self.fold_s += update_s
            result_bytes = _tree_nbytes(params)
            peak = (1.0 + self._held_hwm_bytes / result_bytes
                    if result_bytes else float(bool(self.window_hwm)))
            self._finished = FoldResult(
                params=params, stats=stats, n_samples=self.n_samples,
                fold_s=round(self.fold_s, 6),
                peak_tree_copies=round(peak, 3),
                window_hwm=self.window_hwm, folded=self.folded,
                partials=self.partials,
                update_s=round(update_s, 6), stage_update_ms=stage_ms)
            return self._finished


def plan_fanin_groups(active: list, fan_in: int) -> list:
    """Partition the round's (client_id, stage) send set into L1
    aggregator groups of at most ``fan_in`` clients, per stage (a group
    never spans stages — its partial covers one stage's key slice), in
    canonical sorted order.  Returns ``[AggGroup]``."""
    by_stage: dict[int, list] = {}
    for cid, s in active:
        by_stage.setdefault(int(s), []).append(cid)
    groups: list[AggGroup] = []
    gi = 0
    for s in sorted(by_stage):
        cids = sorted(by_stage[s])
        for i in range(0, len(cids), fan_in):
            groups.append(AggGroup(idx=gi, stage=s,
                                   members=cids[i:i + fan_in]))
            gi += 1
    return groups


def plan_tree(active: list, fan_in: int, levels: int = 1) -> list:
    """:func:`plan_fanin_groups` generalized to a recursive tree
    (``aggregation.levels``): level-1 groups fold ≤ ``fan_in`` client
    Updates; each higher level folds ≤ ``fan_in`` child-group
    PARTIALS (sums of sums, total weight carried, so any depth still
    divides exactly once at the root).  Group indices are globally
    unique across levels — a group's input queue is simply
    ``aggregate_queue(cluster, idx)`` whatever its level.  A stage
    whose level-k population is already a single group is NOT wrapped
    again (a one-child interior node would add a hop for nothing), so
    such a group stays parentless (``parent is None`` = publish to
    the root's rpc queue).  Returns every group of every level,
    canonical order within each level.
    """
    groups = plan_fanin_groups(active, fan_in)
    gi = len(groups)
    tier = groups
    for _ in range(2, levels + 1):
        by_stage: dict[int, list] = {}
        for g in tier:
            by_stage.setdefault(g.stage, []).append(g)
        nxt: list[AggGroup] = []
        for s in sorted(by_stage):
            kids = sorted(by_stage[s], key=lambda g: g.idx)
            if len(kids) <= 1:
                continue   # nothing to reduce at this stage
            for i in range(0, len(kids), fan_in):
                chunk = kids[i:i + fan_in]
                parent = AggGroup(
                    idx=gi, stage=s,
                    members=[c.key for c in chunk],
                    level=chunk[0].level + 1)
                gi += 1
                for c in chunk:
                    c.parent = parent.idx
                nxt.append(parent)
        if not nxt:
            break
        groups += nxt
        tier = nxt
    return groups


def root_groups(groups: list) -> list:
    """The parentless groups — whose PartialAggregates land at the
    server root (canonical order: level then idx)."""
    return sorted((g for g in groups if g.parent is None),
                  key=lambda g: g.idx)


def group_key(idx: int) -> str:
    """Canonical fold key of aggregator group ``idx`` (zero-padded so
    lexicographic order == numeric order)."""
    return f"g{idx:05d}"


@dataclasses.dataclass
class AggGroup:
    idx: int
    stage: int
    members: list               # client ids (level 1) or child keys
    level: int = 1
    parent: int | None = None   # parent group idx; None = root child

    @property
    def key(self) -> str:
        return group_key(self.idx)

    def as_dict(self) -> dict:
        """Wire form for :class:`~split_learning_tpu.runtime.protocol
        .AggAssign` (plain builtins — the restricted unpickler's
        vocabulary stays closed)."""
        return {"idx": self.idx, "stage": self.stage,
                "members": list(self.members), "level": self.level,
                "parent": self.parent}

    @classmethod
    def from_dict(cls, d: dict) -> "AggGroup":
        return cls(idx=int(d["idx"]), stage=int(d["stage"]),
                   members=list(d.get("members") or []),
                   level=int(d.get("level", 1)),
                   parent=d.get("parent"))


# --------------------------------------------------------------------------
# L1 aggregator
# --------------------------------------------------------------------------

class L1Aggregator(threading.Thread):
    """One aggregator-tree interior node: drains its group's
    ``aggregate_queue``, folds its members in canonical member order,
    and publishes one PartialAggregate to ``out_queue`` — the server's
    rpc queue for a parentless group, the parent group's aggregate
    queue below an L2 (``aggregation.levels``).  A level-1 node folds
    client Updates; a level ≥ 2 node folds its children's
    PartialAggregates (sums of sums, total weight carried).

    ``codec`` (a ``transport.codec: partial`` spec) compresses the
    published sums (``runtime/codec/partial.py``); ``base``/
    ``base_gen`` are the stage's START shard for the delta mode — an
    interior node uses the same base to DECODE codec'd child partials.

    Flushes when every expected member has folded, on
    :meth:`request_flush` (the server gave up on stragglers), or at
    ``deadline``.  ``TEST_KILL`` (a set of aggregator names) makes the
    thread die silently mid-round — the failure-injection hook the
    direct-to-root fallback tests use.
    """

    TEST_KILL: set = set()

    def __init__(self, bus, *, cluster: int, group: AggGroup,
                 members: list, gen: int, deadline: float,
                 log=None, faults=None, chunk_bytes: int | None = None,
                 owns_bus: bool = False, out_queue: str = RPC_QUEUE,
                 codec=None, base=None, base_gen: int | None = None):
        self.agg_id = f"aggregator_{cluster}_{group.idx}"
        super().__init__(daemon=True, name=self.agg_id)
        self.bus = bus
        self.cluster = cluster
        self.group = group
        self.members = list(members)
        self.gen = gen
        self.deadline = deadline
        self.log = log
        if faults is None:
            from split_learning_tpu.runtime.trace import (
                default_fault_counters,
            )
            faults = default_fault_counters
        self.faults = faults
        self.chunk_bytes = chunk_bytes
        self.owns_bus = owns_bus
        self.out_queue = out_queue
        self.codec = codec
        self.base = base
        self.base_gen = base_gen
        self.flushed = False
        self._flush = threading.Event()
        self._kill = threading.Event()
        # per-group fold state lives on the INSTANCE so a standalone
        # aggregator node (runtime/aggnode.py) can drive the same
        # object directly — feed_raw()/publish() without start()ing
        # the thread — and the thread run loop is just a driver
        self.fold = StreamingFold({self.group.stage: self.members},
                                  faults=self.faults)
        self.asm = FrameAssembler(faults=self.faults)
        self.meta: list[dict] = []
        self.seen: set = set()
        self.ingress_bytes = 0
        self.egress_bytes = 0

    def request_flush(self) -> None:
        self._flush.set()

    def kill(self) -> None:
        """Die without flushing (tests: the L1-failure path)."""
        self._kill.set()

    @property
    def complete(self) -> bool:
        return self.seen >= set(self.members)

    @property
    def queue(self) -> str:
        return aggregate_queue(self.cluster, self.group.idx)

    def run(self) -> None:
        try:
            while True:
                if self._kill.is_set() \
                        or self.agg_id in L1Aggregator.TEST_KILL:
                    return   # died mid-round: the server's fallback
                    # drains the queue direct-to-root
                raw = self.bus.get(self.queue, timeout=0.2)
                if raw is not None:
                    self.feed_raw(raw)
                if self.complete or self._flush.is_set() \
                        or time.monotonic() >= self.deadline:
                    self.publish()
                    return
        finally:
            if self.owns_bus:
                try:
                    self.bus.close()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass

    def feed_raw(self, raw: bytes) -> None:
        self.ingress_bytes += len(raw)
        try:
            msg = self.asm.feed(raw)
        except Exception as e:  # noqa: BLE001 — one corrupt frame must
            # cost one message, not the aggregator
            self.faults.inc("corrupt_rejected")
            if self.log is not None:
                self.log.warning(f"{self.agg_id}: dropping undecodable "
                                 f"frame: {e}")
            return
        if msg is None:
            return
        if isinstance(msg, Update) and self.group.level == 1:
            self._feed_update(msg)
        elif isinstance(msg, PartialAggregate) and self.group.level > 1:
            self._feed_partial(msg)

    def _feed_update(self, msg: Update) -> None:
        if msg.round_idx != self.gen:
            self.faults.inc("agg_stale_drops")
            return
        if msg.client_id in self.seen:
            self.faults.inc("agg_dup_drops")
            return
        self.seen.add(msg.client_id)
        self.fold.add_update(msg)
        self.meta.append(
            {"client_id": msg.client_id, "stage": msg.stage,
             "num_samples": msg.num_samples, "ok": msg.ok,
             "telemetry": msg.telemetry})
        if self.log is not None:
            self.log.received(f"UPDATE {msg.client_id} (L1 fold)")

    def _feed_partial(self, msg: PartialAggregate) -> None:
        """Interior-level ingest: one child group's partial, dedup'd on
        its key like a level-1 member Update — the at-least-once wire
        must not double-weight a whole group either."""
        if msg.round_idx != self.gen:
            self.faults.inc("agg_stale_drops")
            return
        key = group_key(msg.group)
        if key in self.seen:
            self.faults.inc("agg_dup_drops")
            return
        if msg.codec or msg.members_z:
            from split_learning_tpu.runtime.codec.partial import (
                PartialCodecError, decode_partial_msg,
            )
            try:
                decode_partial_msg(
                    msg, bases={msg.stage: self.base},
                    base_gen=self.base_gen)
            except PartialCodecError as e:
                self.faults.inc("partial_codec_errors")
                if self.log is not None:
                    self.log.warning(f"{self.agg_id}: dropping "
                                     f"undecodable partial: {e}")
                return
        self.seen.add(key)
        self.fold.add_partial(msg.stage, key, msg.sums, msg.weight,
                              msg.dtypes, stat_sums=msg.stat_sums,
                              stat_weight=msg.stat_weight,
                              stat_dtypes=msg.stat_dtypes,
                              n_samples=msg.n_samples)
        self.meta.extend(msg.members or [])
        if self.log is not None:
            self.log.received(f"PARTIALAGGREGATE {msg.aggregator_id} "
                              f"(L{self.group.level} fold)")

    def publish(self) -> int:
        """Flush: one PartialAggregate (codec'd when configured) to
        ``out_queue``; returns the published wire bytes.  Idempotent —
        a second call is a no-op (0 bytes)."""
        if self.flushed:
            return 0
        stages, n_samples = self.fold.partial()
        ent = stages.get(self.group.stage, {})
        codec_s = codec_base = members_z = None
        members = self.meta
        if self.codec is not None:
            if ent.get("sums"):
                from split_learning_tpu.runtime.codec.partial import (
                    encode_partial_entry,
                )
                ent, codec_s, codec_base = encode_partial_entry(
                    ent, self.codec, base=self.base,
                    base_gen=self.base_gen, faults=self.faults)
            # the member metadata is the OTHER O(clients) term of a
            # root partial's bytes — pack it with the sums
            from split_learning_tpu.runtime.protocol import (
                pack_members,
            )
            members_z = pack_members(members)
            if members_z is not None:
                members = None
        msg = PartialAggregate(
            aggregator_id=self.agg_id, cluster=self.cluster,
            group=self.group.idx, stage=self.group.stage,
            round_idx=self.gen, sums=ent.get("sums"),
            weight=float(ent.get("weight") or 0.0),
            dtypes=ent.get("dtypes"), stat_sums=ent.get("stat_sums"),
            stat_weight=float(ent.get("stat_weight") or 0.0),
            stat_dtypes=ent.get("stat_dtypes"), n_samples=n_samples,
            members=members, level=self.group.level, codec=codec_s,
            codec_base=codec_base, members_z=members_z)
        nbytes = 0
        for part in encode_parts(msg, self.chunk_bytes):
            self.bus.publish(self.out_queue, part)  # slcheck: wire=PartialAggregate
            nbytes += len(part)
        self.egress_bytes += nbytes
        self.flushed = True
        if self.log is not None:
            self.log.sent(f"PARTIALAGGREGATE members={len(self.meta)}/"
                          f"{len(self.members)}")
        return nbytes


def drain_group_queue(bus, cluster: int, group_idx: int, gen: int,
                      assembler: FrameAssembler, faults,
                      log=None) -> list:
    """Direct-to-root fallback: drain whatever a dead (or flushed)
    aggregator's queue currently holds and return the fresh-generation
    messages — member Updates for a level-1 group, child
    PartialAggregates for an interior one — so the root can fold the
    members itself."""
    out: list = []
    while True:
        q = aggregate_queue(cluster, group_idx)
        raw = bus.get(q, timeout=0.0)
        if raw is None:
            return out
        try:
            msg = assembler.feed(raw)
        except Exception as e:  # noqa: BLE001 — count and continue
            faults.inc("corrupt_rejected")
            if log is not None:
                log.warning(f"fallback drain: undecodable frame: {e}")
            continue
        if msg is None or not isinstance(msg, (Update, PartialAggregate)):
            continue
        if msg.round_idx != gen:
            faults.inc("agg_stale_drops")
            continue
        out.append(msg)
