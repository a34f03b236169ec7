"""Round strategies: the reference's six server forks as plug-ins.

The reference implements each scheduling/aggregation algorithm as a full
copy of the server (SURVEY.md §2.3): the main concurrent FedAvg server
(``/root/reference/src/Server.py``), Vanilla_SL's sequential relay,
Cluster_FSL's cluster relay, FLEX's periodic aggregation, 2LS's two-level
FedAsync, and DCSL's round-robin SDA.  Here each is a
:class:`RoundStrategy` driving the same :class:`TrainContext` — host
Python decides *who trains when* and *how weights merge*; the compiled
mesh step never changes.

Aggregation math is shared: per-cluster per-stage weighted FedAvg
(``src/Server.py:398-408`` → ``src/Utils.py:35-66``), stage concatenation
(disjoint absolute layer keys), unweighted cross-cluster average
(``:410-434``).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Sequence

import jax
import numpy as np

from split_learning_tpu.config import Config
from split_learning_tpu.ops.fedavg import TreeFold, fedavg_trees
from split_learning_tpu.runtime.context import TrainContext
from split_learning_tpu.runtime.plan import ClusterPlan
from split_learning_tpu.runtime.protocol import Update


def _span(ctx, name: str, **attrs):
    """Tracing span on the context's tracer (no-op without one)."""
    tracer = getattr(ctx, "tracer", None)
    if tracer is None:
        return contextlib.nullcontext()
    return tracer.span(name, **attrs)


@dataclasses.dataclass
class RoundOutcome:
    params: Any
    stats: Any
    ok: bool = True
    num_samples: int = 0
    validate: bool = True           # run full-model validation this round?
    metrics: dict = dataclasses.field(default_factory=dict)
    # what the model's layers counted this round (the mesh context's
    # ``last_counters``); the round record's ``counters``
    counters: dict = dataclasses.field(default_factory=dict)


# --------------------------------------------------------------------------
# shared aggregation math
# --------------------------------------------------------------------------

def aggregate_cluster(updates: Sequence[Update]) -> tuple[Any, Any, int]:
    """Per-stage weighted FedAvg then stage concat for ONE cluster.

    Returns (params_tree, stats_tree, total_stage1_samples).

    When the protocol server already folded the round incrementally
    (``aggregation.streaming``, ``runtime/aggregate.py``), ``updates``
    arrives as an :class:`~split_learning_tpu.runtime.aggregate.
    UpdateBatch` whose ``fold`` member carries the finished
    :class:`~split_learning_tpu.runtime.aggregate.FoldResult` — the
    per-client trees were folded (and freed) the moment each UPDATE
    decoded, so this function just unwraps the result instead of
    re-folding.  Otherwise it runs the **reference oracle**: the
    barrier fold the streaming plane is proven bit-identical against
    in tests, itself streamed per stage through
    :class:`~split_learning_tpu.ops.fedavg.TreeFold` (one contributor
    tree + the accumulator in flight — never a list of full trees,
    slcheck AG001).

    Delta-encoded updates (``transport.codec`` rpc family) must be
    reconstructed against the server's versioned shadow BEFORE they
    reach this fold (``runtime/server.py _fold_update``) — averaging a
    delta as if it were a weight tree would corrupt the global model
    silently, so an un-reconstructed one is a hard error here.
    Weight-less updates (FLEX non-aggregation rounds, streamed rounds
    whose trees already folded, or a delta whose version chain broke
    and was stripped) carry no tree to fold and are skipped; their
    samples still count toward the round total."""
    fold = getattr(updates, "fold", None)
    by_stage: dict[int, list[Update]] = {}
    n_weightless = 0
    # dedup on (client_id, version) BEFORE any sample accounting: an
    # at-least-once transport can redeliver a client's Update after the
    # streaming fold already consumed (and weight-stripped) the first
    # copy — without this guard the weight-less skip path would count
    # the same client's samples twice (PR 6 regression)
    seen: set = set()
    for u in updates:
        if getattr(u, "delta_base", None) is not None:
            raise ValueError(
                f"delta-encoded Update from {u.client_id} (base "
                f"v{u.delta_base}) reached aggregation un-reconstructed")
        key = (u.client_id,
               u.version if getattr(u, "version", None) is not None
               else u.round_idx)
        if key in seen:
            continue
        seen.add(key)
        if fold is not None or u.params is None:
            if u.stage == 1:
                n_weightless += u.num_samples
            continue
        by_stage.setdefault(u.stage, []).append(u)
    if fold is not None:
        # the streamed result IS the barrier fold (bit-identical by
        # the canonical-order contract); its own sample count already
        # includes every stage-1 contribution
        return fold.params, fold.stats, fold.n_samples
    params: dict = {}
    stats: dict = {}
    n_samples = n_weightless   # trained samples count even when the
    # weights were stripped (broken delta chain) — the round's data
    # throughput is real; only the fold skips the client
    for stage, ups in sorted(by_stage.items()):
        # client-id order, not arrival order: float summation order must
        # not depend on which UPDATE won a thread race, or two identical
        # rounds (e.g. a chaos run vs its fault-free twin) diverge in
        # the last bits
        ups = sorted(ups, key=lambda u: u.client_id)
        pfold, sfold = TreeFold(), TreeFold()
        for u in ups:
            w = max(1, u.num_samples)
            pfold.add(u.params, w)
            if u.batch_stats:
                sfold.add(u.batch_stats, w)
        params.update(pfold.finalize())
        if sfold.total_w:
            stats.update(sfold.finalize())
        if stage == 1:
            n_samples += sum(u.num_samples for u in ups)
    return params, stats, n_samples


def merge_clusters(cluster_trees: Sequence[Any]) -> Any:
    """Unweighted cross-cluster average (``src/Server.py:410-434``).

    Deliberately NOT short-circuited for one cluster: the degenerate
    average still runs every leaf through ``nan_to_num`` — relay-style
    strategies feed RAW client trees in here, and that sanitization is
    load-bearing for them.  The FedAvg/SDA round path (whose single
    tree comes out of the already-sanitized fold) skips this call at
    the call site instead."""
    return fedavg_trees(list(cluster_trees))


def _lerp(a: Any, b: Any, alpha: float) -> Any:
    """(1-alpha)*a + alpha*b elementwise over matching pytrees."""
    return jax.tree_util.tree_map(
        lambda x, y: np.asarray((1.0 - alpha) * np.asarray(x, np.float32)
                                + alpha * np.asarray(y, np.float32),
                                dtype=np.asarray(x).dtype), a, b)


def _fill(full: Any, partial: Any) -> Any:
    """Overlay aggregated layers onto the previous full tree (clusters with
    fewer stages than layers exist only in degenerate configs; missing keys
    keep their previous values — the reference's checkpoint-merge
    semantics, ``src/Server.py:230-256``)."""
    out = dict(full)
    out.update(partial)
    return out


# --------------------------------------------------------------------------
# strategies
# --------------------------------------------------------------------------

class RoundStrategy:
    name = "base"

    def __init__(self, cfg: Config):
        self.cfg = cfg

    def run_round(self, ctx: TrainContext, plans: list[ClusterPlan],
                  round_idx: int, params: Any, stats: Any) -> RoundOutcome:
        raise NotImplementedError

    def _lr(self, round_idx: int) -> float | None:
        """DCSL-style lr decay (``other/DCSL/src/Server.py:38-39``)."""
        lrn = self.cfg.learning
        if lrn.lr_decay_every and lrn.lr_decay != 1.0:
            return lrn.learning_rate * (
                lrn.lr_decay ** (round_idx // lrn.lr_decay_every))
        return None


class FedAvgStrategy(RoundStrategy):
    """Main-server behavior: all clusters train concurrently, per-cluster
    FedAvg per stage, cross-cluster average, validate every round
    (``src/Server.py:155-210``)."""
    name = "fedavg"
    sync_all_later_stages = False   # SDA override

    def _epochs(self) -> int:
        return 1

    def run_round(self, ctx, plans, round_idx, params, stats):
        if len(plans) == 1:
            # device-resident fast path (MeshContext, plain FedAvg
            # geometry): weights stay on the mesh between rounds, the
            # round barrier is an on-mesh weighted psum — numerically
            # the same fold, none of the per-round host<->device traffic
            resident = getattr(ctx, "train_cluster_resident", None)
            if resident is not None:
                res = resident(
                    plans[0], params, stats, round_idx=round_idx,
                    epochs=self._epochs(), lr=self._lr(round_idx),
                    sync_all_later_stages=self.sync_all_later_stages)
                if res is not None:
                    if not res.ok:
                        return RoundOutcome(params, stats, ok=False,
                                            validate=False)
                    return RoundOutcome(
                        res.params, res.stats,
                        num_samples=res.num_samples,
                        metrics=getattr(res, "timings", {}) or {},
                        counters=getattr(res, "counters", {}) or {})
        cluster_params, cluster_stats = [], []
        total, ok = 0, True
        agg_s = 0.0
        detail: collections.Counter = collections.Counter()
        for plan in plans:
            ups = ctx.train_cluster(
                plan, params, stats, round_idx=round_idx,
                epochs=self._epochs(), lr=self._lr(round_idx),
                sync_all_later_stages=self.sync_all_later_stages)
            # the mesh context's clock readings for this cluster (the
            # same seconds its spans journal); the protocol context
            # keeps none
            detail.update(getattr(ctx, "last_timings", None) or {})
            ok &= all(u.ok for u in ups)
            t0 = time.perf_counter()
            p, s, n = aggregate_cluster(ups)
            agg_s += time.perf_counter() - t0
            cluster_params.append(_fill(params, p))
            cluster_stats.append(_fill(stats, s))
            total += n
        if not ok:
            # reference: round_result False -> skip aggregation entirely
            # (src/Server.py:162-166, :195-196)
            return RoundOutcome(params, stats, ok=False, validate=False)
        # the round's FedAvg fold as one "aggregate" span (round-phase
        # attribution for the critical-path report); timestamp-shifted
        # spans would misplace the per-cluster folds, so the merged
        # span covers the final merge and carries the fold total
        with _span(ctx, "aggregate", round=round_idx,
                   fold_s=round(agg_s, 6)):
            if len(plans) == 1:
                # one cluster (the common deployment): the tree IS the
                # fold's output — already nan_to_num-sanitized by the
                # fold's contribution path — so the degenerate
                # self-average would only re-materialize every leaf on
                # the round path, defeating the sharded update's
                # one-fetch-per-stage discipline (the next START
                # fan-out and delta shadow slice these arrays in place)
                out = RoundOutcome(cluster_params[0], cluster_stats[0],
                                   num_samples=total)
            else:
                out = RoundOutcome(merge_clusters(cluster_params),
                                   merge_clusters(cluster_stats),
                                   num_samples=total)
        out.metrics = dict(detail)
        # of the last cluster trained (a count is no sum over clusters)
        out.counters = dict(getattr(ctx, "last_counters", None) or {})
        return out


class SDAStrategy(FedAvgStrategy):
    """DCSL: later stages train on concatenated client batches (full
    client-axis gradient sync) for ``local_rounds`` epochs per round
    (``other/DCSL/src/Scheduler.py:152-191``, ``:83``)."""
    name = "sda"
    sync_all_later_stages = True

    def _epochs(self) -> int:
        return self.cfg.aggregation.local_rounds


class RelayStrategy(RoundStrategy):
    """Vanilla_SL: stage-1 clients train ONE AT A TIME; each finisher's
    stage-1 weights seed the next client; later stages train continuously
    (``other/Vanilla_SL/src/Server.py:130-146``, ``:248-268``)."""
    name = "relay"

    def run_round(self, ctx, plans, round_idx, params, stats):
        total, ok = 0, True
        cluster_params, cluster_stats = [], []
        for plan in plans:
            cur_p, cur_s = params, stats
            last_stage_updates: list[Update] = []
            for cid in plan.stage1_clients:
                ups = ctx.train_cluster(plan, cur_p, cur_s,
                                        round_idx=round_idx,
                                        client_subset=[cid],
                                        lr=self._lr(round_idx))
                ok &= all(u.ok for u in ups)
                for u in ups:
                    cur_p = _fill(cur_p, u.params)
                    if u.batch_stats:
                        cur_s = _fill(cur_s, u.batch_stats)
                    if u.stage == 1:
                        total += u.num_samples
                    else:
                        last_stage_updates.append(u)
            # final FedAvg across the relay's later-stage snapshots
            # (other/Vanilla_SL/src/Server.py: stage-2 devices averaged at
            # round end)
            if last_stage_updates:
                p, s, _ = aggregate_cluster(last_stage_updates)
                cur_p = _fill(cur_p, p)
                if s:
                    cur_s = _fill(cur_s, s)
            cluster_params.append(cur_p)
            cluster_stats.append(cur_s)
        if not ok:
            return RoundOutcome(params, stats, ok=False, validate=False)
        return RoundOutcome(merge_clusters(cluster_params),
                            merge_clusters(cluster_stats),
                            num_samples=total)


class ClusterRelayStrategy(RoundStrategy):
    """Cluster_FSL: clusters run sequentially; cluster i's aggregated
    stage-1 weights initialize cluster i+1; later stages carry over
    continuously (``other/Cluster_FSL/src/Server.py:151-167``,
    ``:267-288``)."""
    name = "cluster_relay"

    def run_round(self, ctx, plans, round_idx, params, stats):
        cur_p, cur_s = params, stats
        total, ok = 0, True
        for plan in plans:
            ups = ctx.train_cluster(plan, cur_p, cur_s,
                                    round_idx=round_idx,
                                    lr=self._lr(round_idx))
            ok &= all(u.ok for u in ups)
            p, s, n = aggregate_cluster(ups)
            cur_p = _fill(cur_p, p)
            cur_s = _fill(cur_s, s)
            total += n
        if not ok:
            return RoundOutcome(params, stats, ok=False, validate=False)
        return RoundOutcome(cur_p, cur_s, num_samples=total)


class PeriodicStrategy(RoundStrategy):
    """FLEX: per-client weights PERSIST across rounds; client-level FedAvg
    every ``t_client`` rounds, global merge + validation every ``t_global``
    rounds (``other/FLEX/src/Server.py:169-183``, ``:200-208``).

    Wire economy over the protocol backend (contexts with
    ``clients_hold_state``): on non-aggregation rounds clients neither
    receive weights in START nor upload them in UPDATE — the PAUSE
    ``send`` flag and param-less START of
    ``other/FLEX/src/Server.py:140-143``/``:220-226``.  Stage-1 clients
    upload on ``t_client`` and ``t_global`` boundaries; later stages only
    on ``t_global`` (``client_send``/``edge_send``).  In-process mesh
    contexts rebuild client state every round, so there the strategy
    re-pushes persisted trees each round (no wire to economize).
    """
    name = "periodic"

    def __init__(self, cfg):
        super().__init__(cfg)
        self._client_params: dict = {}   # client_id -> full tree
        self._reseed_stages: set = {0}   # 0 = every stage (initial seed)

    def run_round(self, ctx, plans, round_idx, params, stats):
        agg = self.cfg.aggregation
        hold = getattr(ctx, "clients_hold_state", False)
        boundary_c = (round_idx + 1) % agg.t_client == 0
        boundary_g = (round_idx + 1) % agg.t_global == 0
        total, ok = 0, True
        cluster_params, cluster_stats = [], []
        for plan in plans:
            if hold:
                send_w = {s: (boundary_c or boundary_g) if s == 1
                          else boundary_g
                          for s in range(1, plan.n_stages + 1)}
                send_p = {s: (0 in self._reseed_stages
                              or s in self._reseed_stages)
                          for s in range(1, plan.n_stages + 1)}
            else:
                send_w = send_p = True
            ups = ctx.train_cluster(
                plan, params, stats, round_idx=round_idx,
                per_client_params=dict(self._client_params),
                lr=self._lr(round_idx),
                send_params=send_p, send_weights=send_w)
            ok &= all(u.ok for u in ups)
            for u in ups:
                if u.stage == 1:
                    total += u.num_samples
            # persist each uploading client's full tree (its shard
            # overlaid on the round's base); weight-less updates (FLEX
            # non-aggregation rounds) persist nothing
            got_w = [u for u in ups if u.params is not None]
            for u in got_w:
                base = self._client_params.get(u.client_id, params)
                # FLEX client-level persistence IS the strategy (one
                # bounded tree per stage-1 client, not a round-path
                # accumulation)
                self._client_params[u.client_id] = _fill(base, u.params)  # slcheck: agg-state
            if got_w:
                p, s, _ = aggregate_cluster(got_w)
                cluster_params.append(_fill(params, p))
                cluster_stats.append(_fill(stats, s))
            if boundary_c and not boundary_g and got_w:
                # client-level FedAvg: reset the cluster's stage-1
                # clients to the cluster average
                # (other/FLEX/src/Server.py:169-183)
                for cid in plan.stage1_clients:
                    self._client_params[cid] = cluster_params[-1]
        if not ok:
            self._reseed_stages = {0}   # deterministic recovery re-seed
            return RoundOutcome(params, stats, ok=False, validate=False)
        self._reseed_stages = set()
        if boundary_g:
            merged = merge_clusters(cluster_params)
            merged_stats = merge_clusters(cluster_stats)
            self._client_params.clear()  # re-seed everyone from global
            self._reseed_stages = {0}
            return RoundOutcome(merged, merged_stats, num_samples=total,
                                validate=True)
        if boundary_c:
            self._reseed_stages = {1}
        return RoundOutcome(params, stats, num_samples=total,
                            validate=False)


class FedAsyncStrategy(RoundStrategy):
    """2LS two-level clustering + FedAsync
    (``other/2LS/src/Server.py:170-233``).

    Out-clusters (the ``plans``) execute sequentially in shuffled order
    per round.  Within an out-cluster, ``topology.in_clusters``
    in-clusters — contiguous groups of stage-1 clients, each paired with
    a stage-2 head (``other/2LS/client.py:15-17``) — train
    concurrently; each in-cluster's 2-stage average then merges into the
    global model in completion order with ``alpha = 1/(1+rank)`` (or the
    fixed config alpha): ``g = (1-a) g + a c``.  Rank resets per
    out-cluster, so the first in-cluster's average replaces the global
    (``fed_async_aggregate`` with ``alpha=1``) — continuity across
    out-clusters flows through the training init, reference-faithfully.
    ``in_clusters=1`` degenerates to one merge per out-cluster.

    When head counts don't match ``in_clusters`` the protocol backend
    keeps shared forward queues (no fixed pairing on the wire;
    ``runtime/server.py`` logs it) while aggregation still partitions
    updates round-robin over the in-groups — every update counted
    exactly once, merge order as configured.
    """
    name = "fedasync"

    def _in_groups(self, plan: ClusterPlan) -> list[tuple[list, set]]:
        """[(stage1_member_ids, later_stage_member_ids)] per in-cluster.

        Later-stage clients are PARTITIONED over the in-clusters
        round-robin, so every update belongs to exactly one in-cluster
        (1:1 pairing when counts match — the reference topology; with
        ``in_clusters=1`` every client lands in the single group,
        reducing to a whole-cluster average)."""
        from split_learning_tpu.runtime.context import client_groups
        n_in = max(1, self.cfg.topology.in_clusters)
        s1 = plan.stage1_clients
        groups = client_groups(len(s1), min(n_in, len(s1)))
        later: list[set] = [set() for _ in groups]
        for s in range(2, plan.n_stages + 1):
            for j, cid in enumerate(plan.clients[s - 1]):
                later[j % len(groups)].add(cid)
        return [([s1[i] for i in idxs], later[g])
                for g, idxs in enumerate(groups)]

    def run_round(self, ctx, plans, round_idx, params, stats):
        rng = np.random.default_rng(self.cfg.seed + round_idx)
        order = rng.permutation(len(plans))
        g_p, g_s = params, stats
        total, ok = 0, True
        saved_any = False   # any per-merge checkpoint written this round
        for pi in order:
            plan = plans[pi]
            ups = ctx.train_cluster(plan, g_p, g_s, round_idx=round_idx,
                                    lr=self._lr(round_idx))
            ok &= all(u.ok for u in ups)
            rank = 0   # over REPORTING in-clusters only: the reference
            # enumerates check_in_cluster (groups that actually finished,
            # other/2LS/src/Server.py:178-184), so a dropped in-cluster
            # must not shift the survivors' alphas
            for members, later in self._in_groups(plan):
                in_ups = [u for u in ups
                          if (u.stage == 1 and u.client_id in members)
                          or (u.stage >= 2 and u.client_id in later)]
                if not in_ups:
                    continue
                p, s, n = aggregate_cluster(in_ups)
                alpha = (self.cfg.aggregation.fedasync_alpha
                         if self.cfg.aggregation.fedasync_alpha is not None
                         else 1.0 / (1.0 + rank))
                rank += 1
                g_p = _lerp(g_p, _fill(g_p, p), alpha)
                g_s = _fill(g_s, s)
                total += n
                if (ok and self.cfg.checkpoint.per_merge
                        and self.cfg.checkpoint.save):
                    # 2LS persists every alpha-merge
                    # (other/2LS/src/Server.py:184): a crash mid-round
                    # then loses at most one in-cluster's work.
                    # Synchronous like the reference — per-merge
                    # durability is the point; don't trade it for
                    # overlap.  Gated on `ok` so far: once any update
                    # was NaN-flagged the round will revert, and a
                    # tainted merge must not overwrite the last good
                    # checkpoint on disk (the round loop only saves
                    # rec.ok rounds — same contract here)
                    from split_learning_tpu.runtime.checkpoint import (
                        save_checkpoint,
                    )
                    save_checkpoint(self.cfg.checkpoint.directory,
                                    self.cfg.model_key, g_p, g_s,
                                    round_idx=round_idx)
                    saved_any = True
        if not ok:
            if saved_any:
                # a LATER plan's NaN reverts the round, but earlier
                # clean merges already overwrote the checkpoint — put
                # the round-entry state back so a crash never resumes
                # from a state the run rejected
                from split_learning_tpu.runtime.checkpoint import (
                    save_checkpoint,
                )
                save_checkpoint(self.cfg.checkpoint.directory,
                                self.cfg.model_key, params, stats,
                                round_idx=round_idx)
            return RoundOutcome(params, stats, ok=False, validate=False)
        return RoundOutcome(g_p, g_s, num_samples=total)


_STRATEGIES = {
    cls.name: cls for cls in (
        FedAvgStrategy, SDAStrategy, RelayStrategy, ClusterRelayStrategy,
        PeriodicStrategy, FedAsyncStrategy)
}


def make_strategy(cfg: Config) -> RoundStrategy:
    name = cfg.aggregation.strategy
    if name not in _STRATEGIES:
        raise ValueError(f"unknown strategy {name!r}; "
                         f"known: {sorted(_STRATEGIES)}")
    return _STRATEGIES[name](cfg)
