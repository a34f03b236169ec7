"""The global round loop: train → aggregate → validate → checkpoint.

Parity with the reference server's round handling
(``/root/reference/src/Server.py:155-210``): after each round's updates
are aggregated the full model is validated on the test set; a NaN/exploded
round logs "Training failed!" and is not checkpointed
(``:184-196``); otherwise the checkpoint is (over)written and the next
round begins; resume loads the checkpoint and continues
(``:230-256``).
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import time
from typing import Any

from split_learning_tpu.config import Config
from split_learning_tpu.runtime.checkpoint import (
    load_checkpoint, release_freed_memory, save_checkpoint,
)
from split_learning_tpu.runtime.context import TrainContext
from split_learning_tpu.runtime.log import Logger
from split_learning_tpu.runtime.plan import ClusterPlan
from split_learning_tpu.runtime.strategies import make_strategy
from split_learning_tpu.runtime.trace import StepTimer


@dataclasses.dataclass
class RoundRecord:
    round_idx: int
    ok: bool
    num_samples: int
    wall_s: float
    val_loss: float | None = None
    val_accuracy: float | None = None


@dataclasses.dataclass
class TrainResult:
    params: Any
    stats: Any
    history: list


def _write_checkpoint(tracer, parent: str | None, r: int, directory,
                      model_key: str, held: list) -> None:
    """Round ``r``'s save, on the pool's thread: one ``checkpoint_write``
    span over the whole of it, a child of the round's ``checkpoint``
    span on the loop's thread (hence the explicit parent).

    ``held`` is ``[params, stats]``; the trees are taken out of it and let
    go of HERE, before this returns: once the loop has waited for the
    write, no thread but the loop's holds them.  A worker that still held
    its arguments freed the round's tree while the next round allocated:
    in a round that waited 0.2 s for the write the device's peak read
    15.72 GiB against 15.01, with this 15.02 (PERF.md section 6,
    PR 34)."""
    params, stats = held
    held.clear()
    with tracer.span("checkpoint_write", parent=parent, round=r):
        save_checkpoint(directory, model_key, params, stats,
                        round_idx=r + 1, tracer=tracer)


def run_training(cfg: Config, ctx: TrainContext,
                 plans: list[ClusterPlan],
                 logger: Logger | None = None,
                 init_params: Any | None = None,
                 init_stats: Any | None = None) -> TrainResult:
    logger = logger or Logger.for_run(cfg, "server", console=False)
    strategy = make_strategy(cfg)
    # round tracing (runtime/spans.py): the context's tracer when it
    # has one (ProtocolContext), else a loop-owned one (in-process
    # mesh runs), lent to the context for the run so the spans of its
    # own work (MeshContext: feed, upload, dispatch, fedavg, ...) land
    # under this loop's, and closed on exit
    from split_learning_tpu.runtime.spans import make_tracer
    tracer = getattr(ctx, "tracer", None)
    own_tracer = tracer is None
    if own_tracer:
        tracer = ctx.tracer = make_tracer(cfg, "server")

    start_round = 0
    params, stats = init_params, init_stats
    if cfg.checkpoint.load:
        ck = load_checkpoint(cfg.checkpoint.directory, cfg.model_key)
        if ck is not None:
            params, stats = ck["params"], ck["batch_stats"]
            start_round = ck["round_idx"]
            logger.info(f"Loaded checkpoint at round {start_round}.",
                        "green")
        del ck      # or the host keeps the loaded tree for the whole run
    if params is None:
        variables = ctx.init_variables()
        params = variables["params"]
        stats = variables.get("batch_stats", {})
    stats = stats or {}

    for plan in plans:
        logger.info(
            f"Cluster {plan.cluster_id}: cuts={plan.cuts} "
            f"clients={[len(ids) for ids in plan.clients]} "
            f"rejected={plan.rejected}", "cyan")

    history: list[RoundRecord] = []
    timer = StepTimer()
    # compute-attribution plane (runtime/perf.py): the server side
    # tracks per-round HBM watermarks and drives the on-demand
    # profiler window the exporter's POST /profile armed (protocol
    # clients attribute their own hot loops and emit their own
    # kind=perf records; this one covers the server process)
    from split_learning_tpu.runtime.perf import MemoryWatch, perf_enabled
    # honor the plane's off switch server-side too: `perf: {enabled:
    # false}` must silence the per-round memory_stats()/live_arrays
    # walk and the kind=perf record stream, not just the client half
    # (the on-demand profiler capture stays independent — POST
    # /profile is its own opt-in)
    memwatch = (MemoryWatch(gauges=getattr(ctx, "gauges", None))
                if perf_enabled(cfg) else None)
    capture = getattr(ctx, "perf_capture", None)
    t_start = time.perf_counter()
    # one-slot async checkpoint writer: the save overlaps the next
    # round's training instead of blocking the loop (params trees are
    # immutable host/device arrays, safe to serialize from a thread);
    # one slot bounds memory and keeps saves ordered
    ck_pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
    ck_future: concurrent.futures.Future | None = None
    try:
        for r in range(start_round, cfg.global_rounds):
            if r > start_round:
                # elastic membership (topology.elastic-join): late
                # registrations join, repeatedly-silent clients leave
                new_plans = ctx.refresh_plans(plans)
                if new_plans is not None:
                    plans = new_plans
                    for plan in plans:
                        logger.info(
                            f"Cluster {plan.cluster_id} (re-planned): "
                            f"cuts={plan.cuts} clients="
                            f"{[len(ids) for ids in plan.clients]}",
                            "cyan")
                # closed-loop scheduler (scheduler.enabled, protocol
                # backend): the round-boundary decision pass — online
                # clustering, straggler eviction/demotion, measured-
                # throughput cut re-planning — runs AFTER the elastic
                # refresh so it scores the membership that will
                # actually train
                schedule = getattr(ctx, "schedule_plans", None)
                if schedule is not None:
                    sched_plans = schedule(plans, r)
                    if sched_plans is not None:
                        plans = sched_plans
            if capture is not None:
                # armed via POST /profile: the window opens at this
                # round boundary and closes at the round's end (in the
                # in-process mesh it covers the compiled steps; in
                # protocol mode it profiles the server process)
                capture.maybe_start(r)
            # one span per round, with the loop phases as
            # children: the per-round anchor the critical-path
            # walker (tools/sl_trace.py) starts from
            with tracer.span("round", round=r) as round_span:
                t0 = time.perf_counter()
                with timer.phase("train"), \
                        tracer.span("train", round=r):
                    outcome = strategy.run_round(ctx, plans, r, params, stats)
                wall = time.perf_counter() - t0
                rec = RoundRecord(round_idx=r, ok=outcome.ok,
                                  num_samples=outcome.num_samples, wall_s=wall)
                if not outcome.ok:
                    logger.error(f"Round {r}: Training failed! "
                                 f"(NaN detected; aggregation skipped)")
                    history.append(rec)
                    # explicit kind stamp: these per-round records are
                    # what kind-keyed consumers (bench.py, sl_top's
                    # journal mode) select on
                    logger.metric(kind="round",
                                  **dataclasses.asdict(rec),
                                  phases=timer.summary())
                    timer.reset()  # don't leak this round's time onward
                    if capture is not None:
                        capture.stop()   # a failed round still lands
                                         # its profile artifact
                    # the failed round is the one an operator debugs:
                    # its spans must hit disk like a clean round's (the
                    # continue below skips the loop-tail flush; end()
                    # is idempotent, so the context exit stays a no-op)
                    round_span.end()
                    tracer.flush()
                    continue
                # the round's input, kept for the rollback below and no
                # longer: held into the next round it was one more copy
                # of the tree on the device than training needs
                prev = (params, stats)
                params, stats = outcome.params, outcome.stats
                if outcome.validate and cfg.checkpoint.validate:
                    with timer.phase("validate"), \
                            tracer.span("validate", round=r):
                        val = ctx.validate(params, stats)
                    rec.val_loss, rec.val_accuracy = val.loss, val.accuracy
                    rec.ok = val.ok
                    logger.info(
                        f"Round {r}: samples={outcome.num_samples} "
                        f"val_loss={val.loss:.4f} val_acc={val.accuracy:.4f} "
                        f"({wall:.1f}s)", "green" if val.ok else "red")
                    if not val.ok:
                        # reference aborts on an exploded round
                        # (src/Server.py:185-187); keep the last good weights
                        # rather than training on from garbage
                        logger.error(f"Round {r}: Training failed! "
                                     f"(validation loss exploded)")
                        params, stats = prev
                else:
                    logger.info(f"Round {r}: samples={outcome.num_samples} "
                                f"({wall:.1f}s)", "green")
                del prev
                if rec.ok and cfg.checkpoint.save:
                    with timer.phase("checkpoint"), \
                            tracer.span("checkpoint", round=r) as ck_span:
                        if ck_future is not None:
                            ck_future.result()  # surface errors; keep order
                        ck_future = ck_pool.submit(
                            _write_checkpoint, tracer, ck_span.id, r,
                            cfg.checkpoint.directory, cfg.model_key,
                            [params, stats])
                history.append(rec)
                logger.metric(kind="round", **dataclasses.asdict(rec),
                              phases=timer.summary(),
                              **({"train_detail": outcome.metrics}
                                 if outcome.metrics else {}),
                              **({"counters": outcome.counters}
                                 if outcome.counters else {}))
                timer.reset()
            if capture is not None:
                capture.stop()
            if memwatch is not None:
                try:
                    memwatch.sample()
                except Exception:  # noqa: BLE001 — watermark best-effort
                    pass
                # server-side kind=perf record: round wall + HBM
                # watermark (protocol clients emit their own
                # attribution records)
                logger.metric(kind="perf", round_idx=r, v=1,
                              wall_s=round(rec.wall_s, 6),
                              **memwatch.snapshot())
            tracer.flush()
            if cfg.limited_time and (time.perf_counter() - t_start
                                     > cfg.limited_time):
                logger.warning(f"Wall-clock budget {cfg.limited_time}s "
                               f"exhausted at round {r}.")
                break
    finally:
        # an exception escaping the loop must not leave the
        # process-global jax profiler tracing (start_trace would then
        # fail forever after) — stop() is idempotent on a closed window
        if capture is not None:
            capture.stop()
        # drain on EVERY exit: a crash mid-round must still surface a
        # failed background save and join the worker thread (the
        # protocol server calls run_training repeatedly in-process)
        if ck_future is not None:
            ck_future.result()  # the last checkpoint must be durable
        ck_pool.shutdown(wait=True)
        release_freed_memory()
        if own_tracer:
            ctx.tracer = None
            tracer.close()
        else:
            tracer.flush()
    return TrainResult(params=params, stats=stats, history=history)
