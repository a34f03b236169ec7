"""Per-participant compute performance-attribution plane.

PRs 5 and 7 made the wire and the fleet observable; the compute side
stayed dark: ``train`` is one opaque block in the critical path, the
bench's MFU row has no runtime twin, and nothing accounts for compile
time, retraces, or HBM watermarks while a round runs.  This module is
the compute half of the compute/wire ratio the closed-loop scheduler
(ROADMAP item 1) must consume:

* :class:`SampledStepTimer` — sampled per-stage step timing.  Every hot-loop
  step records its *dispatch* wall (the async-dispatch cost the
  training thread actually pays); every ``perf.sample-every``-th step
  additionally fences the step's outputs (``jax.block_until_ready``
  behind the sampler gate — the ``perf`` slcheck analyzer holds hot
  loops to exactly this discipline) and records the *device* wall, so
  the hot loop stays sync-free in steady state while device time is
  still measured.  A *host* accumulator times data loading/conversion.
  Components feed the existing :class:`~split_learning_tpu.runtime
  .trace.HistogramSet` (``step_dispatch``/``step_device``) and the
  ``step_seconds`` gauge.
* :class:`CompileWatch` — wraps jitted entry points (a
  :class:`~split_learning_tpu.runtime.client.ShardRunner`'s five ops).
  A growth of the wrapped function's jit cache is a compile: counted
  per op, its wall-clock accumulated (``compile_seconds_total``),
  emitted as a ``compile`` span into the span journal (so
  ``tools/sl_trace.py`` critical paths separate compile from compute),
  and — the live twin of slcheck's static JX004 retrace rule — any
  compile after round 0 raises the ``retraces`` fault counter.  The
  compiled step's XLA ``cost_analysis()`` FLOPs are captured once per
  signature, so every later call accrues measured FLOPs for MFU.
* :class:`MemoryWatch` — per-round peak-HBM watermark from
  ``device.memory_stats()`` (falling back to summing
  ``jax.live_arrays()`` where the backend reports none, e.g. CPU),
  published as the ``hbm_peak_bytes`` gauge and compared against a
  static plan estimate (bench.py's memory plan) when one is noted.
* **MFU accounting** — measured FLOPs (CompileWatch) ÷ round wall ÷ a
  per-platform datasheet bf16 peak (:data:`DATASHEET_BF16_TFLOPS`,
  overridable via ``perf.datasheet``; CPU has no datasheet row — the
  bench's measured matmul roofline or a config override stands in).
  Published as the ``mfu`` gauge, piggybacked on HEARTBEAT snapshots
  (gauges ride every :class:`~split_learning_tpu.runtime.telemetry
  .TelemetrySnapshot`), rendered as ``sl_mfu`` on ``/metrics``, and
  written into ``kind=perf`` metrics records.
* :class:`ProfileCapture` — the on-demand ``jax.profiler`` hook:
  ``POST /profile?steps=K`` on the TelemetryExporter arms a K-step
  trace window opened at the next round boundary, artifact landing in
  ``artifacts/runs/<run_id>/profile/round<r>/``.
* :class:`PerfPlane` — the facade a participant owns: round lifecycle
  (``start_round`` / ``note_step`` / ``host`` / ``end_round``), the
  ``kind=perf`` attribution record whose
  ``compute + compile + dispatch + host + wait`` components sum to the
  round's wall by construction, and the gauge updates.

No jax at module import (lazy inside methods): ``tools/sl_perf.py``
and the bench orchestrator read the datasheet table and record schema
without touching an accelerator runtime.
"""

from __future__ import annotations

import contextlib
import json
import pathlib
import threading
import time
import weakref
from typing import Any, Callable

#: kind=perf record schema version (bump on breaking change)
PERF_SCHEMA_VERSION = 1

#: Datasheet bf16 peak TFLOP/s per chip, keyed by jax ``device_kind``
#: (Google Cloud TPU documentation, per-generation spec tables;
#: bench.py's MFU section reads this same table).  The host CPU is a
#: known device with no datasheet peak: its row is None and no MFU is
#: reported for it unless a ``perf.datasheet`` override supplies a
#: measured roofline.  A device that is not in the table is an error
#: (:func:`resolve_peak_tflops`), never a default.
DATASHEET_BF16_TFLOPS = {
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5": 459.0,  # v5p
    "TPU v5p": 459.0,
    "TPU v4": 275.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
    "cpu": None,
}


def resolve_peak_tflops(device_kind: str,
                        override: dict | None = None) -> float | None:
    """Datasheet bf16 peak for ``device_kind``; an override mapping
    (``perf.datasheet``) wins — that is also how a CPU proxy run pins
    its measured roofline as the MFU denominator.  Raises for a device
    neither the override nor the table knows: an MFU against a guessed
    peak would be a wrong number under a device metric's name."""
    if override and device_kind in override:
        return float(override[device_kind])
    try:
        return DATASHEET_BF16_TFLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"device_kind {device_kind!r} is not in the peaks table "
            f"(runtime/perf.py DATASHEET_BF16_TFLOPS: "
            f"{sorted(DATASHEET_BF16_TFLOPS)}); add its datasheet row "
            f"or set perf.datasheet") from None


def flops_of_compiled(fn, *args, **kwargs) -> float | None:
    """Per-call FLOPs from XLA ``cost_analysis()`` of ``fn`` compiled
    for these arguments (compile-cache hit when the caller already
    executed the same signature); None when the backend reports none."""
    try:
        compiled = fn.lower(*args, **kwargs).compile()
        cost = compiled.cost_analysis()
        flops = (cost or {}).get("flops")
        return float(flops) if flops else None
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        return None


# --------------------------------------------------------------------------
# sampled step timing
# --------------------------------------------------------------------------

class SampledStepTimer:
    """Sampled per-step timing: dispatch every step, device on sampled
    steps only, host-data via a context manager.

    The hot loop pays ``note_step(t0, tree)`` per step: dispatch wall
    (``now - t0``) always, and — every ``sample_every``-th step — a
    ``block_until_ready`` fence on ``tree`` to measure device wall.
    The device total for the round is *estimated* by scaling the
    sampled mean to the full step count; ``attribution()`` reports the
    raw sampled seconds too so the extrapolation is auditable."""

    def __init__(self, sample_every: int = 16, hists=None, gauges=None,
                 fence: Callable | None = None,
                 compile_overlap: Callable[[float, float], float]
                 | None = None):
        self.sample_every = max(1, int(sample_every))
        self._hists = hists
        self._gauges = gauges
        self._fence = fence
        # compile-time deduplication: a step whose jitted call COMPILED
        # spent most of its window in XLA, and that wall belongs to the
        # `compile` component, not `dispatch` — the CompileWatch hands
        # back the compile seconds overlapping a step window
        self._compile_overlap = compile_overlap
        self._lock = threading.Lock()
        self.round_idx: int | None = None
        self._reset()

    def _reset(self) -> None:
        self.steps = 0
        self.sampled_steps = 0
        self.dispatch_s = 0.0
        self.device_sampled_s = 0.0
        self.host_s = 0.0
        self.samples = 0
        self._t_round = None

    def start_round(self, round_idx: int) -> None:
        with self._lock:
            self._reset()
            self.round_idx = round_idx
            self._t_round = time.perf_counter()

    def note_step(self, t0: float, tree=None, n: int = 0) -> None:
        """One hot-loop step that began at ``perf_counter()`` time
        ``t0``; ``tree`` is the step's output pytree (fenced only on
        sampled steps), ``n`` the samples it trained."""
        t1 = time.perf_counter()
        dispatch = max(0.0, t1 - t0)
        if self._compile_overlap is not None:
            dispatch = max(0.0, dispatch - self._compile_overlap(t0, t1))
        with self._lock:
            self.steps += 1
            self.dispatch_s += dispatch
            self.samples += n
            sampled = tree is not None and \
                self.steps % self.sample_every == 0
        if self._hists is not None:
            self._hists.observe("step_dispatch", dispatch)
        if sampled:
            # the sampler gate: the ONLY device sync the hot loop pays,
            # once every sample-every steps (the ``perf`` slcheck
            # analyzer, PF001, holds every hot-loop fence to this)
            if self._fence is not None:
                self._fence(tree)
            else:
                import jax
                jax.block_until_ready(tree)
            device = max(0.0, time.perf_counter() - t1)
            with self._lock:
                self.sampled_steps += 1
                self.device_sampled_s += device
            if self._hists is not None:
                self._hists.observe("step_device", dispatch + device)
            if self._gauges is not None:
                self._gauges.set("step_seconds",
                                 round(dispatch + device, 6))

    @contextlib.contextmanager
    def host(self):
        """Time a host-data interval (loader fetch, np->device
        conversion) into the ``host`` attribution component."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = max(0.0, time.perf_counter() - t0)
            with self._lock:
                self.host_s += dt

    def device_est_s(self) -> float:
        """Round device-seconds estimate: sampled mean x step count."""
        with self._lock:
            if self.sampled_steps == 0:
                return 0.0
            return (self.device_sampled_s / self.sampled_steps
                    * self.steps)

    def attribution(self, wall_s: float | None = None) -> dict:
        with self._lock:
            wall = (wall_s if wall_s is not None
                    else (time.perf_counter() - self._t_round
                          if self._t_round is not None else 0.0))
            out = {
                "steps": self.steps,
                "sampled_steps": self.sampled_steps,
                "sample_every": self.sample_every,
                "dispatch_s": round(self.dispatch_s, 6),
                "device_sampled_s": round(self.device_sampled_s, 6),
                "host_s": round(self.host_s, 6),
                "wall_s": round(wall, 6),
            }
        out["device_est_s"] = round(self.device_est_s(), 6)
        return out


# --------------------------------------------------------------------------
# compile / retrace accounting
# --------------------------------------------------------------------------

#: per-inner-fn high-water mark of BOOKED jit-cache sizes.  In-process
#: clients with identical (model, layers, learning) share one jitted
#: fn via client.py's ``_OPS_CACHE`` but wrap it with their OWN
#: CompileWatch; when a new signature compiles, every concurrently
#: blocked caller observes the same cache growth — exactly one of
#: them may book the compile (and a possible retrace), or compile_s
#: double-counts across the fleet.  Weak keys: the ledger must not
#: pin a rebuilt runner's dropped ops.
_CACHE_CLAIMS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_CACHE_CLAIMS_LOCK = threading.Lock()


def _claim_cache_growth(fn, after: int) -> bool:
    """True for exactly one observer of a given cache-size level."""
    try:
        with _CACHE_CLAIMS_LOCK:
            booked = _CACHE_CLAIMS.get(fn, 0)
            if after <= booked:
                return False
            _CACHE_CLAIMS[fn] = after
            return True
    except TypeError:   # not weak-referenceable: book unconditionally
        return True


class CompileWatch:
    """Wrap jitted entry points to count compiles, accumulate compile
    wall-clock, journal ``compile`` spans, capture per-signature FLOPs,
    and raise the ``retraces`` counter on any compile after round 0 —
    the live twin of slcheck's static retrace rule (JX004)."""

    def __init__(self, faults=None, tracer=None, gauges=None, log=None):
        self._faults = faults
        self._tracer = tracer
        self._gauges = gauges
        self._log = log
        self._lock = threading.Lock()
        self.compiles: dict[str, int] = {}
        self.compile_s = 0.0
        self.round_compile_s = 0.0
        self.retraces = 0
        self.round_idx = 0
        #: the first round THIS watch participated in — a client that
        #: joins (or restarts) at round 5 pays its cold compiles there,
        #: and those are warmup, not retraces
        self._first_round: int | None = None
        #: ops that have compiled through the CURRENT wrap generation;
        #: only a RE-compile of a warm op counts as a retrace (a
        #: rebuilt runner's fresh ops reset their entry — see wrap())
        self._warm_ops: set[str] = set()
        self._flops: dict[str, float] = {}   # per-call FLOPs by op name
        self._flops_failed: set[str] = set()  # don't re-lower per call
        self.round_flops = 0.0
        # perf_counter intervals of this round's compiles (bounded),
        # so the SampledStepTimer can subtract compile wall from a step
        # window it overlaps instead of double-counting it as dispatch
        self._round_events: list[tuple[float, float]] = []

    def note_round(self, round_idx: int) -> None:
        with self._lock:
            if self._first_round is None:
                self._first_round = round_idx
            self.round_idx = round_idx
            self.round_flops = 0.0
            self.round_compile_s = 0.0
            self._round_events = []

    def overlap(self, t0: float, t1: float) -> float:
        """Compile seconds overlapping the perf_counter window
        [t0, t1] (fed to SampledStepTimer as ``compile_overlap``)."""
        with self._lock:
            return sum(max(0.0, min(b, t1) - max(a, t0))
                       for a, b in self._round_events)

    @staticmethod
    def _cache_size(fn) -> int | None:
        size = getattr(fn, "_cache_size", None)
        if size is None:
            return None
        try:
            return int(size())
        except Exception:  # noqa: BLE001 — foreign callable
            return None

    def _note_compile(self, name: str, t0_wall: float, t0_pc: float,
                      dt: float) -> None:
        with self._lock:
            self.compiles[name] = self.compiles.get(name, 0) + 1
            self.compile_s += dt
            self.round_compile_s += dt
            if len(self._round_events) < 512:
                self._round_events.append((t0_pc, t0_pc + dt))
            # a retrace is a RE-compile of an op that already compiled
            # through this wrap generation, past the participant's own
            # warmup round — first-time compiles of a client joining
            # (or restarting) mid-run, and of a rebuilt runner's fresh
            # ops, are cold compiles, not leaks
            retrace = (self._first_round is not None
                       and self.round_idx > self._first_round
                       and name in self._warm_ops)
            self._warm_ops.add(name)
            if retrace:
                self.retraces += 1
        if retrace:
            if self._faults is not None:
                self._faults.inc("retraces")
            if self._log is not None:
                self._log.warning(
                    f"retrace of {name!r} at round {self.round_idx} "
                    f"({dt:.2f}s): a post-warmup compile means a shape/"
                    "dtype/hash leaked into trace time")
        if self._tracer is not None:
            self._tracer.record("compile", t0_wall, t0_wall + dt,
                                always=True, op=name,
                                round=self.round_idx)
        if self._gauges is not None:
            with self._lock:
                total = self.compile_s
            self._gauges.set("compile_seconds_total", round(total, 4))

    def _ensure_flops(self, name: str, fn, args, kwargs) -> None:
        """Per-call FLOPs captured on the op's FIRST CALL through this
        watch, not its first observed compile: a client sharing an
        already-warm jit cache (same-process feeders share the runner
        ops bundle) never sees a compile but must still get MFU.  The
        trace+lower wall ``cost_analysis`` pays — real even on a
        compile-cache hit — is booked as compile time and into the
        overlap ledger so the hot-loop step that triggered it doesn't
        misattribute it as dispatch."""
        with self._lock:
            if name in self._flops or name in self._flops_failed:
                return
        t0 = time.perf_counter()
        flops = flops_of_compiled(fn, *args, **kwargs)
        dt = time.perf_counter() - t0
        with self._lock:
            if flops:
                self._flops[name] = flops
            else:
                self._flops_failed.add(name)
            self.compile_s += dt
            self.round_compile_s += dt
            if len(self._round_events) < 512:
                self._round_events.append((t0, t0 + dt))

    def wrap(self, name: str, fn):
        """``fn`` with compile detection; calls accrue round FLOPs."""
        if getattr(fn, "_perf_watch", None) is self:
            return fn   # idempotent (hold STARTs re-wrap the runner)
        with self._lock:
            # a fresh fn under a known name = the runner was rebuilt
            # (hyperparams changed mid-hold): its first compile is
            # warmup again, not a retrace — and its per-call FLOPs
            # must be re-captured (a different shard geometry would
            # otherwise keep accruing the OLD shard's FLOPs into MFU)
            self._warm_ops.discard(name)
            self._flops.pop(name, None)
            self._flops_failed.discard(name)

        def wrapped(*args, **kwargs):
            before = self._cache_size(fn)
            t0_wall = time.time()
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            dt = time.perf_counter() - t0
            if before is not None:
                after = self._cache_size(fn)
                if (after is not None and after > before
                        and _claim_cache_growth(fn, after)):
                    self._note_compile(name, t0_wall, t0, dt)
            self._ensure_flops(name, fn, args, kwargs)
            with self._lock:
                self.round_flops += self._flops.get(name, 0.0)
            return out

        wrapped._perf_watch = self
        wrapped._perf_inner = fn
        return wrapped

    def wrap_runner(self, runner) -> None:
        """Wrap a ShardRunner's five jitted ops in place (instance
        attributes only — the shared ``_OPS_CACHE`` bundle is
        untouched)."""
        for name in ("fwd", "bwd", "last_step", "whole_step",
                     "apply_update"):
            fn = getattr(runner, name, None)
            if fn is not None:
                setattr(runner, name, self.wrap(name, fn))

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "compiles": dict(self.compiles),
                "compile_s_total": round(self.compile_s, 4),
                "compile_s_round": round(self.round_compile_s, 4),
                "retraces": self.retraces,
                "flops_per_step": dict(self._flops),
                "round_flops": self.round_flops,
            }


# --------------------------------------------------------------------------
# HBM watermarks
# --------------------------------------------------------------------------

class MemoryWatch:
    """Per-round device-memory watermarks vs a static plan estimate."""

    def __init__(self, gauges=None):
        self._gauges = gauges
        self._lock = threading.Lock()
        self.peak_bytes: int | None = None
        self.plan_est_bytes: int | None = None

    def note_plan_estimate(self, nbytes: int) -> None:
        """Record the static residency estimate this run was planned
        against (bench.py's memory plan), so the measured watermark is
        comparable to the planner's promise."""
        with self._lock:
            self.plan_est_bytes = int(nbytes)

    def sample(self) -> int | None:
        """Current peak/live device bytes: ``memory_stats()`` where
        the backend reports them, else the summed ``live_arrays``
        footprint (CPU)."""
        import jax
        total = 0
        got = False
        for d in jax.local_devices():
            try:
                ms = d.memory_stats()
            except Exception:  # noqa: BLE001 — backend-dependent API
                ms = None
            if ms:
                total += int(ms.get("peak_bytes_in_use")
                             or ms.get("bytes_in_use") or 0)
                got = True
        if not got:
            try:
                total = sum(int(a.nbytes) for a in jax.live_arrays())
                got = True
            except Exception:  # noqa: BLE001
                return None
        if not got:
            return None
        with self._lock:
            if self.peak_bytes is None or total > self.peak_bytes:
                self.peak_bytes = total
        if self._gauges is not None:
            self._gauges.set("hbm_peak_bytes", total)
        return total

    def snapshot(self) -> dict:
        with self._lock:
            out: dict = {}
            if self.peak_bytes is not None:
                out["hbm_peak_bytes"] = self.peak_bytes
            if self.plan_est_bytes:
                out["hbm_plan_est_bytes"] = self.plan_est_bytes
                if self.peak_bytes:
                    out["hbm_peak_vs_plan"] = round(
                        self.peak_bytes / self.plan_est_bytes, 4)
            return out


# --------------------------------------------------------------------------
# on-demand profiler capture
# --------------------------------------------------------------------------

#: the process-wide capture hot loops tick (see register_process_capture)
_process_capture: "ProfileCapture | None" = None


def register_process_capture(capture: "ProfileCapture | None") -> None:
    """Make ``capture`` the capture every :class:`PerfPlane` in this
    process ticks from its hot loops.  The jax profiler is
    process-global (one trace window per process), so in-process
    deployments — client threads sharing the server process — close a
    server-armed ``steps=K`` window after K hot-loop steps.  Separate
    client processes have no registered capture (their steps cannot
    tick another process's profiler); there the window closes at the
    round boundary and profiles the server process."""
    global _process_capture
    _process_capture = capture


def process_capture() -> "ProfileCapture | None":
    return _process_capture


class ProfileCapture:
    """``POST /profile?steps=K`` arms a ``jax.profiler`` trace window
    opened at the next round boundary and closed after K hot-loop
    steps (or at the round's end, whichever comes first); the artifact
    lands under ``<out_dir>/round<r>/`` with a ``capture.json``
    manifest, so the directory is self-describing even if the XLA
    trace itself fails to materialize."""

    def __init__(self, out_dir: str | pathlib.Path, log=None):
        self.out_dir = pathlib.Path(out_dir)
        self._log = log
        self._lock = threading.Lock()
        self._armed_steps: int | None = None
        self._active_dir: pathlib.Path | None = None
        self._steps_left = 0
        self._t0 = 0.0

    @property
    def armed(self) -> bool:
        with self._lock:
            return self._armed_steps is not None

    @property
    def active(self) -> bool:
        with self._lock:
            return self._active_dir is not None

    def arm(self, steps: int = 1) -> dict:
        """Arm a capture window (idempotent re-arm updates K).  Called
        from the exporter's HTTP handler thread — just flips state."""
        steps = max(1, int(steps))
        with self._lock:
            self._armed_steps = steps
        if self._log is not None:
            self._log.info(f"profiler armed: {steps}-step capture at "
                           "the next round", "cyan")
        return {"armed": True, "steps": steps,
                "dir": str(self.out_dir)}

    def maybe_start(self, round_idx: int) -> bool:
        """Round boundary: open the trace window if armed."""
        with self._lock:
            if self._armed_steps is None or self._active_dir is not None:
                return False
            steps = self._armed_steps
            self._armed_steps = None
            target = self.out_dir / f"round{round_idx}"
            self._active_dir = target
            self._steps_left = steps
            self._t0 = time.time()
        try:
            target.mkdir(parents=True, exist_ok=True)
            import jax
            jax.profiler.start_trace(str(target))
        except Exception as e:  # noqa: BLE001 — a profiler failure
            # must not take the round down; the manifest records it
            self._write_manifest(target, round_idx, steps, error=str(e))
            with self._lock:
                self._active_dir = None
            return False
        if self._log is not None:
            self._log.info(f"profiler capture started -> {target}",
                           "cyan")
        self._round_idx = round_idx
        self._steps_total = steps
        return True

    def note_step(self) -> None:
        """Hot-loop tick; closes the window when K steps elapsed."""
        with self._lock:
            if self._active_dir is None:
                return
            self._steps_left -= 1
            done = self._steps_left <= 0
        if done:
            self.stop()

    def stop(self) -> None:
        """Close an open window (round end forces this)."""
        with self._lock:
            target = self._active_dir
            self._active_dir = None
        if target is None:
            return
        err = None
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            err = str(e)
        self._write_manifest(target, getattr(self, "_round_idx", None),
                             getattr(self, "_steps_total", None),
                             error=err)
        if self._log is not None:
            self._log.info(f"profiler capture written -> {target}",
                           "cyan")

    def _write_manifest(self, target: pathlib.Path, round_idx, steps,
                        error=None) -> None:
        try:
            target.mkdir(parents=True, exist_ok=True)
            rec = {"round": round_idx, "steps": steps,
                   "t_start": round(self._t0, 3),
                   "wall_s": round(time.time() - self._t0, 3)}
            if error:
                rec["error"] = error
            (target / "capture.json").write_text(json.dumps(rec))
        except OSError:
            pass


# --------------------------------------------------------------------------
# the facade
# --------------------------------------------------------------------------

class PerfPlane:
    """One participant's compute-attribution plane: step timer +
    compile watch + memory watch + MFU, emitting one ``kind=perf``
    record per round whose components sum to the round wall."""

    def __init__(self, participant: str, sample_every: int = 16,
                 datasheet: dict | None = None, gauges=None, hists=None,
                 faults=None, tracer=None, log=None,
                 enabled: bool = True,
                 capture: ProfileCapture | None = None):
        self.participant = participant
        self.enabled = enabled
        self.datasheet = dict(datasheet or {})
        self.gauges = gauges
        self.log = log
        self.capture = capture
        self.compile = CompileWatch(faults=faults, tracer=tracer,
                                    gauges=gauges, log=log)
        self.steps = SampledStepTimer(sample_every=sample_every, hists=hists,
                               gauges=gauges,
                               compile_overlap=self.compile.overlap)
        self.memory = MemoryWatch(gauges=gauges)
        self._peak_tflops: float | None = None
        self._peak_resolved = False
        self._t_round: float | None = None
        self._round_idx: int | None = None

    # -- lifecycle -----------------------------------------------------------

    def start_round(self, round_idx: int) -> None:
        if not self.enabled:
            return
        self._round_idx = round_idx
        self._t_round = time.perf_counter()
        self.steps.start_round(round_idx)
        self.compile.note_round(round_idx)
        if self.capture is not None:
            self.capture.maybe_start(round_idx)

    def note_step(self, t0: float, tree=None, n: int = 0) -> None:
        if not self.enabled:
            return
        self.steps.note_step(t0, tree=tree, n=n)
        if self.capture is not None:
            self.capture.note_step()

    def host(self):
        if not self.enabled:
            return contextlib.nullcontext()
        return self.steps.host()

    def wrap_runner(self, runner) -> None:
        if self.enabled:
            self.compile.wrap_runner(runner)

    # -- MFU -----------------------------------------------------------------

    def peak_tflops(self) -> float | None:
        """Datasheet peak for this process's device kind (cached)."""
        if not self._peak_resolved:
            import jax
            self._peak_tflops = resolve_peak_tflops(
                jax.devices()[0].device_kind, self.datasheet)
            self._peak_resolved = True
        return self._peak_tflops

    # -- the round record ----------------------------------------------------

    def end_round(self, samples: int = 0,
                  wall_s: float | None = None) -> dict | None:
        """Close the round: sample HBM, compute the attribution and
        MFU, set the gauges, and return the ``kind=perf`` record (None
        when the plane is disabled or no round was started)."""
        if not self.enabled or self._t_round is None:
            return None
        # deliberately NOT stopping self.capture here: it is the
        # process-wide capture (shared by every in-proc client plane),
        # and the first client to finish its round must not truncate a
        # steps=K window the others are still ticking — the round loop
        # (loop.py) closes it at the round boundary, K hot-loop ticks
        # close it early
        wall = (wall_s if wall_s is not None
                else time.perf_counter() - self._t_round)
        att = self.steps.attribution(wall_s=wall)
        csnap = self.compile.snapshot()
        compile_s = csnap["compile_s_round"]
        device_est = att["device_est_s"]
        dispatch_s = att["dispatch_s"]
        host_s = att["host_s"]
        # the identity the attribution tests pin: compute + compile +
        # dispatch + host + wait == wall (wait = the unattributed rest:
        # queue/barrier/wire waits, control traffic).  In a pipelined
        # hot loop a sampled fence drains ALL in-flight steps, so the
        # extrapolated device estimate can overlap dispatch/host of
        # later steps and overshoot the wall — clamp compute to the
        # unattributed remainder (the overlapped part is not extra
        # wall time) and keep the raw estimate auditable.
        device_s = min(device_est,
                       max(0.0, wall - dispatch_s - host_s - compile_s))
        wait_s = max(0.0, wall - device_s - dispatch_s - host_s
                     - compile_s)
        rec: dict[str, Any] = {
            "v": PERF_SCHEMA_VERSION,
            "round": self._round_idx,
            "wall_s": round(wall, 6),
            "compute_s": round(device_s, 6),
            "compile_s": round(compile_s, 6),
            "dispatch_s": round(dispatch_s, 6),
            "host_s": round(host_s, 6),
            "wait_s": round(wait_s, 6),
            "steps": att["steps"],
            "sampled_steps": att["sampled_steps"],
            "sample_every": att["sample_every"],
            "samples": samples,
            "compiles": csnap["compiles"],
            "compile_s_total": csnap["compile_s_total"],
            "retraces": csnap["retraces"],
        }
        if device_est > device_s + 1e-6:
            rec["compute_est_s"] = round(device_est, 6)
        self._mem_sample()
        rec.update(self.memory.snapshot())
        flops = csnap["round_flops"]
        if flops:
            rec["flops"] = flops
            tflops = flops / max(wall, 1e-9) / 1e12
            rec["tflops_per_sec"] = round(tflops, 4)
            peak = self.peak_tflops()
            if peak:
                rec["mfu"] = round(tflops / peak, 5)
                rec["peak_tflops"] = peak
                if self.gauges is not None:
                    self.gauges.set("mfu", rec["mfu"])
        # compute rate: samples over the time the device/dispatcher was
        # actually busy — lets the fleet monitor tell slow-COMPUTE from
        # slow-WIRE stragglers (overall samples/s conflates them).
        # Uses the RAW device estimate: overlap clamped out of the
        # wall attribution above is still real device busy time.
        # No fenced step this round (steps < sample-every) means NO
        # device estimate — dispatch-only busy would inflate the rate
        # by orders of magnitude and flip _rate_why's compute-vs-wire
        # verdict, so the gauge is withheld until a fence lands
        busy = device_est + dispatch_s
        if samples and busy > 0 and att["sampled_steps"]:
            rec["compute_samples_per_s"] = round(samples / busy, 3)
            if self.gauges is not None:
                self.gauges.set("compute_samples_per_s",
                                rec["compute_samples_per_s"])
        self._t_round = None
        return rec

    def _mem_sample(self):
        try:
            return self.memory.sample()
        except Exception:  # noqa: BLE001 — watermark is best-effort
            return None


def make_perf_plane(cfg, participant: str, gauges=None, hists=None,
                    faults=None, tracer=None, log=None,
                    capture: ProfileCapture | None = None) -> PerfPlane:
    """Build a participant's perf plane from ``cfg.perf`` (tolerates
    configs predating the block: disabled plane, zero overhead)."""
    perf_cfg = getattr(cfg, "perf", None)
    if perf_cfg is None:
        return PerfPlane(participant, enabled=False)
    datasheet = getattr(perf_cfg, "datasheet", None)
    if datasheet is not None and not isinstance(datasheet, dict):
        # tuple-frozen YAML mapping-of-pairs form
        try:
            datasheet = dict(datasheet)
        except (TypeError, ValueError):
            datasheet = None
    return PerfPlane(
        participant,
        sample_every=getattr(perf_cfg, "sample_every", 16),
        datasheet=datasheet, gauges=gauges, hists=hists, faults=faults,
        tracer=tracer, log=log,
        enabled=bool(getattr(perf_cfg, "enabled", True)),
        capture=capture)


def perf_enabled(cfg) -> bool:
    """Whether the perf plane is on for ``cfg`` — shared by the client
    planes (via :func:`make_perf_plane`) and the server-side round loop
    (MemoryWatch + ``kind=perf`` records), so ``perf: {enabled:
    false}`` silences BOTH halves.  Configs predating the block have no
    plane at all."""
    perf_cfg = getattr(cfg, "perf", None)
    return (perf_cfg is not None
            and bool(getattr(perf_cfg, "enabled", True)))


def profile_output_dir(cfg, logger=None) -> pathlib.Path:
    """Where ``/profile`` captures land: the run-scoped output
    directory's ``profile/`` subdir when the logger has one, else
    ``{perf.profile-dir or log_path}/profile``."""
    perf_cfg = getattr(cfg, "perf", None)
    override = getattr(perf_cfg, "profile_dir", None) if perf_cfg else None
    if override:
        return pathlib.Path(override)
    base = getattr(logger, "output_dir", None)
    if base is None:
        base = pathlib.Path(getattr(cfg, "log_path", "."))
    return pathlib.Path(base) / "profile"
