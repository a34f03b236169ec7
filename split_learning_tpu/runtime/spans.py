"""Distributed round tracing: span journals + wire-propagated context.

``metrics.jsonl`` holds per-round aggregates and monotonic counters, but
nothing in it can answer "where did round N's 54 seconds go" — queue
wait, network, encode/decode and device time are indistinguishable once
summed.  This module is the attribution layer:

* :class:`Tracer` — one per participant.  Spans (name, participant,
  trace/span/parent IDs, t_start/duration, queue, frame kind, nbytes)
  are appended to a per-participant ``spans-{participant}.jsonl``
  journal by a thread-safe buffered :class:`SpanJournal`.  Parenting is
  implicit through a per-thread span stack (context-manager spans), or
  explicit for cross-participant edges.
* **Wire context** — :func:`pack_ctx` / :func:`unpack_ctx` encode a
  compact ``(trace_id, span_id, t_send)`` triple (32 bytes) that the
  TENSOR/chunk frame headers carry (``runtime/protocol.py``), so every
  Activation/Gradient/Update frame links the sender's *publish* span to
  the receiver's *consume* span: the merged trace gets a flow edge per
  data-plane frame, and ``t_send`` yields true per-frame RTT.
* ``tools/sl_trace.py`` merges the journals into a Chrome/Perfetto
  ``trace.json`` and walks the span graph backward for a per-round
  critical-path report.
* **Profiler captures** — a context-manager span also enters a
  ``jax.profiler.TraceAnnotation`` named ``sl/<name>`` (with the
  span's ``round``) on the thread that opened it, so any capture that
  is running (``jax.profiler.start_trace``, the exporter's
  ``POST /profile``) shows the host spans on the same clock as the
  device's operations.  With no capture running that is a flag test.
  ``start()``/``end()`` and ``record()`` spans may end on another
  thread and enter none.
* :class:`Laps` — back-to-back spans that share their clock readings
  with the caller's own accounting (``MeshContext._drive_columns``).

Costs are kept off the hot path: a disabled tracer returns a shared
no-op span (no allocation beyond the call), sampling is a single RNG
draw, and journal writes buffer ``flush_every`` records between file
appends.  Timestamps are ``time.time()`` so spans from different
processes merge on one timeline; cross-*machine* deployments inherit
whatever clock skew NTP leaves (flow arrows stay correct — they bind
ids, not timestamps — but RTTs absorb the skew).
"""

from __future__ import annotations

import collections
import contextlib
import json
import pathlib
import random
import struct
import threading
import time
import uuid
from typing import Any

from jax.profiler import TraceAnnotation

from split_learning_tpu.runtime import blackbox

#: spans.jsonl record schema version (bump on breaking change)
SCHEMA_VERSION = 1

# -- wire trace context -----------------------------------------------------
# Fixed 32 bytes: 16-byte trace id | 8-byte sender span id | f64 send
# time (epoch seconds).  Fixed size keeps frame lengths deterministic
# under chaos seeding (corruption positions depend on payload length).

_CTX = struct.Struct(">16s8sd")
CTX_BYTES = _CTX.size


def pack_ctx(trace_id: str, span_id: str, t_send: float | None = None
             ) -> bytes:
    """Encode a wire trace context (hex ids -> 32 opaque bytes)."""
    return _CTX.pack(bytes.fromhex(trace_id), bytes.fromhex(span_id),
                     time.time() if t_send is None else t_send)


def unpack_ctx(raw: bytes | None) -> tuple[str, str, float] | None:
    """Decode a wire trace context; None on absent/malformed input
    (a foreign or pre-tracing frame must degrade to "no edge", never
    raise into a decode path)."""
    if not raw or len(raw) != CTX_BYTES:
        return None
    tid, sid, t_send = _CTX.unpack(raw)
    return tid.hex(), sid.hex(), t_send


class SpanJournal:
    """Thread-safe buffered JSONL appender for span records.

    Buffers ``flush_every`` records between file appends so the hot
    path pays a dict + list append, not a syscall; ``flush`` is called
    at round boundaries and on close so a finished round's spans are
    durable even if the process later dies."""

    def __init__(self, path: str | pathlib.Path, flush_every: int = 128):
        self.path = pathlib.Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        # created eagerly: a run-scoped compat symlink to this journal
        # must never dangle (tools glob then open the directory's
        # spans-*.jsonl, symlinks included)
        self.path.touch(exist_ok=True)
        self._flush_every = max(1, flush_every)
        self._lock = threading.Lock()
        self._buf: list[dict] = []
        self._closed = False

    def append(self, rec: dict) -> None:
        with self._lock:
            if self._closed:
                return
            self._buf.append(rec)
            if len(self._buf) < self._flush_every:
                return
            buf, self._buf = self._buf, []
        self._write(buf)

    def _write(self, buf: list[dict]) -> None:
        if not buf:
            return
        data = "".join(json.dumps(r) + "\n" for r in buf)
        with open(self.path, "a") as f:
            f.write(data)
            f.flush()

    def flush(self) -> None:
        with self._lock:
            buf, self._buf = self._buf, []
        self._write(buf)

    def close(self) -> None:
        self.flush()
        with self._lock:
            self._closed = True


class _NullSpan:
    """Shared no-op span: the disabled/unsampled fast path."""

    __slots__ = ()
    id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def end(self, t1=None, /, **attrs) -> None:
        pass

    def _close(self, t1=None) -> None:
        pass


NULL_SPAN = _NullSpan()


class Span:
    """One open span; ``end()`` (idempotent) writes the journal record.

    May be ended on a different thread than it was started on (the
    async sender finishes *publish* spans) — ``end`` touches no
    tracer thread-state."""

    __slots__ = ("_tracer", "name", "id", "parent", "t0", "attrs",
                 "_thread", "_done", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, parent: str | None,
                 attrs: dict, t0: float | None = None):
        self._tracer = tracer
        self.name = name
        self.id = uuid.uuid4().hex[:16]
        self.parent = parent
        self.t0 = time.time() if t0 is None else t0
        self.attrs = attrs
        self._thread = threading.current_thread().name
        self._done = False
        self._annotation = None

    def end(self, t1: float | None = None, /, **attrs) -> None:
        """Write the record; ``t1`` is a clock reading the caller
        already took (``time.time()``), else the clock is read here."""
        if self._done:
            return
        self._done = True
        if attrs:
            self.attrs.update(attrs)
        self._tracer._emit(self, time.time() if t1 is None else t1)

    def __enter__(self):
        self._tracer._push(self.id)
        # the same span on the profiler's clock: an event `sl/<name>` on
        # this thread's line of any capture that is running
        rnd = self.attrs.get("round")
        self._annotation = (
            TraceAnnotation("sl/" + self.name) if rnd is None
            else TraceAnnotation("sl/" + self.name, round=rnd))
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._close()
        return False

    def _close(self, t1: float | None = None) -> None:
        """Leave what ``__enter__`` entered, on the same thread."""
        annotation, self._annotation = self._annotation, None
        if annotation is not None:
            annotation.__exit__(None, None, None)
        self._tracer._pop()
        self.end(t1)


class Tracer:
    """Per-participant span factory + journal.

    ``trace_id`` is run-scoped: the server generates one and broadcasts
    it in START (``extra["trace_id"]``) so every participant's journal
    — and every wire context — carries the same id even across
    processes (:meth:`adopt_trace_id`)."""

    def __init__(self, participant: str, enabled: bool = True,
                 sample_rate: float = 1.0,
                 journal_dir: str | pathlib.Path = ".",
                 trace_id: str | None = None, flush_every: int = 128):
        self.participant = participant
        self.enabled = enabled
        self.sample_rate = float(sample_rate)
        self.trace_id = trace_id or uuid.uuid4().hex
        self._tls = threading.local()
        self._journal = (SpanJournal(
            pathlib.Path(journal_dir) / f"spans-{participant}.jsonl",
            flush_every) if enabled else None)

    # -- parenting stack (per thread) ---------------------------------------

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _push(self, span_id: str | None) -> None:
        self._stack().append(span_id)

    def _pop(self) -> None:
        st = self._stack()
        if st:
            st.pop()

    def current_id(self) -> str | None:
        st = self._stack()
        return st[-1] if st else None

    # -- span creation ------------------------------------------------------

    def _sampled(self, always: bool) -> bool:
        if not self.enabled:
            return False
        if always or self.sample_rate >= 1.0:
            return True
        return random.random() < self.sample_rate

    def start(self, name: str, parent: str | None = None,
              always: bool = True, t0: float | None = None,
              **attrs: Any):
        """Open a span (ended explicitly via ``span.end()``).  With
        ``always=False`` the configured sample rate applies — use for
        per-frame/per-batch spans; structural spans (rounds, phases)
        always record.  ``t0`` is a ``time.time()`` reading the caller
        already took."""
        if not self._sampled(always):
            return NULL_SPAN
        if parent is None:
            parent = self.current_id()
        return Span(self, name, parent, attrs, t0)

    def span(self, name: str, parent: str | None = None,
             always: bool = True, **attrs: Any):
        """Context-manager span; children opened on this thread inside
        the block inherit it as parent, and a running profiler capture
        shows it as ``sl/<name>``."""
        s = self.start(name, parent=parent, always=always, **attrs)
        if s is NULL_SPAN:
            return contextlib.nullcontext(NULL_SPAN)
        return s

    def record(self, name: str, t0: float, t1: float,
               parent: str | None = None, always: bool = False,
               **attrs: Any) -> str | None:
        """Write an already-timed span (the consume path measures the
        decode before it knows the message carried a context)."""
        if not self._sampled(always):
            return None
        s = Span(self, name, parent if parent is not None
                 else self.current_id(), attrs)
        s.t0 = t0
        s._done = True
        self._emit(s, t1)
        return s.id

    def wire_context(self, span) -> bytes:
        """Wire bytes linking ``span`` to its receiver-side consume
        span; empty (and free) when the span was not sampled."""
        if span is NULL_SPAN or span.id is None:
            return b""
        return pack_ctx(self.trace_id, span.id)

    def adopt_trace_id(self, trace_id: str) -> None:
        """Join the server's run-scoped trace (START extra)."""
        if trace_id:
            self.trace_id = trace_id

    # -- journal ------------------------------------------------------------

    def _emit(self, span: Span, t1: float) -> None:
        if self._journal is None:
            return
        rec = {"v": SCHEMA_VERSION, "trace": self.trace_id,
               "span": span.id, "parent": span.parent,
               "name": span.name, "part": self.participant,
               "thread": span._thread, "ts": round(span.t0, 6),
               "dur": round(max(0.0, t1 - span.t0), 6)}
        for k, v in span.attrs.items():
            if v is not None:
                rec[k] = v
        self._journal.append(rec)
        # flight-recorder feed: span close = "this phase just ran
        # here" — the blackbox ring's primary what-was-it-doing signal
        if blackbox.enabled():
            blackbox.record("span", name=span.name,
                            dur=rec["dur"],
                            queue=span.attrs.get("queue"),
                            nbytes=span.attrs.get("nbytes"),
                            round=span.attrs.get("round"))

    def flush(self) -> None:
        if self._journal is not None:
            self._journal.flush()

    def close(self) -> None:
        if self._journal is not None:
            self._journal.close()


class Laps:
    """Back-to-back spans on one thread that share their clock
    readings: ``lap(name)`` reads ``time.time()`` once, ends the open
    span there and opens the next at the same instant; ``stop()``, or
    leaving the block, ends the last.  ``totals`` holds the seconds by
    name whether or not anything was journaled (no tracer, a disabled
    one, a lap sampled out), so a caller's own accounting and the
    journal are one measurement::

        with Laps(tracer, round=r) as laps:
            for batch in batches:
                laps.lap("feed", always=False)
                ...
                laps.lap("dispatch", always=False)
                ...
            laps.lap("sync")
            ...
        host_s = laps.totals["feed"]
    """

    def __init__(self, tracer: "Tracer | None", **attrs: Any):
        self._tracer = tracer
        self._attrs = attrs
        self._span: Any = NULL_SPAN
        self._name: str | None = None
        self._t = 0.0
        self.totals: dict = collections.defaultdict(float)

    def lap(self, name: str, always: bool = True) -> None:
        t = time.time()
        self._end(t)
        self._name, self._t = name, t
        if self._tracer is not None:
            self._span = self._tracer.start(name, always=always, t0=t,
                                            **self._attrs)
            self._span.__enter__()

    def stop(self) -> None:
        """End the open lap, if any, now."""
        self._end(time.time())

    def _end(self, t: float) -> None:
        if self._name is not None:
            self.totals[self._name] += t - self._t
            self._span._close(t)
            self._span, self._name = NULL_SPAN, None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def make_tracer(cfg, participant: str) -> Tracer:
    """Build a participant's tracer from ``cfg.observability`` (falls
    back to a disabled tracer when the config predates the block).

    Under ``observability.run-scoped`` the journal lands in the same
    ``artifacts/runs/<run_id>/`` directory as the logger's outputs
    (``runtime/log.py``), with a compat symlink at the flat path —
    one directory per run holds app.log + metrics.jsonl +
    spans-*.jsonl together."""
    obs = getattr(cfg, "observability", None)
    if obs is None:
        return Tracer(participant, enabled=False)
    journal_dir = pathlib.Path(obs.journal_dir or cfg.log_path)
    if obs.enabled and getattr(obs, "run_scoped", False):
        from split_learning_tpu.runtime.log import (
            compat_link, run_output_dir, write_run_owner,
        )
        out = run_output_dir(journal_dir)
        name = f"spans-{participant}.jsonl"
        try:
            out.mkdir(parents=True, exist_ok=True)
            ok = True
        except OSError:
            ok = False
        if ok:
            write_run_owner(out)
            # eager target so the link below never dangles
            (out / name).touch(exist_ok=True)
            if compat_link(journal_dir / name, out / name):
                journal_dir = out
    return Tracer(participant, enabled=obs.enabled,
                  sample_rate=obs.sample_rate,
                  journal_dir=journal_dir,
                  flush_every=obs.flush_every)
