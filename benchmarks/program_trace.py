"""What the program itself names in a profiler trace (``.xplane.pb``):
its host spans (``runtime/spans.py`` enters a ``TraceAnnotation``
``sl/<name>`` for every context-manager span) and the scopes inside its
compiled programs (``jax.named_scope`` in ``parallel/pipeline.py``:
``stage<s>``, ``loss``, ``hop``, ``grad_sync``, ``optimizer``; programs
``sl_train_step``, ``sl_fedavg``, ...).  Both are in the same file on one
clock, so nothing here aligns clocks.

``jax.profiler.ProfileData`` gives an event's name and times but not the
stats of its event metadata, where an ``XLA Ops`` event keeps the
``op_name`` path of its HLO instruction (stat ``tf_op``).  The file is
read here instead, by a reader of the protobuf wire format for the few
messages needed (XSpace, XPlane, XLine, XEvent, XEventMetadata, XStat,
XStatMetadata of tsl's ``xplane.proto``): no dependency.

``get(run)`` reads the cell's trace once a run (``_work/<cell>/trace``,
which ``run_cell`` has not yet removed when the metrics are read), keeps
the reduction in ``run["_program_trace"]`` and prints it to stderr as one
line of JSON after ``program_trace:``.  A trace without the clock mark or
without a device plane (a CPU rehearsal) reduces to None; the readers in
``metrics/`` then return None.  Nothing here raises into ``run_cell``.
"""

from __future__ import annotations

import bisect
import collections
import json
import pathlib
import re
import sys

import trace_reduce

HERE = pathlib.Path(__file__).resolve().parent
SPAN_PREFIX = "sl/"
TRAIN_STEP = "sl_train_step"
FEDAVG = "sl_fedavg"
PHASES = ("fwd", "remat", "bwd")
STAGE = re.compile(r"stage\d+")
PATH_SEPARATORS = re.compile(r"[/()]")


# -- the wire format -----------------------------------------------------------

def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: an int for a varint, the
    bytes for a length-delimited or fixed-width field."""
    i, end = 0, len(buf)
    while i < end:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"wire type {kind} at byte {i}")
            value, i = buf[i:i + size], i + size
        yield tag >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_entry(buf):
    """(key, value bytes) of one entry of a map<int64, message>."""
    key, value = 0, b""
    for no, v in fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _stat_names(entries) -> dict:
    """{id: name} of a plane's XStatMetadata."""
    out = {}
    for entry in entries:
        key, meta = _map_entry(entry)
        out[key] = next((_text(v) for no, v in fields(meta) if no == 2), "")
    return out


def _event_metadata(entries, stat_names: dict) -> dict:
    """{id: (name, tf_op)} of a plane's XEventMetadata; ``tf_op`` is the
    stat of that name (a string, or a reference to a stat's name)."""
    out = {}
    for entry in entries:
        key, meta = _map_entry(entry)
        name, tf_op = "", ""
        for no, v in fields(meta):
            if no == 2:
                name = _text(v)
            elif no == 5:                       # XStat
                stat = dict(fields(v))
                if stat_names.get(stat.get(1)) == "tf_op":
                    tf_op = (_text(stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7), ""))
        out[key] = (name, tf_op)
    return out


def _line(buf):
    """(id, name, timestamp_ns, [event bytes]) of one XLine.  A host
    line is one thread; threads may share a name, never an id."""
    ident, name, t0, events = 0, "", 0, []
    for no, v in fields(buf):
        if no == 1:
            ident = v
        elif no == 2:
            name = _text(v)
        elif no == 3:
            t0 = v
        elif no == 4:
            events.append(v)
    return ident, name, t0, events


def _events(t0_ns: int, raw_events, wanted=None) -> list:
    """[(start_ns, end_ns, metadata id)] of a line's events; with
    ``wanted`` only of those ids (an event's first field is its
    metadata id, so the others are skipped after one varint)."""
    out = []
    for raw in raw_events:
        if wanted is not None:
            if not len(raw) or raw[0] != 0x08:
                continue
            if _varint(raw, 1)[0] not in wanted:
                continue
        ev = dict(fields(raw))
        start = t0_ns + ev.get(2, 0) / 1e3
        out.append((start, start + ev.get(3, 0) / 1e3, ev.get(1, 0)))
    return out


def read(path) -> dict:
    """{"device": [{"name", "ops", "modules"}], "spans": {line: [...]},
    "mark": (start_ns, end_ns, line) or None}.  ``ops`` are (start_ns,
    end_ns, event name, tf_op), ``modules`` and spans (start_ns, end_ns,
    name); spans are the ``sl/*`` events of the host planes by the id of
    the line (the thread) they were recorded on."""
    data = memoryview(pathlib.Path(path).read_bytes())
    out = {"device": [], "spans": collections.defaultdict(list),
           "mark": None}
    for no, plane in fields(data):
        if no != 1:
            continue
        name, lines, ev_meta, st_meta = "", [], [], []
        for pno, v in fields(plane):
            if pno == 2:
                name = _text(v)
            elif pno == 3:
                lines.append(v)
            elif pno == 4:
                ev_meta.append(v)
            elif pno == 5:
                st_meta.append(v)
        if name.startswith(trace_reduce.DEVICE_PREFIX):
            meta = _event_metadata(ev_meta, _stat_names(st_meta))
            chip = {"name": name, "ops": [], "modules": []}
            for raw in lines:
                _, line, t0, events = _line(raw)
                if line == trace_reduce.OPS_LINE:
                    chip["ops"] = sorted(
                        (s, e, *meta.get(m, ("", "")))
                        for s, e, m in _events(t0, events))
                elif line == trace_reduce.MODULES_LINE:
                    chip["modules"] = sorted(
                        (s, e, meta.get(m, ("", ""))[0])
                        for s, e, m in _events(t0, events))
            out["device"].append(chip)
        elif ev_meta and lines:
            meta = {k: v[0] for k, v in _event_metadata(ev_meta, {}).items()
                    if v[0].startswith(SPAN_PREFIX)
                    or v[0] == trace_reduce.CLOCK_MARK}
            if not meta:
                continue
            for raw in lines:
                line, _, t0, events = _line(raw)
                for s, e, m in _events(t0, events, wanted=meta):
                    if meta[m] == trace_reduce.CLOCK_MARK:
                        out["mark"] = (s, e, line)
                    else:
                        out["spans"][line].append(
                            (s, e, meta[m][len(SPAN_PREFIX):]))
    return out


# -- the reduction -------------------------------------------------------------

def classify(op_name: str) -> tuple:
    """(scope, phase) of an operation from the ``op_name`` path jax gave
    it, as in ``jit(sl_train_step)/transpose(jvp())/while/body/
    closed_call/checkpoint/rematted_computation/stage1/../dot_general``
    (a name may also stand inside a transform: ``jvp(pipeline)``).  The
    scope is ``optimizer``, ``grad_sync``, ``loss``, the ``stage<s>``,
    ``hop`` or, for what the tick loop does outside them, ``pipeline``
    that occurs in the path, else ``other``; the phase of any but the
    first two is ``remat`` where ``rematted_computation`` occurs
    (``jax.checkpoint``'s second forward), else ``bwd`` where
    ``transpose(`` occurs, else ``fwd``."""
    parts = PATH_SEPARATORS.split(op_name.split(":", 1)[0])
    for scope in ("optimizer", "grad_sync"):
        if scope in parts:
            return scope, None
    scope = ("loss" if "loss" in parts
             else next((p for p in parts if STAGE.fullmatch(p)), None)
             or next((p for p in ("hop", "pipeline") if p in parts), None))
    if scope is None:
        return "other", None
    if "rematted_computation" in parts:
        return scope, "remat"
    # the transform `transpose(jvp(..))`, not the operation `transpose`
    return scope, "bwd" if "transpose(" in op_name else "fwd"


def innermost(spans: list, at: float):
    """Name of the innermost of one thread's (properly nested, sorted)
    spans that holds ``at``: the one that started last."""
    i = bisect.bisect_right(spans, (at, float("inf"), "")) - 1
    while i >= 0:
        if spans[i][1] >= at:
            return spans[i][2]
        i -= 1
    return None


def idle_by_span(busy: list, lo: float, hi: float, spans: list) -> dict:
    """{span name or None: idle ns} inside [lo, hi]: each gap of the
    busy union goes to the innermost span that holds its midpoint."""
    totals, at = collections.Counter(), lo
    for s, e in list(busy) + [(hi, hi)]:
        if s > at:
            totals[innermost(spans, (at + s) / 2)] += s - at
        at = max(at, e)
    return totals


def step_table(chip: dict, lo: float, hi: float):
    """({(scope, phase): ns}, steps) of one chip: own time of the
    operations that ran inside the window's ``sl_train_step`` programs."""
    steps = [(s, e) for s, e, name in chip["modules"]
             if TRAIN_STEP in name and lo <= s and e <= hi]
    starts = [s for s, _ in steps]
    inside = []
    for s, e, _, tf_op in chip["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < steps[i][1]:
            inside.append((s, e, classify(tf_op)))
    return trace_reduce.self_times(inside), len(steps)


def reduce(trace: dict, window_s: float, rounds: int):
    if trace["mark"] is None or not trace["device"]:
        return None
    lo = trace["mark"][1]
    hi = lo + window_s * 1e9
    main = sorted(trace["spans"].get(trace["mark"][2], []))
    per_chip = []
    for chip in trace["device"]:
        ops = trace_reduce.clip(chip["ops"], lo, hi)
        busy = trace_reduce.union(ops)
        table, steps = step_table(chip, lo, hi)
        fedavg = [e - s for s, e, name in chip["modules"]
                  if FEDAVG in name and lo <= s and e <= hi]
        per_chip.append({"busy": busy, "table": table, "steps": steps,
                         "fedavg": fedavg,
                         "idle": 1.0 - trace_reduce.length(busy)
                         / (hi - lo)})
    n = len(per_chip)
    worst = max(per_chip, key=lambda c: c["idle"])
    idle = idle_by_span(worst["busy"], lo, hi, main)
    table = collections.Counter()
    for c in per_chip:
        for key, ns in c["table"].items():
            table[key] += ns / max(c["steps"], 1) / n
    durations = collections.defaultdict(list)
    for spans in trace["spans"].values():
        for s, e, name in spans:
            if lo <= s <= hi:
                durations[name].append(e - s)
    total = sum(table.values())
    step_ms = collections.defaultdict(dict)
    for (scope, phase), ns in sorted(table.items(), key=str):
        if phase is None:
            step_ms[scope] = ns / 1e6
        else:
            step_ms[scope][phase] = ns / 1e6
    phase_ms = {p: sum(ns for (scope, phase), ns in table.items()
                       if phase == p and scope != "hop") / 1e6
                for p in PHASES}
    return {
        "window_s": window_s,
        "steps": worst["steps"],
        "phase_ms": phase_ms,
        "opt_ms": table[("optimizer", None)] / 1e6,
        "step_ms": dict(step_ms),
        "step_total_ms": total / 1e6,
        "unscoped_share": (table[("other", None)] / total
                           if total else None),
        "fedavg_device_ms": (sum(sum(c["fedavg"]) for c in per_chip) / n
                             / rounds / 1e6
                             if rounds and worst["fedavg"] else None),
        "idle_worst": worst["idle"],
        "idle_s": {k or "unattributed": v / 1e9
                   for k, v in idle.most_common()},
        "span_ms": {k: {"n": len(v), "mean": sum(v) / len(v) / 1e6}
                    for k, v in sorted(durations.items())},
    }


def get(run: dict):
    """The reduction of this run's trace, read once; None when there is
    nothing to read."""
    if "_program_trace" not in run:
        got = None
        try:
            path = trace_reduce.find_xplane(
                HERE / "_work" / run["cell"]["name"] / "trace")
            got = reduce(read(path), run["window_s"],
                         len(run["window_rounds"]))
        except Exception as e:  # noqa: BLE001 — a metric never fails a run
            print(f"program_trace: not read ({type(e).__name__}: {e})",
                  file=sys.stderr)
        if got is not None:
            print("program_trace: " + json.dumps(got), file=sys.stderr)
        run["_program_trace"] = got
    return run["_program_trace"]


def phase_ms(run: dict, phase: str):
    """Device milliseconds an optimizer step of one phase of the named
    ``sl_train_step`` program; None where no such program ran."""
    got = get(run)
    if got is None or not got["steps"]:
        return None
    return got["opt_ms"] if phase == "opt" else got["phase_ms"][phase]


def idle_share(run: dict, names: tuple):
    """Idle time of the worst chip that fell to the spans ``names``
    (None: to no span), in per cent of the traced window; None where
    the trace holds no span of the program at all."""
    got = get(run)
    if got is None or not got["span_ms"]:
        return None
    return 100.0 * sum(got["idle_s"].get(name or "unattributed", 0.0)
                       for name in names) / got["window_s"]


def span_ms(run: dict, name: str):
    """Mean duration of the window's spans ``name``."""
    got = get(run)
    if got is None or name not in got["span_ms"]:
        return None
    return got["span_ms"][name]["mean"]
