"""From a profiler trace (``.xplane.pb``) to numbers: the device's busy
union, the table of device operations, the idle gaps by what the host was
doing, and the compiled step's device time.  The table of operations
gives each its own time, net of the operations nested in it.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose line
``XLA Ops`` holds one event per executed HLO operation and whose line
``XLA Modules`` holds one event per executed program, and host planes
whose lines hold ``TraceAnnotation`` events.  All share one clock, in
nanoseconds.  Read with ``jax.profiler.ProfileData`` and nothing else.
"""

from __future__ import annotations

import collections
import pathlib

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all", "collective-broadcast")
CLOCK_MARK = "bench_clock_mark"


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def find_xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An HLO event is named by its whole instruction text,
    ``%fusion.12 = f32[...] fusion(...)``: keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def line_events(plane, line_name: str) -> list:
    """[(start_ns, end_ns, name)] of one named line of a plane."""
    out = []
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            short_name(ev.name)))
    out.sort()
    return out


def union(intervals) -> list:
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for start, end, *_ in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def length(merged) -> float:
    return float(sum(b - a for a, b in merged))


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def self_times(events) -> collections.Counter:
    """{name: ns} of one line's events, each net of the events nested in
    it: a ``while`` holds its body's operations, and only what it spends
    outside them is its own."""
    totals, stack = collections.Counter(), []   # [end, name, own ns]

    def close(until):
        while stack and stack[-1][0] <= until:
            _, name, own = stack.pop()
            totals[name] += own

    for start, end, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(start)
        if stack:
            end = min(end, stack[-1][0])
            stack[-1][2] -= end - start
        stack.append([end, name, end - start])
    close(float("inf"))
    return totals


def collective_times(ops, async_ops) -> tuple:
    """(exposed ns, in-flight ns) of one chip's collectives.  In flight:
    the union of the collectives' events on the operations' line and of
    their start-to-done spans on the asynchronous line.  Exposed: the
    collectives' own time on the operations' line, where the core runs
    nothing else."""
    def mine(name):
        return any(kind in name for kind in COLLECTIVES)
    exposed = sum(ns for name, ns in self_times(ops).items() if mine(name))
    flight = union([ev for ev in list(ops) + list(async_ops) if mine(ev[2])])
    return float(exposed), length(flight)


def clock_offset_ns(profile, mark_s):
    """trace_ns - host perf_counter ns, from the annotation the harness
    wrote at a known host time; None when it is not in the trace."""
    if mark_s is None:
        return None
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == CLOCK_MARK:
                    return ev.start_ns - mark_s * 1e9
    return None


def host_spans(run: dict) -> list:
    """[(t0_s, t1_s, label)] on the host clock from the tap's spans
    around the compiled step: inside a call the host dispatches; between
    calls of one chunk it builds and uploads the next batch; between
    chunks and rounds it aggregates, validates and checkpoints."""
    calls = sorted(run["tap_calls"])
    spans = []
    for i, (t_in, t_out, _) in enumerate(calls):
        spans.append((t_in, t_out, "step_dispatch"))
        if i + 1 < len(calls):
            nxt_in, _, nxt_new = calls[i + 1]
            spans.append((t_out, nxt_in, "round_or_chunk_boundary"
                          if nxt_new else "host_feed"))
    return spans


def idle_gaps(busy, lo, hi, spans_ns) -> list:
    """[[label, seconds]]: the device's idle time inside [lo, hi], each
    gap given to the host span that holds its midpoint."""
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    totals = collections.Counter()
    spans_ns = sorted(spans_ns)
    j = 0
    for s, e in gaps:
        mid, label = (s + e) / 2, "unattributed"
        while j < len(spans_ns) and spans_ns[j][1] < mid:
            j += 1
        if j < len(spans_ns) and spans_ns[j][0] <= mid <= spans_ns[j][1]:
            label = spans_ns[j][2]
        totals[label] += (e - s) / 1e9
    return [[k, v] for k, v in totals.most_common(10)]


def step_module(modules: list, n_steps: int):
    """(name, mean seconds) of the compiled train step among a chip's
    ``XLA Modules`` events: the program that ran once per optimizer step
    (``n_steps`` times in the window) and took the most device time."""
    by_name = collections.defaultdict(list)
    for s, e, name in modules:
        by_name[name].append(e - s)
    fits = {k: v for k, v in by_name.items() if len(v) == n_steps}
    if not fits or not n_steps:
        return None
    name = max(fits, key=lambda k: sum(fits[k]))
    return name, sum(fits[name]) / len(fits[name]) / 1e9


def reduce(profile, window_s: float, mark_s=None, spans=(),
           n_steps: int = 0, window_host=None) -> dict:
    """The numbers of one traced window.  ``window_host`` = (t_open,
    t_close) on the host clock narrows device events to the window when
    the clock mark is found; otherwise the trace's own extent is used."""
    planes = [p for p in profile.planes if p.name.startswith(DEVICE_PREFIX)]
    if not planes:
        raise ValueError("the trace holds no device plane")
    offset = clock_offset_ns(profile, mark_s)
    busy_each, idle_each, ops_total, steps = [], [], \
        collections.Counter(), []
    gaps_each, exposed, flight = [], 0.0, 0.0
    spans_ns = [] if offset is None else [
        (a * 1e9 + offset, b * 1e9 + offset, lab) for a, b, lab in spans]
    for plane in planes:
        ops = line_events(plane, OPS_LINE)
        if offset is not None and window_host is not None:
            lo = window_host[0] * 1e9 + offset
            hi = window_host[1] * 1e9 + offset
        else:
            lo = min((s for s, _, _ in ops), default=0)
            hi = lo + window_s * 1e9
        ops = clip(ops, lo, hi)
        merged = union(ops)
        busy_each.append(length(merged) / 1e9)
        idle_each.append(1.0 - length(merged) / max(hi - lo, 1))
        for name, ns in self_times(ops).items():
            ops_total[name] += ns / 1e9
        mod = step_module(clip(line_events(plane, MODULES_LINE), lo, hi),
                          n_steps)
        if mod:
            steps.append(mod)
        gaps_each.append(idle_gaps(merged, lo, hi, spans_ns))
        ex, fl = collective_times(
            ops, clip(line_events(plane, ASYNC_LINE), lo, hi))
        exposed, flight = exposed + ex, flight + fl
    n = len(planes)
    gaps = gaps_each[idle_each.index(max(idle_each))]   # the worst chip's
    return {
        "busy_s": sum(busy_each) / n,
        "window_s": window_s,
        "idle_worst": max(idle_each),
        "clock_aligned": offset is not None,
        "collective_exposed_s": exposed / 1e9,
        "collective_flight_s": flight / 1e9,
        "step_module": steps[0][0] if steps else None,
        "step_device_s": (sum(s for _, s in steps) / len(steps)
                          if steps else None),
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in ops_total.most_common(10)],
            "idle_gaps": gaps},
    }


def reduce_dir(trace_dir, window_s, mark_s=None, spans=(), n_steps=0,
               window_host=None) -> dict:
    return reduce(load(find_xplane(trace_dir)), window_s, mark_s, spans,
                  n_steps, window_host)
