"""What each layer of a decoder step costs in a traced window, by the
scopes the program puts in ``op_name``: own device time an optimizer step
of the window's ``sl_train_step`` operations, under the layer scope that
holds each.

The layer scopes (``models/decoder.py``, ``parallel/expert.py``,
``parallel/pipeline.py``) partition a decoder step: ``embed``,
``norm_residual`` (every RMSNorm of a block, its residual add, the final
norm), ``attn_proj`` (``q``/``k``/``v`` with the RoPE, and ``o``),
``attn_window``/``attn_full`` (the scores and values), ``mla_latent``,
``ssm_mixer`` with ``ssm_scan`` inside it, ``ffn_dense``, ``moe_shared``,
``moe_route``, ``moe_experts``, ``head``, ``loss``; beside them
``optimizer``.  An operation goes to the INNERMOST of them in its path
(``ssm_scan`` before ``ssm_mixer``); what lies under none goes to
``unscoped``: the instructions the compiler makes with no ``op_name``
(copies), ``hop``, the tick loop (``pipeline``), ``grad_sync`` and the
joins between scopes.  A fusion has its root's name, so it counts where
its root was written.  The scopes are read, never flax's module names:
those are the checkpoint's parameter names, the same name stands in
several layers (``q_proj`` in both attentions, ``up_proj`` in the dense
block and every expert) and a block's element-wise work carries the
block's name alone.

``get(run)`` reads the trace once a run (:func:`read`: what
``program_trace.read`` gives of the device planes and the clock mark, and
no host span) and keeps the parse in ``run["_trace_read"]`` for any scope
reader that comes after; the reduction goes to ``run["_layer_trace"]``
and, as one line of JSON after ``layer_trace:``, to stderr: the
milliseconds a step by scope, the ten
longest instructions of each scope under the names the result line's
``breakdown`` prints, and the seconds the reading took.  Where the trace
holds none of the scopes that partition a decoder step (a program that
lacks them, a model that is no decoder, a CPU rehearsal) every reader
returns None; nothing here raises into ``run_cell``.
"""

from __future__ import annotations

import bisect
import collections
import json
import pathlib
import sys
import time

import program_trace
import trace_reduce

HERE = pathlib.Path(__file__).resolve().parent
LAYERS = ("embed", "norm_residual", "attn_proj", "attn_window", "attn_full",
          "mla_latent", "ssm_mixer", "ssm_scan", "ffn_dense", "moe_shared",
          "moe_route", "moe_experts", "head", "loss")
OPTIMIZER, UNSCOPED = "optimizer", "unscoped"
KNOWN = frozenset(LAYERS + (OPTIMIZER,))
#: the scopes a decoder step gains beside the mixers' and experts' own:
#: without any of them the step is not partitioned, and nothing is read
PARTITION = ("attn_proj", "ffn_dense", "norm_residual", "embed", "head")
TOP = 10
MARK_BYTES = trace_reduce.CLOCK_MARK.encode()


def classify(op_name: str) -> str:
    """The innermost scope of :data:`KNOWN` in an ``op_name`` path, else
    ``unscoped``."""
    parts = program_trace.PATH_SEPARATORS.split(op_name.split(":", 1)[0])
    return next((p for p in reversed(parts) if p in KNOWN), UNSCOPED)


def scope_times(trace: dict, window_s: float):
    """``{"steps", "ms": {scope: own ms a step}, "top": {scope: [[name,
    ms a step], ...]}}`` over the train steps inside the window; None
    where the window holds no train step."""
    if trace["mark"] is None or not trace["device"]:
        return None
    lo = trace["mark"][1]
    hi = lo + window_s * 1e9
    own, steps, scope_of = collections.Counter(), 0, {}
    for chip in trace["device"]:
        windows = [(s, e) for s, e, name in chip["modules"]
                   if program_trace.TRAIN_STEP in name
                   and lo <= s and e <= hi]
        starts = [s for s, _ in windows]
        steps += len(windows)
        inside = []
        for s, e, name, op_name in chip["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < windows[i][1]:
                if op_name not in scope_of:
                    scope_of[op_name] = classify(op_name)
                inside.append((s, e, (scope_of[op_name],
                                      trace_reduce.short_name(name))))
        own.update(trace_reduce.self_times(inside))
    if not steps:
        return None
    by_scope, by_name = collections.Counter(), collections.defaultdict(
        collections.Counter)
    for (scope, name), ns in own.items():
        by_scope[scope] += ns
        by_name[scope][name] += ns
    return {"steps": steps,
            "ms": {s: ns / steps / 1e6 for s, ns in by_scope.items()},
            "top": {s: [[n, ns / steps / 1e6]
                        for n, ns in by_name[s].most_common(TOP)]
                    for s in by_scope}}


def _varint_bytes(value: int) -> bytes:
    out = bytearray()
    while True:
        out.append(value & 0x7F | (0x80 if value > 0x7F else 0))
        value >>= 7
        if not value:
            return bytes(out)


def _clock_mark(lines, ev_meta):
    """(start_ns, end_ns, line id) of the clock mark on a host plane, or
    None.  A line's events are looked through only where its bytes hold
    the mark's metadata id, and only until the mark."""
    ids = [key for key, (name, _) in program_trace._event_metadata(
        [e for e in ev_meta if MARK_BYTES in bytes(e)], {}).items()
        if name == trace_reduce.CLOCK_MARK]
    for key in ids:
        head = b"\x08" + _varint_bytes(key)
        for raw in lines:
            if head not in bytes(raw):
                continue
            ident, t0 = 0, 0
            for no, v in program_trace.fields(raw):
                if no == 1:
                    ident = v
                elif no == 3:
                    t0 = v
                elif no == 4 and bytes(v[:len(head)]) == head:
                    (start, end, _), = program_trace._events(t0, [v])
                    return start, end, ident
    return None


def _device_line(raw, wanted: tuple):
    """(name, [(start_ns, end_ns, metadata id)]) of one device XLine whose
    name is ``wanted``, in one walk of its bytes; None for another line.
    An event's own fields come in number order, so its metadata id,
    offset and duration are read and its stats skipped."""
    buf, i, name, t0, events = bytes(raw), 0, None, 0, []
    varint = program_trace._varint
    while i < len(buf):
        tag, i = varint(buf, i)
        if tag & 7 == 0:
            value, i = varint(buf, i)
            if tag == 0x18:                     # timestamp_ns
                t0 = value
            continue
        if tag & 7 != 2:
            raise ValueError(f"wire type {tag & 7} at byte {i}")
        size, i = varint(buf, i)
        end = i + size
        if tag == 0x12:                         # name
            name = program_trace._text(buf[i:end])
            if name not in wanted:
                return None
        elif tag == 0x22:                       # an event
            meta = offset = duration = 0
            while i < end and buf[i] <= 0x18:
                field = buf[i]
                value, i = varint(buf, i + 1)
                if field == 0x08:
                    meta = value
                elif field == 0x10:
                    offset = value
                else:
                    duration = value
                    break
            start = t0 + offset / 1e3
            events.append((start, start + duration / 1e3, meta))
        i = end
    return (name, events) if name in wanted else None


def read(path) -> dict:
    """``program_trace.read`` without the host spans: the device planes'
    operations and programs, and the clock mark (``spans`` is empty).  The
    host planes hold the Python tracer's events, and a scope reader needs
    none of them."""
    data = memoryview(pathlib.Path(path).read_bytes())
    out = {"device": [], "spans": {}, "mark": None}
    for no, plane in program_trace.fields(data):
        if no != 1:
            continue
        name, lines, ev_meta, st_meta = "", [], [], []
        for pno, v in program_trace.fields(plane):
            if pno == 2:
                name = program_trace._text(v)
            elif pno == 3:
                lines.append(v)
            elif pno == 4:
                ev_meta.append(v)
            elif pno == 5:
                st_meta.append(v)
        if name.startswith(trace_reduce.DEVICE_PREFIX):
            meta = program_trace._event_metadata(
                ev_meta, program_trace._stat_names(st_meta))
            chip = {"name": name, "ops": [], "modules": []}
            for raw in lines:
                got = _device_line(raw, (trace_reduce.OPS_LINE,
                                         trace_reduce.MODULES_LINE))
                if got and got[0] == trace_reduce.OPS_LINE:
                    chip["ops"] = sorted((s, e, *meta.get(m, ("", "")))
                                         for s, e, m in got[1])
                elif got:
                    chip["modules"] = sorted(
                        (s, e, meta.get(m, ("", ""))[0]) for s, e, m in got[1])
            out["device"].append(chip)
        elif out["mark"] is None and MARK_BYTES in bytes(plane):
            out["mark"] = _clock_mark(lines, ev_meta)
    return out


def parsed(run: dict) -> dict:
    """The run's trace as :func:`read` gives it, read once."""
    if "_trace_read" not in run:
        run["_trace_read"] = read(trace_reduce.find_xplane(
            HERE / "_work" / run["cell"]["name"] / "trace"))
    return run["_trace_read"]


def get(run: dict):
    if "_layer_trace" not in run:
        got = None
        try:
            t0 = time.perf_counter()
            trace = parsed(run)
            t1 = time.perf_counter()
            got = scope_times(trace, run["window_s"])
            if got is not None:
                got["read_s"] = t1 - t0
                got["reduce_s"] = time.perf_counter() - t1
        except Exception as e:  # noqa: BLE001 — a metric never fails a run
            print(f"layer_trace: not read ({type(e).__name__}: {e})",
                  file=sys.stderr)
        if got is not None:
            print("layer_trace: " + json.dumps(got), file=sys.stderr)
        run["_layer_trace"] = got
    return run["_layer_trace"]


def scope_ms(run: dict, *scopes: str):
    """Own device milliseconds an optimizer step under ``scopes``
    together; None where the trace holds none of them, or none of the
    scopes that partition a decoder step (:data:`PARTITION`)."""
    got = get(run)
    if not got or not any(s in got["ms"] for s in PARTITION):
        return None
    found = [got["ms"][s] for s in scopes if s in got["ms"]]
    return sum(found) if found else None
