"""What attention's output gate costs in a traced window: own device time
an optimizer step of the window's ``sl_train_step`` operations whose
``op_name`` path holds the scope ``attn_gate``.

``models/decoder.py Attention`` opens ``attn_gate`` INSIDE ``attn_proj``,
around the gate's projection (``g_proj``), its sigmoid and the product with
each head's result, in both passes and the recomputed one.
``layer_trace`` does not know the scope, so it gives those operations to
``attn_proj`` (the innermost scope it knows) and its partition of the step
stays whole; this reader counts the part of ``attn_proj`` that the gate
is.  It reads the parse ``layer_trace`` keeps in ``run["_trace_read"]``,
never the trace file again, and returns None where ``layer_trace`` found
no partitioned decoder step or no operation lies under the scope (a model
without the gate, a program that lacks the scope, a CPU rehearsal).
"""

from __future__ import annotations

import bisect
import collections

import layer_trace
import program_trace
import trace_reduce

SCOPE = "attn_gate"


def under(op_name: str, scope: str = SCOPE) -> bool:
    """Whether ``scope`` is one of the parts of an ``op_name`` path."""
    return scope in program_trace.PATH_SEPARATORS.split(
        op_name.split(":", 1)[0])


def scope_ms(trace: dict, window_s: float, scope: str = SCOPE):
    """Own ms a step under ``scope`` over the train steps inside the
    window (each operation net of the operations nested in it); None where
    the window holds no train step or no such operation."""
    if trace["mark"] is None or not trace["device"]:
        return None
    lo = trace["mark"][1]
    hi = lo + window_s * 1e9
    own, steps, seen = collections.Counter(), 0, {}
    for chip in trace["device"]:
        windows = [(s, e) for s, e, name in chip["modules"]
                   if program_trace.TRAIN_STEP in name
                   and lo <= s and e <= hi]
        starts = [s for s, _ in windows]
        steps += len(windows)
        inside = []
        for s, e, _, op_name in chip["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < windows[i][1]:
                if op_name not in seen:
                    seen[op_name] = under(op_name, scope)
                inside.append((s, e, seen[op_name]))
        own.update(trace_reduce.self_times(inside))
    if not steps or not own[True]:
        return None
    return own[True] / steps / 1e6


def get(run: dict):
    """:func:`scope_ms` of the run's trace as ``layer_trace`` parsed it."""
    if not layer_trace.scope_ms(run, "attn_proj"):
        return None
    return scope_ms(layer_trace.parsed(run), run["window_s"])
