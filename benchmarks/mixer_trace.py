"""What a token model's mixers cost in a traced window: the flash
attention kernels and the expert layer, by the scopes the program puts in
``op_name`` inside ``stage<s>`` (``models/mellum.py``: ``attn_window``,
``attn_full``; ``parallel/expert.py``: ``moe_route``, ``moe_experts``) and
by the kernels' own names (``ops/flash_attention.py``: ``slt_flash_fwd``,
``slt_flash_bwd_dq``, ``slt_flash_bwd_dkv``; XLA's ``ragged-dot*`` for the
grouped products, which lose their ``op_name``).

``program_trace.py`` knows the six scopes of ``parallel/pipeline.py`` and
was not this file's author's to edit; this reads the same trace with its
reader (``program_trace.read``) and the same reduction
(``trace_reduce.self_times``), keyed by these scopes.  One reader should
serve both: PERF.md section 7.

The kernels' operations and bytes are counted here from shapes, by what
the ALGORITHM needs for one call, whatever implements it and in whatever
blocks: a query multiplies with the keys it may see (``keys_seen``: the
causal triangle, or the window's band) and with no other.

``get(run)`` reduces once a run, keeps the result in
``run["_mixer_trace"]`` and prints it to stderr as one line of JSON after
``mixer_trace:``.  Where the trace holds none of these scopes (a program
that lacks them, a CPU rehearsal) every reader returns None; nothing here
raises into ``run_cell``.
"""

from __future__ import annotations

import bisect
import collections
import json
import pathlib
import sys

import program_trace
import trace_reduce

HERE = pathlib.Path(__file__).resolve().parent
FLASH = "slt_flash"
KERNELS = ("bwd_dkv", "bwd_dq", "fwd")          # as the kernels are named
ATTN_SCOPES = ("attn_window", "attn_full")
MOE_SCOPES = ("moe_route", "moe_experts")
GROUPED_PRODUCT = "ragged-dot"      # as XLA names what it makes of one


# -- operations and bytes of one kernel call -----------------------------------

def keys_seen(seq: int, window=None) -> float:
    """(query, key) pairs of one head over one row: ``min(p + 1, window)``
    keys for the query at ``p``."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) / 2 + (seq - w) * w


# matrix products over the seen pairs that each kernel owes the algorithm
# (FlashAttention-2's count: forward 2, backward 5 = the scores once more,
# dP, dV, dK, dQ).  The two backward kernels each rebuild the scores and
# dP; the second rebuilding is the implementation's, and is not counted.
PRODUCTS = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 2}


def flash_flops(kernel: str, rows: int, seq: int, heads: int,
                head_dim: int, window=None) -> float:
    """FLOPs one call of ``kernel`` needs: 2 x head_dim a pair and
    product, over the pairs a query may see."""
    return PRODUCTS[kernel] * 2.0 * head_dim * heads * rows \
        * keys_seen(seq, window)


def flash_bytes(kernel: str, rows: int, seq: int, heads: int,
                kv_heads: int, head_dim: int, width: int = 2) -> float:
    """Bytes one call must move to and from memory once: its operands
    and results at ``width`` bytes an element, the row statistics and the
    float32 key-value gradients at 4."""
    q = rows * seq * heads * head_dim * width
    kv = rows * seq * kv_heads * head_dim * width
    stat = rows * seq * heads * 4
    if kernel == "fwd":                      # q, k, v -> o, lse
        return 2 * q + 2 * kv + stat
    if kernel == "bwd_dq":                   # q, k, v, do, lse, delta -> dq
        return 3 * q + 2 * kv + 2 * stat
    return 2 * q + 2 * kv + 2 * stat + 2 * kv * 4 / width   # -> dk, dv


def least_seconds(kernel: str, scope: str, shapes: dict,
                  peaks: dict) -> float:
    """The least time the chip could take for one call: the larger of its
    operations over the bf16 peak and its bytes over the memory's."""
    window = shapes["window"] if scope == "attn_window" else None
    flops = flash_flops(kernel, shapes["rows"], shapes["seq"],
                        shapes["heads"], shapes["head_dim"], window)
    moved = flash_bytes(kernel, shapes["rows"], shapes["seq"],
                        shapes["heads"], shapes["kv_heads"],
                        shapes["head_dim"])
    return max(flops / (peaks["bf16_tflops"] * 1e12),
               moved / (peaks["hbm_gbps"] * 1e9))


def cell_shapes(run: dict):
    """The shapes of one kernel call, from the cell's configuration."""
    import yaml
    conf = yaml.safe_load((HERE / "configs"
                           / f"{run['cell']['config']}.yaml").read_text())
    kw = conf["program"].get("model-kwargs") or {}
    try:
        return {"rows": conf["program"]["learning"]["batch-size"],
                "seq": conf["dataset"]["seq-len"],
                "heads": kw["num_attention_heads"],
                "kv_heads": kw["num_key_value_heads"],
                "head_dim": kw["head_dim"], "window": kw["sliding_window"]}
    except KeyError:
        return None


# -- the reduction ---------------------------------------------------------------

def classify(name: str, op_name: str):
    """``("flash", kernel, scope)`` for a call of a flash kernel — the
    instruction is named after the kernel, ``slt_flash_fwd.3``; an
    operation that only reads a kernel's result names it too, further on
    in its text, and is none — ``(scope, None, None)`` for an operation
    of the expert layer, or None.  XLA turns a grouped product into
    instructions of its own (``ragged-dot-none.4``) that carry no
    ``op_name``: they are the expert layer's products by their name."""
    own = trace_reduce.short_name(name)
    parts = set(program_trace.PATH_SEPARATORS.split(op_name.split(":", 1)[0]))
    kernel = next((k for k in KERNELS if own.startswith(f"{FLASH}_{k}")),
                  None)
    if kernel:
        return ("flash", kernel,
                next((s for s in ATTN_SCOPES if s in parts), None))
    if own.startswith(GROUPED_PRODUCT):
        return ("moe_experts", None, None)
    scope = next((s for s in MOE_SCOPES if s in parts), None)
    return (scope, None, None) if scope else None


def reduce(trace: dict, window_s: float, shapes, peaks):
    if trace["mark"] is None or not trace["device"]:
        return None
    lo = trace["mark"][1]
    hi = lo + window_s * 1e9
    own, calls, steps = collections.Counter(), collections.Counter(), 0
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
                key = classify(name, op_name)
                inside.append((s, e, key))
                if key and key[0] == "flash":
                    calls[key] += 1
        own.update(trace_reduce.self_times(inside))
    own.pop(None, None)
    if not steps or not own:
        return None
    flash_ns = sum(ns for key, ns in own.items() if key[0] == "flash")
    out = {"steps": steps,
           "ms": {"flash": flash_ns / steps / 1e6,
                  **{s: own[(s, None, None)] / steps / 1e6
                     for s in MOE_SCOPES}},
           "flash_ms": {f"{k[1]}.{k[2]}": ns / steps / 1e6
                        for k, ns in sorted(own.items(), key=str)
                        if k[0] == "flash"},
           "flash_calls_a_step": {f"{k[1]}.{k[2]}": n / steps
                                  for k, n in sorted(calls.items(),
                                                     key=str)},
           "flash_roofline": None}
    if flash_ns and shapes and peaks:
        # a call outside both scopes is counted as the cheaper, windowed one
        least = sum(n * least_seconds(k[1], k[2] or "attn_window", shapes,
                                      peaks) for k, n in calls.items())
        out["flash_roofline"] = 100.0 * least / (flash_ns / 1e9)
    return out


def get(run: dict):
    if "_mixer_trace" not in run:
        got = None
        try:
            path = trace_reduce.find_xplane(
                HERE / "_work" / run["cell"]["name"] / "trace")
            got = reduce(program_trace.read(path), run["window_s"],
                         cell_shapes(run), run["peaks"])
        except Exception as e:  # noqa: BLE001 — a metric never fails a run
            print(f"mixer_trace: not read ({type(e).__name__}: {e})",
                  file=sys.stderr)
        if got is not None:
            print("mixer_trace: " + json.dumps(got), file=sys.stderr)
        run["_mixer_trace"] = got
    return run["_mixer_trace"]


def scope_ms(run: dict, key: str):
    """Own device milliseconds an optimizer step of ``flash``,
    ``moe_route`` or ``moe_experts``; None where the trace has none."""
    got = get(run)
    return (got["ms"].get(key) or None) if got else None


def counter_mean(run: dict, name: str):
    """Mean over the window's rounds of a counter the program journals in
    its round records (``counters``); None where it does not."""
    values = [rec["counters"][name] for rec in run["window_rounds"]
              if name in (rec.get("counters") or {})]
    return sum(values) / len(values) if values else None
