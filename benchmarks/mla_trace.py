"""What latent attention and the shared expert cost in a traced window, by
the scopes the program puts in ``op_name`` (``models/decoder.py``:
``mla_latent`` around the latent mixer's projections, its norm, the rotary
part, the shaping of the kernel's operands and ``o_proj``; ``moe_shared``
around the shared SwiGLU), and the latent kernels' share of their
roofline.

The flash kernels' own times and calls are ``mixer_trace.get(run)``'s
(they run under ``attn_full``, beside ``mla_latent`` and not inside it);
this module reads the trace once more for its two scopes alone, with the
same reader (``program_trace.read``) and the same reduction
(``trace_reduce.self_times``).

The kernels' operations and bytes are counted from shapes, by what the
ALGORITHM needs for one call: a seen (query, key) pair costs a product
over the score width (``qk_nope_head_dim + qk_rope_head_dim``) where
scores, ``dK`` or ``dQ`` are formed and one over ``v_head_dim`` where
values, ``dP`` or ``dV`` are; the ONE rotary key a token is moved once,
whatever copies an implementation makes of it.

``get(run)`` reduces once a run, keeps the result in ``run["_mla_trace"]``
and prints it to stderr as one line of JSON after ``mla_trace:``.  Where
the trace holds neither scope (a program that lacks them, a CPU
rehearsal) every reader returns None; nothing here raises into
``run_cell``.
"""

from __future__ import annotations

import bisect
import collections
import json
import pathlib
import sys

import mixer_trace
import program_trace
import trace_reduce

HERE = pathlib.Path(__file__).resolve().parent
SCOPES = ("mla_latent", "moe_shared")
# of each kernel's products over the seen pairs (``mixer_trace.PRODUCTS``:
# forward 2, dq 3, dkv 2), how many run over the score width and how many
# over the value width: forward S and PV; dq S again, dP and dQ; dkv dV, dK
WIDTHS = {"fwd": (1, 1), "bwd_dq": (2, 1), "bwd_dkv": (1, 1)}


def mla_flops(kernel: str, rows: int, seq: int, heads: int, score_dim: int,
              value_dim: int) -> float:
    """FLOPs one call of ``kernel`` needs: 2 x width a seen pair and
    product, over the causal triangle."""
    over_scores, over_values = WIDTHS[kernel]
    assert over_scores + over_values == mixer_trace.PRODUCTS[kernel]
    return 2.0 * (over_scores * score_dim + over_values * value_dim) \
        * heads * rows * mixer_trace.keys_seen(seq)


def mla_bytes(kernel: str, rows: int, seq: int, heads: int, nope_dim: int,
              rope_dim: int, value_dim: int, width: int = 2) -> float:
    """Bytes one call must move to and from memory once: queries ``nope +
    rope`` wide a head, keys ``nope`` a head and ONE rotary key a token,
    values and results ``value_dim`` a head, at ``width`` bytes an
    element; the row statistics and the float32 key-value gradients at
    4."""
    tokens = rows * seq
    q = tokens * heads * (nope_dim + rope_dim) * width
    k = tokens * (heads * nope_dim + rope_dim) * width
    v = tokens * heads * value_dim * width
    stat = tokens * heads * 4
    if kernel == "fwd":                      # q, k, v -> o, lse
        return q + k + 2 * v + stat
    if kernel == "bwd_dq":                   # q, k, v, do, lse, delta -> dq
        return 2 * q + k + 2 * v + 2 * stat
    return q + k + 2 * v + 2 * stat + (k + v) * 4 / width    # -> dk, dv


def least_seconds(kernel: str, shapes: dict, peaks: dict) -> float:
    """The least time the chip could take for one call: the larger of its
    operations over the bf16 peak and its bytes over the memory's."""
    score = shapes["nope_dim"] + shapes["rope_dim"]
    flops = mla_flops(kernel, shapes["rows"], shapes["seq"], shapes["heads"],
                      score, shapes["value_dim"])
    moved = mla_bytes(kernel, shapes["rows"], shapes["seq"], shapes["heads"],
                      shapes["nope_dim"], shapes["rope_dim"],
                      shapes["value_dim"])
    return max(flops / (peaks["bf16_tflops"] * 1e12),
               moved / (peaks["hbm_gbps"] * 1e9))


def cell_shapes(run: dict):
    """The shapes of one kernel call, from the cell's configuration; None
    where it has no latent attention."""
    import yaml
    conf = yaml.safe_load((HERE / "configs"
                           / f"{run['cell']['config']}.yaml").read_text())
    kw = conf["program"].get("model-kwargs") or {}
    try:
        return {"rows": conf["program"]["learning"]["batch-size"],
                "seq": conf["dataset"]["seq-len"],
                "heads": kw["num_attention_heads"],
                "nope_dim": kw["qk_nope_head_dim"],
                "rope_dim": kw["qk_rope_head_dim"],
                "value_dim": kw["v_head_dim"]}
    except KeyError:
        return None


def classify(op_name: str):
    """The scope of :data:`SCOPES` an operation lies under, or None."""
    parts = set(program_trace.PATH_SEPARATORS.split(op_name.split(":", 1)[0]))
    return next((s for s in SCOPES if s in parts), None)


def scope_times(trace: dict, window_s: float):
    """``{"steps": n, "ms": {scope: own ms a step}}`` over the train steps
    inside the window; None where the trace has neither scope."""
    if trace["mark"] is None or not trace["device"]:
        return None
    lo = trace["mark"][1]
    hi = lo + window_s * 1e9
    own, steps = collections.Counter(), 0
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
                inside.append((s, e, classify(op_name)))
        own.update(trace_reduce.self_times(inside))
    own.pop(None, None)
    if not steps or not own:
        return None
    return {"steps": steps,
            "ms": {s: own[s] / steps / 1e6 for s in SCOPES if s in own}}


def roofline(mixers, shapes, peaks):
    """The latent kernels' least time over their own time, in percent,
    from ``mixer_trace``'s reduction (calls and own milliseconds a step of
    every flash kernel); None where there is nothing to divide."""
    if not mixers or not shapes or not peaks or not mixers["ms"].get("flash"):
        return None
    least = sum(n * least_seconds(name.split(".")[0], shapes, peaks)
                for name, n in mixers["flash_calls_a_step"].items())
    return 100.0 * least / (mixers["ms"]["flash"] / 1e3)


def get(run: dict):
    if "_mla_trace" not in run:
        got = None
        try:
            path = trace_reduce.find_xplane(
                HERE / "_work" / run["cell"]["name"] / "trace")
            got = scope_times(program_trace.read(path), run["window_s"])
            if got is not None:
                got["mla_roofline"] = roofline(
                    mixer_trace.get(run), cell_shapes(run), run["peaks"])
        except Exception as e:  # noqa: BLE001 — a metric never fails a run
            print(f"mla_trace: not read ({type(e).__name__}: {e})",
                  file=sys.stderr)
        if got is not None:
            print("mla_trace: " + json.dumps(got), file=sys.stderr)
        run["_mla_trace"] = got
    return run["_mla_trace"]


def scope_ms(run: dict, scope: str):
    """Own device milliseconds an optimizer step under ``scope``; None
    where the trace has none."""
    got = get(run)
    return (got["ms"].get(scope) or None) if got else None
