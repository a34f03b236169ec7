"""What a state-space mixer costs in a traced window, by the scopes the
program puts in ``op_name`` (``models/decoder.py Mamba2``: ``ssm_mixer``
around the whole mixer, ``ssm_scan`` inside it around the scan alone), and
the scan's share of its roofline.

Everything is read by SCOPE, never by a kernel's name: ``ssm_scan`` is the
own time of every operation under that scope, whatever implements the scan
(today the chunked form in plain ``jax.numpy``, ``ops/ssd_scan.py``; later
perhaps a kernel), in both passes and with whatever a rematerialized stage
runs twice; ``ssm_mixer`` is the mixer's own time OUTSIDE the scan (the
projections, the convolution and its ``silu``, the softplus, the gated
group norm).

The scan's operations and bytes are counted from shapes, by what the
chunked ALGORITHM needs at the configuration's chunk for one layer and
microbatch: forward a head's product inside the chunk, its part of the
chunk's state and its read of the state it was handed, and a group's
scores; backward twice that; no recomputation.  Its bytes are ``x``,
``B``, ``C``, ``dt`` and ``y`` and their gradients moved once.

``get(run)`` reads the trace once a run (``program_trace.read``, the
reduction ``trace_reduce.self_times``), keeps the result in
``run["_ssm_trace"]`` and prints it to stderr as one line of JSON after
``ssm_trace:``.  Where the trace holds neither scope (a program that lacks
them, a CPU rehearsal) every reader returns None; nothing here raises into
``run_cell``.
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
SCAN, MIXER = "ssm_scan", "ssm_mixer"


def ssd_flops(tokens: int, heads: int, head_dim: int, groups: int,
              state: int, chunk: int) -> float:
    """FLOPs the chunked scan needs for ``tokens`` tokens of one layer,
    forward once and backward twice that.  Forward, a token: a head's
    product inside the chunk (``2 x chunk x head_dim``), its part of the
    chunk's state and its read of the state it was handed (``2 x 2 x state
    x head_dim``), a group's scores (``2 x chunk x state``)."""
    forward = heads * (2 * chunk * head_dim + 4 * state * head_dim) \
        + groups * 2 * chunk * state
    return 3.0 * tokens * forward


def ssd_bytes(tokens: int, heads: int, head_dim: int, groups: int,
              state: int, width: int = 2) -> float:
    """Bytes the scan of one layer must move to and from memory once:
    ``x`` and ``y`` (heads x head_dim), ``B`` and ``C`` (groups x state) at
    ``width`` bytes an element, ``dt`` (a float32 a head), and the gradient
    of each."""
    once = tokens * (2 * heads * head_dim * width
                     + 2 * groups * state * width + heads * 4)
    return 2.0 * once


def least_seconds(shapes: dict, peaks: dict) -> float:
    """The least time the chip could take for the scans of one optimizer
    step: the larger of their operations over the bf16 peak and their
    bytes over the memory's, a layer and microbatch, times both."""
    tokens = shapes["rows"] * shapes["seq"]
    flops = ssd_flops(tokens, shapes["heads"], shapes["head_dim"],
                      shapes["groups"], shapes["state"], shapes["chunk"])
    moved = ssd_bytes(tokens, shapes["heads"], shapes["head_dim"],
                      shapes["groups"], shapes["state"])
    return shapes["layers"] * shapes["microbatches"] * max(
        flops / (peaks["bf16_tflops"] * 1e12),
        moved / (peaks["hbm_gbps"] * 1e9))


def cell_shapes(run: dict):
    """The scans of one optimizer step, from the cell's configuration;
    None where it has no state-space layer."""
    import yaml
    conf = yaml.safe_load((HERE / "configs"
                           / f"{run['cell']['config']}.yaml").read_text())
    kw = conf["program"].get("model-kwargs") or {}
    try:
        return {"rows": conf["program"]["learning"]["batch-size"],
                "microbatches": conf["program"]["learning"]["control-count"],
                "seq": conf["dataset"]["seq-len"],
                "layers": kw["hybrid_override_pattern"].count("M"),
                "heads": kw["mamba_num_heads"],
                "head_dim": kw["mamba_head_dim"], "groups": kw["n_groups"],
                "state": kw["ssm_state_size"], "chunk": kw["chunk_size"]}
    except KeyError:
        return None


def classify(op_name: str):
    """``ssm_scan`` for an operation under that scope, ``ssm_mixer`` for
    one under the mixer's scope and outside the scan's, else None."""
    parts = set(program_trace.PATH_SEPARATORS.split(op_name.split(":", 1)[0]))
    return SCAN if SCAN in parts else MIXER if MIXER in parts else None


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
    return {"steps": steps, "ms": {s: own[s] / steps / 1e6
                                   for s in (SCAN, MIXER) if s in own}}


def roofline(times, shapes, peaks):
    """The scans' least time a step over the own time a step of everything
    under ``ssm_scan``, in percent; None where there is nothing to
    divide."""
    if not times or not shapes or not peaks or not times["ms"].get(SCAN):
        return None
    return 100.0 * least_seconds(shapes, peaks) / (times["ms"][SCAN] / 1e3)


def get(run: dict):
    if "_ssm_trace" not in run:
        got = None
        try:
            path = trace_reduce.find_xplane(
                HERE / "_work" / run["cell"]["name"] / "trace")
            got = scope_times(program_trace.read(path), run["window_s"])
            if got is not None:
                got["ssd_roofline"] = roofline(got, cell_shapes(run),
                                               run["peaks"])
        except Exception as e:  # noqa: BLE001 — a metric never fails a run
            print(f"ssm_trace: not read ({type(e).__name__}: {e})",
                  file=sys.stderr)
        if got is not None:
            print("ssm_trace: " + json.dumps(got), file=sys.stderr)
        run["_ssm_trace"] = got
    return run["_ssm_trace"]


def scope_ms(run: dict, scope: str):
    """Own device milliseconds an optimizer step under ``scope``; None
    where the trace has none."""
    got = get(run)
    return (got["ms"].get(scope) or None) if got else None
