#!/usr/bin/env python
"""Merge span journals into a Perfetto trace + critical-path report.

Every participant of a traced run (``observability:`` config block)
journals spans to ``spans-{participant}.jsonl`` (``runtime/spans.py``).
This tool merges them:

* ``trace.json`` — Chrome/Perfetto trace-event JSON: one process track
  per participant, one thread track per (participant, thread), and a
  flow arrow per data-plane frame binding the sender's *publish* span
  to the receiver's *consume* span (open at https://ui.perfetto.dev).
* **critical-path report** — per round, walk the span graph BACKWARD
  from the server's ``round`` span end: follow the latest activity on
  the current participant, hop across participants along frame flow
  edges, and accrue every walked interval into one of ``compute`` /
  ``compile`` / ``wire`` / ``input`` / ``queue_wait`` /
  ``aggregate`` / ``control`` (``compile`` spans come from the perf
  plane's CompileWatch, so a cold round's compile tax is separated
  from device compute; ``input`` is the mesh context building and
  uploading a step's batch).
  The walk covers the round interval exactly, so the components sum to
  the round's wall time by construction; ``queue_wait`` absorbs the
  un-spanned intervals (queue residency, barrier waits, client-side
  setup).  The slowest frame edges per round are listed so a stage
  bubble names its queue.

    python tools/sl_trace.py <log-dir>                 # report only
    python tools/sl_trace.py <log-dir> -o trace.json   # + Perfetto
"""

from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys

#: structural spans excluded from the critical-path walk: they overlap
#: the leaf spans recorded inside them (a barrier wait contains the
#: consume spans that end it) and carry no attributable work themselves
CONTAINER_NAMES = frozenset({
    "round", "client_round", "train", "train_cluster",
    "ready_wait", "notify_wait", "update_wait",
})

#: leaf-span name -> critical-path category.  `compile` spans come
#: from the perf plane's CompileWatch (runtime/perf.py): XLA compiles
#: get their own category so a cold round's compile tax stops
#: masquerading as device compute in the breakdown.
CATEGORY = {
    "fwd": "compute", "bwd": "compute", "sda_step": "compute",
    "whole_step": "compute", "step": "compute",
    "compile": "compile",
    "publish": "wire", "consume": "wire", "wire_send": "wire",
    "encode": "wire", "decode": "wire",
    "aggregate": "aggregate", "validate": "aggregate",
    "checkpoint": "aggregate", "plan": "aggregate",
    "start_fanout": "control", "syn_fanout": "control",
    "pause_fanout": "control",
    # the in-process mesh path (runtime/context.py): a step's batch is
    # built and uploaded (`input`), the compiled step is called and the
    # last loss awaited (`compute`: the device's queued work), columns
    # are averaged on the mesh or pulled and folded on the host
    "feed": "input", "upload": "input",
    "dispatch": "compute", "sync": "compute",
    "fedavg": "aggregate", "pull": "aggregate", "extract": "aggregate",
    "checkpoint_write": "aggregate",
    "round_setup": "control",
}

CATEGORIES = ("compute", "compile", "wire", "input", "queue_wait",
              "aggregate", "control")

#: categorized spans that run beside the round's thread: the loop only
#: waits for one inside its ``checkpoint`` span, so the walk back from a
#: ``train`` span's end never passes through them
BACKGROUND_NAMES = frozenset({"checkpoint_write"})

#: required keys of one spans.jsonl record (schema v1)
SPAN_REQUIRED = frozenset({"v", "trace", "span", "name", "part", "ts",
                           "dur"})


# --------------------------------------------------------------------------
# loading + validation
# --------------------------------------------------------------------------

def find_span_files(directory: str | pathlib.Path) -> list[pathlib.Path]:
    d = pathlib.Path(directory)
    return sorted(set(d.glob("spans-*.jsonl")) | set(d.glob("spans.jsonl")))


def load_spans(paths) -> list[dict]:
    """All span records from the given journals; malformed lines are
    skipped (a crashed writer may leave a torn tail line)."""
    spans: list[dict] = []
    for path in paths:
        for line in pathlib.Path(path).read_text().splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                spans.append(rec)
    return spans


def validate_spans(spans: list[dict]) -> list[str]:
    """Schema errors ('' clean) for a merged span set."""
    errors = []
    seen = set()
    for i, s in enumerate(spans):
        missing = SPAN_REQUIRED - set(s)
        if missing:
            errors.append(f"span #{i} missing keys {sorted(missing)}")
            continue
        if not isinstance(s["ts"], (int, float)) \
                or not isinstance(s["dur"], (int, float)) \
                or s["dur"] < 0:
            errors.append(f"span #{i} ({s['name']}) bad ts/dur")
        if s["span"] in seen:
            errors.append(f"duplicate span id {s['span']}")
        seen.add(s["span"])
    return errors


def orphan_spans(spans: list[dict]) -> list[dict]:
    """Spans whose parent id resolves to no span in the merged set —
    a connected per-round span tree has none."""
    ids = {s["span"] for s in spans}
    return [s for s in spans
            if s.get("parent") is not None and s["parent"] not in ids]


# --------------------------------------------------------------------------
# Perfetto export
# --------------------------------------------------------------------------

def build_trace(spans: list[dict]) -> dict:
    """Chrome trace-event JSON: X events per span, M metadata naming
    the tracks, s/f flow pairs along the frame edges."""
    events: list[dict] = []
    parts = sorted({s["part"] for s in spans})
    pid_of = {p: i + 1 for i, p in enumerate(parts)}
    tid_of: dict[tuple, int] = {}
    for s in spans:
        key = (s["part"], s.get("thread", "main"))
        if key not in tid_of:
            tid_of[key] = sum(1 for k in tid_of if k[0] == s["part"]) + 1
    t0 = min(s["ts"] for s in spans) if spans else 0.0

    for p in parts:
        events.append({"ph": "M", "name": "process_name",
                       "pid": pid_of[p], "tid": 0,
                       "args": {"name": p}})
    for (p, thread), tid in sorted(tid_of.items()):
        events.append({"ph": "M", "name": "thread_name",
                       "pid": pid_of[p], "tid": tid,
                       "args": {"name": thread}})

    by_id = {s["span"]: s for s in spans}
    flow_id = 0
    for s in spans:
        pid = pid_of[s["part"]]
        tid = tid_of[(s["part"], s.get("thread", "main"))]
        args = {k: v for k, v in s.items()
                if k not in ("ts", "dur", "part", "thread", "v")}
        events.append({
            "ph": "X", "name": s["name"],
            "cat": CATEGORY.get(s["name"], "control"),
            "pid": pid, "tid": tid,
            "ts": round((s["ts"] - t0) * 1e6, 1),
            "dur": max(0.1, round(s["dur"] * 1e6, 1)),
            "args": args})
        if s["name"] != "consume":
            continue
        pub = by_id.get(s.get("parent"))
        if pub is None:
            continue
        flow_id += 1
        events.append({
            "ph": "s", "id": flow_id, "cat": "frame",
            "name": s.get("kind", "frame"),
            "pid": pid_of[pub["part"]],
            "tid": tid_of[(pub["part"], pub.get("thread", "main"))],
            "ts": round((pub["ts"] + pub["dur"] - t0) * 1e6, 1)})
        events.append({
            "ph": "f", "bp": "e", "id": flow_id, "cat": "frame",
            "name": s.get("kind", "frame"), "pid": pid, "tid": tid,
            "ts": round((s["ts"] - t0) * 1e6, 1)})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def validate_trace(trace: dict) -> list[str]:
    """Structural Perfetto-JSON checks ([] = valid)."""
    errors = []
    events = trace.get("traceEvents")
    if not isinstance(events, list) or not events:
        return ["traceEvents missing or empty"]
    named_pids = set()
    flows: dict[tuple, list] = collections.defaultdict(list)
    for i, e in enumerate(events):
        for key in ("ph", "pid", "name"):
            if key not in e:
                errors.append(f"event #{i} missing {key!r}")
        ph = e.get("ph")
        if ph == "M":
            if e.get("name") == "process_name":
                named_pids.add(e.get("pid"))
            if "name" not in e.get("args", {}):
                errors.append(f"metadata event #{i} lacks args.name")
        elif ph == "X":
            if not isinstance(e.get("ts"), (int, float)) \
                    or not isinstance(e.get("dur"), (int, float)) \
                    or e["dur"] < 0:
                errors.append(f"X event #{i} bad ts/dur")
        elif ph in ("s", "f"):
            flows[(e.get("cat"), e.get("id"))].append(ph)
    for e in events:
        if e.get("ph") == "X" and e.get("pid") not in named_pids:
            errors.append(f"X event pid {e.get('pid')} has no "
                          "process_name metadata")
            break
    for key, phs in flows.items():
        if sorted(phs) != ["f", "s"]:
            errors.append(f"flow {key} unbalanced: {phs}")
    return errors


# --------------------------------------------------------------------------
# critical path
# --------------------------------------------------------------------------

def _leaves_by_part(spans: list[dict]) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = collections.defaultdict(list)
    for s in spans:
        if s["name"] in CATEGORY and s["name"] not in BACKGROUND_NAMES:
            out[s["part"]].append(s)
    return out


def _pick(leaves: list[dict], t: float, t_lo: float):
    """Latest leaf activity strictly before ``t`` (straddlers win)."""
    best, best_key = None, None
    for s in leaves:
        if s["ts"] >= t:
            continue
        key = min(s["ts"] + s["dur"], t)
        if key <= t_lo:
            continue
        if best is None or key > best_key \
                or (key == best_key and s["ts"] > best["ts"]):
            best, best_key = s, key
    return best


def _compile_overlap(leaves, lo: float, hi: float) -> float:
    """Total time within ``[lo, hi]`` covered by ``compile`` spans
    (overlapping spans merged so the result never exceeds hi-lo)."""
    ivals = sorted((max(s["ts"], lo), min(s["ts"] + s["dur"], hi))
                   for s in leaves if s["name"] == "compile"
                   and s["ts"] < hi and s["ts"] + s["dur"] > lo)
    total, cursor = 0.0, lo
    for a, b in ivals:
        a = max(a, cursor)
        if b > a:
            total += b - a
            cursor = b
    return total


def critical_path_round(round_span: dict, spans: list[dict]) -> dict:
    """Backward walk from the round's end: every interval of
    [round start, round end] lands in exactly one category, so the
    breakdown sums to the round's wall time by construction."""
    t_lo = round_span["ts"]
    t_hi = round_span["ts"] + round_span["dur"]
    root = round_span["part"]
    leaves = _leaves_by_part(spans)
    by_id = {s["span"]: s for s in spans}

    acc: dict[str, float] = {c: 0.0 for c in CATEGORIES}
    path: list[dict] = []
    cur, t = root, t_hi
    fellback_at = None
    for _ in range(1_000_000):
        if t <= t_lo + 1e-9:
            break
        s = _pick(leaves.get(cur, ()), t, t_lo)
        if s is None:
            if cur != root and fellback_at != t:
                # no earlier activity on this participant: resume on
                # the round's own timeline (the server drove this part
                # of the round — fan-outs, planning)
                fellback_at, cur = t, root
                continue
            acc["queue_wait"] += t - t_lo
            break
        end = min(s["ts"] + s["dur"], t)
        if t > end:
            acc["queue_wait"] += t - end
        seg_start = max(s["ts"], t_lo)
        acc[CATEGORY[s["name"]]] += end - seg_start
        path.append(s)
        t = seg_start
        if s["name"] != "consume":
            continue
        pub = by_id.get(s.get("parent"))
        if pub is None or pub["part"] == cur:
            continue
        pub_end = pub["ts"] + pub["dur"]
        if not t_lo < pub_end <= t:
            continue
        # hop across the frame edge: transit time is wire — minus any
        # part of it the RECEIVER spent compiling (CompileWatch spans):
        # a frame sitting in the queue while a cold consumer compiles
        # is compile tax, not a slow wire
        busy = _compile_overlap(leaves.get(cur, ()), pub_end, t)
        acc["compile"] += busy
        acc["wire"] += (t - pub_end) - busy
        acc["wire"] += pub_end - max(pub["ts"], t_lo)
        path.append(pub)
        t = max(pub["ts"], t_lo)
        cur = pub["part"]

    wall = t_hi - t_lo
    edges = [s for s in spans
             if s["name"] == "consume" and "rtt_ms" in s
             and t_lo <= s["ts"] <= t_hi]
    edges.sort(key=lambda s: -s["rtt_ms"])
    by_id_part = {s["span"]: s["part"] for s in spans}
    return {
        "round": round_span.get("round"),
        "wall_s": round(wall, 6),
        "components_s": {c: round(v, 6) for c, v in acc.items()},
        "components_sum_s": round(sum(acc.values()), 6),
        "path_spans": len(path),
        "slowest_edges": [
            {"kind": e.get("kind"), "queue": e.get("queue"),
             "rtt_ms": e["rtt_ms"],
             "from": by_id_part.get(e.get("parent"), "?"),
             "to": e["part"]}
            for e in edges[:5]],
        "frame_edges": len(edges),
    }


def critical_path(spans: list[dict]) -> list[dict]:
    """One report per round, anchored on the round's ``train`` span:
    its duration is exactly the ``wall_s`` the round's metrics record
    reports (validate/checkpoint are timed outside it), so the
    component sum is comparable to the recorded round wall time."""
    anchors = sorted((s for s in spans if s["name"] == "train"),
                     key=lambda s: s["ts"])
    reports = []
    for a in anchors:
        rep = critical_path_round(a, spans)
        for extra in ("validate", "checkpoint"):
            sib = [s for s in spans if s["name"] == extra
                   and s.get("round") == a.get("round")]
            if sib:
                rep[f"{extra}_s"] = round(sum(s["dur"] for s in sib), 6)
        reports.append(rep)
    return reports


def render_report(rounds: list[dict]) -> str:
    if not rounds:
        return "no 'round' spans found — was tracing enabled?"
    lines = ["per-round critical path (compute | compile | wire | "
             "input | queue-wait | aggregate | control; queue-wait "
             "includes barrier/idle time):"]
    for r in rounds:
        c = r["components_s"]
        pct = {k: (100.0 * v / r["wall_s"] if r["wall_s"] else 0.0)
               for k, v in c.items()}
        lines.append(
            f"  round {r['round']}: wall={r['wall_s']:.3f}s  "
            + "  ".join(f"{k}={c[k]:.3f}s({pct[k]:.0f}%)"
                        for k in CATEGORIES)
            + f"  [sum={r['components_sum_s']:.3f}s, "
              f"{r['frame_edges']} frame edges]")
        for e in r["slowest_edges"][:3]:
            lines.append(f"      slow edge: {e['kind']} "
                         f"{e['from']} -> {e['to']} on {e['queue']} "
                         f"rtt={e['rtt_ms']:.2f}ms")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Merge spans-*.jsonl journals into a Perfetto "
                    "trace.json and print a per-round critical-path "
                    "report.")
    ap.add_argument("directory", nargs="?", default=".",
                    help="directory holding spans-*.jsonl (a run's "
                         "log_path)")
    ap.add_argument("-o", "--out", default=None,
                    help="write Perfetto trace JSON here")
    ap.add_argument("--json", action="store_true",
                    help="print the report as JSON")
    args = ap.parse_args(argv)

    files = find_span_files(args.directory)
    if not files:
        print(f"no span journals under {args.directory!r} "
              "(expected spans-*.jsonl)", file=sys.stderr)
        return 1
    spans = load_spans(files)
    errors = validate_spans(spans)
    for e in errors[:10]:
        print(f"schema: {e}", file=sys.stderr)
    if args.out:
        trace = build_trace(spans)
        terr = validate_trace(trace)
        for e in terr[:10]:
            print(f"trace: {e}", file=sys.stderr)
        pathlib.Path(args.out).write_text(json.dumps(trace))
        print(f"wrote {args.out}: {len(trace['traceEvents'])} events "
              f"from {len(spans)} spans across {len(files)} journals")
        errors += terr
    rounds = critical_path(spans)
    print(json.dumps(rounds, indent=2) if args.json
          else render_report(rounds))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
