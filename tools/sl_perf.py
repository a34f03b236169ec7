#!/usr/bin/env python
"""``sl_perf`` — per-round compute attribution report + perf
regression gate.

Two data sources, merged into one report:

* ``kind=perf`` records from a run's ``metrics.jsonl``
  (``runtime/perf.py PerfPlane``): per-participant, per-round
  ``compute | compile | dispatch | host | wait`` attribution, MFU,
  HBM watermark, compile counts and retraces;
* the run-scoped ``bench.json`` artifacts bench.py writes (and the
  driver-wrapper shapes older records used): the stable
  regression-tracking keys mirrored at the top of ``extra``.

Modes:

    python tools/sl_perf.py --metrics artifacts/runs/<run_id>  # report
    python tools/sl_perf.py --metrics <dir> --report out.json
    python tools/sl_perf.py --diff <old>/bench.json <new>/bench.json \
        --threshold 0.15                                       # gate

``--diff`` compares the LAST bench record against the previous one on
the stable keys and exits 1 on any regression beyond the noise
threshold (default 15%).  Improvements and
within-noise drift pass; keys missing or null on either side are
skipped (a section that never ran is not a regression).

Stdlib only: runs anywhere the repo does.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys

#: noise threshold: relative change beyond which a worsened stable key
#: fails the gate
DEFAULT_THRESHOLD = 0.15

#: stable bench keys: dotted path into the bench payload -> direction
#: ("up" = higher is better, "down" = lower is better).  These are the
#: keys successive BENCH_r*.json rounds mirror at fixed paths exactly
#: so this gate can diff them without knowing section nesting.
STABLE_KEYS = {
    "value": "up",                              # headline samples/s
    "extra.protocol_samples_per_sec": "up",
    "extra.split_ratio_vs_unsplit": "down",     # split slowdown factor
    "extra.cold_round_wall_s": "down",
    "extra.wire_mb_per_round": "down",
    "extra.wire_mb_per_round_compressed": "down",
    "extra.per_device_hbm_gb.total_est": "down",
    "extra.mfu.mfu_vs_datasheet": "up",
    "extra.mfu.measured_matmul_roofline_tflops": "up",
    # streaming aggregation plane (round-9): server aggregate wall per
    # client (flat-vs-fleet-width headline) and peak simultaneous
    # full-tree copies at the UPDATE barrier (O(1) memory headline)
    "extra.agg_wall_per_client_ms": "down",
    "extra.agg_peak_tree_copies": "down",
    # multi-process aggregator tree (round-12): end-to-end aggregate
    # wall per client at 10k synthetic clients through 3 real
    # aggregator processes over TCP, and the root's PartialAggregate
    # ingress bytes with the partial codec on vs raw fp32
    "extra.agg_wall_per_client_ms_10k": "down",
    "extra.agg_root_ingress_mb_ratio": "down",
    # async decoupled mode (round-10): delayed-cell throughput, the
    # delayed async/sync wall ratio (<1 = async wins under RTT), and
    # the accuracy parity delta at equal sample budget
    "extra.async_samples_per_sec": "up",
    "extra.async_wall_ratio_vs_sync": "down",
    "extra.async_accuracy_delta": "up",
    # sharded weight update + sync overlap (round-11): the serial
    # round-boundary update wall per boundary, and the fraction of it
    # hidden behind client compute
    "extra.update_bubble_ms": "down",
    "extra.update_overlap_ratio": "up",
    # closed-loop scheduler (round-13): steady-state round wall with
    # the scheduler on vs the static plan on the heterogeneous
    # simulated fleet (<1 = the control loop pays for itself), and the
    # scheduler's own decision-pass wall at 10k simulated clients (the
    # control plane must never become the bottleneck)
    "extra.sched_wall_ratio_vs_static": "down",
    "extra.sched_decision_ms_10k": "down",
    # hierarchical fleet telemetry (round-14): server-side digest
    # ingest + decision-input build per interval at 100k synthetic
    # clients, and one /metrics render under the series cap at 100k —
    # both must stay flat as the fleet grows (the digest path is
    # O(nodes + top-K), the render O(max-client-series))
    "extra.fleet_digest_ingest_ms_100k": "down",
    "extra.fleet_metrics_render_ms_100k": "down",
    # sharded event-loop broker plane (round-15): aggregate ingest
    # throughput multiplier of 4 shard processes over the 1-shard
    # baseline (>1 = the plane scales past one GIL), and the 4-vs-1
    # shard round-wall ratio on the 100k synthetic fleet round
    "extra.broker_shard_scaling": "up",
    "extra.broker_round_wall_ratio_100k": "down",
    # cross-host MPMD stage pipeline (round-16): end-to-end samples/s
    # of the 3-stage-host cell over the single-process twin (>1 =
    # spreading the hops across processes buys real throughput), and
    # the 3-host cell's absolute rate
    "extra.mpmd_scaling_3host": "up",
    "extra.mpmd_samples_per_sec": "up",
    # Pallas hot-path kernel plane (round-17): fused-kernel wall over
    # the XLA-chain wall for the codec quantize and the round-boundary
    # stage update (< 1 = the single-pass kernel wins).  Recorded only
    # on real TPU runs — the CPU interpreter cell leaves them null,
    # and the diff gate skips null keys
    "extra.quant_kernel_wall_ratio": "down",
    "extra.update_kernel_wall_ratio": "down",
}

#: absolute pins, enforced on the NEWEST record regardless of trend: a
#: "down" key must stay <= its cap, an "up" key >= it.  The split
#: ratio drifted 1.5 -> 2.1 across BENCH_r02-r05 while the
#: trend-only gate read the torn driver tails as unparseable (the
#: escaped-quote scavenge gap fixed below) — a pin cannot recalcify.
STABLE_KEY_CAPS = {
    "extra.split_ratio_vs_unsplit": 1.7,
    "extra.update_overlap_ratio": 0.5,
    # multi-process tree acceptance pins (round-12): codec'd root
    # ingress must stay <= 0.35x of raw fp32, and the 10k-client
    # aggregate wall per client must stay flat (the 100-client point
    # of the same leg measured ~1.4 ms and 10k ~0.94 ms on the r07
    # host; the absolute pin is ~1.5x the measurement so a
    # superlinear-aggregation regression cannot calcify)
    "extra.agg_root_ingress_mb_ratio": 0.35,
    "extra.agg_wall_per_client_ms_10k": 1.5,
    # closed-loop scheduler acceptance pins (round-13): the scheduler
    # must keep beating the static plan by >= 30% on the heterogeneous
    # fleet cell, and one decision pass at 10k clients must stay
    # bounded (measured ~490 ms = 0.05 ms/client, flat from 24 ->
    # 10k; the pin is host headroom, not a target — against a ~30 s
    # 10k-client round wall the pass is ~1.6%)
    "extra.sched_wall_ratio_vs_static": 0.7,
    "extra.sched_decision_ms_10k": 1000.0,
    # hierarchical fleet telemetry acceptance pins (round-14): ONE
    # interval's server-side digest ingest + decision-input build at
    # 100k synthetic clients (measured ~4 ms on the r09 host: 24 node
    # digests + advance + summary snapshot — O(nodes + watchlist),
    # not O(clients)), and one capped /metrics render at 100k
    # (~1.5 ms; the page is O(max-client-series)).  Caps are host
    # headroom over the measurement so a superlinear regression —
    # anything that re-introduces a per-client walk — cannot calcify.
    "extra.fleet_digest_ingest_ms_100k": 50.0,
    "extra.fleet_metrics_render_ms_100k": 20.0,
    # sharded broker plane acceptance pins (round-15): 4 shard
    # processes must keep ingesting >= 2x the single broker's
    # aggregate rate, and the 100k-fleet round wall through 4 shards
    # must stay <= 0.7x the 1-shard wall — a regression toward
    # re-serializing the plane (a shared lock, a single-connection
    # funnel) cannot calcify
    "extra.broker_shard_scaling": 2.0,
    "extra.broker_round_wall_ratio_100k": 0.7,
    # MPMD stage-pipeline acceptance pin (round-16): the 3-stage-host
    # cell must keep >= 1.5x the single-process twin's samples/s — a
    # regression toward re-serializing the hops (a shared lock, a
    # single-process fallback) cannot calcify
    "extra.mpmd_scaling_3host": 1.5,
}

#: attribution components of a kind=perf record, in report order
COMPONENTS = ("compute_s", "compile_s", "dispatch_s", "host_s",
              "wait_s")


# --------------------------------------------------------------------------
# bench history loading
# --------------------------------------------------------------------------

#: raw-text rescue patterns for stable keys whose JSON wrapper is
#: unrecoverable (the historical BENCH_r*.json shape: a driver wrapper
#: with ``parsed: null`` and a FRONT-TRUNCATED stdout tail — exactly
#: the gap the run-scoped bench.json artifact closes).  Only keys with
#: globally unique spellings are scavenged; ambiguous ones (e.g. the
#: many nested "samples_per_sec") are left to structured parses.
#:
#: The quotes match BOTH ``"key":`` and ``\"key\":`` — a driver tail
#: embeds the payload as a JSON string, so every quote arrives
#: backslash-escaped.  The round-11 split-ratio hunt found the old
#: plain-quote patterns silently scavenged NOTHING from r02-r05,
#: which is how a 1.5 -> 2.1 regression of a gated key calcified
#: unseen.
_NUM = r"(-?[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)"
_Q = r'\\?"'


def _kv_re(key: str, suffix: str = "") -> "re.Pattern":
    return re.compile(_Q + key + _Q + r":\s*" + _NUM + suffix)


_SCAVENGE_RES = {
    "value": re.compile(_Q + "value" + _Q + r":\s*" + _NUM
                        + r",\s*" + _Q + "unit" + _Q + r":\s*"
                        + _Q + "samples/sec/chip"),
    "extra.per_device_hbm_gb.total_est":
        re.compile(_Q + "per_device_hbm_gb" + _Q + r":\s*\{[^{}]*"
                   + _Q + "total_est" + _Q + r":\s*" + _NUM),
    # the split ratio has two spellings: the mirrored stable key and
    # the in-section "ratio_vs_unsplit" older records carry
    "extra.split_ratio_vs_unsplit":
        re.compile(_Q + r"(?:split_)?ratio_vs_unsplit" + _Q
                   + r":\s*" + _NUM),
}
for _k in ("protocol_samples_per_sec", "cold_round_wall_s",
           "wire_mb_per_round", "wire_mb_per_round_compressed",
           "mfu_vs_datasheet", "measured_matmul_roofline_tflops",
           "agg_wall_per_client_ms", "agg_peak_tree_copies",
           "agg_wall_per_client_ms_10k", "agg_root_ingress_mb_ratio",
           "async_samples_per_sec", "async_wall_ratio_vs_sync",
           "async_accuracy_delta", "update_bubble_ms",
           "update_overlap_ratio", "sched_wall_ratio_vs_static",
           "sched_decision_ms_10k", "fleet_digest_ingest_ms_100k",
           "fleet_metrics_render_ms_100k", "broker_shard_scaling",
           "broker_round_wall_ratio_100k", "mpmd_scaling_3host",
           "mpmd_samples_per_sec", "quant_kernel_wall_ratio",
           "update_kernel_wall_ratio"):
    _path = ("extra.mfu." + _k
             if _k.startswith(("mfu_vs", "measured_matmul"))
             else "extra." + _k)
    _SCAVENGE_RES[_path] = _kv_re(_k)


def _dig(d: dict, dotted: str):
    cur = d
    for part in dotted.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return cur if isinstance(cur, (int, float)) \
        and not isinstance(cur, bool) else None


def stable_values(payload: dict) -> dict:
    """Flat {stable key: value} map from a structured bench payload."""
    return {k: v for k in STABLE_KEYS
            if (v := _dig(payload, k)) is not None}


def scavenge_stable_values(text: str) -> dict:
    """Stable keys regex-rescued from raw (possibly torn) bench text."""
    out = {}
    for key, pat in _SCAVENGE_RES.items():
        m = pat.search(text)
        if m:
            out[key] = float(m.group(1))
    return out


def _extract_payload(rec: dict) -> dict | None:
    """The structured bench payload, when one survives: a plain
    payload (the new bench.json artifact), a driver wrapper with
    ``parsed`` set, or a full ``{"metric": ...}`` line in the captured
    stdout tail."""
    if not isinstance(rec, dict):
        return None
    if "metric" in rec and "extra" in rec:
        return rec
    parsed = rec.get("parsed")
    if isinstance(parsed, dict) and "extra" in parsed:
        return parsed
    tail = rec.get("tail")
    if isinstance(tail, str):
        # last parseable {"metric": ...} start wins (partial flushes
        # may precede the final emit)
        idx = tail.rfind('{"metric"')
        if idx >= 0:
            chunk = tail[idx:].strip()
            for end in (len(chunk), chunk.rfind("}") + 1):
                try:
                    cand = json.loads(chunk[:end])
                except json.JSONDecodeError:
                    continue
                if isinstance(cand, dict) and "extra" in cand:
                    return cand
    return None


def load_bench(path: str | pathlib.Path) -> dict | None:
    """Flat stable-key map for one bench record on disk; None when
    nothing at all is recoverable (e.g. the rc=124 empty round)."""
    try:
        raw = pathlib.Path(path).read_text()
        rec = json.loads(raw)
    except (OSError, json.JSONDecodeError):
        return None
    payload = _extract_payload(rec)
    text = rec.get("tail") if isinstance(rec, dict) \
        and isinstance(rec.get("tail"), str) else raw
    scavenged = scavenge_stable_values(text)
    if payload is not None:
        vals = stable_values(payload)
        # scavenge fills keys the structured payload predates (e.g.
        # r02's split ratio lived only inside its section before the
        # mirrored stable key existed)
        for k, v in scavenged.items():
            vals.setdefault(k, v)
        return vals or None
    return scavenged or None


def diff_bench(prev: dict, cur: dict,
               threshold: float = DEFAULT_THRESHOLD) -> dict:
    """Stable-key comparison of two flat maps: per-key old/new/
    relative change and a regression verdict.  ``regressions`` lists
    the keys that worsened beyond the threshold, plus any key whose
    NEWEST value crosses its absolute pin (``STABLE_KEY_CAPS``) — a
    pinned key fails even when the round-over-round trend is flat,
    so a regression that slipped through once can never calcify."""
    keys = {}
    regressions = []
    for key, direction in STABLE_KEYS.items():
        old, new = prev.get(key), cur.get(key)
        if old is None or new is None or old == 0:
            continue
        change = (new - old) / abs(old)
        worse = change < -threshold if direction == "up" \
            else change > threshold
        keys[key] = {"old": old, "new": new,
                     "change": round(change, 4),
                     "direction": direction,
                     "regression": worse}
        if worse:
            regressions.append(key)
    for key, cap in STABLE_KEY_CAPS.items():
        new = cur.get(key)
        if new is None:
            continue
        direction = STABLE_KEYS.get(key, "down")
        pinned = new < cap if direction == "up" else new > cap
        ent = keys.setdefault(key, {"old": prev.get(key), "new": new,
                                    "change": None,
                                    "direction": direction,
                                    "regression": False})
        ent["cap"] = cap
        if pinned:
            ent["regression"] = True
            if key not in regressions:
                regressions.append(key)
    return {"threshold": threshold, "keys": keys,
            "regressions": regressions}


# --------------------------------------------------------------------------
# kind=perf attribution report
# --------------------------------------------------------------------------

def metrics_files(path: str | pathlib.Path) -> list[pathlib.Path]:
    """metrics.jsonl plus its size-rotated siblings
    (``observability.metrics-max-mb`` → ``metrics.jsonl.N``), oldest
    first, so a rotated run reads exactly like an unrotated one.
    ONE implementation for all the stdlib tools: sl_top owns it."""
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from sl_top import journal_files
    return journal_files(pathlib.Path(path))


def load_perf_records(path: str | pathlib.Path) -> list[dict]:
    """All ``kind=perf`` records from a metrics.jsonl (or a run/log
    directory holding one), rotated files included."""
    out = []
    for p in metrics_files(path):
        for line in p.read_text().splitlines():
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict) and rec.get("kind") == "perf":
                out.append(rec)
    return out


def attribution_report(records: list[dict],
                       bench: list[dict] | None = None) -> dict:
    """Per-(participant, round) attribution rows + MFU trend, plus the
    bench history's stable keys when given."""
    rows = []
    mfu_trend = []
    for rec in records:
        wall = rec.get("wall_s") or 0.0
        comps = {c: rec.get(c, 0.0) or 0.0 for c in COMPONENTS}
        row = {
            "participant": rec.get("participant") or rec.get("client"),
            "round": rec.get("round", rec.get("round_idx")),
            # pipeline hop this record ran (clients stamp their stage
            # since the MPMD plane; None for older records)
            "stage": rec.get("stage"),
            "wall_s": wall,
            **{c: round(v, 4) for c, v in comps.items()},
            "attributed_frac": (round(sum(comps.values()) / wall, 4)
                                if wall else None),
            "steps": rec.get("steps"),
            "samples": rec.get("samples"),
            "retraces": rec.get("retraces"),
        }
        for opt in ("mfu", "tflops_per_sec", "hbm_peak_bytes",
                    "compute_samples_per_s", "hbm_peak_vs_plan"):
            if rec.get(opt) is not None:
                row[opt] = rec[opt]
        rows.append(row)
        if rec.get("mfu") is not None:
            mfu_trend.append({"round": row["round"],
                              "participant": row["participant"],
                              "mfu": rec["mfu"]})
    report: dict = {"rounds": rows, "mfu_trend": mfu_trend}
    # per-hop attribution (MPMD stage pipeline): every stage-stamped
    # record — stage-host processes' inner clients included, their
    # metrics.jsonl files merge into the same load — rolls up by hop,
    # so compute|wire|wait is reported per STAGE, not just per client.
    # wire = dispatch + host (frame encode/decode + dispatch around
    # the hot loop); wait = barrier/queue waits incl. the inter-hop
    # activation/gradient queues.  Records predating the stage stamp
    # simply don't contribute.
    hops: dict = {}
    for row in rows:
        st = row.get("stage")
        if st is None:
            continue
        ent = hops.setdefault(str(st), {
            "n": 0, "wall_s": 0.0, "compute_s": 0.0, "wire_s": 0.0,
            "wait_s": 0.0, "samples": 0})
        ent["n"] += 1
        ent["wall_s"] += row["wall_s"]
        ent["compute_s"] += row["compute_s"]
        ent["wire_s"] += row["dispatch_s"] + row["host_s"]
        ent["wait_s"] += row["wait_s"]
        ent["samples"] += int(row.get("samples") or 0)
    if hops:
        report["hops"] = {
            st: {k: (round(v, 4) if isinstance(v, float) else v)
                 for k, v in ent.items()}
            for st, ent in sorted(hops.items())}
    if bench:
        report["bench_history"] = [dict(b) for b in bench]
    return report


def render_report(report: dict) -> str:
    lines = []
    rows = report.get("rounds", [])
    if rows:
        head = ("PART", "ROUND", "WALL s", "COMPUTE", "COMPILE",
                "DISPATCH", "HOST", "WAIT", "MFU")
        table = [head]
        for r in rows:
            table.append((
                str(r.get("participant") or "?"),
                str(r.get("round")),
                f"{r.get('wall_s', 0):.2f}",
                f"{r.get('compute_s', 0):.2f}",
                f"{r.get('compile_s', 0):.2f}",
                f"{r.get('dispatch_s', 0):.2f}",
                f"{r.get('host_s', 0):.2f}",
                f"{r.get('wait_s', 0):.2f}",
                ("-" if r.get("mfu") is None
                 else f"{r['mfu']:.4f}"),
            ))
        widths = [max(len(row[i]) for row in table)
                  for i in range(len(head))]
        for row in table:
            lines.append("  ".join(f"{v:<{w}}"
                                   for v, w in zip(row, widths)))
    else:
        lines.append("no kind=perf records found")
    hops = report.get("hops")
    if hops:
        lines.append("")
        lines.append("per-hop attribution (stage pipeline):")
        head = ("STAGE", "RECS", "WALL s", "COMPUTE", "WIRE", "WAIT",
                "SAMPLES")
        table = [head]
        for st, ent in hops.items():
            table.append((
                st, str(ent["n"]), f"{ent['wall_s']:.2f}",
                f"{ent['compute_s']:.2f}", f"{ent['wire_s']:.2f}",
                f"{ent['wait_s']:.2f}", str(ent["samples"])))
        widths = [max(len(row[i]) for row in table)
                  for i in range(len(head))]
        for row in table:
            lines.append("  " + "  ".join(
                f"{v:<{w}}" for v, w in zip(row, widths)))
    hist = report.get("bench_history")
    if hist:
        # stable-key trend across the given history (oldest..newest):
        # the update-bubble / split-ratio trajectory at a glance
        lines.append("")
        lines.append("stable-key trend (oldest -> newest):")
        seen_keys = sorted({k for b in hist for k in b})
        for key in seen_keys:
            vals = [(f"{b[key]:g}" if key in b else "-") for b in hist]
            pin = (f"  [pin {'>=' if STABLE_KEYS.get(key) == 'up' else '<='}"
                   f" {STABLE_KEY_CAPS[key]:g}]"
                   if key in STABLE_KEY_CAPS else "")
            lines.append(f"  {key}: " + " -> ".join(vals) + pin)
    diff = report.get("diff")
    if diff:
        lines.append("")
        lines.append(f"regression gate (threshold "
                     f"{diff['threshold']:.0%}):")
        for key, d in sorted(diff["keys"].items()):
            mark = "REGRESSION" if d["regression"] else "ok"
            change = ("" if d.get("change") is None
                      else f"{d['change']:+.1%}, ")
            cap = ""
            if d.get("cap") is not None:
                op = ">=" if d["direction"] == "up" else "<="
                cap = f", pin {op} {d['cap']:g}"
            lines.append(f"  {key}: {d['old']} -> {d['new']} "
                         f"({change}want {d['direction']}{cap}) "
                         f"[{mark}]")
        if not diff["keys"]:
            lines.append("  (no comparable stable keys)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Compute-attribution report (kind=perf records) "
                    "and bench regression gate (stable keys).")
    ap.add_argument("--metrics", default=None, metavar="DIR",
                    help="run dir or metrics.jsonl with kind=perf "
                         "records")
    ap.add_argument("--diff", nargs="+", default=None, metavar="BENCH",
                    help="bench records (oldest..newest); compares the "
                         "last against the previous and exits 1 on a "
                         "regression beyond --threshold")
    ap.add_argument("--bench", nargs="*", default=None, metavar="BENCH",
                    help="bench history to fold into the report "
                         "(no gating)")
    ap.add_argument("--threshold", type=float,
                    default=DEFAULT_THRESHOLD)
    ap.add_argument("--report", default=None, metavar="OUT.json",
                    help="also write the full report as JSON")
    args = ap.parse_args(argv)
    if not args.metrics and not args.diff:
        ap.error("need --metrics and/or --diff")

    records = load_perf_records(args.metrics) if args.metrics else []
    bench_hist = [b for p in (args.bench or [])
                  if (b := load_bench(p)) is not None]
    report = attribution_report(records, bench=bench_hist or None)

    rc = 0
    if args.diff:
        loaded = [(p, load_bench(p)) for p in args.diff]
        usable = [(p, b) for p, b in loaded if b is not None]
        for p, b in loaded:
            if b is None:
                print(f"sl_perf: skipping unparseable bench record "
                      f"{p}", file=sys.stderr)
        if len(usable) < 2:
            print("sl_perf: need at least 2 parseable bench records "
                  "to diff", file=sys.stderr)
            rc = 2
        else:
            report["diff"] = diff_bench(usable[-2][1], usable[-1][1],
                                        threshold=args.threshold)
            report["diff"]["compared"] = [usable[-2][0], usable[-1][0]]
            if report["diff"]["regressions"]:
                rc = 1

    if args.report:
        pathlib.Path(args.report).write_text(json.dumps(report,
                                                        indent=1))
    print(render_report(report))
    if rc == 1:
        print(f"\nsl_perf: PERF REGRESSION on "
              f"{report['diff']['regressions']}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
