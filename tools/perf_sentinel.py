"""Perf-regression sentinel: machine-gate fresh bench/serving rows
against the banked baselines (ISSUE 12).

The repo keeps performance rows (bench rows files and the CPU-harness
serving baselines) but until now nothing DIFFED a fresh run against
them — a regression only surfaced
when a human read two JSON files.  This tool compares a fresh
one-JSON-line row set against a baseline, keyed by workload identity
(bench rows: bench.py's ``_workload_sig``; serving rows: the
generator-config signature), and flags any metric drifting beyond its
noise band.

Direction-aware bands: latency-shaped metrics flag when
``fresh > base * band``, throughput-shaped metrics when
``fresh < base / band``.  The default band is deliberately wide
(4x) because the CPU harness runs on whatever machine CI landed on —
the sentinel exists to catch order-of-magnitude breakage (a retrace
per request, a lost compile cache, an accidental sync), not 20% noise.

Modes:
    --mode serving   fresh = serving_load one-JSON-line outputs;
                     baseline = docs/perf_baseline_cpu.json (commit a
                     new one with --update-baseline).  The ci.sh step
                     gates the CPU-harness rows: inter-token p50 and
                     time_to_first_batch warm/cold.
    --mode bench     fresh = bench.py's rows file
                     (chiprun_out/bench_rows.json); baseline = another
                     one, named with --baseline.  Rows pair by
                     _workload_sig and only same-device rows compare.

stdout contract: EXACTLY ONE JSON line —

    {"metric": "perf_sentinel", "value": <n flagged>, "unit":
     "regressions", "ok": bool, "checked": N, "flagged": [...]}

Exit 0 iff nothing flagged (or --advise, which always exits 0).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# metric name -> direction ("lower" = lower is better)
METRIC_DIRECTION = {
    "p50_ms": "lower", "p99_ms": "lower",
    "inter_token_p50_ms": "lower", "inter_token_p99_ms": "lower",
    "time_to_first_batch_s": "lower",
    "time_to_first_batch_cold_s": "lower",
    "time_to_first_batch_warm_s": "lower",
    "step_ms": "lower",
    "goodput_qps": "higher", "capacity_qps": "higher",
    "tokens_per_sec": "higher", "examples_per_sec": "higher",
    "mfu_pct": "higher", "acceptance_rate": "higher",
}
# the CPU-harness rows the ci.sh step gates (ISSUE 12 satellite)
SERVING_GATED_METRICS = (
    "inter_token_p50_ms", "time_to_first_batch_cold_s",
    "time_to_first_batch_warm_s", "p50_ms", "tokens_per_sec",
    "goodput_qps",
)
DEFAULT_BAND = 4.0
# ignore latency drift when both sides are under this floor — a 0.2ms
# -> 0.9ms jitter on an idle box is not a regression signal
ABS_FLOOR = {"lower": 1e-3, "higher": 0.0}


def _log(msg):
    print("# " + msg, file=sys.stderr)


def _load_lines(paths):
    recs = []
    for path in paths:
        with open(path) as f:
            for ln in f:
                if ln.strip():
                    recs.append(json.loads(ln))
    return recs


# ---------------------------------------------------------------------------
# row extraction + keying
# ---------------------------------------------------------------------------

def serving_sig(rec):
    """Workload identity of a serving_load row: everything that
    changes what is being measured, nothing that is a measurement."""
    parts = [
        "serving", str(rec.get("metric")), str(rec.get("mode")),
        "r%s" % rec.get("replicas"), "mb%s" % rec.get("max_batch"),
        "dl%s" % rec.get("deadline_ms"),
    ]
    for k in ("spec_k", "prefix_shared", "prefill_chunk",
              "mean_prompt", "max_new", "disagg_prefill"):
        if rec.get(k):
            parts.append("%s%s" % (k, rec[k]))
    return ":".join(parts)


def serving_rows(recs):
    """{sig: {metric: value}} from serving_load one-line records."""
    out = {}
    for rec in recs:
        row = {}
        for m in METRIC_DIRECTION:
            v = rec.get(m)
            if isinstance(v, (int, float)):
                row[m] = float(v)
        if row:
            out[serving_sig(rec)] = row
    return out


def bench_rows(recs):
    """{sig_str: {metric: value}} from bench stdout records (their
    ``extras``, following ``rows_file`` pointers), keyed by bench.py's
    _workload_sig so key spelling never splits a measurement slot."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    out = {}
    for rec in recs:
        extras = rec.get("extras")
        if extras is None and rec.get("rows_file"):
            try:
                with open(rec["rows_file"]) as f:
                    extras = json.load(f).get("extras")
            except OSError:
                extras = None
        if not isinstance(extras, dict):
            continue
        for key, row in extras.items():
            if not isinstance(row, dict):
                continue
            sig = repr(bench._workload_sig(key, row)) + \
                "|dev=%s" % row.get("device")
            metrics = {m: float(row[m]) for m in METRIC_DIRECTION
                       if isinstance(row.get(m), (int, float))}
            if metrics:
                out[sig] = metrics
    return out


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def compare(fresh, baseline, band=DEFAULT_BAND, bands=None,
            gated_metrics=None):
    """Diff {sig: {metric: value}} maps.  Returns (checked, flagged,
    missing): ``flagged`` lists per-metric drift records; rows only in
    one side land in ``missing`` (informational — a new leg is not a
    regression)."""
    bands = bands or {}
    checked, flagged, missing = 0, [], []
    for sig, base_row in sorted(baseline.items()):
        fresh_row = fresh.get(sig)
        if fresh_row is None:
            missing.append(sig)
            continue
        for metric, base_v in sorted(base_row.items()):
            if gated_metrics is not None and \
                    metric not in gated_metrics:
                continue
            fresh_v = fresh_row.get(metric)
            if fresh_v is None:
                continue
            direction = METRIC_DIRECTION.get(metric, "lower")
            b = float(bands.get(metric, band))
            checked += 1
            floor = ABS_FLOOR[direction]
            if direction == "lower":
                bad = fresh_v > max(base_v * b, base_v + floor) and \
                    fresh_v > floor
            else:
                bad = base_v > 0 and fresh_v < base_v / b
            if bad:
                flagged.append({
                    "sig": sig, "metric": metric,
                    "baseline": base_v, "fresh": fresh_v,
                    "band": b, "direction": direction,
                    "ratio": round(fresh_v / base_v, 3)
                    if base_v else None,
                })
    return checked, flagged, missing


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="perf-regression sentinel over banked baselines")
    ap.add_argument("--mode", choices=["serving", "bench"],
                    default="serving")
    ap.add_argument("--fresh", required=True,
                    help="comma-separated files of one-JSON-line rows")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default for serving: docs/"
                         "perf_baseline_cpu.json; bench mode has no "
                         "default)")
    ap.add_argument("--band", type=float, default=DEFAULT_BAND,
                    help="default noise band (ratio, default 4.0)")
    ap.add_argument("--update-baseline", default=None,
                    help="write the fresh rows as a new baseline file "
                         "and exit")
    ap.add_argument("--advise", action="store_true",
                    help="report drift but always exit 0")
    ap.add_argument("--all-metrics", action="store_true",
                    help="serving mode: gate every known metric, not "
                         "just the CPU-harness set")
    args = ap.parse_args(argv)

    fresh_recs = _load_lines(p for p in args.fresh.split(",") if p)
    if args.mode == "serving":
        fresh = serving_rows(fresh_recs)
        default_baseline = os.path.join(REPO, "docs",
                                        "perf_baseline_cpu.json")
        gated = None if args.all_metrics else SERVING_GATED_METRICS
    else:
        fresh = bench_rows(fresh_recs)
        default_baseline = None
        gated = None
        if not (args.baseline or args.update_baseline):
            ap.error("--mode bench needs --baseline: no bench rows "
                     "file is committed")

    if args.update_baseline:
        doc = {"mode": args.mode, "band": args.band, "rows": fresh}
        with open(args.update_baseline, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        _log("baseline written: %s (%d rows)"
             % (args.update_baseline, len(fresh)))
        print(json.dumps({"metric": "perf_sentinel", "value": 0,
                          "unit": "regressions", "ok": True,
                          "updated": args.update_baseline,
                          "rows": len(fresh)}))
        return 0

    baseline_path = args.baseline or default_baseline
    bands = {}
    with open(baseline_path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("rows"), dict):
        baseline = doc["rows"]
        bands = doc.get("bands") or {}
        if doc.get("band"):
            args.band = float(doc["band"]) \
                if args.band == DEFAULT_BAND else args.band
    else:
        # a raw bench rows file (bench.py's chiprun_out/ shape)
        baseline = bench_rows([doc]) if args.mode == "bench" \
            else serving_rows([doc])

    checked, flagged, missing = compare(
        fresh, baseline, band=args.band, bands=bands,
        gated_metrics=gated)
    for fl in flagged:
        _log("REGRESSION %(metric)s @ %(sig)s: baseline %(baseline)s"
             " -> fresh %(fresh)s (band %(band)sx)" % fl)
    if missing:
        _log("%d baseline rows had no fresh counterpart (not gated)"
             % len(missing))
    ok = not flagged
    print(json.dumps({
        "metric": "perf_sentinel", "value": len(flagged),
        "unit": "regressions", "ok": ok, "mode": args.mode,
        "checked": checked, "flagged": flagged,
        "missing_rows": len(missing), "band": args.band,
        "baseline": os.path.relpath(baseline_path, REPO),
    }))
    return 0 if (ok or args.advise) else 1


if __name__ == "__main__":
    sys.exit(main())
