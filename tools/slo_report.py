"""Render the QPS-vs-p99-vs-SLO dashboard row from serving_load runs
as ONE parseable JSON line (ISSUE 10; the row the ROADMAP observability
item asks to bank on the next chip window).

Input: one or more serving_load one-JSON-line outputs —

    python tools/slo_report.py --inputs /tmp/a.json,/tmp/b.json
    ... | python tools/slo_report.py            # lines on stdin
    python tools/slo_report.py --run --mode overload2x --seconds 4

``--run`` invokes tools/serving_load.py as a subprocess (args after
--run pass through) and reports on its line.  This process never
touches JAX, so the child may hold the chip.

``--fleet <path>`` (ISSUE 12) additionally ingests a collector fleet
snapshot (observability/collector.py ``snapshot()`` / ``dump()``
output): the per-process burn rates roll up to ONE fleet SLO row
(mode "fleet") appended after the per-run rows — sum of per-process
(good, total) per objective, burn weighted by each process's total,
firing iff any process fires.

stdout contract (gated like every tool here): EXACTLY ONE JSON line —

    {"metric": "serving_qps_slo", "value": <goodput_qps of the
     heaviest-load row>, "unit": "req/s", "rows": [{offered_qps,
     goodput_qps, capacity_qps, p50_ms, p99_ms, deadline_ms, mode,
     slo}], "ok": <availability objective present in every row>}

progress/diagnostics go to stderr.  Exit 0 iff every row carries the
availability objective (the 5b-gate contract, applied row-wise).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _row_of(rec):
    """The dashboard row of one serving_load record: load vs latency
    vs objective, nothing else (the full record stays in the source
    file)."""
    return {
        "mode": rec.get("mode"),
        "offered_qps": rec.get("offered_qps"),
        "goodput_qps": rec.get("goodput_qps"),
        "capacity_qps": rec.get("capacity_qps"),
        "tokens_per_sec": rec.get("tokens_per_sec"),
        "p50_ms": rec.get("p50_ms"),
        "p99_ms": rec.get("p99_ms"),
        "deadline_ms": rec.get("deadline_ms"),
        "seed": rec.get("seed"),
        "slo": rec.get("slo"),
    }


def _records_from_paths(paths):
    recs = []
    for path in paths:
        with open(path) as f:
            for line in f:
                if line.strip():
                    recs.append(json.loads(line))
    return recs


def _records_from_stdin():
    return [json.loads(line) for line in sys.stdin if line.strip()]


def _fleet_row(path):
    """The fleet SLO roll-up row from a collector snapshot/dump file.
    The snapshot already carries ``slo_fleet`` (observability/
    collector.py fleet_slo()); this just reshapes it to the dashboard
    row contract."""
    with open(path) as f:
        doc = json.load(f)
    slo_fleet = doc.get("slo_fleet") or {}
    procs = doc.get("processes") or {}
    return {
        "mode": "fleet",
        "offered_qps": None, "goodput_qps": None,
        "capacity_qps": None, "tokens_per_sec": None,
        "p50_ms": None, "p99_ms": None, "deadline_ms": None,
        "seed": None,
        "slo": {name: {"attained": e.get("attained"),
                       "target": e.get("target"),
                       "burn_rate": e.get("burn_rate"),
                       "firing": e.get("firing")}
                for name, e in slo_fleet.items()},
        "processes": len(procs),
        "stale_processes": sorted(
            n for n, p in procs.items() if p.get("stale")),
    }


def _record_from_run(passthrough):
    cmd = [sys.executable,
           os.path.join(REPO, "tools", "serving_load.py")] \
        + list(passthrough)
    print("# running: %s" % " ".join(cmd), file=sys.stderr)
    out = subprocess.run(cmd, capture_output=True, text=True)
    for ln in out.stderr.splitlines():
        print(ln, file=sys.stderr)
    if out.returncode != 0:
        raise RuntimeError("serving_load exited %d" % out.returncode)
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    if len(lines) != 1:
        raise RuntimeError(
            "serving_load stdout must be one JSON line, got %d"
            % len(lines))
    return [json.loads(lines[0])]


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="QPS-vs-p99-vs-SLO row from serving_load runs")
    ap.add_argument("--inputs", default=None,
                    help="comma-separated serving_load JSON-line "
                         "files (default: read lines from stdin)")
    ap.add_argument("--run", action="store_true",
                    help="invoke tools/serving_load.py with the "
                         "remaining args and report on its line")
    ap.add_argument("--fleet", default=None,
                    help="collector fleet snapshot/dump file: roll "
                         "per-process burn rates up to one fleet SLO "
                         "row")
    args, passthrough = ap.parse_known_args(argv)

    if args.run:
        recs = _record_from_run(passthrough)
    elif args.inputs:
        recs = _records_from_paths(
            p for p in args.inputs.split(",") if p)
    else:
        recs = _records_from_stdin()
    if not recs and not args.fleet:
        print("no serving_load records given", file=sys.stderr)
        return 1

    rows = sorted((_row_of(r) for r in recs),
                  key=lambda r: (r["offered_qps"] or 0.0))
    ok = all(isinstance(r.get("slo"), dict)
             and "serving_availability" in r["slo"]
             and {"attained", "target", "burn_rate"} <= set(
                 r["slo"]["serving_availability"])
             for r in rows)
    if args.fleet:
        # the fleet roll-up rides AFTER the per-run rows (it is a
        # different aggregation level, not a heavier load point)
        rows.append(_fleet_row(args.fleet))
    headline = next((r for r in reversed(rows)
                     if r.get("goodput_qps") is not None), rows[-1])
    report = {
        "metric": "serving_qps_slo",
        "value": headline.get("goodput_qps"),
        "unit": "req/s",
        "n_rows": len(rows),
        "rows": rows,
        "ok": ok,
    }
    print(json.dumps(report))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
