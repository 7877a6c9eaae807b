"""Alternating parent/change runs of benchmark cells, in ONE chip call.

    git archive <parent> | tar -x -C _parent        # _parent/ is ignored
    chiprun --timeout 3000 -- python tools/bench_pairs.py \\
        --out chiprun_out/pairs.json tfm_base_train_s8k:0:8 \\
        tfm_base_train_s8k:1:1

Each positional is `cell:trace:pairs`.  A pair is the cell run once
from the parent's checkout and once from this one with the same seed,
in the order parent, change, change, parent, ... (the two sides of a
pair share a seed, every pair has its own), every run a process of its
own: one process holds the chip at a time, and this one never touches
jax.  `--warm` first makes one throw-away run a side of each cell, so
that every counted run finds its compile cache.

A run is what `python3 benchmarks/run.py` does (the checkout's own
`benchmarks/harness.run_cell`), and then the process writes what
run.py cannot: the `run` records of the program's step record
(observability/step_record.py), so that a slow run can be laid beside
its host phases (`returned - committed` is the wait for the loss).
Written to --out after every run: side, cell, seed, trace, exit code,
wall seconds, the result line, the harness's `correctness`, `setup` and
`trace` lines and per-step times, the step record's phases, ms, and the
program's stat rings (observability/step_stats.py: a row a step of what
the compiled step said of itself; the last rows are the window's, or
the traced stretch's), so that a step's time can be laid beside the
live row tiles its expert layers held.
"""

import time

_CLOCK_START = time.perf_counter()   # as benchmarks/run.py: setup_s

import argparse
import io
import json
import os
import subprocess
import sys

PHASES = (("prepare", "enter", "conformed"),
          ("enqueue", "conformed", "dispatched"),
          ("commit", "dispatched", "committed"),
          ("fetch", "committed", "returned"),
          ("teardown", "returned", "done"))


def _child(checkout, cell, seed, trace, seconds, out):
    """One run, in the checkout it measures; never returns a chip."""
    os.chdir(checkout)
    sys.path.insert(0, os.path.join(checkout, "benchmarks"))
    import harness

    lines = io.StringIO()
    result = harness.run_cell(checkout, cell, seed, seconds, trace,
                              clock_start=_CLOCK_START, out=lines)
    events = [json.loads(x) for x in lines.getvalue().splitlines()[:-1]]
    record = {e["event"]: e for e in events
              if e.get("event") in ("correctness", "setup", "trace")}
    path = os.path.join(checkout, "benchmarks", "out",
                        "%s.seed%d.trace%d.json" % (cell, seed, trace))
    if os.path.exists(path):
        with open(path) as f:
            kept = json.load(f)
        record.update(steps=kept.get("steps"), losses=kept.get("losses"))
    try:        # a parent from before the step record has none
        from paddle_tpu.observability import step_record

        runs = [r for r in step_record.records("run") if r.get("fetched")]
    except ImportError:
        runs = []
    try:        # a parent from before the stat rings has none
        from paddle_tpu.observability import step_stats

        record["step_stats"] = {
            name: {"columns": list(s["columns"]),
                   "steps": s["steps"].tolist(), "rows": s["rows"].tolist()}
            for name, s in step_stats.read().items()}
    except ImportError:
        pass
    record["enter_s"] = [r["enter"] / 1e9 for r in runs if "enter" in r]
    for name, a, b in PHASES:
        record[name + "_ms"] = [(r[b] - r[a]) / 1e6 for r in runs
                                if a in r and b in r]
    with open(out, "w") as f:
        json.dump({"result": result, "record": record}, f)


def _run(side, checkout, cell, seed, trace, seconds, tmp):
    if os.path.exists(tmp):
        os.remove(tmp)
    t = time.time()
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", checkout,
         cell, str(seed), str(trace), str(seconds), tmp],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    row = {"side": side, "cell": cell, "seed": seed, "trace": trace,
           "rc": p.returncode, "wall_s": round(time.time() - t, 1),
           "result": None, "record": None,
           "stderr_tail": "" if p.returncode == 0 else p.stderr[-2000:]}
    if os.path.exists(tmp):
        with open(tmp) as f:
            row.update(json.load(f))
    return row


def main(argv=None):
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        checkout, cell, seed, trace, seconds, out = sys.argv[2:8]
        return _child(os.path.abspath(checkout), cell, int(seed),
                      int(trace), float(seconds), out)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("plan", nargs="+", metavar="cell:trace:pairs")
    ap.add_argument("--parent", default="_parent")
    ap.add_argument("--out", default="chiprun_out/pairs.json")
    ap.add_argument("--seed0", type=int, default=2147483901)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--warm", action="store_true")
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sides = {"p": os.path.abspath(args.parent), "c": here}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    tmp = os.path.abspath(args.out) + ".run"
    rows, seed = [], args.seed0

    def one(side, cell, seed, trace, keep=True):
        row = _run(side, sides[side], cell, seed, trace, args.seconds, tmp)
        metrics = (row["result"] or {}).get("metrics", {})
        print(json.dumps({
            "side": side, "cell": cell, "seed": seed, "trace": trace,
            "rc": row["rc"], "wall_s": row["wall_s"], "counted": keep,
            "correct": (row["result"] or {}).get("correct"),
            **{k: v["value"] for k, v in metrics.items()}}), flush=True)
        if keep:
            rows.append(row)
            with open(args.out, "w") as f:
                json.dump(rows, f)

    warmed = set()
    for spec in args.plan:
        cell, trace, pairs = spec.split(":")
        if args.warm and cell not in warmed:
            warmed.add(cell)
            for side in "pc":
                one(side, cell, seed, int(trace), keep=False)
            seed += 1
        for i in range(int(pairs)):
            for side in ("pc", "cp")[i % 2]:
                one(side, cell, seed, int(trace))
            seed += 1
    if os.path.exists(tmp):
        os.remove(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
