#!/usr/bin/env python
"""Batch/seq sweep over the bench workloads — finds the MFU knee on a
real chip in one command (round-3 verdict do-this #2 'sweep batch').

Usage:
  python tools/bench_sweep.py                     # default grids
  python tools/bench_sweep.py --workload transformer --batches 16,32,64
  python tools/bench_sweep.py --workload resnet --batches 64,128,256

Prints one JSON line per point and a best-point summary per workload.
Runs in ONE process on the chip and refuses any other device: a sweep
point without a chip is not a number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="all",
                    choices=["all", "transformer", "resnet", "bert"])
    ap.add_argument("--batches", default=None,
                    help="comma list overriding the default grid")
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--chain", type=int, default=20)
    args = ap.parse_args()

    import bench

    bench._assert_on_chip()
    grids = {
        "transformer": [16, 32, 64],
        "resnet": [64, 128, 256],
        "bert": [4, 8, 16],
    }
    if args.batches:
        override = [int(b) for b in args.batches.split(",")]
        for k in grids:
            grids[k] = override
    seq, chain = args.seq, args.chain

    runners = {
        "transformer": lambda b: bench.bench_transformer_train(
            batch=b, seq=seq, chain=chain),
        "resnet": lambda b: bench.bench_resnet50_train(
            batch=b, chain=chain),
        "bert": lambda b: bench.bench_bert_train(
            batch=b, seq=seq, chain=chain),
    }
    wanted = list(runners) if args.workload == "all" \
        else [args.workload]
    best = {}
    for w in wanted:
        for b in grids[w]:
            try:
                r = runners[w](b)
            except Exception as e:  # OOM at large batch ends the sweep
                print(json.dumps({"workload": w, "batch": b,
                                  "error": repr(e)[:200]}))
                break
            print(json.dumps({"workload": w, **r}))
            mfu = r.get("mfu_pct", 0.0)
            if mfu >= best.get(w, (0.0, None))[0]:
                best[w] = (mfu, b)
    for w, (mfu, b) in best.items():
        print(json.dumps({"best": w, "mfu_pct": mfu, "batch": b}))


if __name__ == "__main__":
    main()
