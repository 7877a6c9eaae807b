"""python tools/reference_controls.py --cell CELL --seeds N [N ...]
    [--variants V ...] [--variant-seeds K] [--dtype bfloat16]
    [--logits EVERY] [--out FILE]

What a cell's plain reference reads when it is made WRONG on purpose,
or computed in a lower precision: for each seed the cell's program is
built and initialised as benchmarks/kinds/train_steps.py does (the
builder, np.random.seed(seed), the startup program, pool batch 0 from
default_rng(seed)), and the reference's loss on those weights is
printed as it is, for each `variant` asked for (`--variants all`: every
one the reference lists in `VARIANTS`, as benchmarks/reference/mellum2.py
does: no_window, no_yarn, gates_not_renormalised), and wholly in
`dtype`; each beside its distance from the float32 reading, as a share
of it (the variants for the first K seeds only, where K is given), and
beside what the loop kind's own comparison says of it at the
configuration's `reference_rtol` (`correct`: |wrong - reference| <=
rtol |reference|).  These are the second reading and the controls a
configuration's `reference_rtol_why` quotes.

`--logits EVERY` (a builder that returns `logits`, a reference that has
`logits()`): the same controls on a number the loss's mean hides, the
logits of every EVERY-th token, as the root mean square of the
difference over that of the reference's; and, under `program`, what the
cell's OWN program reads there: the forward pass of the step program
(`clone(for_test=True)`: the ops AMP rewrote, no backward, no update)
on the same weights and batch, its loss and its logits against the
reference's.  Where the configuration gives `reference_logits_rms`,
the limit on that share, `logits_correct` says of the program and of
each control whether it lies inside it, and the tool exits 1 where the
PROGRAM misses either limit (the loop kind compares the loss alone:
this is where the second number is held until it can).  No training
step is run.  One JSON line a seed; `--out` keeps them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rms_share(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def _program_forward(fluid, built, batch, every):
    """(loss, logits of every `every`-th token) of the step program's
    forward pass on the weights the scope holds."""
    # compiled whole: op by op, every layer's activations of all the
    # tokens would stay alive beside the state
    test = fluid.CompiledProgram(
        fluid.default_main_program().clone(for_test=True))
    feed = {v.name: a for v, a in zip(built["feed_list"], batch)}
    loss, logits = fluid.Executor(fluid.TPUPlace()).run(
        test, feed=feed, fetch_list=[built["loss"].name,
                                     built["logits"].name],
        return_numpy=False)
    return (float(np.asarray(loss).reshape(-1)[0]),
            np.asarray(logits[:, ::every], np.float32))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="*", default=[])
    ap.add_argument("--variant-seeds", type=int)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--logits", type=int, metavar="EVERY")
    ap.add_argument("--root", default=ROOT,
                    help="the checkout whose BENCHMARK.json and "
                    "benchmarks/ are read (the tests' tiny one)")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    bench = os.path.join(args.root, "benchmarks")
    sys.path.insert(0, bench)
    import harness

    import paddle_tpu as fluid

    spec = harness._read_json(os.path.join(args.root, "BENCHMARK.json"))
    cell = harness._by_name(spec["workloads"], args.cell, "workload")
    config = harness._read_json(os.path.join(args.root, harness._by_name(
        spec["configs"], cell["config"], "config")["file"]))
    job = harness._read_json(os.path.join(
        bench, "traffic", cell["traffic"] + ".json"))

    def load(kind, name):
        return harness._load_file(os.path.join(bench, kind, name + ".py"))

    kind = load("kinds", job["kind"])
    flops = harness._load_file(os.path.join(bench, "flops.py"))
    ref = load("reference", config["reference"])
    tol = config["reference_rtol"]
    if args.variants == ["all"]:
        args.variants = list(ref.VARIANTS)
    rows = []
    for i, seed in enumerate(args.seeds):
        variants = args.variants if args.variant_seeds is None \
            or i < args.variant_seeds else []
        controls = [(args.dtype, {"dtype": args.dtype})] + [
            (v, {"variant": v}) for v in variants]
        kind._fresh_programs()
        np.random.seed(seed)
        built = load("builders", config["builder"]).build(config, job,
                                                          flops)
        fluid.Executor(fluid.TPUPlace()).run(
            fluid.default_startup_program())
        batch = built["make_batch"](np.random.default_rng(seed))
        # the program first: its logits of all tokens are the largest
        # array here, and are gone before the reference makes its own
        program = _program_forward(fluid, built, batch, args.logits) \
            if args.logits else None
        params = ref.read_params(config, kind._scope_get)
        base = ref.loss(params, batch, config)
        wrong = {n: ref.loss(params, batch, config, **kw)
                 for n, kw in controls}
        if program:
            wrong["program"] = program[0]
        row = {"seed": seed, "reference_loss": base, "rtol": tol,
               "losses": wrong,
               "rel_diff": {k: abs(v - base) / abs(base)
                            for k, v in wrong.items()},
               "signed_diff": {k: v - base for k, v in wrong.items()}}
        row["correct"] = {k: v <= tol for k, v in row["rel_diff"].items()}
        if program:
            want = np.asarray(ref.logits(params, batch, config,
                                         every=args.logits))
            row["logits_rms_share"] = {"program": _rms_share(program[1],
                                                             want)}
            for n, kw in controls:
                row["logits_rms_share"][n] = _rms_share(ref.logits(
                    params, batch, config, every=args.logits, **kw), want)
            limit = config.get("reference_logits_rms")
            if limit is not None:
                row["logits_limit"] = limit
                row["logits_correct"] = {
                    k: v <= limit
                    for k, v in row["logits_rms_share"].items()}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    missed = [r["seed"] for r in rows
              if not (r["correct"].get("program", True) and r.get(
                  "logits_correct", {}).get("program", True))]
    return 1 if missed else 0


if __name__ == "__main__":
    sys.exit(main())
