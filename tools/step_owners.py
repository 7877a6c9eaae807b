"""python tools/step_owners.py --workload CELL [--steps 8] [--seed N]
    [--sorted-key total|calls|ave] [--out FILE]

Where a cell's step spends its DEVICE time, by pass and by the Fluid op
that owns each instruction: what a `perf_opt` builder runs first.

    chiprun -- python tools/step_owners.py --workload lfm2_24b_train_s8k

The cell's program is built and initialised as
benchmarks/kinds/train_steps.py does (the builder, np.random.seed(seed),
the startup program, batches from default_rng(seed)); three warm steps
compile or load the step, then `--steps` steps run under jax's profiler
with the Python tracer off (as benchmarks/observe.py:Profiler sets it)
and `fluid.profiler.device_op_table` joins the first device's `XLA Ops`
events to `CompiledProgram.step_text`'s `op_name` metadata
(paddle_tpu/observability/step_owners.py).  Printed: the table, ms a
step (`Event  Calls  Total(ms)  Ave(ms)  Share`: a pass, then
`role.type [scope]` within it, what has no owner under the compiler's
own names), then one JSON line with the step's device time, the share
of it under a row with an owner and the time of each pass; `--out`
keeps every row.  No reference, no window, no metric: the benchmark's
numbers come from benchmarks/run.py.  The process keys jax's persistent
compile cache by the metadata too (a cached executable keeps the
`op_name`s of whoever compiled it), so its first run of a cell on a
machine compiles the step anew.  Exits 2 without a TPU, like the
benchmark; `--platform cpu` is for the rehearsal test, whose trace has
no device plane to read.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WARM_STEPS = 3


def build_cell(root, workload, seed):
    """(fluid, exe, built, batches): the cell's program through the
    benchmark's own builder, its startup program run."""
    bench = os.path.join(root, "benchmarks")
    sys.path.insert(0, bench)
    import harness

    import paddle_tpu as fluid

    spec = harness._read_json(os.path.join(root, "BENCHMARK.json"))
    cell = harness._by_name(spec["workloads"], workload, "workload")
    config = harness._read_json(os.path.join(root, harness._by_name(
        spec["configs"], cell["config"], "config")["file"]))
    job = harness._read_json(os.path.join(
        bench, "traffic", cell["traffic"] + ".json"))

    def load(kind, name):
        return harness._load_file(os.path.join(bench, kind, name + ".py"))

    load("kinds", job["kind"])._fresh_programs()
    np.random.seed(seed)
    built = load("builders", config["builder"]).build(
        config, job, harness._load_file(os.path.join(bench, "flops.py")))
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.default_rng(seed)
    names = [v.name for v in built["feed_list"]]
    batches = [dict(zip(names, built["make_batch"](rng))) for _ in range(4)]
    return fluid, exe, built, batches


def summary(rows, steps):
    """The JSON line: ms a step in all, by pass, and the share owned."""
    whole = sum(r[5] for r in rows) or 1
    owned = sum(r[5] for r in rows if r[1] != "-")
    by_pass = {}
    for r in rows:
        by_pass[r[0]] = by_pass.get(r[0], 0) + r[5]
    return {"steps": steps, "device_ms_per_step": whole / steps / 1e6,
            "owned_pct": 100.0 * owned / whole,
            "pass_ms_per_step": {k: v / steps / 1e6
                                 for k, v in by_pass.items()}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=2147483951)
    ap.add_argument("--sorted-key", default="total",
                    choices=("total", "calls", "ave"))
    ap.add_argument("--root", default=ROOT,
                    help="the checkout whose BENCHMARK.json and "
                    "benchmarks/ are read (the tests' tiny one)")
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    import jax
    from jax.profiler import ProfileOptions

    if jax.devices()[0].platform != args.platform:
        print("tools/step_owners.py: jax.devices()[0] is %s; the table "
              "is device time of the %s or nothing"
              % (jax.devices()[0].platform, args.platform),
              file=sys.stderr)
        return 2
    import paddle_tpu

    # jax's persistent cache leaves metadata out of its key: an
    # executable it loads carries the `op_name`s of the process that
    # compiled it, which may be a tree from before the owner scope.
    # With the metadata in the key this process compiles, once, the
    # text it reads
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    paddle_tpu.enable_compile_cache()
    fluid, exe, built, batches = build_cell(args.root, args.workload,
                                            args.seed)
    compiled, loss = built["compiled"], built["loss"]

    def step(i):
        out, = exe.run(compiled, feed=batches[i % len(batches)],
                       fetch_list=[loss])
        return float(np.asarray(out).reshape(-1)[0])

    for i in range(WARM_STEPS):
        step(i)
    logdir = os.path.join(args.root, "benchmarks", "out",
                          "_owners_" + args.workload)
    shutil.rmtree(logdir, ignore_errors=True)
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        losses = [step(WARM_STEPS + i) for i in range(args.steps)]
    finally:
        jax.profiler.stop_trace()
    try:
        rows, steps = fluid.profiler.device_op_table(
            logdir, compiled, batches[0], sorted_key=args.sorted_key)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    line = dict(summary(rows, steps), workload=args.workload,
                seed=args.seed, last_loss=losses[-1],
                device=jax.devices()[0].device_kind)
    print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(dict(line, rows=rows), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
