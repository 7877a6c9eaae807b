#!/usr/bin/env python
"""Per-op micro-benchmark (reference operators/benchmark/op_tester.cc:1
— a standalone tool timing one registered op from a config of shapes/
dtypes/attrs, so per-op perf regressions surface before they show up in
a model bench).

Usage:
  # one op from the CLI
  python tools/op_bench.py --op conv2d \
      --input "Input=float32:8,64,56,56" --input "Filter=float32:64,64,3,3" \
      --attr "strides=[1,1]" --attr "paddings=[1,1]" --repeat 50

  # the committed hot-op suite (+ optional regression gate)
  python tools/op_bench.py --suite tools/op_bench_suite.json
  python tools/op_bench.py --suite tools/op_bench_suite.json \
      --baseline tools/op_bench_baseline_cpu.json --tolerance 2.0

Prints one JSON line per spec: {"op", "ms", "repeat", "shapes",
"device"}.  With --baseline, exits 1 if any op is slower than
tolerance x its recorded ms (on a comparable device).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _parse_input(spec):
    """'Name=dtype:d0,d1,...' -> (name, dtype, shape)."""
    name, rest = spec.split("=", 1)
    dtype, _, shape_s = rest.partition(":")
    shape = tuple(int(d) for d in shape_s.split(",") if d)
    return name.strip(), dtype.strip(), shape


def _parse_attr(spec):
    name, _, val = spec.partition("=")
    return name.strip(), json.loads(val)


def _make_value(rng, dtype, shape):
    import numpy as np

    if dtype.startswith("int") or dtype.startswith("uint"):
        return rng.randint(0, 8, size=shape).astype(dtype)
    if dtype == "bool":
        return rng.rand(*shape) > 0.5
    return rng.rand(*shape).astype(dtype)


def bench_op(op_type, inputs, attrs=None, repeat=30, warmup=3, seed=0,
             detail=False):
    """Time `repeat` jitted runs of one registered op.  inputs:
    {slot: (dtype, shape)} or {slot: ndarray}.  Returns ms/run, or
    (ms, meta) with detail=True — meta["timing"] names the path that
    produced the number ("difference", "upper_bound_fallback",
    "host_loop", "host_dispatch"), so a dispatch-inflated fallback
    can never masquerade as a clean difference measurement."""
    import jax
    import numpy as np

    import paddle_tpu  # noqa: F401  (registers ops)
    from paddle_tpu.core.registry import get_op_def

    d = get_op_def(op_type)
    rng = np.random.RandomState(seed)
    ins = {}
    for slot, v in inputs.items():
        if isinstance(v, tuple):
            dtype, shape = v
            v = _make_value(rng, dtype, shape)
        ins[slot] = jax.device_put(v)
    cattrs = d.canonical_attrs(attrs or {})

    # A host-side repeat loop times the dispatch, not the op, whenever
    # per-dispatch latency is of the op's own order, and a timing that
    # does not end in a fetched value can measure the enqueue.  On TPU
    # the repeat loop therefore runs ON DEVICE (lax.fori_loop, one
    # dispatch): a scalar from each iteration's output folds into the
    # next iteration's input, making the loop body un-hoistable, and
    # the carried scalar is fetched to host as the fence.  Timing n
    # and 2n iterations and taking the difference cancels the
    # remaining constant dispatch+fence cost.
    # On CPU the host loop stays: XLA:CPU runs while-loop bodies
    # single-threaded, so a looped conv2d times ~20x slower than the
    # standalone op the committed baseline measured (the gate tripped
    # exactly this way); local dispatch is cheap and block_until_ready
    # is a real fence there.
    import jax.numpy as jnp
    from jax import lax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu" or \
        "tpu" in str(getattr(dev, "device_kind", "")).lower()

    def _ret(ms, timing):
        return (ms, {"timing": timing}) if detail else ms

    if ins and not on_tpu:
        fn1 = jax.jit(lambda i: d.compute(i, cattrs))
        out = fn1(ins)
        jax.block_until_ready(out)  # compile
        for _ in range(warmup):
            out = fn1(ins)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(repeat):
            out = fn1(ins)
        jax.block_until_ready(out)
        return _ret((time.perf_counter() - t0) / repeat * 1e3,
                    "host_loop")

    if not ins:
        # zero-input generators (gaussian_random, fill_constant, ...)
        # have nothing to thread a loop-carried dependency through, so
        # an on-device loop would be hoistable; fall back to host
        # dispatch with a scalar-fetch fence and accept the dispatch
        # floor (these ops are gated on relative regression only)
        fn0 = jax.jit(lambda: d.compute({}, cattrs))

        def fence():
            leaf = jax.tree_util.tree_leaves(fn0())[0]
            return float(np.asarray(
                leaf.reshape(-1)[0].astype(jnp.float32)))

        fence()
        for _ in range(warmup):
            fence()
        t0 = time.perf_counter()
        for _ in range(repeat):
            fence()
        return _ret((time.perf_counter() - t0) / repeat * 1e3,
                    "host_dispatch")

    slot0 = next((s for s in ins
                  if ins[s].dtype != jnp.bool_), next(iter(ins)))

    def body(_, t):
        j = dict(ins)
        # value-preserving for floats (t ~ 1e-38 * out[0]); for int
        # slots the cast truncates to 0 but the dependency remains
        if j[slot0].dtype == jnp.bool_:
            j[slot0] = jnp.logical_xor(j[slot0], t != t)  # always False
        else:
            j[slot0] = j[slot0] + t.astype(j[slot0].dtype)
        out = d.compute(j, cattrs)
        leaf = jax.tree_util.tree_leaves(out)[0]
        return leaf.reshape(-1)[0].astype(jnp.float32) * 1e-38

    def run_n(n):
        return lax.fori_loop(0, n, body, jnp.float32(0.0))

    fn = jax.jit(run_n, static_argnums=0)

    def timed(n):
        """min-of-3 timed runs at trip count n: a single scheduler
        hiccup in one sample must not flip t_2n - t_n negative
        and silently demote the measurement to the dispatch-inflated
        upper bound (ADVICE r5)."""
        float(np.asarray(fn(n)))  # compile + warm this trip count
        for _ in range(warmup):
            fn(n)
        float(np.asarray(fn(n)))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(np.asarray(fn(n)))
            best = min(best, time.perf_counter() - t0)
        return best

    t_n, t_2n = timed(repeat), timed(2 * repeat)
    per_iter = max(t_2n - t_n, 0.0) / repeat
    if per_iter == 0.0:
        # below difference-timing resolution (overhead jitter >= op
        # cost): report the 2n-run upper bound instead of a flat 0 so
        # downstream ratio gates never divide by zero — and SAY so in
        # the returned meta, because this number includes the
        # dispatch+fence constant the difference form exists to cancel
        return _ret(t_2n / (2 * repeat) * 1e3, "upper_bound_fallback")
    return _ret(per_iter * 1e3, "difference")


def run_spec(spec, repeat_override=None):
    import jax

    inputs = {}
    for slot, v in spec["inputs"].items():
        inputs[slot] = (v["dtype"], tuple(v["shape"]))
    ms, meta = bench_op(spec["op"], inputs, spec.get("attrs") or {},
                        repeat=repeat_override or spec.get("repeat",
                                                           30),
                        detail=True)
    return {
        "op": spec["op"],
        "ms": round(ms, 4),
        "repeat": repeat_override or spec.get("repeat", 30),
        "shapes": {k: list(v["shape"])
                   for k, v in spec["inputs"].items()},
        "device": jax.devices()[0].device_kind,
        "timing": meta["timing"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--op")
    ap.add_argument("--input", action="append", default=[],
                    help="Name=dtype:d0,d1,...")
    ap.add_argument("--attr", action="append", default=[],
                    help="name=json_value")
    ap.add_argument("--repeat", type=int, default=None)
    ap.add_argument("--suite", help="JSON file with a list of specs")
    ap.add_argument("--baseline",
                    help="JSON file of prior results to gate against")
    ap.add_argument("--tolerance", type=float, default=2.0,
                    help="fail if ms > tolerance * baseline ms")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (hermetic CI runs)")
    args = ap.parse_args(argv)

    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    results = []
    if args.suite:
        specs = json.load(open(args.suite))
        for spec in specs:
            r = run_spec(spec, args.repeat)
            results.append(r)
            print(json.dumps(r))
    elif args.op:
        inputs = dict()
        for s in args.input:
            name, dtype, shape = _parse_input(s)
            inputs[name] = (dtype, shape)
        attrs = dict(_parse_attr(a) for a in args.attr)
        ms, meta = bench_op(args.op, inputs, attrs,
                            repeat=args.repeat or 30, detail=True)
        import jax

        r = {"op": args.op, "ms": round(ms, 4),
             "repeat": args.repeat or 30,
             "shapes": {k: list(v[1]) for k, v in inputs.items()},
             "device": jax.devices()[0].device_kind,
             "timing": meta["timing"]}
        results.append(r)
        print(json.dumps(r))
    else:
        ap.error("need --op or --suite")

    if args.baseline:
        base = {b["op"]: b for b in json.load(open(args.baseline))}
        failures = []
        for r in results:
            b = base.get(r["op"])
            if b is None:
                continue
            if b.get("device") != r["device"]:
                continue  # cross-device ms comparisons are meaningless
            if r["ms"] > args.tolerance * b["ms"]:
                failures.append(
                    f"{r['op']}: {r['ms']:.3f} ms vs baseline "
                    f"{b['ms']:.3f} ms (> {args.tolerance}x)")
        if failures:
            print("REGRESSIONS:\n" + "\n".join(failures),
                  file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
