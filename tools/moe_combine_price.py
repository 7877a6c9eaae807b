"""One layer's combine by token alone, at the three expert cells'
shapes and five held shares: XLA's by-pair gathers
(llm_ops._tokens_of_rows) against pt_moe_combine, bf16 rows with a
gate (the forward's combine) and float32 rows without (d x), gates
that are no powers of two.

    chiprun -- python tools/moe_combine_price.py [ling3 xing4 dsv2]
    python tools/moe_combine_price.py --tiny      (a CPU, interpret mode,
                                                   gates powers of two)

A line a case: the held share, `diff_*`, the largest difference of any
element between the two forms' float32 sums (PERF.md, PR 43: 0.0 on
the chip in every case, the same float32 products added in the same
order), and the device's milliseconds a call with the op's cast to
bf16, the median of five calls' module events in a profile (the host's
clock round a call reads the dispatch, not the kernel).  Exits 1 where
an element differs.  The rows also go to
chiprun_out/moe_combine_price.json.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops import pallas_moe_combine as pc  # noqa: E402
from paddle_tpu.ops.llm_ops import (  # noqa: E402
    _group_layout, _tokens_of_rows)

# tokens, pairs a token, width; 8 experts held in row tiles of 256
CELLS = {"ling3": (4096, 8, 2560), "xing4": (4096, 4, 3584),
         "dsv2": (8192, 6, 2048)}
HELD = 8
# experts a uniform router chooses among: 1.6% of the pairs held to all
EXPERTS = (512, 64, 32, 13, 8)


def device_ms(fn, *args):
    """(the device's ms a call of jitted fn, None off the chip; its
    result)."""
    fn.__name__ = "priced"
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    trace = tempfile.mkdtemp()
    with jax.profiler.trace(trace):
        for _ in range(5):
            out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for path in glob.glob(trace + "/plugins/profile/*/*.xplane.pb"):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name == "/device:TPU:0":
                times += [e.duration_ns for line in plane.lines
                          if line.name == "XLA Modules"
                          for e in line.events if "priced" in e.name]
    return (sorted(times)[len(times) // 2] / 1e6 if times else None), out


def price(cell, shape, tm, interpret):
    n, k, c = shape
    for experts in EXPERTS:
        rng = np.random.default_rng(experts)
        idx = rng.random((n, experts)).argsort(1)[:, :k].astype(np.int32)
        lay = jax.jit(lambda i: _group_layout(i, tuple(range(HELD)), tm))(
            jnp.asarray(idx))
        m = lay["row_pair"].shape[0]
        gate = jax.random.uniform(jax.random.key(3), (n, k), jnp.float32,
                                  0.2, 1.0)
        if interpret:   # a CPU's compiler may fuse a product into its
            gate = 2.0 ** jnp.round(jnp.log2(gate))     # add: exact ones
        plan_ms, plan = device_ms(
            lambda lay: pc.combine_plan(lay["dest"], lay["slot"], HELD, c, m),
            lay)
        row = {"cell": cell, "experts": experts, "plan_ms": plan_ms,
               "held_share": float(np.asarray(lay["mine"]).mean())}
        for name, dtype, g in (("bf16", jnp.bfloat16, gate),
                               ("f32", jnp.float32, None)):
            a = jax.random.normal(jax.random.key(1), (m, c), dtype)
            row["xla_%s_ms" % name], _ = device_ms(
                lambda lay, a, g: _tokens_of_rows(lay, a, g).astype(
                    jnp.bfloat16), lay, a, g)
            row["kernel_%s_ms" % name], _ = device_ms(
                lambda a, plan, g: pc.moe_combine_pallas(
                    a, plan, g, out_dtype=jnp.bfloat16, interpret=interpret),
                a, plan, g)
            # the float32 sums themselves, before the op's cast
            row["diff_" + name] = float(jnp.abs(
                pc.moe_combine_pallas(a, plan, g, out_dtype=jnp.float32,
                                      interpret=interpret)
                - jax.jit(_tokens_of_rows)(lay, a, g)).max())
        print(json.dumps(row), flush=True)
        yield row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*", default=list(CELLS))
    ap.add_argument("--tiny", action="store_true",
                    help="256 tokens of width 256 in interpret mode")
    args = ap.parse_args(argv)
    print(jax.devices())
    rows = [row for cell in args.cells for row in (
        price("tiny", (256, CELLS[cell][1], 256), 32, True) if args.tiny
        else price(cell, CELLS[cell], 256, False))]
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_combine_price.json", "w") as f:
        json.dump(rows, f, indent=1)
    return int(any(row["diff_bf16"] or row["diff_f32"] for row in rows))


if __name__ == "__main__":
    sys.exit(main())
