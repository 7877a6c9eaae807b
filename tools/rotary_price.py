"""The rotary embedding alone at the cells' shapes: the XLA form
(reshape, flip, float32) against the kernel pt_rotary of
ops/pallas_rotary.py, each pass a program of its own as the step runs
them: the forward op on a projection [B, T, H D] as it comes, and the
grad op (jax.vjp over the op's compute with the forward's output
unused).  The tables' fusions are inside the program that is timed.

    chiprun -- python tools/rotary_price.py [--rows 64] [--entries 65536]
        [--row-tile 512] [--lane-block 1024] [--cells mellum2_q ouro]
    python tools/rotary_price.py --tiny     (a CPU, interpret mode, no times)

A line a case: the cell's call, the pass, the form, the device's
milliseconds a call (the median of five calls' module events in a
profile), the bytes the pass has to move (X read and written once, the
two float32 tables read once) and the GB/s that makes; for the kernel
the number of entries that differ from the XLA form's and the largest
|difference|, compared in float32 (on the chip, which fuses no product
into a sum, the two are the same float32 arithmetic: 0 entries).  The
rows also go to chiprun_out/rotary_price.json.  --rows / --entries /
--row-tile / --lane-block set the kernel's chunk, pass and blocks for a
sweep; the defaults are the module's.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.ops import pallas_rotary  # noqa: E402
from tools.moe_combine_price import device_ms  # noqa: E402

F32 = jnp.float32
YARN = dict(factor=4.0, original_max_position=4096, mscale=1.2)
# call -> shape [B, T, H D], heads, the op's attributes
CELLS = {
    "mellum2_q": ((1, 16384, 4096), 32, dict(pairing="halves", **YARN)),
    "mellum2_k": ((1, 16384, 512), 4, dict(pairing="halves", **YARN)),
    "ouro": ((1, 4096, 2048), 16, dict(pairing="halves", theta=1e6)),
    "lfm2_q": ((1, 8192, 2048), 32, dict(pairing="halves", theta=1e6)),
    "lfm2_k": ((1, 8192, 512), 8, dict(pairing="halves", theta=1e6)),
    "xing4_q": ((1, 4096, 6144), 32, dict(rotary_dim=64, **YARN)),
    "dsv2_q": ((2, 4096, 3072), 16, dict(rotary_dim=64, **YARN)),
}
TINY = {"halves_128": ((2, 64, 256), 2, dict(pairing="halves", **YARN)),
        "halves_64": ((2, 64, 128), 2, dict(pairing="halves")),
        "pairs_192": ((2, 64, 384), 2, dict(rotary_dim=64, **YARN))}


def passes(heads, attrs, impl):
    op = get_op_def("rotary_embedding")
    attrs = op.canonical_attrs(dict(attrs, n_head=heads, impl=impl))

    def fwd(x):
        return op.compute({"X": x}, attrs)["Out"]

    return {"fwd": fwd, "bwd": lambda g: jax.vjp(fwd, g)[1](g)[0]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--rows", type=int, default=pallas_rotary._ROWS)
    ap.add_argument("--entries", type=int, default=pallas_rotary._PASS,
                    help="entries of X a pass of the body works on")
    ap.add_argument("--row-tile", type=int,
                    default=pallas_rotary._ROW_TILES[0])
    ap.add_argument("--lane-block", type=int,
                    default=pallas_rotary._LANE_BLOCK)
    ap.add_argument("--cells", nargs="*")
    ap.add_argument("--out", default="chiprun_out/rotary_price.json")
    args = ap.parse_args(argv)
    pallas_rotary._ROWS, pallas_rotary._PASS = args.rows, args.entries
    pallas_rotary._LANE_BLOCK = args.lane_block
    pallas_rotary._ROW_TILES = tuple(
        r for r in (4096, 2048, 1024) + pallas_rotary._ROW_TILES
        if args.rows <= r <= args.row_tile)
    cells = TINY if args.tiny else CELLS
    dtype = jnp.dtype(args.dtype)
    kernel = "interpret" if args.tiny else "pallas"
    print(jax.devices(), dtype.name, "chunks of %d rows, %d entries a pass, "
          "blocks %d x %d" % (args.rows, args.entries, args.row_tile,
                              args.lane_block), flush=True)
    rows = []
    for cell in args.cells or cells:
        shape, heads, attrs = cells[cell]
        d = shape[2] // heads
        x = jax.random.normal(jax.random.key(1), shape, F32).astype(dtype)
        moved = 2 * x.size * dtype.itemsize \
            + 2 * shape[1] * pallas_rotary.table_lanes(d) * 4
        for name in ("fwd", "bwd"):
            outs = {}
            for impl in ("xla", kernel):
                ms, out = device_ms(passes(heads, attrs, impl)[name], x)
                outs[impl] = out.astype(F32)
                row = {"cell": cell, "pass": name, "impl": impl, "ms": ms,
                       "blocks": pallas_rotary.blocks(*shape[1:], d),
                       "bytes": moved, "gb_per_s": ms and moved / ms / 1e6}
                if impl != "xla":
                    diff = jnp.abs(outs[impl] - outs["xla"])
                    row["differ"] = int(jnp.sum(diff > 0))
                    row["max_abs_diff"] = float(jnp.max(diff))
                rows.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"dtype": dtype.name, "rows": args.rows,
                   "entries": args.entries,
                   "row_tile": args.row_tile, "lane_block": args.lane_block,
                   "passes": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
