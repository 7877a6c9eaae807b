"""The two halves of a hyper-connection alone at xing4_29b_train_s4k's
shape (X 1 x 4 x 4,096 x 3,584 bfloat16, Y bfloat16, the coefficients
and the parameters float32): the XLA composition against the kernels of
ops/pallas_mhc.py, each pass a program of its own as the step runs
them: the forward op, and the grad op (jax.vjp over the op's compute
with the forward's outputs unused, so a forward kernel is dropped and
the line prices the backward alone).

    chiprun -- python tools/mhc_price.py [--dtype float32] [--rows 32]
    python tools/mhc_price.py --tiny        (a CPU, interpret mode, no times)

A line a case: the pass, the form, the device's milliseconds a call
(the median of five calls' module events in a profile), the bytes the
pass has to move by ISSUE 52's count (S = X's bytes: mhc_pre forward
1.25 S, backward 3.25 S; mhc_post forward 2.25 S, backward 3.5 S) and
the GB/s that makes; then the largest |difference| of every output and
gradient between the two forms, compared in float32, beside the
largest |value|.  The rows also go to chiprun_out/mhc_price.json.
--rows / --width set the kernels' chunk (tokens x channels a pass of
the body) for a sweep; the defaults are the module's.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops import llm_ops, pallas_mhc  # noqa: E402
from tools.moe_combine_price import device_ms  # noqa: E402

F32 = jnp.float32
ATTRS = {"sinkhorn_iters": 20, "eps": 1e-6, "clamp_min": -30.0,
         "clamp_max": 30.0}
# the bytes a pass moves, in units of X's
PASS_BYTES = {"pre_fwd": 1.25, "pre_bwd": 3.25, "post_fwd": 2.25,
              "post_bwd": 3.5}


def operands(b, n, t, c, dtype, seed=0):
    """X, Y, mhc_pre's parameters at sizes that move every gate
    (pre-activations of order 1, where the model's initial values give
    0.01) and a cotangent for every output."""
    ks = jax.random.split(jax.random.key(seed), 10)
    k = 2 * n + n * n
    normal = jax.random.normal
    return {
        "x": normal(ks[0], (b, n, t, c), F32).astype(dtype),
        "y": normal(ks[1], (b, t, c), F32).astype(dtype),
        "norm_scale": 1 + 0.1 * normal(ks[2], (n * c,), F32),
        "phi": normal(ks[3], (n * c, k), F32) * (n * c) ** -0.5,
        "alpha": jnp.array([0.7, 1.1, 1.6], F32),
        "bias": 0.5 * normal(ks[4], (k,), F32),
        "d_out": normal(ks[5], (b, n, t, c), F32).astype(dtype),
        "d_u": normal(ks[6], (b, t, c), F32).astype(dtype),
        "d_post": normal(ks[7], (b, n, t), F32),
        "d_res": normal(ks[8], (b, n, n, t), F32),
    }


def passes(impl):
    """{pass: (fn, operand names)} of one form."""
    def pre(x, norm_scale, phi, alpha, bias):
        return llm_ops._mhc_pre(x, norm_scale, phi, alpha, bias, ATTRS, impl)

    def post(x, y, h_post, h_res):
        return llm_ops._mhc_post(x, y, h_post, h_res, impl)

    def grad_of(fn, n_in):
        def grads(*a):
            return jax.vjp(fn, *a[:n_in])[1](
                a[n_in] if len(a) == n_in + 1 else tuple(a[n_in:]))
        return grads

    pre_in = ("x", "norm_scale", "phi", "alpha", "bias")
    post_in = ("x", "y", "h_post", "h_res")
    return {
        "pre_fwd": (pre, pre_in),
        "pre_bwd": (grad_of(pre, 5), pre_in + ("d_u", "d_post", "d_res")),
        "post_fwd": (post, post_in),
        "post_bwd": (grad_of(post, 4), post_in + ("d_out",)),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="2 x 4 x 256 x 128 in interpret mode")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--rows", type=int, default=pallas_mhc._ROWS)
    ap.add_argument("--width", type=int, default=pallas_mhc._WIDTH)
    ap.add_argument("--out", default="chiprun_out/mhc_price.json")
    args = ap.parse_args(argv)
    pallas_mhc._ROWS, pallas_mhc._WIDTH = args.rows, args.width
    shape = (2, 4, 256, 128) if args.tiny else (1, 4, 4096, 3584)
    dtype = jnp.dtype(args.dtype)
    print(jax.devices(), shape, dtype.name, "chunk %d x %d"
          % (args.rows, args.width), flush=True)
    ops = operands(*shape, dtype)
    _, (_, ops["h_post"], ops["h_res"]) = device_ms(
        passes("xla")["pre_fwd"][0],
        *[ops[k] for k in passes("xla")["pre_fwd"][1]])
    stream_bytes = ops["x"].size * dtype.itemsize
    kernel = "interpret" if args.tiny else "pallas"
    rows = []
    for name, units in PASS_BYTES.items():
        outs = {}
        for impl in ("xla", kernel):
            fn, names = passes(impl)[name]
            ms, out = device_ms(fn, *[ops[k] for k in names])
            outs[impl] = jax.tree_util.tree_leaves(out)
            row = {"pass": name, "impl": impl, "ms": ms,
                   "bytes": units * stream_bytes,
                   "gb_per_s": ms and units * stream_bytes / ms / 1e6}
            if impl != "xla":
                row["max_abs_diff"] = [
                    float(jnp.max(jnp.abs(a.astype(F32) - r.astype(F32))))
                    for a, r in zip(outs[impl], outs["xla"])]
                row["max_abs"] = [float(jnp.max(jnp.abs(r.astype(F32))))
                                  for r in outs["xla"]]
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"shape": shape, "dtype": dtype.name, "rows": args.rows,
                   "width": args.width, "passes": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
