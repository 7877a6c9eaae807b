"""The flash kernels of one attention layer of mellum2_12b_train_s16k
alone (1 x 16,384 tokens, 32 query heads on 4 KV heads of 128,
token-major bfloat16): the window layer's calls (a window of 1,024 on
the band grid) at each block choice, beside the full layer's at the
default block, the forward (`_flash_attention_fwd`) and the backward on
the saved residuals (`_flash_attention_bwd`) each a program of its own
as the step runs them.

    chiprun -- python tools/flash_window_price.py [--blocks 256x256 ...]
    python tools/flash_window_price.py --tiny    (a CPU, interpret mode)

A line a case: the device's milliseconds a call (the median of five
calls' module events in a profile: the kernel with the XLA ops round
it, the backward's `delta` product and the sum of dk and dv over a KV
head's group; `*_kernel_ms`: the Mosaic call alone), the work's least
time on a v5e by its operations (builders/mellum2_flops.py: the allowed pairs, backward
twice the forward) and the share that is; for a window case the grid's
steps a q block; then the largest |difference| of out, dq, dk and dv
from plain attention with the same window at a length plain attention
can hold (`--check`, 2,048 tokens).  The rows also go to
chiprun_out/flash_window_price.json.  The block `_default_block` gives a
windowed call is pinned by this sweep (its docstring quotes it).
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

from paddle_tpu.ops import pallas_kernels as pk  # noqa: E402

HEADS, KV_HEADS, D = 32, 4, 128
V5E_FLOPS = 197e12


def device_ms(fn, *args):
    """((ms a call of jitted fn, ms of its Mosaic calls named pt_flash*),
    result): medians of five calls' events on the device's lines; (None,
    None) off the chip."""
    fn.__name__ = "priced"
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    trace = tempfile.mkdtemp()
    with jax.profiler.trace(trace):
        for _ in range(5):
            out = fn(*args)
        jax.block_until_ready(out)
    module, kernel = [], []
    for path in glob.glob(trace + "/plugins/profile/*/*.xplane.pb"):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/device:TPU:0":
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    module += [e.duration_ns for e in line.events
                               if "priced" in e.name]
                if line.name == "XLA Ops":
                    kernel += [e.duration_ns for e in line.events
                               if "pt_flash" in e.name]

    def median(ns):
        return sorted(ns)[len(ns) // 2] / 1e6 if ns else None

    return (median(module), median(kernel)), out


def operands(t, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    shape = lambda h: (1, t, h * D)       # noqa: E731
    return tuple(jax.random.normal(k, shape(h), jnp.float32)
                 .astype(jnp.bfloat16)
                 for k, h in zip(ks, (HEADS, KV_HEADS, KV_HEADS, HEADS)))


def pairs(t, window):
    w = min(window, t) if window else t
    return w * (w + 1) // 2 + (t - w) * w


def price(t, window, bq, bk, impl):
    q, k, v, g = operands(t)
    call = dict(causal=True, heads=HEADS, impl=impl, window=window,
                block_q=bq, block_k=bk)
    (fwd_ms, fwd_kernel), (out, lse) = device_ms(
        lambda q, k, v: pk._flash_attention_fwd(q, k, v, **call), q, k, v)
    (bwd_ms, bwd_kernel), _ = device_ms(
        lambda q, k, v, out, lse, g: pk._flash_attention_bwd(
            q, k, v, out, lse, g, **call), q, k, v, out, lse, g)
    _, kw = pk._call_args(q, k, **call)
    row = {"tokens": t, "window": window, "block_q": kw["block_q"],
           "block_k": kw["block_k"], "fwd_ms": fwd_ms, "bwd_ms": bwd_ms,
           "fwd_kernel_ms": fwd_kernel, "bwd_kernel_ms": bwd_kernel}
    if window:
        nq, nk = -(-t // kw["block_q"]), -(-t // kw["block_k"])
        diagonal = pk._Diagonal(kw["block_q"], kw["block_k"], 0, window)
        row["kv_steps"] = diagonal.band_steps(nq, nk, "kv")
        row["q_steps"] = diagonal.band_steps(nq, nk, "q")
    least = 4.0 * D * HEADS * pairs(t, window) / V5E_FLOPS * 1e3
    row["least_fwd_ms"], row["least_bwd_ms"] = least, 2 * least
    if fwd_ms and bwd_ms:
        row["roofline_pct"] = 100 * 3 * least / (fwd_ms + bwd_ms)
    return row


def check(t, window, bq, bk, impl):
    """Largest |difference| of out, dq, dk, dv from plain attention
    with the same window, over the largest |value|."""
    q, k, v, g = operands(t, seed=1)
    call = dict(causal=True, heads=HEADS, window=window)

    def both(impl, **blocks):
        def f(q, k, v):
            out = pk.flash_attention(q, k, v, impl=impl, **call, **blocks)
            return (out.astype(jnp.float32)
                    * g.astype(jnp.float32)).sum(), out
        grads, out = jax.jit(jax.grad(f, argnums=(0, 1, 2),
                                      has_aux=True))(q, k, v)
        return (out, *grads)

    got = both(impl, block_q=bq, block_k=bk)
    want = both("xla")
    return {n: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))
                     / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for n, a, b in zip(("out", "dq", "dk", "dv"), got, want)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="256 tokens under a window of 64, interpret mode")
    ap.add_argument("--blocks", nargs="*",
                    default=["256x256", "512x512", "512x256", "256x512",
                             "1024x1024", "1024x512", "512x1024",
                             "2048x1024"])
    ap.add_argument("--check", type=int, default=2048)
    ap.add_argument("--out", default="chiprun_out/flash_window_price.json")
    args = ap.parse_args(argv)
    t, window, impl = (256, 64, "interpret") if args.tiny \
        else (16384, 1024, "pallas")
    blocks = [tuple(int(x) for x in b.split("x")) for b in args.blocks]
    if args.tiny:
        blocks, args.check = [(32, 32), (64, 32)], 256
    print(jax.devices(), flush=True)
    rows = []
    for bq, bk in [(None, None)] + blocks:
        row = price(t, window, bq, bk, impl)
        row["default"] = bq is None
        row["max_rel_diff"] = check(args.check, window, bq, bk, impl)
        rows.append(row)
        print(json.dumps(row), flush=True)
    full = price(t, 0, None, None, impl)
    rows.append(full)
    print(json.dumps(full), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
