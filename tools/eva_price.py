"""The kernels of one EVA attention layer of evabyte_6_5b_train_s8k
alone (1 x 8,192 bytes, 32 heads of 128, windows of 2,048, chunks of
16, token-major bfloat16), each a program of its own as the step runs
them, over their block choices:

  window     the window part: causal flash over the free reshape
             [4, 2048, 4096], forward and backward on the saved
             residuals, at blocks 1024 x 1024 (the length's default),
             512 x 512, 2048 x 2048, 1024 x 512, 512 x 1024
  staircase  pt_eva_chunk_fwd / pt_eva_chunk_bwd over the 384 chunk
             keys of windows 0-2, begun from a window part's (out,
             lse) and dq, q blocks 512, 1024, 2048 by key blocks 128,
             64
  pool       pt_eva_pool_fwd / pt_eva_pool_bwd at 256, 512, 1024, 2048
             tokens a grid step
  layer      the aggregation whole (`_aggregate_fwd`, `_aggregate_bwd`
             at the defaults) beside FULL causal flash attention at
             1 x 8,192 x 32 heads of 128: ISSUE 55's ratio

    chiprun -- python tools/eva_price.py
    python tools/eva_price.py --tiny        (a CPU, interpret mode)

A line a case: the device's milliseconds a call (the median of five
calls' module events in a profile) and of its Mosaic calls alone, by
name; then (`--check`, 4 heads over 4,096 bytes, where the XLA form's
score arrays fit) the largest |difference| of out, dq, dk, dv, dmu,
dphi between the kernels and the XLA form ON THE CHIP, over the largest
|value|.  The rows also go to chiprun_out/eva_price.json.
"""

import argparse
import collections
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops import pallas_eva as pe  # noqa: E402
from paddle_tpu.ops import pallas_kernels as pk  # noqa: E402

D = 128


def device_ms(fn, *args):
    """({"module": ms a call of jitted fn, "<kernel>": ms of its Mosaic
    calls of that name}, result): medians of five calls' events on the
    device's lines; no numbers off the chip."""
    fn.__name__ = "priced"
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    trace = tempfile.mkdtemp()
    with jax.profiler.trace(trace):
        for _ in range(5):
            out = fn(*args)
        jax.block_until_ready(out)
    found = collections.defaultdict(list)
    for path in glob.glob(trace + "/plugins/profile/*/*.xplane.pb"):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/device:TPU:0":
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    found["module"] += [e.duration_ns for e in line.events
                                        if "priced" in e.name]
                if line.name == "XLA Ops":
                    for e in line.events:
                        if e.name.lstrip("%").startswith("pt_"):
                            name = e.name.lstrip("%").split(".")[0] \
                                .split(" ")[0]
                            found[name].append(e.duration_ns)
    # a call may hold a kernel several times: the sum a call
    return {k: sum(sorted(v)) / 5 / 1e6 if k != "module"
            else sorted(v)[len(v) // 2] / 1e6
            for k, v in found.items()}, out


def operands(t, heads, chunk, seed=0):
    ks = jax.random.split(jax.random.key(seed), 8)

    def normal(k, *shape):
        return jax.random.normal(k, shape, jnp.float32)

    x = [normal(k, 1, t, heads * D).astype(jnp.bfloat16) for k in ks[:4]]
    pooled = [normal(k, 1, t // chunk, heads * D).astype(jnp.bfloat16)
              for k in ks[4:6]]
    vectors = [normal(k, heads, D) * D ** -0.5 for k in ks[6:]]
    return x, pooled, vectors


def price(t, heads, window, chunk, interpret, args):
    (q, k, v, g), (ks, vs), (mu, phi) = operands(t, heads, chunk)
    scale = D ** -0.5
    rows = []

    def say(**row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    impl = "interpret" if interpret else "pallas"
    qw, kw, vw, gw = (pe._windows(x, window) for x in (q, k, v, g))
    for bq, bk in args.window_blocks:
        call = dict(causal=True, heads=heads, impl=impl, scale=scale,
                    block_q=bq, block_k=bk)
        fwd, (out, lse) = device_ms(
            lambda q, k, v: pk._flash_attention_fwd(q, k, v, **call),
            qw, kw, vw)
        bwd, _ = device_ms(
            lambda q, k, v, out, lse, g: pk._flash_attention_bwd(
                q, k, v, out, lse, g, **call), qw, kw, vw, out, lse, gw)
        say(case="window", block_q=bq, block_k=bk, fwd=fwd, bwd=bwd)
    lse = jnp.zeros((t // window, heads, window), jnp.float32) + 8.0
    for bq, bk in args.chunk_blocks:
        geometry = dict(heads=heads, window=window, chunk=chunk,
                        scale=scale, block_q=bq, block_k=bk,
                        interpret=interpret)
        # from a window part's (out, lse) on: v stands for out_w and,
        # backward, for the window part's dq
        fwd, _ = device_ms(
            lambda q, ks, vs, ow, lw: pe.eva_chunk_fwd_pallas(
                q, ks, vs, ow, lw, **geometry), q, ks, vs, v, lse)
        bwd, _ = device_ms(
            lambda q, ks, vs, o, lse, g, dqw: pe.eva_chunk_bwd_pallas(
                q, ks, vs, o, lse, g, dqw, **geometry),
            q, ks, vs, v, lse, g, k)
        say(case="staircase", block_q=bq, block_k=bk, fwd=fwd, bwd=bwd,
            steps=len(pe.staircase(t, window, chunk, bq, bk)[0]))
    for n in args.pool_rows:
        pool = dict(heads=heads, chunk=chunk, interpret=interpret, rows=n)
        fwd, _ = device_ms(lambda k, v, mu, phi: pe.eva_pool_fwd_pallas(
            k, v, mu, phi, **pool), k, v, mu, phi)
        bwd, _ = device_ms(
            lambda k, v, mu, phi, dks, dvs: pe.eva_pool_bwd_pallas(
                k, v, mu, phi, dks, dvs, **pool), k, v, mu, phi, ks, vs)
        say(case="pool", rows=n, fwd=fwd, bwd=bwd)
    geometry = (heads, window, chunk, scale, interpret)
    fwd, (out, lse) = device_ms(
        lambda *a: pe._aggregate_fwd(*a, *geometry), q, k, v, ks, vs)
    bwd, _ = device_ms(lambda *a: pe._aggregate_bwd(*a, *geometry),
                       q, k, v, ks, vs, out, lse, g)
    say(case="layer", fwd=fwd, bwd=bwd)
    call = dict(causal=True, heads=heads, impl=impl, scale=scale)
    full_fwd, (out, lse) = device_ms(
        lambda q, k, v: pk._flash_attention_fwd(q, k, v, **call), q, k, v)
    full_bwd, _ = device_ms(
        lambda q, k, v, out, lse, g: pk._flash_attention_bwd(
            q, k, v, out, lse, g, **call), q, k, v, out, lse, g)
    say(case="full_causal_flash", fwd=full_fwd, bwd=full_bwd)
    if fwd.get("module") and full_fwd.get("module"):
        say(case="ratio", eva_over_full=(fwd["module"] + bwd["module"])
            / (full_fwd["module"] + full_bwd["module"]))
    return rows


def check(t, heads, window, chunk, interpret):
    """Largest |difference| between the kernels and the XLA form, over
    the largest |value|, of out and every gradient."""
    (q, k, v, g), _, (mu, phi) = operands(t, heads, chunk, seed=1)
    scale = D ** -0.5

    def both(kernels):
        def f(q, k, v, mu, phi):
            if kernels:
                ks, vs = pe.eva_pool_kernels(k, v, mu, phi, heads, chunk,
                                             interpret)
                out = pe.eva_attention_kernels(
                    q, k, v, ks, vs, heads, window, chunk, scale,
                    interpret)[0]
            else:
                ks, vs = pe.eva_pool_xla(k, v, mu, phi, heads, chunk)
                out = pe.eva_attention_xla(q, k, v, ks, vs, heads, window,
                                           chunk, scale)[0]
            return (out.astype(jnp.float32)
                    * g.astype(jnp.float32)).sum(), out
        grads, out = jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4),
                                      has_aux=True))(q, k, v, mu, phi)
        return (out, *grads)

    got, want = both(True), both(False)
    return {n: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))
                     / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for n, a, b in zip(("out", "dq", "dk", "dv", "dmu", "dphi"),
                               got, want)}


def _pairs(text):
    return [tuple(int(x) for x in b.split("x")) for b in text]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="512 bytes in windows of 128, chunks of 8, 2 "
                    "heads, interpret mode")
    ap.add_argument("--window-blocks", nargs="*", default=[
        "1024x1024", "512x512", "2048x2048", "1024x512", "512x1024"])
    ap.add_argument("--chunk-blocks", nargs="*", default=[
        "2048x128", "1024x128", "512x128", "1024x64"])
    ap.add_argument("--pool-rows", nargs="*", type=int,
                    default=[2048, 512, 1024, 4096])
    ap.add_argument("--out", default="chiprun_out/eva_price.json")
    args = ap.parse_args(argv)
    t, heads, window, chunk = 8192, 32, 2048, 16
    if args.tiny:
        t, heads, window, chunk = 512, 2, 128, 8
        args.window_blocks, args.chunk_blocks = ["128x128"], ["64x16"]
        args.pool_rows = [256]
    args.window_blocks = _pairs(args.window_blocks)
    args.chunk_blocks = _pairs(args.chunk_blocks)
    print(jax.devices(), flush=True)
    rows = price(t, heads, window, chunk, args.tiny, args)
    diff = check(*((512, 2, 128, 8) if args.tiny
                   else (4096, 4, 2048, 16)), args.tiny)
    rows.append({"case": "max_rel_diff_from_xla", **diff})
    print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
