"""Sweep flash-attention Pallas block sizes on a real chip.

The kernel defaults to block_q = block_k = 512 (ops/pallas_kernels.py
flash_attention), a size chosen off-chip.  This tool times fwd and
fwd+bwd at the transformer-bench shape (and the long-context shape)
across block combos so the default can be re-pinned to what the v5e
actually prefers.  Prints one JSON line per combo; errors (e.g. a
combo exceeding VMEM) are reported per-combo, not fatal.

Run on chip (the chaser queues it): python tools/flash_block_sweep.py
"""
import itertools
import json
import sys
import time


def time_fn(fn, q, k, v, repeat=20, warmup=3, pick=None):
    """Chained timing: feed each call's output back as the next q and
    sync by fetching a scalar reduction to host.

    A timing that does not end in a fetched value can measure the
    enqueue: the first on-chip sweep (2026-08-01) "measured" 0.02 ms
    for a seq-32k flash forward whose compute ideal is ~5.6 ms.  The
    data dependency chain plus a host transfer (the same pattern as
    bench._chain_timed) forces real execution into the timed window.
    `pick` maps fn's output to a q-shaped array (identity by default;
    grad callers pick dq)."""
    import jax.numpy as jnp
    import numpy as np

    pick = pick or (lambda o: o)

    def sync(x):
        return float(np.asarray(jnp.sum(x.astype(jnp.float32))))

    x = q
    for _ in range(warmup + 1):  # +1 covers compile
        x = pick(fn(x, k, v))
    sync(x)
    t0 = time.perf_counter()
    x = q
    for _ in range(repeat):
        x = pick(fn(x, k, v))
    sync(x)
    return (time.perf_counter() - t0) / repeat * 1e3


def main():
    import os

    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny interpret-mode plumbing check on CPU")
    ap.add_argument("--shape", default=None,
                    help="sweep only this shape (tf_base | longctx)")
    args = ap.parse_args()
    smoke, only = args.smoke, args.shape
    import jax

    if smoke:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.ops.pallas_kernels import flash_attention

    print("devices:", jax.devices(), flush=True)
    impl = "interpret" if smoke else "pallas"
    if smoke:  # tiny plumbing check, interpret-mode kernel on CPU
        shapes = [dict(name="smoke", b=1, h=2, t=128, d=32,
                       causal=True, combos=[(64, 64), (128, 64)])]
    else:
        shapes = [
            # transformer-base bench: batch 32, 8 heads, seq 512, d 64
            dict(name="tf_base", b=32, h=8, t=512, d=64, causal=True,
                 combos=[(256, 256), (256, 512), (512, 256),
                         (512, 512)]),
            # long-context leg shape (single chip); fewer combos —
            # every fwd+bwd compile at seq 32k counts against the
            # call's time limit
            # bigger block_q cuts K/V streaming passes linearly (the
            # dominant HBM traffic at seq 32k: T/bq full K+V reads per
            # head); VMEM stays comfortable through bq=2048 at d=64
            dict(name="longctx", b=1, h=8, t=32768, d=64, causal=True,
                 combos=[(512, 512), (512, 1024), (1024, 512),
                         (1024, 1024), (2048, 512)]),
            # past the 1024x1024 winner (2026-08-01: 1.5x over the old
            # 512x512 default) — scores VMEM at 2048x2048 is 16 MB f32,
            # comfortably inside v5e VMEM
            dict(name="longctx_big", b=1, h=8, t=32768, d=64,
                 causal=True,
                 combos=[(1024, 1024), (1024, 2048), (2048, 1024),
                         (2048, 2048)]),
            # LLM head width: the d128 legs run at ~2x the d64 MFU, so
            # their block optimum deserves its own probe
            dict(name="longctx_d128", b=1, h=8, t=32768, d=128,
                 causal=True,
                 combos=[(512, 1024), (1024, 1024), (1024, 2048),
                         (2048, 1024)]),
            # flash memory-overhaul variants (ops/pallas_kernels.py):
            # the 1024x1024 default was pinned on the UNPACKED kernel;
            # head packing doubles per-step VMEM (two heads of q/k/v +
            # two score blocks), so its optimum may sit at smaller
            # tiles — probe around the default before trusting the
            # d64 A/B verdict
            dict(name="longctx_hp2", b=1, h=8, t=32768, d=64,
                 causal=True, kw=dict(head_pack=True),
                 combos=[(512, 512), (512, 1024), (1024, 1024),
                         (1024, 2048)]),
            # packed row-stats only gates ON at bq >= 1024 — sweep
            # the legal range (2048 halves the relayout count/step)
            dict(name="longctx_packed", b=1, h=8, t=32768, d=64,
                 causal=True, kw=dict(packed_stats=True),
                 combos=[(1024, 1024), (1024, 2048), (2048, 1024),
                         (2048, 2048)]),
        ]
        if only:
            shapes = [s for s in shapes if s["name"] == only]
            if not shapes:
                # an unknown name must NOT exit 0 — the chaser would
                # mark the task done with zero data collected
                print("unknown --shape %r" % only, file=sys.stderr)
                return 2
    key = jax.random.PRNGKey(0)
    shapes_ok = 0
    for s in shapes:
        n_good = 0
        q = jax.random.normal(
            key, (s["b"], s["h"], s["t"], s["d"]), jnp.bfloat16)
        kw = s.get("kw", {})
        for bq, bk in s["combos"]:
            if bq > s["t"] or bk > s["t"]:
                continue
            try:
                fwd = jax.jit(lambda q, k, v, bq=bq, bk=bk:
                              flash_attention(q, k, v, causal=s["causal"],
                                              block_q=bq, block_k=bk,
                                              impl=impl, **kw))
                ms_f = time_fn(fwd, q, q, q)

                def loss(qq, kk, vv, bq=bq, bk=bk):
                    return flash_attention(
                        qq, kk, vv, causal=s["causal"], block_q=bq,
                        block_k=bk, impl=impl, **kw).astype(
                        jnp.float32).sum()

                gfn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                # chain dq (q-shaped) into the next call's q
                ms_fb = time_fn(gfn, q, q, q, pick=lambda o: o[0])
                print(json.dumps({
                    "shape": s["name"], "block_q": bq, "block_k": bk,
                    **{k: v for k, v in kw.items() if v},
                    "fwd_ms": round(ms_f, 3),
                    "fwd_bwd_ms": round(ms_fb, 3)}), flush=True)
                n_good += 1
            except Exception as e:  # noqa: BLE001 - per-combo isolation
                print(json.dumps({
                    "shape": s["name"], "block_q": bq, "block_k": bk,
                    "error": "%s: %s" % (type(e).__name__,
                                         str(e)[:200])}), flush=True)
        shapes_ok += n_good > 0
    # a shape with zero surviving combos (e.g. mid-sweep wedge) must
    # exit nonzero so the chaser re-queues instead of marking done
    return 0 if shapes_ok == len(shapes) else 1


if __name__ == "__main__":
    sys.exit(main())
