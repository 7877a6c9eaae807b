"""The compiled KDA kernels on the path for an UNBOUNDED decay, held to
the token-by-token recurrence where that path is needed: g far below
the bounded path's -5.33 a token.

    chiprun -- python tools/kda_unbounded_chip.py
    python tools/kda_unbounded_chip.py --tiny   (a CPU, interpret mode)

`kda_scan` and `kda_scan_grad` as registered (impl "pallas": pt_kda_fwd
and pt_kda_bwd as the chip's compiler builds them; decay "unbounded")
at solar_open2_train_s8k's shape, 1 x 8,192 tokens, 8 heads of 128,
blocks of 4 chunks of 64, float32 operands and bfloat16 Q, K, V (the
cell's under AMP), in three cases: g = -30 a token throughout; each
channel of each token drawn from {-1e-4, -40}, so that steps that keep
everything and steps that keep nothing lie inside one 16-row sub-block;
and the cell's own start, -g log-uniform in [1e-4, 1e-1].  beta at the
ends of (0, 2) in the first two.  O and the five gradients against the
recurrence in float32 at matmul precision "highest" on the same
(rounded) operands, each as the largest difference over the largest
element (d G absolutely where it is e^-30-small, as tests/
test_kda_scan.py holds it).  A line a case; the rows also go to
chiprun_out/kda_unbounded.json.  Exits 1 where a float32 case misses
its tolerance or anything is not finite; the bfloat16 rows say what the
operands' rounding inside the kernels costs and decide nothing.  The
BOUNDED path is run too: at g = -30, to show that the path matters
there (its output is not the recurrence's), and at the cell's start,
where both paths are exact and read alike.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

SLOTS = ("Q", "K", "V", "G", "Beta")
# tests/test_kda_scan.py's for the first two.  The cell's start keeps a
# state for up to 10^4 tokens: over 8,192 of them float32 sums in
# another order differ by 4e-5 to 9e-5 on EITHER path (the chip's
# reading, PERF.md PR 49), where the tests' 256 tokens read 6e-7
TOL = {"g=-30": 5e-5, "mixed": 2e-4, "cell": 2e-4}
REPLAY = 256        # tokens a checkpointed stretch of the recurrence


def recurrence(q, k, v, g, beta):
    """q, k, v, g [1, T, H*D], beta [1, T, H] -> o: S <- e^g S, then
    the delta rule's write, a token at a time; stretches of REPLAY
    tokens are run again in the backward, so that its residuals are a
    state a stretch and not a state a token (4.3 GB at 8,192)."""
    _, t, width = q.shape
    h = beta.shape[-1]
    d = width // h

    def heads(x):
        return x.reshape(t // REPLAY, REPLAY, h, -1)

    def step(s, inp):
        qt, kt, vt, gt, bt = inp
        s = jnp.exp(gt)[..., None] * s
        seen = jnp.einsum("hk,hkv->hv", kt, s)
        s = s + (bt * kt)[..., None] * (vt - seen)[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s)

    @jax.checkpoint
    def stretch(s, inp):
        return lax.scan(step, s, inp)

    _, o = lax.scan(stretch, jnp.zeros((h, d, d), jnp.float32),
                    tuple(map(heads, (q[0], k[0], v[0], g[0], beta[0]))))
    return o.reshape(1, t, width)


def operands(case, t, h, d, seed=0):
    r = np.random.RandomState(seed)

    def unit(x):
        x = x.reshape(1, t, h, d)
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            1, t, h * d)

    f = lambda *s: r.randn(*s).astype(np.float32)  # noqa: E731
    q, k, v, go = (unit(f(1, t, h * d)) * d ** -0.5, unit(f(1, t, h * d)),
                   f(1, t, h * d), f(1, t, h * d))
    if case == "cell":
        g = -np.exp(r.uniform(np.log(1e-4), np.log(1e-1), (1, t, h * d)))
        beta = r.uniform(0.2, 1.8, (1, t, h))
    else:
        g = np.full((1, t, h * d), -30.0) if case == "g=-30" else \
            np.where(r.rand(1, t, h * d) < 0.5, -1e-4, -40.0)
        beta = np.where(r.rand(1, t, h) < 0.5, 0.01, 1.99)
    return q, k, v, g.astype(np.float32), beta.astype(np.float32), go


def share(got, want, floor=0.0):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.abs(got - want).max()
                 / max(float(np.abs(want).max()), floor, 1e-30))


def run_case(case, dtype, t, h, d, sizes, impl, decay="unbounded"):
    from paddle_tpu.core.registry import get_op_def

    q, k, v, g, beta, go = operands(case, t, h, d)
    cast = lambda x: jnp.asarray(x).astype(dtype)  # noqa: E731
    args = (cast(q), cast(k), cast(v), jnp.asarray(g), jnp.asarray(beta))
    go = cast(go)
    attrs = {"chunk_size": sizes[0], "block_chunks": sizes[1],
             "impl": impl, "decay": decay}
    ins = dict(zip(SLOTS, args))
    outs = jax.jit(lambda i: get_op_def("kda_scan").compute(i, attrs))(ins)
    grads = jax.jit(lambda i: get_op_def("kda_scan_grad").compute(
        i, attrs))(dict(ins, **outs, **{"O@GRAD": go}))
    with jax.default_matmul_precision("highest"):
        want_o, vjp = jax.vjp(jax.jit(recurrence), *(
            a.astype(jnp.float32) for a in args))
        want_g = vjp(go.astype(jnp.float32))
    errors = {"O": share(outs["O"], want_o)}
    for s, w in zip(SLOTS, want_g):
        # at -30 a token d G is e^-30-small itself: held absolutely
        errors["d" + s] = share(grads[s + "@GRAD"], w,
                                floor=0.2 if s == "G" else 0.0)
    finite = all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
                 for x in [outs["O"], *grads.values()])
    return {"case": case, "dtype": jnp.dtype(dtype).name, "decay": decay,
            "tokens": t, "heads": h, "finite": finite, "errors": errors,
            "worst": max(errors.values()) if finite else None}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "kda_unbounded.json"))
    args = ap.parse_args(argv)
    if not args.tiny and jax.devices()[0].platform != "tpu":
        print("no TPU here: the compiled kernels are a chip's",
              file=sys.stderr)
        return 2
    t, h, impl = (512, 2, "interpret") if args.tiny else (8192, 8, "pallas")
    rows = []
    for dtype in (jnp.float32, jnp.bfloat16):
        for case in ("g=-30", "mixed", "cell"):
            rows.append(run_case(case, dtype, t, h, 128, (64, 4), impl))
            print(json.dumps(rows[-1]), flush=True)
    for case in ("g=-30", "cell"):
        rows.append(run_case(case, jnp.float32, t, h, 128, (64, 4), impl,
                             decay="bounded"))
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "impl": impl,
                   "tolerance": TOL, "rows": rows}, f, indent=1)
    held = [r for r in rows if r["decay"] == "unbounded"]
    missed = [r for r in held if not r["finite"] or (
        r["dtype"] == "float32" and r["worst"] > TOL[r["case"]])]
    return 1 if missed else 0


if __name__ == "__main__":
    sys.exit(main())
