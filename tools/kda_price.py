"""The two KDA kernels alone at ling3_flash_train_s4k's shape (1 x 4,096
tokens, 32 heads of 128, 16 blocks of 4 chunks of 64), this tree's and,
where `_parent/` holds a checkout, the parent's, whole and BY PARTS: a
chunk's inverse replaced by `I - N` (wrong results on purpose), which
prices the ten dependent float32 products where a kernel still forms
them.

    git archive <parent> | tar -x -C _parent        # _parent/ is ignored
    chiprun -- python tools/kda_price.py
    python tools/kda_price.py --tiny        (a CPU, interpret mode, no times)

A line a case: side, operand dtype, variant, and the device's
milliseconds a call of the Mosaic kernel itself (`pt_kda_fwd`,
`pt_kda_bwd`: the median of five calls' events on the profile's `XLA
Ops` line; the running sums XLA makes round the kernels are not in it).
Then the largest difference between the two sides' outputs and five
gradients, whole: the backward that reads the forward's inverse against
the one that formed it again.  Exits 1 where an element differs.  The
rows also go to chiprun_out/kda_price.json.
"""

import argparse
import glob
import importlib.util
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

KERNELS = ("pt_kda_fwd", "pt_kda_bwd")


def load(checkout, name):
    """ops/pallas_kda.py of a checkout, a module of its own (it
    imports jax alone)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(checkout, "paddle_tpu", "ops", "pallas_kda.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def operands(t, h, d, dtype, seed=0):
    r = np.random.RandomState(seed)

    def unit(x):
        x = x.reshape(1, t, h, d)
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            1, t, h * d)

    f = lambda *s: r.randn(*s).astype(np.float32)  # noqa: E731
    g = -np.exp(r.uniform(np.log(1e-4), np.log(1e-1), (1, t, h * d)))
    q, k, v, go = (unit(f(1, t, h * d)) * d ** -0.5, unit(f(1, t, h * d)),
                   f(1, t, h * d), f(1, t, h * d))
    beta = r.uniform(0.1, 0.9, (1, t, h)).astype(np.float32)
    cast = lambda x: jnp.asarray(x).astype(dtype)  # noqa: E731
    return (cast(q), cast(k), cast(v), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta)), cast(go)


def kernel_ms(fn, *args):
    """({kernel name: the device's ms a call, none off the chip}, fn's
    result)."""
    out = jax.block_until_ready(fn(*args))
    trace = tempfile.mkdtemp()
    with jax.profiler.trace(trace):
        for _ in range(5):
            out = fn(*args)
        jax.block_until_ready(out)
    times = {k: [] for k in KERNELS}
    for path in glob.glob(trace + "/plugins/profile/*/*.xplane.pb"):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/device:TPU:0":
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    for k in KERNELS:
                        if k in e.name.split(" = ", 1)[0]:
                            times[k].append(e.duration_ns)
    return {k: sorted(v)[len(v) // 2] / 1e6
            for k, v in times.items() if v}, out


def entries(mod, sizes, interpret, plain_inverse):
    """(forward, backward) of a checkout's kernels, traced anew: `jit`
    keeps no trace from before `_inverse` was replaced.  The parent's
    backward takes no inverse."""
    real = mod._inverse
    if plain_inverse:
        def inverse(n, mk):
            return jnp.where(mk["eye"], 1.0, 0.0).astype(n.dtype) - n
    else:
        inverse = real

    def patched(fn):
        def call(*args):
            mod._inverse = inverse
            try:
                return fn.__wrapped__(*args, *sizes, interpret=interpret)
            finally:
                mod._inverse = real
        return jax.jit(call)

    return patched(mod.kda_fwd_pallas), patched(mod.kda_bwd_pallas)


def price(sides, dtype, t, h, d, sizes, interpret):
    args, go = operands(t, h, d, dtype)
    rows, whole = [], {}
    for side, mod in sides.items():
        for variant in ("whole", "inverse_as_I-N"):
            fwd, bwd = entries(mod, sizes, interpret,
                               variant != "whole")
            f_ms, kept = kernel_ms(fwd, *args)
            b_ms, grads = kernel_ms(bwd, *args, *kept[1:], go)
            rows.append({"side": side, "dtype": jnp.dtype(dtype).name,
                         "variant": variant,
                         "fwd_ms": f_ms.get("pt_kda_fwd"),
                         "bwd_ms": b_ms.get("pt_kda_bwd")})
            print(json.dumps(rows[-1]), flush=True)
            if variant == "whole":
                whole[side] = (kept[0], kept[1]) + tuple(grads)
    if len(whole) == 2:
        names = ("o", "states", "dq", "dk", "dv", "dg", "dbeta")
        diff = {n: float(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32)).max())
                for n, a, b in zip(names, whole["parent"], whole["change"])}
        rows.append({"dtype": jnp.dtype(dtype).name, "diff": diff})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--parent", default=os.path.join(HERE, "_parent"))
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "kda_price.json"))
    args = ap.parse_args(argv)
    if not args.tiny and jax.devices()[0].platform != "tpu":
        print("no TPU here: a price is a chip's", file=sys.stderr)
        return 2
    sides = {"change": load(HERE, "kda_change")}
    if os.path.isdir(os.path.join(args.parent, "paddle_tpu")):
        sides = {"parent": load(args.parent, "kda_parent"), **sides}
    t, h = (256, 2) if args.tiny else (4096, 32)
    rows = []
    for dtype in (jnp.bfloat16, jnp.float32):
        rows += price(sides, dtype, t, h, 128, (64, 4), args.tiny)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows},
                  f)
    differs = [r for r in rows if any(r.get("diff", {}).values())]
    return 1 if differs else 0


if __name__ == "__main__":
    sys.exit(main())
