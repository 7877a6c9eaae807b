"""Minimal on-chip int8 repro: decide in <2 min whether the 2026-07-31
bench int8-leg crash (backend UNAVAILABLE mid-device_put, 25 min into
the leg) was an int8 lowering problem or the machine going away.

Runs escalating probes, each its own jit, printing PROBE-OK /
PROBE-FAIL per stage with timings:
  1. bf16 matmul           — is the chip alive at all?
  2. s8xs8->s32 dot        — the mul_int8 primitive pattern
  3. s8xs8->s32 conv       — the conv2d_int8 primitive pattern
  4. im2col escape hatch   — FLAGS int8_conv_algo=im2col
  5. requantize chain      — the ISSUE-5 interlayer pattern: s8 conv
     -> s32 accumulator -> fused per-channel requantize (scale + bias
     + ReLU + round/clip -> s8) -> a SECOND s8 conv consuming the s8
     tensor.  Run before the rn_infer_int8_interlayer leg spends a
     chip call on it.
  6. requantize cross-lowering — the same chain jax.export-lowered for
     platform=tpu (Mosaic legality without needing the device; gives a
     verdict even when probing from a CPU-only host).
If 1 passes and 3 fails reproducibly, the conv int8 lowering is the
culprit and conv2d_int8 needs an im2col+dot (or Pallas) fallback on
TPU; if everything passes, the bench crash was the wedge.

--json PATH records the per-stage verdict
({"stages": {name: ok}, "verdict": "ALL-OK"|"FAILED"}) for the chaser
and post-mortems.
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

RESULTS = {}


def stage(name, fn):
    t0 = time.time()
    try:
        out = fn()
        if hasattr(out, "block_until_ready"):
            out.block_until_ready()
        print("PROBE-OK   %-18s %.1fs dtype=%s" %
              (name, time.time() - t0, getattr(out, "dtype", "-")),
              flush=True)
        RESULTS[name] = True
        return True
    except Exception as e:  # noqa: BLE001 - report and continue
        print("PROBE-FAIL %-18s %.1fs %s: %s" %
              (name, time.time() - t0, type(e).__name__,
               str(e)[:300]), flush=True)
        RESULTS[name] = False
        return False


def _bf16_matmul():
    return jax.jit(lambda a: a @ a)(jnp.ones((512, 512), jnp.bfloat16))


def _ints(shape):
    # host-side construction: nothing touches the device until the
    # jitted call inside stage()'s try
    import numpy as np

    return jnp.asarray(np.random.RandomState(0)
                       .randint(-10, 10, shape).astype("int8"))


def _int8_dot():
    a8 = _ints((512, 512))
    return jax.jit(lambda a, b: lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32))(a8, a8)


def _int8_conv(fmt):
    shp = (8, 64, 28, 28) if fmt == "NCHW" else (8, 28, 28, 64)
    x8, w8 = _ints(shp), _ints((64, 64, 3, 3))
    dn = lax.conv_dimension_numbers(shp, w8.shape, (fmt, "OIHW", fmt))
    return jax.jit(lambda x, w: lax.conv_general_dilated(
        x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn,
        preferred_element_type=jnp.int32))(x8, w8)


def _int8_im2col():
    """The escape-hatch lowering (FLAGS int8_conv_algo=im2col): if the
    integer conv stages fail but this passes, flip the flag's default
    on TPU and the int8 path still runs on the MXU."""
    import os as _os
    import sys as _sys

    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))))
    from paddle_tpu.ops.quant import _int8_conv_im2col

    x8, w8 = _ints((8, 28, 28, 64)), _ints((64, 64, 3, 3))
    return jax.jit(lambda x, w: _int8_conv_im2col(
        x, w, (1, 1), (1, 1), (1, 1), 1, "NHWC"))(x8, w8)


def _requant_chain_fn():
    """The exact interlayer primitive pattern the
    rn_infer_int8_interlayer leg compiles, shapes shrunk: s8xs8->s32
    conv, fused per-channel requantize epilogue (scale mult + bias +
    ReLU + round/clip -> s8), and a second conv consuming the s8
    tensor (int8-in)."""
    sc = jnp.linspace(0.005, 0.02, 64, dtype=jnp.float32)
    b = jnp.linspace(-1.0, 1.0, 64, dtype=jnp.float32)
    shp = (8, 28, 28, 64)
    dn = lax.conv_dimension_numbers(shp, (64, 64, 3, 3),
                                    ("NHWC", "OIHW", "NHWC"))

    def f(x, w):
        acc = lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn,
            preferred_element_type=jnp.int32)
        y = acc.astype(jnp.float32) * sc.reshape(1, 1, 1, -1)
        y = y.astype(jnp.bfloat16) + b.reshape(1, 1, 1, -1)
        y = jax.nn.relu(y)
        y8 = jnp.clip(jnp.round(y.astype(jnp.float32) / 0.05 * 127.0),
                      -127, 127).astype(jnp.int8)
        return lax.conv_general_dilated(
            y8, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn,
            preferred_element_type=jnp.int32)

    return f, shp


def _int8_requant_chain():
    f, shp = _requant_chain_fn()
    return jax.jit(f)(_ints(shp), _ints((64, 64, 3, 3)))


def _int8_requant_xlower():
    """Device-free Mosaic/TPU cross-lowering of the same chain
    (jax.export): a verdict exists even without a chip."""
    from jax import export

    f, shp = _requant_chain_fn()
    export.export(jax.jit(f), platforms=("tpu",))(
        jax.ShapeDtypeStruct(shp, jnp.int8),
        jax.ShapeDtypeStruct((64, 64, 3, 3), jnp.int8))
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write the per-stage verdict JSON here")
    args = ap.parse_args()

    print("devices:", jax.devices(), flush=True)
    ok = stage("bf16_matmul", _bf16_matmul)
    ok &= stage("int8_dot", _int8_dot)
    conv_ok = stage("int8_conv", lambda: _int8_conv("NCHW"))
    # NHWC variant too — the bench int8 path runs after nhwc_transpile
    conv_ok &= stage("int8_conv_nhwc", lambda: _int8_conv("NHWC"))
    im2col_ok = stage("int8_im2col", _int8_im2col)
    ok &= conv_ok or im2col_ok
    if not conv_ok and im2col_ok:
        print("VERDICT: integer conv lowering is broken but the "
              "im2col escape hatch works — set "
              "PADDLE_TPU_INT8_CONV_ALGO=im2col for the bench",
              flush=True)
    # ISSUE 5: the interlayer pattern must prove out BEFORE the
    # rn_infer_int8_interlayer leg spends a chip call on a 25-minute
    # compile
    ok &= stage("int8_requant", _int8_requant_chain)
    ok &= stage("int8_requant_xlower", _int8_requant_xlower)
    verdict = "ALL-OK" if ok else "FAILED"
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"stages": dict(RESULTS), "verdict": verdict,
                       "devices": [str(d) for d in jax.devices()]},
                      f, indent=1)
            f.write("\n")
        print("verdict JSON -> %s" % args.json, flush=True)
    print("INT8PROBE " + verdict, flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
