"""int8 accuracy harness: top-1 delta of the calibrated int8 path vs
bf16 on ResNet32-cifar10, on CPU (emulated int8) / interpret mode.

The reference publishes accuracy ALONGSIDE throughput for its int8
pipeline (/root/reference/paddle/fluid/inference/tests/api/
int8_mkldnn_quantization.md — per-model top-1 deltas); the repo so far
had bit-exactness unit tests but no end-to-end prediction-level bound — "an int8 number
without an accuracy bound is half a result" (VERDICT r5 #2 /
next-round #4, accuracy half).

Method: build the SAME rn32-cifar10 graph three ways through the real
transpile pipelines — f32 reference, bf16 (the production inference
path: conv+bn fold is skipped, NHWC + bf16_transpile), and calibrated
int8 (conv+bn fold + NHWC + per-channel abs-max weights + static
InScale activation scales from a calibration batch + bf16 inter-layer,
exactly tools/gate_programs._build_resnet50_infer_int8's recipe) — then compare
top-1 predictions over N held-out inputs.  No trained checkpoint
exists in this environment, so inputs are synthetic and the metric is
top-1 AGREEMENT between paths (delta_pp = 100 - agreement%): the same
quantization-consistency bound, measured at the prediction level the
reference tables use.  Random-init logits have SMALLER margins than a
trained net's, so the bound here is conservative.

The row is written to docs/int8_accuracy_rn32cifar.json.  Asserts delta(int8, bf16) <= 0.5 pp (the reference
tables' bar) unless --no-assert.

Usage: python tools/int8_accuracy.py [--n 256] [--batch 64]
       [--no-write] [--no-assert]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _fresh():
    from tools import gate_programs

    gate_programs._fresh_programs()


def _predict_fn(kind):
    """Build rn32-cifar10 inference in one of three execution modes;
    returns fn(images_f32[N,3,32,32]) -> argmax[N]."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import framework
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.models.resnet import resnet_cifar10
    from paddle_tpu.transpiler import InferenceTranspiler, nhwc_transpile

    _fresh()
    np.random.seed(0)  # identical param init across the three builds
    model = resnet_cifar10(is_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    prog = framework.default_main_program().clone(for_test=True)
    logits = model["logits"].name

    if kind in ("int8", "int8_interlayer"):
        from paddle_tpu.contrib.slim.quantization import (
            convert_to_int8_execution, post_training_quantize,
            quantize_weights_abs_max)

        # same recipe as the rn50 int8 gate program
        # (gate_programs._build_resnet50_infer_int8): fold conv+bn, NHWC,
        # per-channel abs-max weights, static InScale from a
        # calibration batch, bf16 inter-layer activations;
        # "int8_interlayer" additionally runs the ISSUE-5 interlayer
        # pass (fused requantize epilogues, int8 activations across
        # layer boundaries) — the exact rn_infer_int8_interlayer
        # pipeline
        inter = kind == "int8_interlayer"
        InferenceTranspiler().transpile(prog, protected=[logits])
        nhwc_transpile(prog)
        qw = quantize_weights_abs_max(prog, global_scope())
        rng_c = np.random.RandomState(7)
        calib = [{"image": rng_c.rand(8, 3, 32, 32).astype(np.float32),
                  "label": np.zeros((8, 1), np.int64)}]
        act_scales, _ = post_training_quantize(
            prog, global_scope(), exe, calib,
            fetch_list=[model["logits"]], fold_boundaries=inter)
        convert_to_int8_execution(prog, global_scope(), qw,
                                  act_scales=act_scales,
                                  out_dtype="bfloat16",
                                  int8_activations=inter,
                                  protected=[logits])
        if inter:
            stats = getattr(prog, "_int8_interlayer_stats", {})
            assert stats.get("n_edges_folded", 0) > 0, (
                "interlayer pass folded zero edges on rn32-cifar — "
                "the column would silently measure the plain int8 "
                "path: %s" % stats)
        in_dtype = jnp.float32
    elif kind == "bf16":
        from paddle_tpu.contrib.float16 import bf16_transpile

        nhwc_transpile(prog)
        bf16_transpile(prog, scope=global_scope())
        in_dtype = jnp.bfloat16
    else:  # f32 reference
        nhwc_transpile(prog)
        in_dtype = jnp.float32

    compiled = fluid.CompiledProgram(prog)

    def predict(images):
        feed = {"image": jax.device_put(
                    jnp.asarray(images, in_dtype)),
                "label": jax.device_put(
                    np.zeros((images.shape[0], 1), np.int64))}
        (out,) = exe.run(compiled, feed=feed, fetch_list=[logits])
        return np.argmax(np.asarray(out, np.float32), axis=-1)

    return predict


def run(n=256, batch=64, int8_activations=True):
    from paddle_tpu.core.scope import Scope, scope_guard

    rng = np.random.RandomState(123)
    images = rng.rand(n, 3, 32, 32).astype(np.float32)
    kinds = ["f32", "bf16", "int8"]
    if int8_activations:
        kinds.append("int8_interlayer")
    preds = {}
    for kind in kinds:
        with scope_guard(Scope()):
            fn = _predict_fn(kind)
            preds[kind] = np.concatenate(
                [fn(images[i:i + batch])
                 for i in range(0, n, batch)])

    def delta_pp(a, b):
        return round(100.0 * float(np.mean(preds[a] != preds[b])), 3)

    row = {
        "model": "resnet32_cifar10",
        "n": int(n),
        "metric": "top1_agreement_delta_pp",
        "int8_vs_bf16_pp": delta_pp("int8", "bf16"),
        "int8_vs_f32_pp": delta_pp("int8", "f32"),
        "bf16_vs_f32_pp": delta_pp("bf16", "f32"),
        "recipe": "calibrated static InScale + per-channel abs-max "
                  "weights + conv-bn fold + bf16 inter-layer "
                  "(= the lowering gate's int8 graph)",
        "inputs": "synthetic (no trained checkpoint in this env); "
                  "agreement bound, conservative vs a trained net",
    }
    if int8_activations:
        # ISSUE 5: the interlayer column through the REAL pipeline
        # (fused requantize epilogues).  The interlayer graph is
        # BIT-identical to the plain calibrated int8 graph by the
        # requantize parity contract, so _vs_int8_pp must be 0.0 —
        # anything else is a fold bug, caught here at the
        # prediction level too.
        row.update({
            "int8_interlayer_vs_bf16_pp":
                delta_pp("int8_interlayer", "bf16"),
            "int8_interlayer_vs_f32_pp":
                delta_pp("int8_interlayer", "f32"),
            "int8_interlayer_vs_int8_pp":
                delta_pp("int8_interlayer", "int8"),
        })
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--no-write", action="store_true")
    ap.add_argument("--no-assert", action="store_true")
    ap.add_argument("--int8-activations", dest="int8_activations",
                    action="store_true", default=True,
                    help="include the ISSUE-5 interlayer column "
                         "(default on)")
    ap.add_argument("--no-int8-activations", dest="int8_activations",
                    action="store_false")
    args = ap.parse_args(argv)

    row = run(args.n, args.batch,
              int8_activations=args.int8_activations)
    print(json.dumps(row))
    if not args.no_write:
        out = os.path.join(REPO, "docs", "int8_accuracy_rn32cifar.json")
        with open(out, "w") as f:
            json.dump(row, f, indent=1)
            f.write("\n")
        print("wrote %s" % out, file=sys.stderr)
    rc = 0
    if not args.no_assert:
        for col in ("int8_vs_bf16_pp", "int8_interlayer_vs_bf16_pp"):
            if row.get(col, 0.0) > 0.5:
                print("FAIL: %s %.3f pp > 0.5 pp" % (col, row[col]),
                      file=sys.stderr)
                rc = 1
        if row.get("int8_interlayer_vs_int8_pp", 0.0) != 0.0:
            print("FAIL: interlayer graph is bit-identical to the "
                  "calibrated int8 graph by contract, but predictions "
                  "diverge %.3f pp"
                  % row["int8_interlayer_vs_int8_pp"], file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
