"""IR-verifier sweep (ISSUE 15, ci.sh gate): build the gate workloads
with ``ir_verify`` forced to "full" — so every transpiler pass each
build runs is bracketed by the structural verifier AND the static
shape/dtype check — then verify the final program once more with the
serialization round-trip property (to_bytes/parse_from_bytes and
clone() must preserve ``program_fingerprint``, the jit-cache / model-
registry key).

A legal workload must produce ZERO error diagnostics end to end; any
pass that hands broken IR forward fails the sweep with a typed
diagnostic naming the pass, the block/op-index, and the var
(docs/ANALYSIS.md).  Shapes are tiny: the property under test
is IR structure, not perf.

Usage: python tools/verifier_sweep.py [--json] [workload ...]
Exit 0 iff every selected workload sweeps clean.  ONE JSON line on
stdout (the ci.sh/driver stdout contract); progress on stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def _rn32_infer(progs, conv_epilogue=False):
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.resnet import resnet_cifar10 as build

    rng = np.random.RandomState(0)
    feed = lambda: {  # noqa: E731
        "image": jnp.asarray(rng.rand(8, 3, 32, 32).astype(np.float32),
                             jnp.bfloat16),
        "label": np.zeros((8, 1), np.int64)}
    return progs._build_infer(lambda: build(is_test=True), feed,
                              "logits", conv_epilogue=conv_epilogue)


def _vgg_cifar_infer(progs):
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.vgg import vgg

    rng = np.random.RandomState(0)
    feed = lambda: {  # noqa: E731
        "image": jnp.asarray(rng.rand(8, 3, 32, 32).astype(np.float32),
                             jnp.bfloat16)}
    return progs._build_infer(
        lambda: vgg(16, class_dim=10, img_shape=(3, 32, 32),
                    is_test=True),
        feed, "logits")


def _workloads():
    """Tiny-scale forms of the gate workloads, exercising every
    wrapped pass family: AMP rewrite + fused-adam (tf), gspmd
    annotate+shard (tf_gspmd), inference/fc/elewise fusions + nhwc +
    bf16 (infer legs), conv-epilogue fuse (convep), PTQ + int8
    execution + interlayer requantize fold (int8 legs).  The decode
    engine builds no Program IR (its step is a jax function over the
    paged cache), so it has no entry here — its serving contracts are
    gated by ci.sh 5b/5g and the chaos soak."""
    from tools import gate_programs as progs

    return {
        "transformer_train": lambda:
            progs._build_transformer_train(2, 64),
        "transformer_train_fusedadam": lambda:
            progs._build_transformer_train(2, 64, fused_adam=True),
        # ISSUE 17: the unified epilogue pass (fc anchor) under full
        # verification — the fuse rewrite, the stamped epilogue attrs
        # (the epilogue-spec rule re-parses every one) and the derived
        # fc_epilogue_grad ops all sweep
        "transformer_train_fcep": lambda:
            progs._build_transformer_train(2, 64, fc_epilogue=True),
        "transformer_train_gspmd": lambda:
            progs._build_transformer_train(2, 64, gspmd=True, tp=2),
        "deepfm_train": lambda: progs._build_deepfm_train(64),
        "resnet32_cifar_infer": lambda: _rn32_infer(progs),
        "resnet32_cifar_infer_convep": lambda:
            _rn32_infer(progs, conv_epilogue=True),
        "vgg16_cifar_infer": lambda: _vgg_cifar_infer(progs),
        "resnet50_infer_int8": lambda:
            progs._build_resnet50_infer_int8(2),
        "resnet50_infer_int8_interlayer": lambda:
            progs._build_resnet50_infer_int8(2, int8_activations=True),
    }


def sweep_workload(name, build):
    from paddle_tpu import framework
    from paddle_tpu.analysis import check_shapes, verify
    from paddle_tpu.flags import set_flags
    from tools import gate_programs as progs

    t0 = time.time()
    # a fresh default program per workload: a builder that constructs
    # no IR must read as empty, not as the previous workload's graph
    progs._fresh_programs()
    set_flags({"ir_verify": "full"})
    try:
        build()
        prog = framework.default_main_program()
        if not any(b.ops for b in prog.blocks):
            return {"ok": False, "ops": 0, "warnings": 0,
                    "errors": ["builder constructed no IR program"],
                    "seconds": round(time.time() - t0, 1)}
        diags = list(verify(prog, roundtrip=True, raise_=False))
        diags += check_shapes(prog, raise_=False)
        diags += verify(framework.default_startup_program(),
                        raise_=False)
        errors = [str(d) for d in diags if d.severity == "error"]
        warnings = sum(1 for d in diags if d.severity == "warning")
        ops = sum(len(b.ops) for b in prog.blocks)
        return {"ok": not errors, "ops": ops, "warnings": warnings,
                "errors": errors[:5],
                "seconds": round(time.time() - t0, 1)}
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        return {"ok": False, "ops": 0, "warnings": 0,
                "errors": ["%s: %s" % (type(e).__name__, str(e)[:400])],
                "seconds": round(time.time() - t0, 1)}
    finally:
        set_flags({"ir_verify": "off"})


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("workloads", nargs="*",
                    help="subset to sweep (default: all)")
    ap.add_argument("--json", action="store_true",
                    help="(default behavior; kept for tool symmetry)")
    args = ap.parse_args(argv)

    table = _workloads()
    names = args.workloads or list(table)
    unknown = [n for n in names if n not in table]
    if unknown:
        ap.error("unknown workloads: %s (have: %s)"
                 % (unknown, list(table)))

    report, ok_all = {}, True
    for n in names:
        r = sweep_workload(n, table[n])
        report[n] = r
        ok_all &= r["ok"]
        print("  %-32s %s (%d ops, %d warnings, %.1fs)%s"
              % (n, "OK" if r["ok"] else "FAIL", r["ops"],
                 r["warnings"], r["seconds"],
                 "" if r["ok"] else " — " + "; ".join(r["errors"])),
              file=sys.stderr)
    print(json.dumps({
        "metric": "verifier_sweep", "value": sum(
            1 for r in report.values() if r["ok"]),
        "unit": "workloads", "ok": ok_all, "level": "full",
        "workloads": report}))
    return 0 if ok_all else 1


if __name__ == "__main__":
    sys.path.insert(0, ".")
    sys.exit(main())
