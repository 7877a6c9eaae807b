"""Differential timing of rn50 train-step variants on one chip session.

The 2026-08-01 profile (tools/profile_resnet.py) pinned the rn50 step
as HBM-bound (51.9 ms measured vs 15.6 ms compute roofline).  This
tool decomposes the 52 ms by timing semantically-degraded variants —
each ablation removes exactly one suspected cost — in a single
process so one chip call answers all of them:

  base       : full train step (mb128, NHWC, bf16, s2d stem)
  bn_global  : BN with use_global_stats=True (no batch-stats
               reduction passes, fwd or bwd)            -> stats cost
  avg_stem   : stem max-pool swapped for avg-pool (kills the
               select_and_scatter in the backward)      -> sas cost
  nchw       : skip the NHWC transpile                  -> layout win
  infer      : is_test bf16 forward (mb128)             -> fwd floor

Each variant compiles separately (about a minute); total budget
~8 min.  Prints one JSON line per variant:
  ABLATE {"variant": ..., "step_ms": ..., "delta_vs_base_ms": ...}
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

sys.path.insert(0, ".")


def build_step(variant, batch=128):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import framework, layers, optimizer
    import importlib

    # paddle_tpu.models re-exports the resnet *function*, which shadows
    # the submodule under `from ... import resnet`
    resnet_mod = importlib.import_module("paddle_tpu.models.resnet")
    from paddle_tpu.transpiler import nhwc_transpile, space_to_depth_stem
    from paddle_tpu.contrib.mixed_precision import decorate
    from bench import _build_compiled_fn, _fresh_programs

    _fresh_programs()

    # variant hooks: patch the layer fns the model builder calls
    # (models/resnet.py _conv_bn -> layers.batch_norm; stem max-pool
    # -> layers.pool2d) instead of forking the builder
    orig_bn = layers.batch_norm
    orig_pool = layers.pool2d
    if variant == "bn_global":
        def bn_global(input, **kw):
            kw["use_global_stats"] = True
            return orig_bn(input, **kw)
        resnet_mod.layers.batch_norm = bn_global
    if variant == "avg_stem":
        def pool_avg(input, **kw):
            if kw.get("pool_type", "max") == "max":
                kw["pool_type"] = "avg"
            return orig_pool(input, **kw)
        resnet_mod.layers.pool2d = pool_avg
    try:
        model = resnet_mod.resnet50(is_test=(variant == "infer"))
    finally:
        resnet_mod.layers.batch_norm = orig_bn
        resnet_mod.layers.pool2d = orig_pool

    prog = framework.default_main_program()
    exe = fluid.Executor(fluid.TPUPlace())

    if variant == "infer":
        # mirrors bench.py _build_infer (no s2d: the floor reference
        # is the shipping inference build)
        from paddle_tpu.contrib.float16 import bf16_transpile
        from paddle_tpu.core.scope import global_scope

        exe.run(framework.default_startup_program())
        prog = prog.clone(for_test=True)
        nhwc_transpile(prog)
        bf16_transpile(prog, scope=global_scope())
        fetch = model["logits"].name
    else:
        space_to_depth_stem(prog)
        if variant != "nchw":
            nhwc_transpile(prog)
        opt = decorate(
            optimizer.Momentum(learning_rate=0.1, momentum=0.9),
            init_loss_scaling=1.0, use_dynamic_loss_scaling=False)
        opt.minimize(model["loss"])
        exe.run(framework.default_startup_program())
        fetch = model["loss"].name

    compiled = fluid.CompiledProgram(prog)
    rng = np.random.RandomState(0)
    img = rng.rand(batch, 3, 224, 224).astype(np.float32)
    feed = {
        # the bf16-transpiled inference program takes bf16 images
        # (mirrors bench_resnet50_infer's feed)
        "image": jax.device_put(jnp.asarray(
            img, jnp.bfloat16 if variant == "infer" else None)),
        "label": jax.device_put(
            rng.randint(0, 1000, (batch, 1)).astype(np.int64)),
    }
    fn, state = _build_compiled_fn(compiled, feed, [fetch])
    return fn, state, feed, fetch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("variants", nargs="?",
                    default="base,bn_global,avg_stem,nchw,infer")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--chain", type=int, default=10)
    args = ap.parse_args()

    from bench import _chain_timed

    base_ms = None
    for v in args.variants.split(","):
        try:
            fn, state, feed, fetch = build_step(v, args.batch)
            sec, _ = _chain_timed(fn, state, feed, fetch, args.chain)
            ms = round(sec * 1e3, 3)
            rec = {"variant": v, "step_ms": ms}
            if v == "base":
                base_ms = ms
            elif base_ms is not None:
                rec["delta_vs_base_ms"] = round(ms - base_ms, 3)
            print("ABLATE " + json.dumps(rec), flush=True)
        except Exception as e:  # keep later variants alive
            print("ABLATE " + json.dumps(
                {"variant": v, "error": repr(e)[:300]}), flush=True)


if __name__ == "__main__":
    main()
