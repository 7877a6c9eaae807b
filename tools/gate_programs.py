"""The programs the gates build: each ``_build_*`` constructs one
program through the framework's own path (``layers.*`` -> transpiles ->
``minimize`` -> ``CompiledProgram``), runs its startup program, and
returns the jittable step with its state and feed.  Nothing here runs
or times a step.

Callers: ``tools/tpu_lowering_check.py`` compiles each at real size for
a described v5e; ``tools/verifier_sweep.py`` and
``tests/test_ir_roundtrip.py`` verify the IR at small size;
``tools/hlo_traffic.py`` reads the compiled HLO; ``chip_smoke.py``
takes the Transformer widths and the reset helper.
"""

from __future__ import annotations

import numpy as np

def _fresh_programs():
    """Fresh default programs, scope and name counters, no mesh."""
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core.program import Program
    from paddle_tpu.flags import set_flags
    from paddle_tpu.parallel import env as penv

    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    scope_mod._global_scope = scope_mod.Scope()
    # a prior gspmd build in this process set a global mesh + flag
    # (the lowering gate builds several workloads per process); a
    # fresh build must never inherit them
    penv.reset()
    set_flags({"gspmd": False, "serving_sharded": False})


def _build_compiled_fn(compiled, feed, fetch_names):
    """(fn, state): the CompiledProgram's jittable step for this feed,
    and its persistable state as the startup program left it."""
    import jax

    from paddle_tpu.core.scope import global_scope

    state = {n: global_scope().find_var(n).get()
             for n in compiled._persistable_names}
    fspecs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for k, v in feed.items()}
    sspecs = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
              for k, v in state.items()}
    fn = compiled._build_fn(list(feed), fspecs, fetch_names, sspecs)
    return fn, state


def _build_resnet50_train(batch=128, s2d=False, conv_epilogue=False,
                          conv_bn_stats=False):
    """Build + init the ResNet-50 train step (NHWC, AMP bf16,
    Momentum); returns (fn, state, feed, loss_name)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import framework, optimizer
    from paddle_tpu.models.resnet import resnet50

    _fresh_programs()
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.transpiler import nhwc_transpile

    from paddle_tpu.flags import set_flags

    # the Pallas fused conv-epilogue kernel (ops/pallas_conv.py): one
    # flag flips every NHWC conv in the step onto the VMEM-resident
    # kernel, and the IR pass below fuses the conv+bias+residual+relu
    # chains.  Always set explicitly: "off" is the default graph, not
    # "whatever a previous in-process build left behind"
    set_flags({"conv_epilogue": "on" if conv_epilogue else "off"})
    # the conv+BN-stats train-chain fusion (ops/pallas_conv.py
    # conv2d_bn_train): the IR pass below rewrites every
    # conv+BN(train)[+residual][+relu] chain onto the two-kernel fused
    # path (stats as conv sibling outputs + ONE normalize+residual+relu
    # pass).  Always set explicitly, same rule
    set_flags({"conv_bn_stats": "on" if conv_bn_stats else "off"})
    model = resnet50(is_test=False)
    # rewrite the conv stack NHWC before autodiff so the whole step
    # (fwd+bwd) avoids MXU relayouts (see tests/test_layout.py), then
    # AMP-rewrite to bf16 activations with fp32 master weights
    if s2d:
        # space-to-depth stem (exact-equivalence rewrite,
        # tests/test_layout.py)
        from paddle_tpu.transpiler import space_to_depth_stem

        space_to_depth_stem(framework.default_main_program())
    if conv_epilogue:
        from paddle_tpu.transpiler import fuse_conv_epilogue

        fuse_conv_epilogue(framework.default_main_program(),
                           protected=[model["loss"].name,
                                      model["logits"].name,
                                      model["acc"].name])
    if conv_bn_stats:
        from paddle_tpu.transpiler import fuse_conv_bn_train

        fuse_conv_bn_train(framework.default_main_program(),
                           protected=[model["loss"].name,
                                      model["logits"].name,
                                      model["acc"].name])
    nhwc_transpile(framework.default_main_program())
    opt = decorate(optimizer.Momentum(learning_rate=0.1, momentum=0.9),
                   init_loss_scaling=1.0,
                   use_dynamic_loss_scaling=False)
    opt.minimize(model["loss"])
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    compiled = fluid.CompiledProgram(framework.default_main_program())

    rng = np.random.RandomState(0)
    feed = {
        "image": jax.device_put(jnp.asarray(
            rng.rand(batch, 3, 224, 224).astype(np.float32))),
        "label": jax.device_put(
            rng.randint(0, 1000, (batch, 1)).astype(np.int64)),
    }
    fn, state = _build_compiled_fn(compiled, feed, [model["loss"].name])
    return fn, state, feed, model["loss"].name


# Vaswani et al. 2017, Table 3 base (the widths of
# benchmarks/configs/transformer-base-lm.json)
TRANSFORMER_BASE = dict(vocab=32000, d_model=512, n_layer=6,
                        d_inner=2048, n_head=8)


def _build_transformer_train(batch, seq, fused_adam=False, gspmd=False,
                             tp=2, fc_epilogue=False, devices=None):
    """Build + init the Transformer-base LM train step (AMP: bf16
    activations, fp32 master weights; Adam); returns
    (fn, state, feed, loss_name).

    fused_adam=True emits ONE multi-tensor fused_adam op over every
    (param, grad) pair instead of ~100 per-param adam ops.

    fc_epilogue=True fuses the fc+bias+act chains onto fc_epilogue ops
    (transpiler/epilogue_transpiler.py) before the backward is derived.

    gspmd=True shards the SAME step over `devices` as ONE pjit program:
    MeshPlan(dp=n_dev//tp, tp=tp), ZeRO-3 params/optimizer state on dp,
    Megatron column/row tp specs on the fc weights, flash attention
    under shard_map — via transpiler.shard_program behind the `gspmd`
    flag.  tp is clamped to the device count.  `devices`: what the
    mesh is built over (default: all jax sees;
    tools/tpu_lowering_check.py hands in a described chip's)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import framework, optimizer
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.flags import set_flags
    from paddle_tpu.models.transformer import transformer_encoder_model

    _fresh_programs()
    # flag hygiene: always set explicitly (same rule as conv_epilogue)
    set_flags({"gspmd": bool(gspmd),
               "fc_epilogue": "on" if fc_epilogue else "off"})
    c = TRANSFORMER_BASE
    model = transformer_encoder_model(
        vocab_size=c["vocab"], max_len=seq, d_model=c["d_model"],
        n_head=c["n_head"], d_inner=c["d_inner"],
        n_layer=c["n_layer"], dropout_rate=0.0,
        # the tp name grammar needs deterministic param names; only
        # the gspmd variant opts in
        param_prefix="tfm" if gspmd else None)
    if fc_epilogue:
        from paddle_tpu.transpiler import fuse_epilogue

        # fuse BEFORE minimize (same ordering rule as the resnet
        # step's conv fusions): the fc+bias+act chains of every ffn
        # and the attention projections collapse onto fc_epilogue ops,
        # and the backward derives from the fused graph
        fuse_epilogue(framework.default_main_program(),
                      protected=[model["loss"].name],
                      anchors=("fc",))
    # bf16 has fp32's exponent range: static scaling 1.0 is safe
    decorate(optimizer.Adam(learning_rate=1e-4, fuse=fused_adam),
             init_loss_scaling=1.0,
             use_dynamic_loss_scaling=False).minimize(model["loss"])
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    compiled = fluid.CompiledProgram(framework.default_main_program())
    if gspmd:
        from paddle_tpu.parallel.gspmd import MeshPlan
        from paddle_tpu.transpiler import shard_program

        ndev = len(devices or jax.devices())
        tp_eff = max(1, min(int(tp), ndev))
        while ndev % tp_eff != 0:
            tp_eff -= 1
        plan = MeshPlan(dp=ndev // tp_eff, tp=tp_eff)
        compiled = shard_program(compiled, plan,
                                 loss_name=model["loss"].name,
                                 devices=devices)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, c["vocab"], (batch, seq, 1)).astype(np.int64)
    feed = {"src_ids": jax.device_put(jnp.asarray(ids)),
            "tgt_label": jax.device_put(jnp.asarray(ids))}
    fn, state = _build_compiled_fn(compiled, feed, [model["loss"].name])
    return fn, state, feed, model["loss"].name


def _build_cell_train(config_file, builder, batch, seq, sizes):
    """Build + init a benchmark cell's train step through the
    benchmark's own builder (benchmarks/builders/<builder> `build`) on
    its configuration (benchmarks/configs/<config_file>), so one place
    says what the cell's step is; `sizes` override the file's keys.
    Returns (fn, state, feed, loss_name)."""
    import importlib.util
    import json
    import os

    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import framework

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")

    def load(*path):
        spec = importlib.util.spec_from_file_location(
            "_gate_" + path[-1][:-3], os.path.join(bench, *path))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    _fresh_programs()
    with open(os.path.join(bench, "configs", config_file)) as f:
        config = dict(json.load(f), **sizes)
    built = load("builders", builder).build(
        config, {"batch": batch, "seq_len": seq}, load("flops.py"))
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    ids, labels = built["make_batch"](np.random.default_rng(0))
    feed = {"src_ids": jax.device_put(jnp.asarray(ids)),
            "tgt_label": jax.device_put(jnp.asarray(labels))}
    loss = built["loss"].name
    fn, state = _build_compiled_fn(built["compiled"], feed, [loss])
    return fn, state, feed, loss


def _build_ouro_train(batch=1, seq=4096, **sizes):
    """The looped decoder's train step as the cell
    `ouro_2_6b_train_s4k` runs it (the IR tests build it small)."""
    return _build_cell_train("ouro-2.6b.json", "ouro.py", batch, seq,
                             sizes)


def _build_dsv2_train(batch=2, seq=4096, **sizes):
    """The DeepSeek-V2 block's train step as the cell
    `dsv2_lite_train_s4k` runs it (the IR tests build it small)."""
    return _build_cell_train("deepseek-v2-lite.json", "deepseek_v2.py",
                             batch, seq, sizes)


def _build_granite_train(batch=1, seq=8192, **sizes):
    """The hybrid state-space decoder's train step as the cell
    `granite4_h_micro_train_b1` runs it."""
    return _build_cell_train("granite-4.0-h-micro.json",
                             "granite_hybrid.py", batch, seq, sizes)


def _build_ling3_train(batch=1, seq=4096, **sizes):
    """The KDA / latent-attention hybrid's train step as the cell
    `ling3_flash_train_s4k` runs it."""
    return _build_cell_train("ling-3.0-flash-vl.json", "ling3.py", batch,
                             seq, sizes)


def _build_lfm2_train(batch=1, seq=8192, **sizes):
    """The gated-convolution / attention hybrid's train step as the
    cell `lfm2_24b_train_s8k` runs it."""
    return _build_cell_train("lfm2-24b-a2b.json", "lfm2.py", batch, seq,
                             sizes)


def _build_solar_open2_train(batch=1, seq=8192, **sizes):
    """One tensor- and expert-parallel rank's train step of
    Solar-Open2-250B as the cell `solar_open2_train_s8k` runs it."""
    return _build_cell_train("solar-open2-250b.json", "solar_open2.py",
                             batch, seq, sizes)


def _build_mellum2_train(batch=1, seq=16384, **sizes):
    """One expert-parallel rank's train step of Mellum2-12B-A2.5B as
    the cell `mellum2_12b_train_s16k` runs it."""
    return _build_cell_train("mellum2-12b-a2.5b.json", "mellum2.py",
                             batch, seq, sizes)


def _build_evabyte_train(batch=1, seq=8192, **sizes):
    """EvaByte's train step (EVA attention in every layer, eight
    next-byte heads) as the cell `evabyte_6_5b_train_s8k` runs it."""
    return _build_cell_train("evabyte-6.5b.json", "evabyte.py", batch,
                             seq, sizes)


def _build_xing4_train(batch=1, seq=4096, **sizes):
    """The 2024-26 decoder block's train step as the cell
    `xing4_29b_train_s4k` runs it."""
    return _build_cell_train("xing4.0-29b-a4b.json", "xing4.py", batch,
                             seq, sizes)


# Devlin et al. 2018, BERT-base
BERT_BASE = dict(d_model=768, n_layer=12, d_inner=3072, vocab=30522)


def _build_bert_train(batch=8, seq=512):
    """Build + init the BERT-base pretraining step (MLM+NSP, AMP,
    Adam); returns (fn, state, feed, loss_name)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import framework, optimizer
    from paddle_tpu.models.bert import bert_inputs_synthetic, bert_model

    _fresh_programs()
    from paddle_tpu.contrib.mixed_precision import decorate

    c = BERT_BASE
    d_model, n_layer, d_inner, vocab = (c["d_model"], c["n_layer"],
                                        c["d_inner"], c["vocab"])
    model = bert_model(vocab_size=vocab, max_len=seq, d_model=d_model,
                       n_head=12, d_inner=d_inner, n_layer=n_layer,
                       dropout_rate=0.0)
    # same AMP as the Transformer step: bf16 activations, fp32 master
    # weights, static scaling (bf16 keeps fp32's exponent range)
    decorate(optimizer.Adam(learning_rate=1e-4), init_loss_scaling=1.0,
             use_dynamic_loss_scaling=False).minimize(model["loss"])
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    compiled = fluid.CompiledProgram(framework.default_main_program())

    feed = {k: jax.device_put(jnp.asarray(v))
            for k, v in bert_inputs_synthetic(batch, seq, vocab).items()}
    fn, state = _build_compiled_fn(compiled, feed, [model["loss"].name])
    return fn, state, feed, model["loss"].name


def _build_deepfm_train(batch=2048):
    """Build + init the DeepFM CTR train step (dense lookups, Adam);
    returns (fn, state, feed, loss_name)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import framework, optimizer
    from paddle_tpu.models.deepfm import deepfm_model

    _fresh_programs()
    model = deepfm_model(is_sparse=False)  # dense lookups jit whole-graph
    optimizer.Adam(learning_rate=1e-3).minimize(model["loss"])
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    compiled = fluid.CompiledProgram(framework.default_main_program())

    rng = np.random.RandomState(0)
    feed = {
        "sparse_ids": jax.device_put(jnp.asarray(
            rng.randint(0, 100_000, (batch, 26, 1)).astype(np.int64))),
        "dense_x": jax.device_put(jnp.asarray(
            rng.rand(batch, 13).astype(np.float32))),
        "label": jax.device_put(jnp.asarray(
            rng.randint(0, 2, (batch, 1)).astype(np.int64))),
    }
    fn, state = _build_compiled_fn(compiled, feed, [model["loss"].name])
    return fn, state, feed, model["loss"].name


def _build_infer(model_builder, feed_builder, fetch_key,
                 conv_epilogue=False):
    """Shared bf16-inference build: build through the IR, clone for
    test, NHWC + bf16 transpile, compile.  Returns
    (fn, state, feed, fetch_name).

    conv_epilogue=True additionally folds conv+bn (the BN scale/shift
    lands in the conv weights) and collapses the resulting
    conv+bias+residual+relu chains onto the Pallas fused kernel — the
    inference graph is where the kernel fuses the WHOLE epilogue (the
    train path's BN batch stats sit between conv and residual add)."""
    import paddle_tpu as fluid
    from paddle_tpu import framework
    from paddle_tpu.contrib.float16 import bf16_transpile
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.flags import set_flags
    from paddle_tpu.transpiler import nhwc_transpile

    _fresh_programs()
    set_flags({"conv_epilogue": "on" if conv_epilogue else "off"})
    model = model_builder()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    infer_prog = framework.default_main_program().clone(for_test=True)
    if conv_epilogue:
        from paddle_tpu.transpiler import (InferenceTranspiler,
                                           fuse_conv_epilogue)

        protected = [model[fetch_key].name]
        InferenceTranspiler().transpile(infer_prog,
                                        protected=protected)
        fuse_conv_epilogue(infer_prog, protected=protected)
    nhwc_transpile(infer_prog)
    bf16_transpile(infer_prog, scope=global_scope())
    compiled = fluid.CompiledProgram(infer_prog)
    feed = feed_builder()
    fn, state = _build_compiled_fn(compiled, feed,
                                   [model[fetch_key].name])
    return fn, state, feed, model[fetch_key].name


def _build_resnet50_infer_int8(batch=128, int8_activations=False):
    """Build + init the true-int8 ResNet-50 inference path: conv+bn
    folded, every conv/mul on int8 operands with int32 accumulation
    (convert_to_int8_execution), activation scales calibrated.
    int8_activations=True keeps the activations int8 ACROSS layer
    boundaries (fused requantize epilogues).  Returns
    (fn, state, feed, fetch_name, calib_stats, infer_prog);
    tools/hlo_traffic.py --int8-interlayer reads the last two."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import framework
    from paddle_tpu.contrib.slim.quantization import (
        convert_to_int8_execution, post_training_quantize,
        quantize_weights_abs_max)
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.models.resnet import resnet50
    from paddle_tpu.transpiler import InferenceTranspiler, nhwc_transpile

    _fresh_programs()
    model = resnet50(is_test=True)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    infer_prog = framework.default_main_program().clone(for_test=True)
    # fold conv+bn BEFORE quantizing (same as the reference int8
    # pipeline): the BN scale/shift lands in the conv weights, so the
    # int8 graph loses ~53 elementwise BN ops and the per-channel
    # weight scales absorb the fold exactly
    InferenceTranspiler().transpile(
        infer_prog, protected=[model["logits"].name])
    nhwc_transpile(infer_prog)
    qw = quantize_weights_abs_max(infer_prog, global_scope())
    # calibrate per-tensor activation scales on a small batch so every
    # conv gets a static InScale: the dynamic-scale path re-reads each
    # activation for its max-reduction; bf16 inter-layer activations
    # halve the remaining traffic
    rng_c = np.random.RandomState(7)
    calib = [{"image": rng_c.rand(8, 3, 224, 224).astype(np.float32),
              "label": np.zeros((8, 1), np.int64)}]
    # interlayer mode needs scales at every fold boundary (chain
    # TAILS behind the bias add / relu, not just raw conv inputs)
    act_scales, _ = post_training_quantize(
        infer_prog, global_scope(), exe, calib,
        fetch_list=[model["logits"]],
        fold_boundaries=int8_activations)
    convert_to_int8_execution(infer_prog, global_scope(), qw,
                              act_scales=act_scales,
                              out_dtype="bfloat16",
                              int8_activations=int8_activations,
                              protected=[model["logits"].name])
    # calibration-coverage gate: post_training_quantize silently
    # records scale 0.0 (-> the dynamic max-reduction path) for any
    # activation the executor did not retain; a scope-retention
    # regression must fail loud here instead of building a graph that
    # is calibrated in name only
    int8_ops = [op for op in infer_prog.global_block().ops
                if op.type.endswith("_int8")]
    n_cal = sum(1 for op in int8_ops if op.inputs.get("InScale"))
    coverage = n_cal / max(len(int8_ops), 1)
    calib = {"n_int8_ops": len(int8_ops),
             "n_int8_calibrated": n_cal,
             "calibration_coverage": round(coverage, 4)}
    if coverage < 0.9:
        raise AssertionError(
            "int8 calibration coverage regressed: only %d/%d "
            "converted ops carry a static InScale (the rest fall back "
            "to the dynamic max-reduction path calibration exists "
            "to avoid)" % (n_cal, len(int8_ops)))
    if int8_activations:
        # interlayer fold coverage, counted+asserted like the InScale
        # check above: an 'interlayer' graph in which most edges
        # silently stayed bf16/f32 is not the graph the gate names.
        # Foldable universe on rn50 = the non-residual conv->conv edges
        # (bottleneck conv1->conv2 and conv2->conv3, plus the
        # projection-block fan-outs) — ~2/3 of the 53 convs; the
        # residual-add tails stay float by design.
        stats = getattr(infer_prog, "_int8_interlayer_stats", {})
        # a FULL fold = the requantize epilogue riding in the producer
        # (OutScale wired, int8 out); partial folds (bias/relu only)
        # don't count toward interlayer coverage
        n_req = sum(1 for op in infer_prog.global_block().ops
                    if op.type.endswith("_int8")
                    and op.inputs.get("OutScale"))
        fold_cov = n_req / max(len(int8_ops), 1)
        nz = sum(1 for v in act_scales.values() if v > 0)
        bound_cov = nz / max(len(act_scales), 1)
        calib.update({
            "n_requant_epilogues": n_req,
            "n_partial_folds": stats.get("n_partial_folds", 0),
            "interlayer_fold_coverage": round(fold_cov, 4),
            "n_int8_inputs": stats.get("n_int8_inputs", 0),
            "boundary_scale_coverage": round(bound_cov, 4)})
        if n_req != stats.get("n_edges_folded"):
            raise AssertionError(
                "interlayer bookkeeping drift: %d requantize epilogues "
                "vs %s folded edges" % (n_req, stats))
        if fold_cov < 0.5:
            raise AssertionError(
                "int8 interlayer fold coverage regressed: only %d "
                "requantize epilogues across %d int8 ops (< 50%%) — "
                "most inter-layer tensors would still flow float "
                "while the graph is called 'interlayer'" %
                (n_req, len(int8_ops)))
        if bound_cov < 0.9:
            raise AssertionError(
                "fold-boundary calibration coverage regressed: only "
                "%d/%d boundary tensors carry a recorded scale — "
                "uncalibrated boundaries silently reject their fold"
                % (nz, len(act_scales)))
    compiled = fluid.CompiledProgram(infer_prog)

    rng = np.random.RandomState(0)
    feed = {
        "image": jax.device_put(jnp.asarray(
            rng.rand(batch, 3, 224, 224).astype(np.float32))),
        "label": jax.device_put(np.zeros((batch, 1), np.int64)),
    }
    fn, state = _build_compiled_fn(compiled, feed,
                                   [model["logits"].name])
    return fn, state, feed, model["logits"].name, calib, infer_prog


def _build_longctx_train(batch=1, heads=8, seq=32768, head_dim=64):
    """Build the long-context attention step: causal flash fwd+bwd
    over bf16 q, k, v fed as data, gradients fetched.  Unfused
    attention at seq 32k materializes an ~34 GB fp32 score matrix
    (8 heads x 32768^2 x 4 B); the Pallas kernel keeps scores in VMEM.
    Returns (fn, state, feed, fetches)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import backward, framework, layers

    _fresh_programs()
    qkv = []
    for n in "qkv":
        x = layers.data(n, shape=[heads, seq, head_dim],
                        dtype="bfloat16")
        x.stop_gradient = False
        qkv.append(x)
    out = layers.flash_attention(*qkv, causal=True)
    loss = layers.reduce_sum(layers.cast(out, "float32"))
    backward.append_backward(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    compiled = fluid.CompiledProgram(framework.default_main_program())
    rng = np.random.RandomState(0)
    feed = {n: jax.device_put(jnp.asarray(
        rng.randn(batch, heads, seq, head_dim).astype(np.float32),
        jnp.bfloat16)) for n in "qkv"}
    # fetching the grads keeps the backward kernels live (no params
    # here; grads flow to the data vars)
    fetches = [loss.name, "q@GRAD", "k@GRAD", "v@GRAD"]
    fn, state = _build_compiled_fn(compiled, feed, fetches)
    return fn, state, feed, fetches


def _build_serving_tp_sharded(tp=2, devices=None):
    """Build the tp-sharded serving-inference step: an fc chain
    (256 -> 3 x 1024 -> 256, batch 8) annotated COLUMN-parallel over a
    dp1 x tp mesh slice (parallel/gspmd.annotate_tp_inference — every
    weight dim-sharded on its output dim, contractions full-width so
    sharded output is bit-identical to unsharded) compiled as ONE jit
    with in/out NamedShardings through
    CompiledProgram.with_sharding_rules — the graph a mesh-sliced
    ReplicaPool replica serves.  Returns (fn, state, feed, fetch_name).
    tp clamps to the device count.  `devices` as in
    _build_transformer_train."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import framework, layers
    from paddle_tpu.flags import set_flags
    from paddle_tpu.parallel.gspmd import (MeshPlan,
                                           annotate_tp_inference,
                                           partition_spec_of)

    _fresh_programs()
    set_flags({"serving_sharded": True})
    try:
        x = layers.data("x", shape=[256], dtype="float32")
        h = x
        for _ in range(3):
            h = layers.fc(h, size=1024, act="relu")
        pred = layers.fc(h, size=256)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(framework.default_startup_program())
        infer_prog = framework.default_main_program().clone(
            for_test=True)
        devices = list(devices or jax.devices())
        tp_eff = max(1, min(int(tp), len(devices)))
        plan = MeshPlan(dp=1, tp=tp_eff)
        annotate_tp_inference(infer_prog, plan)
        mesh = plan.build_mesh(devices=devices[:tp_eff])
        compiled = fluid.CompiledProgram(infer_prog) \
            .with_inference_optimize()

        def rule(name, shape):
            var = infer_prog.global_block().vars.get(name)
            if var is None:
                return None
            return partition_spec_of(var, plan, shape=shape)

        compiled.with_sharding_rules(rule, mesh=mesh)
        rng = np.random.RandomState(0)
        feed = {"x": jnp.asarray(rng.rand(8, 256).astype(np.float32))}
        fn, state = _build_compiled_fn(compiled, feed, [pred.name])
        return fn, state, feed, pred.name
    finally:
        set_flags({"serving_sharded": False})


def _build_llm_decode(streams=8, prefill_len=128, heads=8, head_dim=128,
                      page_size=128, kv_int8=False, head_pack=False,
                      dtype=None, spec_k=0, disagg=False):
    """Build ONE jitted continuous-decode step: token embed + qkv
    projections + the paged KV append scatter + flash_decode over the
    block-table page pool + the output projection + greedy argmax —
    the device half of what serving/decode_engine.py runs per
    iteration.  Returns (fn, state, feed): fn(state, feed) ->
    (new_state, next_tokens); state carries the page pools, feed the
    per-step indices.

    Streams own static contiguous page ranges (stream s -> pages
    [s*mp, (s+1)*mp)) with seeded RAGGED prefill lengths in
    [prefill_len/2, prefill_len]; the pools hold room for 64 more
    tokens a stream.

    spec_k > 0 builds the SPECULATIVE VERIFY step instead: feed
    carries the k+1-token window per stream (tokens / page_ids /
    offsets all [streams, k+1]) and the step appends the whole window
    then scores every row in ONE q-len-(k+1) flash_decode — fn returns
    next-token picks [streams, k+1].

    disagg=True lays the block tables out the way the DISAGGREGATED
    prefill tier leaves them: pages allocated in prefill-completion
    order, round-robin ACROSS streams, so each stream's page list is
    strided through the pool instead of contiguous.  Same kernel, same
    shapes."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_kv import kv_scales_of, quantize_kv
    from paddle_tpu.ops.pallas_kernels import flash_decode
    from paddle_tpu.serving.decode_engine import TinyDecodeLM

    vocab, gen_tokens = 32000, 64
    dtype = dtype or jnp.float32
    model = TinyDecodeLM(vocab=vocab, d_model=heads * head_dim,
                         num_heads=heads, head_dim=head_dim,
                         seed=0, dtype=dtype)
    rng = np.random.RandomState(0)
    spec_margin = (spec_k + 1) * (gen_tokens + 1) if spec_k else 0
    max_len = prefill_len + gen_tokens + spec_margin + 4
    mp = -(-max_len // page_size)                # pages/stream
    num_pages = streams * mp
    if disagg:
        # handoff fragmentation: stream s owns pages s, s+streams,
        # s+2*streams, ... (prefill-completion order round-robin)
        tables_np = np.arange(
            num_pages, dtype=np.int32).reshape(mp, streams).T
    else:
        tables_np = np.arange(
            num_pages, dtype=np.int32).reshape(streams, mp)
    lens0 = rng.randint(max(1, prefill_len // 2), prefill_len + 1,
                        size=streams).astype(np.int32)
    store = jnp.int8 if kv_int8 else dtype
    k_pages = jnp.zeros((num_pages, heads, page_size, head_dim), store)
    v_pages = jnp.zeros((num_pages, heads, page_size, head_dim), store)
    kv_scales = None

    def write_pages(kp, vp, k, v, pids):
        # page-by-page pool writes of [T, H, d] rows along pids
        w = 0
        for pid in pids:
            n = min(page_size, k.shape[0] - w)
            if n <= 0:
                break
            kp = kp.at[int(pid), :, :n, :].set(
                jnp.transpose(k[w:w + n], (1, 0, 2)))
            vp = vp.at[int(pid), :, :n, :].set(
                jnp.transpose(v[w:w + n], (1, 0, 2)))
            w += n
        return kp, vp

    for s in range(streams):
        prompt = rng.randint(2, vocab, size=int(lens0[s]))
        _, k, v = model.qkv(prompt.astype(np.int32))
        if kv_int8:
            if kv_scales is None:
                kv_scales = (kv_scales_of(k), kv_scales_of(v))
            k = quantize_kv(k, kv_scales[0])
            v = quantize_kv(v, kv_scales[1])
        else:
            k, v = k.astype(store), v.astype(store)
        k_pages, v_pages = write_pages(k_pages, v_pages, k, v,
                                       tables_np[s])

    r = spec_k + 1

    def step(state, feed):
        q, k, v = model.qkv_fn(feed["tokens"].reshape(-1))
        if kv_int8:
            k = quantize_kv(k, kv_scales[0])
            v = quantize_kv(v, kv_scales[1])
        else:
            k, v = k.astype(store), v.astype(store)
        kp = state["k_pages"].at[feed["page_ids"].reshape(-1), :,
                                 feed["offsets"].reshape(-1), :] \
            .set(k)
        vp = state["v_pages"].at[feed["page_ids"].reshape(-1), :,
                                 feed["offsets"].reshape(-1), :] \
            .set(v)
        if spec_k:
            q = jnp.reshape(q, (streams, r, heads, head_dim))
        out = flash_decode(q, kp, vp, feed["tables"], feed["lens"],
                           head_pack=head_pack, kv_scales=kv_scales)
        if spec_k:
            out = jnp.reshape(out, (streams * r, heads, head_dim))
        logits = model.logits_fn(out)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if spec_k:
            nxt = jnp.reshape(nxt, (streams, r))
        return {"k_pages": kp, "v_pages": vp}, nxt

    state = {"k_pages": k_pages, "v_pages": v_pages}
    if spec_k:
        pos = lens0[:, None] + np.arange(r, dtype=np.int32)[None, :]
        feed = {
            "tokens": jnp.asarray(
                rng.randint(2, vocab, size=(streams, r))
                .astype(np.int32)),
            "page_ids": jnp.asarray(
                tables_np[np.arange(streams)[:, None],
                          pos // page_size]),
            "offsets": jnp.asarray(pos % page_size),
            "tables": jnp.asarray(tables_np),
            "lens": jnp.asarray(lens0 + r),
        }
    else:
        feed = {
            "tokens": jnp.asarray(rng.randint(2, vocab, size=streams)
                                  .astype(np.int32)),
            "page_ids": jnp.asarray(
                tables_np[np.arange(streams), lens0 // page_size]),
            "offsets": jnp.asarray(lens0 % page_size),
            "tables": jnp.asarray(tables_np),
            "lens": jnp.asarray(lens0 + 1),
        }
    return jax.jit(step), state, feed
