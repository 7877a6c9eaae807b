"""Benchmark: TRAINING throughput + MFU for ResNet-50 and
Transformer-base, plus the round-1 inference anchor, on one TPU chip.

The BASELINE.md target metric is samples/sec/chip + MFU for training
(north star >=50% MFU); the reference's only published numbers are
inference fp16 latencies (/root/reference/paddle/contrib/float16/
float16_benchmark.md), kept here as the vs_baseline sanity anchor.

Methodology: every program is built and compiled through the
framework's own IR + CompiledProgram path (this benches the framework,
not hand-written JAX).  Training steps run fwd+bwd+optimizer with the
persistable state dict donated to XLA; N steps are enqueued
back-to-back (the donated state chains them on-device) and synced
once, amortizing host dispatch latency the way real training
amortizes it via async queueing.  Matmuls/convs use the
TPU default precision (bf16 multiply passes on the MXU), the moral
equivalent of the reference's fp16 tensor-core path.

MFU = analytic model FLOPs / elapsed / chip peak bf16 FLOP/s.  Model
FLOPs use the standard closed forms (3x forward for training: fwd +
2x bwd), NOT XLA cost analysis, so remat or fusion tricks can't
inflate the number.

Needs a TPU: without one ``main`` exits non-zero before any leg, a leg
that fails fails the run, and no row is carried over from an older
run.  Prints ONE JSON line {metric, value, unit, vs_baseline, ...} and
writes the full rows to ``chiprun_out/bench_rows.json``.
"""

from __future__ import annotations

import json
import time

import numpy as np

BASELINE_INFER_MS = 64.52  # V100 fp16 mb=128, float16_benchmark.md:42-44
BASELINE_VGG16_MB64_MS = 60.23  # V100 fp16 mb=64, float16_benchmark.md:23-25
BASELINE_VGG16_CIFAR_MS = 17.37  # V100 fp16 mb=512, float16_benchmark.md:61-63
BASELINE_RN32_CIFAR_MS = 11.02  # V100 fp16 mb=512, float16_benchmark.md:72-74
MFU_TARGET = 0.50          # BASELINE.md north star

# peak HBM bandwidth per chip by device kind (public spec sheets) —
# the denominator of the BW% bound for memory-bound rows (DeepFM CTR:
# the step is a gather/scatter over the embedding tables, so MFU alone
# says nothing — VERDICT r5 next-round #7)
_PEAK_BW_BY_KIND = {
    "TPU v2": 700e9,
    "TPU v3": 900e9,
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1638e9,
    "TPU v6e": 1638e9,
    "TPU7x": 7370e9,
}

# bf16 peak FLOP/s per chip by device kind (public spec sheets)
_PEAK_BY_KIND = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
    "TPU7x": 2307e12,
}


def _chip_peak(table, what):
    """(peak, device_kind) of the default device from a spec-sheet
    table.  A device the table does not know is an error, not a
    default: a utilization against a made-up peak is not a number."""
    import jax

    kind = jax.devices()[0].device_kind
    for k, v in table.items():
        if kind.lower().startswith(k.lower()):
            return v, kind
    raise RuntimeError(
        "bench: no published %s for device kind %r (known: %s) — the "
        "bench measures TPU chips only" % (what, kind, sorted(table)))


def _chip_peak_flops():
    return _chip_peak(_PEAK_BY_KIND, "bf16 peak FLOP/s")


def _chip_peak_bw():
    return _chip_peak(_PEAK_BW_BY_KIND, "peak HBM bandwidth")


def _fresh_programs():
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


def _resnet50_train_flops_per_image():
    """Fwd FLOPs of ResNet-50 @224 (convs+fc, 2*MACs) ~= 8.2 GFLOP;
    training ~= 3x (bwd wrt inputs + wrt weights)."""
    return 3 * 8.2e9


def _transformer_train_flops_per_token(n_params, d_model, n_layer, seq):
    """PaLM-style 6N + attention term: 6*N + 12*L*d*s flops/token."""
    return 6.0 * n_params + 12.0 * n_layer * d_model * seq


def _chain_timed(fn, state, feed, fetch_probe, chain, warmup=2):
    """Run `chain` donated-state steps back-to-back, sync once."""
    import jax
    import jax.numpy as jnp

    for _ in range(warmup):
        state, f = fn(state, feed)
    float(np.asarray(f[0].astype(jnp.float32)).sum())  # sync
    platform = jax.devices()[0].platform
    stray = [k for k, v in state.items()
             if any(d.platform != platform for d in v.devices())]
    if stray:
        raise RuntimeError("bench: stepped state not on the default "
                           "device (%s): %s" % (platform, stray[:5]))
    t0 = time.perf_counter()
    for _ in range(chain):
        state, f = fn(state, feed)
    float(np.asarray(f[0].astype(jnp.float32)).sum())  # single sync
    dt = time.perf_counter() - t0
    return dt / chain, state


def _build_compiled_fn(compiled, feed, fetch_names):
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


def _build_resnet50_train(batch=128, s2d=False, maxpool_grad=None,
                          conv_epilogue=False, conv_bn_stats=False):
    """Build + init the ResNet-50 bench train step; returns
    (fn, state, feed, loss_name).  Shared by the bench and
    tools/tpu_lowering_check.py so the lowering gate checks exactly
    the program the bench times."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import framework, optimizer
    from paddle_tpu.models.resnet import resnet50

    _fresh_programs()
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.transpiler import nhwc_transpile

    # A/B lever: 'compare' routes max-pool grads via k*k shifted
    # compares instead of select_and_scatter (flags.py).  Always set
    # explicitly: None means the sas default, not "inherit whatever a
    # previous in-process build left behind"
    from paddle_tpu.flags import set_flags

    set_flags({"maxpool_grad_algo": maxpool_grad or "sas"})
    # A/B lever: the Pallas fused conv-epilogue kernel
    # (ops/pallas_conv.py) — one flag flips every NHWC conv in the
    # step onto the VMEM-resident kernel, and the IR pass below fuses
    # the conv+bias+residual+relu chains.  Always set explicitly, like
    # maxpool_grad_algo: "off" is the default graph, not "whatever a
    # previous in-process build left behind"
    set_flags({"conv_epilogue": "on" if conv_epilogue else "off"})
    # A/B lever: the conv+BN-stats train-chain fusion
    # (ops/pallas_conv.py conv2d_bn_train) — the IR pass below rewrites
    # every conv+BN(train)[+residual][+relu] chain onto the two-kernel
    # fused path (stats as conv sibling outputs + ONE
    # normalize+residual+relu pass).  Always set explicitly, same rule
    set_flags({"conv_bn_stats": "on" if conv_bn_stats else "off"})
    model = resnet50(is_test=False)
    # TPU fast path: rewrite the conv stack NHWC before autodiff so the
    # whole step (fwd+bwd) avoids MXU relayouts (see tests/test_layout.py),
    # then AMP-rewrite to bf16 activations with fp32 master weights —
    # the moral equivalent of the reference's float16 training story
    # (contrib/float16/float16_benchmark.md)
    if s2d:
        # A/B lever: space-to-depth stem (exact-equivalence rewrite,
        # tests/test_layout.py).  MFU keeps the ORIGINAL model's
        # analytic numerator, so compare variants by step time.
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


def bench_resnet50_train(batch=128, chain=30, s2d=True,
                         maxpool_grad=None, conv_epilogue=False,
                         conv_bn_stats=False):
    # s2d default flipped after the 2026-08-01 on-chip A/B: mb128+s2d
    # 30.65% MFU vs 30.41% plain (docs/bench_onchip_20260801_0302.json)
    fn, state, feed, loss_name = _build_resnet50_train(
        batch, s2d=s2d, maxpool_grad=maxpool_grad,
        conv_epilogue=conv_epilogue, conv_bn_stats=conv_bn_stats)
    sec_per_step, _ = _chain_timed(fn, state, feed, loss_name, chain)
    sps = batch / sec_per_step
    peak, kind = _chip_peak_flops()
    mfu = _resnet50_train_flops_per_image() * sps / peak
    res = {
        "samples_per_sec": round(sps, 1),
        "step_ms": round(sec_per_step * 1e3, 3),
        "mfu_pct": round(100 * mfu, 2),
        "batch": batch,
        "device": kind,
    }
    if s2d:
        res["s2d_stem"] = True
    if maxpool_grad:
        res["maxpool_grad"] = maxpool_grad
    if conv_epilogue:
        res["conv_epilogue"] = True
    if conv_bn_stats:
        res["conv_bn_stats"] = True
    return res


def bench_resnet50_train_convbnstats(**kw):
    """The conv+BN-stats train-chain fusion A/B leg: identical workload
    and analytic-MFU numerator as rn_train, with every
    conv+BN(train)[+residual][+relu] chain rewritten onto
    conv2d_bn_train (ops/pallas_conv.py) — per-channel Σy/Σy² ride out
    of the conv kernel as sibling outputs and ONE fused
    normalize+residual+ReLU pass finishes the chain, so the train
    graph's BN-moment re-read of the conv output disappears.  Queued
    right behind the convep pair (the train path's structural cut where
    convep could only fuse the conv itself)."""
    kw.setdefault("conv_bn_stats", True)
    return bench_resnet50_train(**kw)


def bench_resnet50_train_convep(**kw):
    """The fused conv-epilogue A/B leg: identical workload to rn_train
    (same shapes, same analytic MFU numerator) with every conv routed
    through the Pallas fused kernel and the residual/ReLU chains
    IR-fused (ops/pallas_conv.py).  Separate leg so the ladder banks
    both sides of the A/B."""
    kw.setdefault("conv_epilogue", True)
    return bench_resnet50_train(**kw)


# Transformer-base config shared with tools/profile_transformer.py so
# the profiler's MFU numbers can never diverge from the bench's
TRANSFORMER_BASE = dict(vocab=32000, d_model=512, n_layer=6,
                        d_inner=2048, n_head=8)


def _transformer_n_params(seq, vocab, d_model, n_layer, d_inner,
                          n_head):
    """embeddings + 12*d^2 per layer (attn 4d^2 + ffn 8d^2) + untied
    output projection."""
    return (vocab * d_model + seq * d_model
            + n_layer * (4 * d_model * d_model
                         + 2 * d_model * d_inner)
            + d_model * vocab)


def _build_transformer_train(batch, seq, amp=True, fused_adam=False,
                             gspmd=False, tp=2, fc_epilogue=False,
                             devices=None):
    """Build + init the bench transformer train step; returns
    (fn, state, feed, loss_name) — the exact path bench and profiler
    share.  amp=True rewrites activations to bf16 with fp32 master
    weights (contrib.mixed_precision), the transformer counterpart of
    the resnet bench's AMP story.

    fused_adam=True emits ONE multi-tensor fused_adam op over every
    (param, grad) pair instead of ~100 per-param adam ops — the
    Adam-tail A/B, to diagnose the 50.17->42.02% batch slide of the
    2026-08-01 chip rows (ROADMAP 1.3):
    at mb128 the optimizer tail is the step fraction that GROWS with
    batch the least, so if the slide is scheduling overhead across the
    many small elementwise kernels, fusing them names it.

    gspmd=True (ISSUE 8) shards the SAME step over every attached
    device as ONE pjit program: MeshPlan(dp=n_dev//tp, tp=tp), ZeRO-3
    params/optimizer state on dp, Megatron column/row tp specs on the
    fc weights, flash attention under shard_map — via
    transpiler.shard_program behind the typed `gspmd` flag.  tp is
    clamped to the device count, so the leg degrades to a 1-device
    mesh on a single chip instead of failing.  `devices`: what the
    mesh is built over (default: all jax sees;
    tools/tpu_lowering_check.py hands in a described chip's)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import framework, optimizer
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
        # the gspmd variant opts in so the baseline program is
        # byte-identical to every previous round's
        param_prefix="tfm" if gspmd else None)
    if fc_epilogue:
        from paddle_tpu.transpiler import fuse_epilogue

        # fuse BEFORE minimize (same ordering rule as the resnet
        # bench's conv fusions): the fc+bias+act chains of every ffn
        # and the attention projections collapse onto fc_epilogue ops,
        # and the backward derives from the fused graph
        fuse_epilogue(framework.default_main_program(),
                      protected=[model["loss"].name],
                      anchors=("fc",))
    opt = optimizer.Adam(learning_rate=1e-4, fuse=fused_adam)
    if amp:
        from paddle_tpu.contrib.mixed_precision import decorate

        # bf16 has fp32's exponent range: static scaling 1.0 is safe
        # (same choice as the resnet bench)
        opt = decorate(opt, init_loss_scaling=1.0,
                       use_dynamic_loss_scaling=False)
    opt.minimize(model["loss"])
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


def bench_transformer_train(batch=32, seq=512, chain=30,
                            fused_adam=False, fc_epilogue=False):
    """Transformer-base LM (d=512, 6L, 8H, ffn 2048), seq 512."""
    fn, state, feed, loss_name = _build_transformer_train(
        batch, seq, fused_adam=fused_adam, fc_epilogue=fc_epilogue)
    sec_per_step, _ = _chain_timed(fn, state, feed, loss_name, chain)
    toks_per_sec = batch * seq / sec_per_step
    c = TRANSFORMER_BASE
    n_params = _transformer_n_params(seq, **c)
    peak, kind = _chip_peak_flops()
    fpt = _transformer_train_flops_per_token(
        n_params, c["d_model"], c["n_layer"], seq)
    mfu = fpt * toks_per_sec / peak
    res = {
        "tokens_per_sec": round(toks_per_sec, 0),
        "samples_per_sec": round(batch / sec_per_step, 2),
        "step_ms": round(sec_per_step * 1e3, 3),
        "mfu_pct": round(100 * mfu, 2),
        "batch": batch,
        "seq": seq,
        "device": kind,
    }
    if fused_adam:
        res["fused_adam"] = True
    if fc_epilogue:
        # canonical epilogue-workload marker (see _workload_sig): the
        # fused anchor name, not a per-flag bool
        res["epilogue"] = "fc"
    return res


def bench_transformer_train_fcep(**kw):
    """The fused fc-epilogue A/B leg (ISSUE 17): identical workload to
    tf_train (same shapes, same analytic MFU numerator) with the ffn
    and projection fc+bias+act chains IR-fused onto fc_epilogue ops
    (transpiler/epilogue_transpiler.py) and routed through the Pallas
    fused matmul kernel (ops/epilogue.py).  Separate leg so the ladder
    banks both sides of the A/B."""
    kw.setdefault("fc_epilogue", True)
    return bench_transformer_train(**kw)


def bench_transformer_train_gspmd(batch=32, seq=512, chain=30, tp=2):
    """Transformer-base train as ONE pjit program over every attached
    device (ISSUE 8): dp x tp MeshPlan, ZeRO-3 + Megatron tp as
    PartitionSpecs, flash under shard_map.  Same analytic MFU
    numerator as the baseline leg over the GLOBAL batch, so the row
    reads as achieved fraction of the whole fleet's peak — the
    "v5p-64 at >=50% MFU" end state's measurement shape."""
    import jax

    fn, state, feed, loss_name = _build_transformer_train(
        batch, seq, gspmd=True, tp=tp)
    sec_per_step, _ = _chain_timed(fn, state, feed, loss_name, chain)
    toks_per_sec = batch * seq / sec_per_step
    c = TRANSFORMER_BASE
    n_params = _transformer_n_params(seq, **c)
    ndev = len(jax.devices())
    peak, kind = _chip_peak_flops()
    fpt = _transformer_train_flops_per_token(
        n_params, c["d_model"], c["n_layer"], seq)
    # fleet MFU: the numerator is the whole model's step FLOPs, the
    # denominator every attached chip's peak
    mfu = fpt * toks_per_sec / (peak * ndev)
    tp_eff = max(1, min(int(tp), ndev))
    while ndev % tp_eff != 0:
        tp_eff -= 1
    return {
        "tokens_per_sec": round(toks_per_sec, 0),
        "samples_per_sec": round(batch / sec_per_step, 2),
        "step_ms": round(sec_per_step * 1e3, 3),
        "mfu_pct": round(100 * mfu, 2),
        "batch": batch,
        "seq": seq,
        "device": kind,
        "devices": ndev,
        "gspmd": True,
        "dp": ndev // tp_eff,
        "tp": tp_eff,
    }


# BERT-base config shared by the builder and the FLOPs accounting (one
# source of truth, like TRANSFORMER_BASE)
BERT_BASE = dict(d_model=768, n_layer=12, d_inner=3072, vocab=30522)


def _build_bert_train(batch=8, seq=512):
    """Build + init the BERT-base bench train step; returns
    (fn, state, feed, loss_name) — shared with the lowering gate."""
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
    # same AMP story as the transformer bench: bf16 activations, fp32
    # master weights, static scaling (bf16 keeps fp32's exponent range)
    decorate(optimizer.Adam(learning_rate=1e-4), init_loss_scaling=1.0,
             use_dynamic_loss_scaling=False).minimize(model["loss"])
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    compiled = fluid.CompiledProgram(framework.default_main_program())

    feed = {k: jax.device_put(jnp.asarray(v))
            for k, v in bert_inputs_synthetic(batch, seq, vocab).items()}
    fn, state = _build_compiled_fn(compiled, feed, [model["loss"].name])
    return fn, state, feed, model["loss"].name


def bench_bert_train(batch=8, seq=512, chain=20):
    """BASELINE workload 4: BERT-base pretraining seq-512 (MLM+NSP)."""
    c = BERT_BASE
    d_model, n_layer, d_inner, vocab = (c["d_model"], c["n_layer"],
                                        c["d_inner"], c["vocab"])
    fn, state, feed, loss_name = _build_bert_train(batch, seq)
    sec_per_step, _ = _chain_timed(fn, state, feed, loss_name, chain)
    toks_per_sec = batch * seq / sec_per_step
    # embeddings + per-layer attn/FFN + the untied MLM decoder
    # projection (d_model*vocab) — same accounting as the transformer
    # bench so the two MFU numbers are comparable
    n_params = (vocab * d_model + seq * d_model + 2 * d_model
                + n_layer * (4 * d_model * d_model
                             + 2 * d_model * d_inner)
                + d_model * vocab)
    peak, kind = _chip_peak_flops()
    fpt = _transformer_train_flops_per_token(n_params, d_model, n_layer,
                                             seq)
    mfu = fpt * toks_per_sec / peak
    return {"tokens_per_sec": round(toks_per_sec, 1),
            "step_ms": round(sec_per_step * 1e3, 3),
            "mfu_pct": round(100 * mfu, 2),
            "batch": batch, "seq": seq, "device": kind}


def _deepfm_train_flops_per_example(num_fields=26, embed_dim=16,
                                    dense_dim=13,
                                    hidden=(400, 400, 400)):
    """Analytic DeepFM train FLOPs/example (3x fwd, 2*MACs), closed
    form from the deepfm_model defaults — like every other leg, NOT
    XLA cost analysis, so fusion tricks can't inflate MFU.  MLP MACs
    dominate; the FM/embedding elementwise terms ride along for
    honesty (~1% of the total)."""
    mlp_in = num_fields * embed_dim + dense_dim
    macs = 0
    prev = mlp_in
    for w in hidden:
        macs += prev * w
        prev = w
    macs += prev * 1
    # FM second order: square/sum over [F, E] twice + first-order sum
    fm_elem = 3 * num_fields * embed_dim + 2 * embed_dim + num_fields
    return 3 * (2.0 * macs + fm_elem)


def _build_deepfm_train(batch=2048):
    """Build + init the DeepFM bench train step; returns
    (fn, state, feed, loss_name) — shared with the lowering gate."""
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


def bench_deepfm_train(batch=2048, chain=30):
    """BASELINE workload 5: DeepFM CTR (sparse lookup + dense DNN).

    The row carries its roofline context (VERDICT r5 next-round #7):
    MFU from the analytic MLP/FM FLOPs (tiny — CTR is not a FLOPs
    workload) and the achieved-vs-peak HBM BW% from the compiled
    step's bytes accessed — the bound that actually prices the
    embedding gather/scatter + optimizer traffic this leg is made of.
    tools/hlo_traffic.py --model deepfm names the per-op consumers."""
    fn, state, feed, loss_name = _build_deepfm_train(batch)
    # bytes accessed of the EXACT compiled step (the jit cache reuses
    # this compile for the timed calls)
    bytes_step = None
    try:
        ca = fn.lower(state, feed).compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        bytes_step = float(ca.get("bytes accessed", 0.0)) or None
    except Exception:  # noqa: BLE001 — roofline is best-effort context
        pass
    sec_per_step, _ = _chain_timed(fn, state, feed, loss_name, chain)
    eps = batch / sec_per_step
    peak, kind = _chip_peak_flops()
    mfu = _deepfm_train_flops_per_example() * eps / peak
    res = {"examples_per_sec": round(eps, 1),
           "step_ms": round(sec_per_step * 1e3, 3), "batch": batch,
           "mfu_pct": round(100 * mfu, 3),
           "device": kind}
    if bytes_step:
        bw, _ = _chip_peak_bw()
        res["hbm_gb_per_step"] = round(bytes_step / 1e9, 3)
        res["hbm_bw_pct"] = round(
            100 * bytes_step / sec_per_step / bw, 2)
    return res


def _build_infer(model_builder, feed_builder, fetch_key,
                 conv_epilogue=False):
    """Shared bf16-inference build: build through the IR, clone for
    test, NHWC + bf16 transpile, compile.  Returns
    (fn, state, feed, fetch_name) — shared with the lowering gate.

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


def _bench_infer(model_builder, feed_builder, fetch_key, chain,
                 conv_epilogue=False):
    fn, state, feed, fetch_name = _build_infer(
        model_builder, feed_builder, fetch_key,
        conv_epilogue=conv_epilogue)
    sec_per_step, _ = _chain_timed(fn, state, feed, fetch_name, chain)
    return sec_per_step


def bench_resnet50_infer(batch=128, chain=100, conv_epilogue=False):
    """Round-1 anchor: bf16 inference vs the reference's V100 fp16
    headline (float16_benchmark.md:42-44).  conv_epilogue=True runs
    the conv-bn-folded + fully-fused graph through the Pallas fused
    conv kernel (the A/B lever)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.resnet import resnet50

    rng = np.random.RandomState(0)

    def feed():
        return {
            "image": jax.device_put(jnp.asarray(
                rng.rand(batch, 3, 224, 224).astype(np.float32),
                jnp.bfloat16)),
            "label": jax.device_put(np.zeros((batch, 1), np.int64)),
        }

    sec = _bench_infer(lambda: resnet50(is_test=True), feed, "logits",
                       chain, conv_epilogue=conv_epilogue)
    res = {"ms_per_batch": round(sec * 1e3, 3), "batch": batch}
    if conv_epilogue:
        res["conv_epilogue"] = True
    return res


def bench_vgg16_infer(batch=64, chain=60):
    """The reference's HEADLINE fp16 benchmark network
    (float16_benchmark.md:23-25: VGG16 ImageNet fp16 mb=1 3.32 ms,
    mb=64 60.23 ms on V100) — bf16 on TPU via the same transpiles."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.vgg import vgg16

    rng = np.random.RandomState(0)

    def feed():
        return {"image": jax.device_put(jnp.asarray(
            rng.rand(batch, 3, 224, 224).astype(np.float32),
            jnp.bfloat16))}

    sec = _bench_infer(lambda: vgg16(is_test=True), feed, "logits",
                       chain)
    return {"ms_per_batch": round(sec * 1e3, 3), "batch": batch}


def bench_vgg16_cifar_infer(batch=512, chain=60):
    """The reference's cifar10 fp16 table (float16_benchmark.md:61-63:
    VGG16 cifar10 fp32 44.97 / fp16 17.37 ms at mb=512 on V100) —
    bf16 on TPU via the same transpiles as the ImageNet legs."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.vgg import vgg

    rng = np.random.RandomState(0)

    def feed():
        return {"image": jax.device_put(jnp.asarray(
            rng.rand(batch, 3, 32, 32).astype(np.float32),
            jnp.bfloat16))}

    sec = _bench_infer(
        lambda: vgg(16, class_dim=10, img_shape=(3, 32, 32),
                    is_test=True),
        feed, "logits", chain)
    return {"ms_per_batch": round(sec * 1e3, 3), "batch": batch}


def bench_resnet32_cifar_infer(batch=512, chain=100):
    """The reference's cifar10 fp16 table (float16_benchmark.md:72-74:
    ResNet32 cifar10 fp32 21.16 / fp16 11.02 ms at mb=512 on V100)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.resnet import resnet_cifar10

    rng = np.random.RandomState(0)

    def feed():
        return {
            "image": jax.device_put(jnp.asarray(
                rng.rand(batch, 3, 32, 32).astype(np.float32),
                jnp.bfloat16)),
            "label": jax.device_put(np.zeros((batch, 1), np.int64)),
        }

    sec = _bench_infer(lambda: resnet_cifar10(is_test=True), feed,
                       "logits", chain)
    return {"ms_per_batch": round(sec * 1e3, 3), "batch": batch}


def bench_resnet50_infer_int8(batch=128, chain=100, fold=True,
                              int8_activations=False):
    """True-int8 inference (round-3 verdict do-this #3; reference
    inference/tests/api/int8_mkldnn_quantization.md): every conv/mul
    executes on int8 operands with int32 accumulation
    (convert_to_int8_execution), not dequantize-then-bf16.
    fold=False skips the conv+bn fold (the A/B lever).
    int8_activations=True is the ISSUE-5 interlayer mode: fused
    requantize epilogues keep the activations int8 ACROSS layer
    boundaries (the ~30% traffic cut on this HBM-bound row)."""
    fn, state, feed, fetch_name, n_q, calib, _prog = \
        _build_resnet50_infer_int8(batch, fold=fold,
                                   int8_activations=int8_activations)
    sec_per_step, _ = _chain_timed(fn, state, feed, fetch_name, chain)
    res = {"ms_per_batch": round(sec_per_step * 1e3, 3),
           "batch": batch,
           "n_int8_params": n_q,
           # calibration coverage rides in the row so a 'calibrated'
           # label can never again hide a silent dynamic-scale
           # fallback (ADVICE r5)
           **calib}
    if fold:
        res["conv_bn_folded"] = True
    if int8_activations:
        res["int8_interlayer"] = True
    return res


def bench_resnet50_infer_int8_interlayer(batch=128, chain=100,
                                         fold=True):
    """ISSUE-5 leg: same workload as the calibrated/folded int8 rows
    with int8 activations flowing BETWEEN layers (fused per-channel
    requantize through the folded-BN shift and ReLU) — the structural
    cut ROADMAP names for the HBM-bound int8 infer row."""
    return bench_resnet50_infer_int8(batch, chain, fold=fold,
                                     int8_activations=True)


def _build_resnet50_infer_int8(batch=128, fold=True,
                               int8_activations=False):
    """Build + init the true-int8 ResNet-50 inference path; returns
    (fn, state, feed, fetch_name, n_int8_params, calib_stats,
    infer_prog) — shared with the lowering gate ([:3]) and
    tools/hlo_traffic.py --int8-interlayer (which needs the program
    for the op-boundary traffic model)."""
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
    if fold:
        # fold conv+bn BEFORE quantizing (same as the reference int8
        # pipeline): the BN scale/shift lands in the conv weights, so
        # the int8 graph loses ~53 elementwise BN ops and the
        # per-channel weight scales absorb the fold exactly
        InferenceTranspiler().transpile(
            infer_prog, protected=[model["logits"].name])
    nhwc_transpile(infer_prog)
    qw = quantize_weights_abs_max(infer_prog, global_scope())
    # calibrate per-tensor activation scales on a small batch so every
    # conv gets a static InScale: the dynamic-scale path re-reads each
    # activation for its max-reduction, which made the first on-chip
    # int8 row 2x slower than bf16 (2026-08-01); bf16 inter-layer
    # activations halve the remaining traffic
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
    # calibration-coverage gate (ADVICE r5): post_training_quantize
    # silently records scale 0.0 (-> the 2x-slower dynamic
    # max-reduction path) for any activation the executor did not
    # retain; the row must SAY how many converted ops actually carry a
    # static InScale, and a scope-retention regression must fail loud
    # here instead of shipping a mislabelled 'calibrated' number
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
            "to the dynamic max-reduction path the calibrated row "
            "exists to avoid)" % (n_cal, len(int8_ops)))
    if int8_activations:
        # interlayer fold coverage, counted+asserted like the InScale
        # check above: an 'interlayer' label on a row where most edges
        # silently stayed bf16/f32 would misprice the structural cut.
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
                "while the row claims 'interlayer'" %
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
    return (fn, state, feed, model["logits"].name, len(qw), calib,
            infer_prog)


def _build_longctx_train(batch=1, heads=8, seq=32768, head_dim=64,
                         block_q=None, block_k=None,
                         packed_stats=False, head_pack=False):
    """Build the long-context attention step: flash fwd+bwd at 64x the
    reference's sequence ceiling (BERT seq-512, SURVEY §5 long-context
    row).  Unfused attention at seq 32k materializes an ~34 GB fp32
    score matrix (8 heads x 32768^2 x 4 B) — over twice the chip's
    16 GB HBM before backward even starts; this workload exists
    because the Pallas kernel keeps scores in VMEM.  Returns
    (fn, state, feed, fetches)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import backward, framework, layers

    _fresh_programs()
    # A/B levers: the flash memory-layout variants (packed [T/128,128]
    # row-stats; two d<=64 heads per grid block — ops/pallas_kernels.py,
    # docs/FLASH_ATTENTION.md).  Always set explicitly, like
    # conv_epilogue: "off" is the default graph, not "whatever a
    # previous in-process build left behind"
    from paddle_tpu.flags import set_flags

    set_flags({"flash_packed_stats": "on" if packed_stats else "off",
               "flash_head_pack": "on" if head_pack else "off"})
    qkv = []
    for n in "qkv":
        x = layers.data(n, shape=[heads, seq, head_dim],
                        dtype="bfloat16")
        x.stop_gradient = False
        qkv.append(x)
    out = layers.flash_attention(*qkv, causal=True, block_q=block_q,
                                 block_k=block_k)
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


def bench_longctx_train_d128(head_dim=128, **kw):
    """LLM-style head width (d=128, e.g. LLaMA-family): doubles the
    MXU work per softmax element relative to the d=64 leg, so the
    flash kernel's achievable MFU ceiling is ~2x higher.  All other
    defaults forward to bench_longctx_train — one source of truth."""
    return bench_longctx_train(head_dim=head_dim, **kw)


def _resolved_block(seq):
    """What an unset block_q/block_k actually resolves to in the
    kernel — keeps banked rows honest when only one block is pinned."""
    from paddle_tpu.ops.pallas_kernels import _default_block

    return _default_block(seq)


def bench_longctx_train(batch=1, heads=8, seq=32768, head_dim=64,
                        chain=10, block_q=None, block_k=None,
                        packed_stats=False, head_pack=False):
    """Long-context attention: tokens/sec + kernel MFU for causal
    flash attention fwd+bwd at seq 32k on one chip.

    packed_stats=True runs the packed row-stats layout (the seq-1M
    enabler: drops ~12 GB of lane replication at 1M x 8 heads);
    head_pack=True packs two d<=64 heads per kernel block (the d64
    ladder re-key).  Both default off — the plain legs stay the
    banked A/B baselines."""
    fn, state, feed, fetches = _build_longctx_train(
        batch, heads, seq, head_dim, block_q=block_q, block_k=block_k,
        packed_stats=packed_stats, head_pack=head_pack)
    sec_per_step, _ = _chain_timed(fn, state, feed, fetches[0], chain)
    toks_per_sec = batch * seq / sec_per_step
    peak, kind = _chip_peak_flops()
    # causal fwd = 2*B*H*T^2*D (half the full 4BHT^2D); train = 3x fwd.
    # The kernel actually recomputes scores in backward (7 matmuls vs
    # the standard 5) but recompute earns no MFU credit, same rule as
    # the model benches.
    flops = 3 * 2.0 * batch * heads * float(seq) ** 2 * head_dim
    mfu = flops / sec_per_step / peak
    res = {
        "tokens_per_sec": round(toks_per_sec, 1),
        "step_ms": round(sec_per_step * 1e3, 3),
        "mfu_pct": round(100 * mfu, 2),
        "batch": batch, "seq": seq, "heads": heads,
        "head_dim": head_dim,
        **({"block_q": block_q or _resolved_block(seq),
            "block_k": block_k or _resolved_block(seq)}
           if block_q or block_k else {}),
        "device": kind,
    }
    # variant markers ride in the row (the re-key rule: a dashboard
    # diffing rounds must never read a layout flip as a same-graph
    # perf change) — _workload_sig keys on them too
    if packed_stats:
        res["packed_stats"] = True
    if head_pack:
        res["head_pack"] = True
    return res


def _build_serving_tp_sharded(batch=8, in_dim=256, hidden=1024,
                              depth=3, out_dim=256, tp=2,
                              devices=None):
    """Build the tp-sharded serving-inference step (ISSUE 14): an fc
    chain annotated COLUMN-parallel over a dp1 x tp mesh slice
    (parallel/gspmd.annotate_tp_inference — every weight dim-sharded
    on its output dim, contractions full-width so sharded output is
    bit-identical to unsharded) compiled as ONE jit with in/out
    NamedShardings through CompiledProgram.with_sharding_rules — the
    exact graph a mesh-sliced ReplicaPool replica serves.  Returns
    (fn, state, feed, aux); shared with tools/tpu_lowering_check.py
    so the gate cross-lowers exactly the program the bench times.
    tp clamps to the device count (1-device degrade keeps the leg an
    honest liveness check everywhere).  `devices` as in
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
        x = layers.data("x", shape=[in_dim], dtype="float32")
        h = x
        for _ in range(int(depth)):
            h = layers.fc(h, size=hidden, act="relu")
        pred = layers.fc(h, size=out_dim)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(framework.default_startup_program())
        infer_prog = framework.default_main_program().clone(
            for_test=True)
        devices = list(devices or jax.devices())
        tp_eff = max(1, min(int(tp), len(devices)))
        plan = MeshPlan(dp=1, tp=tp_eff)
        annotated = annotate_tp_inference(infer_prog, plan)
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
        feed = {"x": jnp.asarray(
            rng.rand(batch, in_dim).astype(np.float32))}
        fn, state = _build_compiled_fn(compiled, feed, [pred.name])
        aux = {"annotated": annotated, "tp": tp_eff,
               "fetch": pred.name}
        return fn, state, feed, aux
    finally:
        set_flags({"serving_sharded": False})


def bench_serving_tp_sharded(batch=8, in_dim=256, hidden=1024,
                             depth=3, out_dim=256, tp=2, chain=30):
    """Mesh-sliced serving replica leg (ISSUE 14): latency of the
    tp-sharded inference step — every fc weight dim-sharded
    column-parallel across the slice, activations all-gathered
    between layers by the XLA SPMD partitioner.  On a single chip the
    mesh degrades to tp1 (the row then prices the sharded compile
    path ≈ parity); a multi-chip window banks the real above-one-HBM
    serving row.  Compare against the unsharded serving_load
    time-per-batch at the same shape: the per-layer all-gather is
    the price of fitting the model, the verdict is how small it is."""
    import jax

    fn, state, feed, aux = _build_serving_tp_sharded(
        batch=batch, in_dim=in_dim, hidden=hidden, depth=depth,
        out_dim=out_dim, tp=tp)
    sec_per_step, _ = _chain_timed(fn, state, feed, aux["fetch"],
                                   chain)
    return {"ms_per_batch": round(sec_per_step * 1e3, 3),
            "batch": batch, "in_dim": in_dim, "hidden": hidden,
            "depth": depth, "out_dim": out_dim,
            "tp": aux["tp"], "devices": len(jax.devices()),
            "serving_sharded": True,
            "annotated_params": len(aux["annotated"])}


def _build_llm_decode(streams=8, prefill_len=128, gen_tokens=64,
                      heads=8, head_dim=128, page_size=128,
                      vocab=32000, kv_int8=False, head_pack=False,
                      dtype=None, seed=0, impl=None, spec_k=0,
                      prefix_share=0, disagg=False):
    """Build ONE jitted continuous-decode step (ISSUE 7): token embed +
    qkv projections + the paged KV append scatter + flash_decode over
    the block-table page pool + the output projection + greedy argmax —
    the device half of what serving/decode_engine.py runs per
    iteration.  Returns (fn, state, feed, aux): fn(state, feed) ->
    (new_state, next_tokens); state carries the page pools, feed the
    per-step indices.  Shared with tools/tpu_lowering_check.py so the
    gate cross-lowers exactly the graph the bench times.

    Streams own static contiguous page ranges (stream s -> pages
    [s*mp, (s+1)*mp)) with seeded RAGGED prefill lengths in
    [prefill_len/2, prefill_len] — the kernel still walks the block
    table page-by-page, but the timed loop pays zero allocator churn
    (allocation/retire dynamics are tools/serving_load.py --mode
    decode's job).

    spec_k > 0 builds the SPECULATIVE VERIFY step instead (ISSUE
    11c): feed carries the k+1-token window per stream (tokens /
    page_ids / offsets all [streams, k+1]) and the step appends the
    whole window then scores every row in ONE q-len-(k+1)
    flash_decode — fn returns next-token picks [streams, k+1].

    prefix_share > 0 makes every stream's first prefix_share prompt
    tokens IDENTICAL and their pages PHYSICALLY SHARED (ISSUE 11b:
    one page set, written once, in every block table — the
    serving-side radix-tree outcome expressed as static tables), so
    the pool holds shared + per-stream-tail pages instead of
    streams x full-length (rounded down to full pages).

    disagg=True (ISSUE 14) lays the block tables out the way the
    DISAGGREGATED prefill tier leaves them: pages allocated in
    prefill-completion order, round-robin ACROSS streams, so each
    stream's page list is strided through the pool instead of
    contiguous — the fragmentation pattern page-list handoff
    produces.  Same kernel, same shapes; the row prices the decode
    sweep under handoff-fragmented tables vs the contiguous
    llm_decode row (expect ~parity: the kernel gathers pages through
    the table either way — banking that IS the evidence)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_kv import kv_scales_of, quantize_kv
    from paddle_tpu.ops.pallas_kernels import flash_decode
    from paddle_tpu.serving.decode_engine import TinyDecodeLM

    dtype = dtype or jnp.float32
    model = TinyDecodeLM(vocab=vocab, d_model=heads * head_dim,
                         num_heads=heads, head_dim=head_dim,
                         seed=seed, dtype=dtype)
    rng = np.random.RandomState(seed)
    shared_tokens = (prefix_share // page_size) * page_size
    n_sp = shared_tokens // page_size            # shared pages
    spec_margin = (spec_k + 1) * (gen_tokens + 1) if spec_k else 0
    max_len = prefill_len + gen_tokens + spec_margin + 4
    mp = -(-max_len // page_size)                # private pages/stream
    num_pages = n_sp + streams * mp
    tables_np = np.zeros((streams, n_sp + mp), np.int32)
    tables_np[:, :n_sp] = np.arange(n_sp, dtype=np.int32)[None, :]
    if disagg:
        # handoff fragmentation: stream s owns pages s, s+streams,
        # s+2*streams, ... (prefill-completion order round-robin)
        tables_np[:, n_sp:] = n_sp + np.arange(
            streams * mp, dtype=np.int32).reshape(mp, streams).T
    else:
        tables_np[:, n_sp:] = n_sp + np.arange(
            streams * mp, dtype=np.int32).reshape(streams, mp)
    lens0 = (shared_tokens + rng.randint(
        max(1, prefill_len // 2), prefill_len + 1,
        size=streams)).astype(np.int32)
    store = jnp.int8 if kv_int8 else dtype
    k_pages = jnp.zeros((num_pages, heads, page_size, head_dim), store)
    v_pages = jnp.zeros((num_pages, heads, page_size, head_dim), store)
    kv_scales = None
    shared_prompt = rng.randint(2, vocab, size=shared_tokens) \
        if shared_tokens else None

    def write_pages(kp, vp, k, v, pids, first_off=0):
        # page-by-page pool writes of [T, H, d] rows along pids
        w = 0
        off = first_off
        for pid in pids:
            n = min(page_size - off, k.shape[0] - w)
            if n <= 0:
                break
            kp = kp.at[int(pid), :, off:off + n, :].set(
                jnp.transpose(k[w:w + n], (1, 0, 2)))
            vp = vp.at[int(pid), :, off:off + n, :].set(
                jnp.transpose(v[w:w + n], (1, 0, 2)))
            w += n
            off = 0
        return kp, vp

    def store_kv(k, v):
        nonlocal kv_scales
        if kv_int8:
            if kv_scales is None:
                kv_scales = (kv_scales_of(k), kv_scales_of(v))
            return (quantize_kv(k, kv_scales[0]),
                    quantize_kv(v, kv_scales[1]))
        return k.astype(store), v.astype(store)

    if shared_tokens:
        # the shared prefix is computed + written ONCE — the
        # amortized-to-zero prefill the sharing leg measures
        _, k, v = model.qkv(shared_prompt.astype(np.int32))
        k, v = store_kv(k, v)
        k_pages, v_pages = write_pages(k_pages, v_pages, k, v,
                                       tables_np[0, :n_sp])
    for s in range(streams):
        tail = int(lens0[s]) - shared_tokens
        prompt = rng.randint(2, vocab, size=tail)
        _, k, v = model.qkv(prompt.astype(np.int32))
        k, v = store_kv(k, v)
        k_pages, v_pages = write_pages(k_pages, v_pages, k, v,
                                       tables_np[s, n_sp:])

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
                           impl=impl, head_pack=head_pack,
                           kv_scales=kv_scales)
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
    aux = {"lens0": lens0, "tables_np": tables_np, "model": model,
           "kv_scales": kv_scales, "page_size": page_size,
           "kv_itemsize": jnp.dtype(store).itemsize,
           "num_pages": num_pages, "shared_tokens": shared_tokens,
           "disagg": bool(disagg),
           # what the pool would need with every stream owning its
           # own copy of the shared prefix
           "unshared_pages": streams * (n_sp + mp)}
    return jax.jit(step), state, feed, aux


def bench_llm_decode(streams=64, prefill_len=128, gen_tokens=32,
                     heads=8, head_dim=128, page_size=128,
                     vocab=32000, kv_int8=False, head_pack=False,
                     warmup=2, chain=None, prefix_share=0,
                     disagg=False):
    """LLM continuous-decode leg (ISSUE 7): tokens/s/chip and
    inter-token p50/p99 at `streams` concurrent ragged sequences,
    decoding through the paged KV-cache + flash_decode step.  Every
    step blocks on its next-token output (the engine needs the token
    host-side to detect eos — the sync IS part of real inter-token
    latency).  Decode is K/V-streaming bound, so the row carries the
    analytic KV-traffic roofline (kv_gb_per_step, kv_bw_pct) next to
    the rate, the DeepFM-roofline convention.  `chain` is accepted for
    ladder uniformity and maps onto gen_tokens."""
    import jax.numpy as jnp

    if chain:
        gen_tokens = int(chain)
    fn, state, feed, aux = _build_llm_decode(
        streams=streams, prefill_len=prefill_len,
        gen_tokens=gen_tokens + warmup, heads=heads,
        head_dim=head_dim, page_size=page_size, vocab=vocab,
        kv_int8=kv_int8, head_pack=head_pack,
        prefix_share=prefix_share, disagg=disagg)
    lens = aux["lens0"].copy()
    tables_np = aux["tables_np"]
    tables_dev = feed["tables"]
    tokens = np.asarray(feed["tokens"])
    times = []
    kv_bytes = 0.0
    for i in range(gen_tokens + warmup):
        idx = np.arange(streams)
        feed_i = {
            "tokens": jnp.asarray(tokens),
            "page_ids": jnp.asarray(
                tables_np[idx, lens // page_size]),
            "offsets": jnp.asarray(lens % page_size),
            "tables": tables_dev,
            "lens": jnp.asarray(lens + 1),
        }
        t0 = time.perf_counter()
        state, nxt = fn(state, feed_i)
        tokens = np.asarray(nxt)          # sync: the inter-token beat
        dt = time.perf_counter() - t0
        lens += 1
        if i >= warmup:
            times.append(dt)
            # the kernel streams every LIVE page of K and V per step
            pages_live = np.sum(-(-lens // page_size))
            kv_bytes += (2.0 * pages_live * page_size * heads *
                         head_dim * aux["kv_itemsize"])
    total = sum(times)
    lat_ms = sorted(t * 1e3 for t in times)

    def pct(p):
        return lat_ms[min(len(lat_ms) - 1, int(p / 100 * len(lat_ms)))]

    peak_bw, kind = _chip_peak_bw()
    res = {
        "tokens_per_sec": round(streams * len(times) / total, 1),
        "inter_token_p50_ms": round(pct(50), 3),
        "inter_token_p99_ms": round(pct(99), 3),
        "streams": streams,
        "prefill_len": prefill_len,
        "gen_tokens": len(times),
        "heads": heads,
        "head_dim": head_dim,
        "page_size": page_size,
        "paged": True,
        "kv_gb_per_step": round(kv_bytes / max(len(times), 1) / 1e9,
                                4),
        "kv_bw_pct": round(100 * kv_bytes / total / peak_bw, 2),
        "device": kind,
    }
    if kv_int8:
        res["kv_int8"] = True
    if head_pack:
        res["head_pack"] = True
    if disagg:
        # ISSUE 14: decode throughput under handoff-fragmented block
        # tables (pages strided across the pool in prefill-completion
        # order) — the disaggregated tier's steady state
        res["disagg"] = True
    if prefix_share:
        # the capacity win of prefix sharing (ISSUE 11b): one shared
        # page set in every table instead of per-stream copies —
        # tokens/s is expected ~flat (the kernel still streams shared
        # pages per stream), the pool shrinks
        res["prefix_shared"] = aux["shared_tokens"]
        res["pool_pages"] = aux["num_pages"]
        res["pool_pages_unshared_equiv"] = aux["unshared_pages"]
    return res


def bench_llm_decode_spec(streams=64, spec_k=4, prefill_len=128,
                          gen_tokens=32, heads=8, head_dim=128,
                          page_size=128, vocab=32000, draft_heads=2,
                          draft_head_dim=16, warmup=2, chain=None):
    """Lossless speculative decoding leg (ISSUE 11c): a small draft
    model (its own paged pool) proposes ``spec_k`` tokens per
    iteration, the target model scores the k+1-token window in ONE
    q-len-(k+1) flash_decode verify sweep, greedy acceptance
    (decode.spec_accept_length) takes the longest agreeing prefix and
    the rejected tail is a pure length rewind (static page ranges —
    the engine-side truncate expressed as arithmetic).  Headline:
    EMITTED tokens/s x the measured acceptance rate, reported
    together — the verdict is their product, not either alone.
    `chain` maps onto gen_tokens (verify iterations) for ladder
    uniformity."""
    import jax.numpy as jnp

    from paddle_tpu.decode import spec_accept_length

    if chain:
        gen_tokens = int(chain)
    iters = gen_tokens + warmup
    r = spec_k + 1
    vfn, vstate, vfeed, vaux = _build_llm_decode(
        streams=streams, prefill_len=prefill_len, gen_tokens=iters,
        heads=heads, head_dim=head_dim, page_size=page_size,
        vocab=vocab, spec_k=spec_k)
    # the draft decodes the SAME prompts (same seed -> same token
    # stream) through its own small model + pool; q-len-1 step
    dfn, dstate, dfeed, daux = _build_llm_decode(
        streams=streams, prefill_len=prefill_len,
        gen_tokens=(iters + 1) * r, heads=draft_heads,
        head_dim=draft_head_dim, page_size=page_size, vocab=vocab)
    tables_v = vfeed["tables"]
    tables_d = dfeed["tables"]
    tv_np, td_np = vaux["tables_np"], daux["tables_np"]
    lens_v = vaux["lens0"].copy()
    lens_d = daux["lens0"].copy()
    assert np.array_equal(lens_v, lens_d)  # same seeded prompts
    pending = np.asarray(dfeed["tokens"]).copy()
    idx = np.arange(streams)
    rpos = np.arange(r, dtype=np.int32)
    times, emitted_total, agreed_total, proposed_total = [], 0, 0, 0
    for i in range(iters):
        t0 = time.perf_counter()
        # draft phase: k sequential q-len-1 proposals
        proposals = np.zeros((streams, spec_k), np.int32)
        cur = pending.copy()
        dl = lens_d.copy()
        for j in range(spec_k):
            dfeed_i = {
                "tokens": jnp.asarray(cur),
                "page_ids": jnp.asarray(td_np[idx, dl // page_size]),
                "offsets": jnp.asarray(dl % page_size),
                "tables": tables_d,
                "lens": jnp.asarray(dl + 1),
            }
            dstate, nxt = dfn(dstate, dfeed_i)
            cur = np.asarray(nxt)
            proposals[:, j] = cur
            dl += 1
        # verify phase: ONE q-len-(k+1) sweep over [pending, d_1..d_k]
        window = np.concatenate([pending[:, None], proposals], axis=1)
        pos = lens_v[:, None] + rpos[None, :]
        vfeed_i = {
            "tokens": jnp.asarray(window.astype(np.int32)),
            "page_ids": jnp.asarray(
                tv_np[idx[:, None], pos // page_size]),
            "offsets": jnp.asarray(pos % page_size),
            "tables": tables_v,
            "lens": jnp.asarray(lens_v + r),
        }
        vstate, tgt = vfn(vstate, vfeed_i)
        targets = np.asarray(tgt)              # sync: the verify beat
        dt = time.perf_counter() - t0
        # acceptance + length rewind (host arithmetic on the static
        # page ranges; overwrites at the same offsets next round)
        n_emits = np.zeros((streams,), np.int32)
        for s in range(streams):
            m = spec_accept_length(proposals[s], targets[s])
            n_emits[s] = m + 1
            agreed_total += m
            pending[s] = targets[s, m]
        proposed_total += spec_k * streams
        lens_v += n_emits
        # draft catch-up: one append step realigns the draft cache —
        # a full-acceptance stream is owed the d_k row (at its
        # base + k slot); any other stream's write lands one PAST its
        # new end, exactly where the next round's pending overwrites
        # it
        pos_c = lens_d + np.where(n_emits == r, spec_k, n_emits)
        lens_d = lens_d + n_emits
        dfeed_c = {
            "tokens": jnp.asarray(proposals[:, -1]),
            "page_ids": jnp.asarray(
                td_np[idx, pos_c // page_size]),
            "offsets": jnp.asarray(pos_c % page_size),
            "tables": tables_d,
            "lens": jnp.asarray(lens_d),
        }
        dstate, _ = dfn(dstate, dfeed_c)
        if i >= warmup:
            times.append(dt)
            emitted_total += int(n_emits.sum())
    total = sum(times)
    lat_ms = sorted(t * 1e3 for t in times)

    def pct(p):
        return lat_ms[min(len(lat_ms) - 1, int(p / 100 * len(lat_ms)))]

    acceptance = agreed_total / max(1, proposed_total)
    peak_bw, kind = _chip_peak_bw()
    return {
        "tokens_per_sec": round(emitted_total / total, 1)
        if total else 0.0,
        "acceptance_rate": round(acceptance, 4),
        "emitted_per_iter": round(
            emitted_total / max(1, len(times)) / streams, 3),
        "iter_p50_ms": round(pct(50), 3),
        "iter_p99_ms": round(pct(99), 3),
        "streams": streams,
        "spec_k": spec_k,
        "prefill_len": prefill_len,
        "verify_iters": len(times),
        "heads": heads,
        "head_dim": head_dim,
        "draft_heads": draft_heads,
        "draft_head_dim": draft_head_dim,
        "page_size": page_size,
        "paged": True,
        "device": kind,
    }


def bench_llm_decode_chunked_join(streams=16, join_prompt=32768,
                                  chunk=512, prefill_len=128,
                                  gen_tokens=64, heads=8,
                                  head_dim=128, page_size=128,
                                  vocab=32000, warmup=2, chain=None):
    """Chunked-prefill join leg (ISSUE 11a): ``streams`` sequences
    decode steadily while ONE ``join_prompt``-token prompt prefills in
    fixed ``chunk``-token slices INTERLEAVED with their decode steps —
    the row's verdict is the running streams' inter-token p99 DURING
    the join vs after it (the 32k-join-never-stretches-p99 claim,
    measured; the serving-side SLO assertion lives in
    tests/test_decode_act2.py).  chunk must be a page_size multiple
    (aligned page writes).  `chain` maps onto gen_tokens."""
    import jax
    import jax.numpy as jnp

    if chain:
        gen_tokens = int(chain)
    if chunk % page_size:
        raise ValueError("chunk must be a multiple of page_size")
    fn, state, feed, aux = _build_llm_decode(
        streams=streams, prefill_len=prefill_len,
        gen_tokens=gen_tokens + warmup, heads=heads,
        head_dim=head_dim, page_size=page_size, vocab=vocab)
    model = aux["model"]
    tables_np = aux["tables_np"]
    lens = aux["lens0"].copy()
    # the joiner owns its own page range appended past the pool — the
    # running streams' tables never see it until the join completes
    join_pages = -(-(join_prompt + gen_tokens + 4) // page_size)
    base_pages = aux["num_pages"]
    store = state["k_pages"].dtype
    state = {
        "k_pages": jnp.concatenate(
            [state["k_pages"],
             jnp.zeros((join_pages,) + state["k_pages"].shape[1:],
                       store)]),
        "v_pages": jnp.concatenate(
            [state["v_pages"],
             jnp.zeros((join_pages,) + state["v_pages"].shape[1:],
                       store)]),
    }
    rng = np.random.RandomState(7)
    join_tokens = rng.randint(2, vocab, size=join_prompt) \
        .astype(np.int32)
    cpp = chunk // page_size                  # pages per chunk

    def chunk_fn(st, ctokens, cpages):
        _, k, v = model.qkv_fn(ctokens)       # [chunk, H, d]
        kc = jnp.transpose(
            k.astype(store).reshape(cpp, page_size, heads, head_dim),
            (0, 2, 1, 3))
        vc = jnp.transpose(
            v.astype(store).reshape(cpp, page_size, heads, head_dim),
            (0, 2, 1, 3))
        return {"k_pages": st["k_pages"].at[cpages].set(kc),
                "v_pages": st["v_pages"].at[cpages].set(vc)}

    chunk_jit = jax.jit(chunk_fn)
    tokens = np.asarray(feed["tokens"])
    tables_dev = feed["tables"]
    idx = np.arange(streams)
    n_chunks = -(-join_prompt // chunk)
    during, after = [], []
    prefilled = 0
    for i in range(gen_tokens + warmup):
        joining = prefilled < join_prompt
        if joining:
            # ONE chunk of the long prompt between decode steps — the
            # interleave that bounds what the join adds per token
            c0 = prefilled
            span = join_tokens[c0:c0 + chunk]
            padded = np.zeros((chunk,), np.int32)
            padded[:len(span)] = span
            pids = base_pages + c0 // page_size + np.arange(cpp)
            state = chunk_jit(state, jnp.asarray(padded),
                              jnp.asarray(pids.astype(np.int32)))
            prefilled += len(span)
        feed_i = {
            "tokens": jnp.asarray(tokens),
            "page_ids": jnp.asarray(
                tables_np[idx, lens // page_size]),
            "offsets": jnp.asarray(lens % page_size),
            "tables": tables_dev,
            "lens": jnp.asarray(lens + 1),
        }
        t0 = time.perf_counter()
        state, nxt = fn(state, feed_i)
        tokens = np.asarray(nxt)              # sync: inter-token beat
        dt = time.perf_counter() - t0
        lens += 1
        if i >= warmup:
            (during if joining else after).append(dt)

    def pct(vals, p):
        vs = sorted(v * 1e3 for v in vals)
        return round(vs[min(len(vs) - 1, int(p / 100 * len(vs)))], 3) \
            if vs else None

    peak_bw, kind = _chip_peak_bw()
    total = sum(during) + sum(after)
    n_steps = len(during) + len(after)
    return {
        "tokens_per_sec": round(streams * n_steps / total, 1)
        if total else 0.0,
        "inter_token_p50_ms": pct(during + after, 50),
        "inter_token_p99_ms": pct(during + after, 99),
        "inter_token_p99_during_join_ms": pct(during, 99),
        "inter_token_p99_after_join_ms": pct(after, 99),
        "join_steps": len(during),
        "chunks_prefilled": min(n_chunks, len(during) + warmup),
        "chunked_join": True,
        "join_prompt_len": join_prompt,
        "chunk": chunk,
        "streams": streams,
        "heads": heads,
        "head_dim": head_dim,
        "page_size": page_size,
        "paged": True,
        "device": kind,
    }


# ---------------------------------------------------------------------------
# Main: one subprocess per leg, one at a time, and a parent that never
# touches JAX — a chip belongs to one process, and a parent holding it
# would starve every leg.
# ---------------------------------------------------------------------------

_LEG_FUNCS = {
    "rn_train": "bench_resnet50_train",
    # fused conv-epilogue A/B (ops/pallas_conv.py) — same workload,
    # Pallas kernel graph; rides right after the baseline leg so an
    # on-chip window banks the A/B pair together
    "rn_train_convep": "bench_resnet50_train_convep",
    # conv+BN-stats train-chain fusion A/B (ops/pallas_conv.py
    # conv2d_bn_train) — the train path's structural cut; rides behind
    # the convep pair so a window banks the full A/B/C set together
    "rn_train_convbnstats": "bench_resnet50_train_convbnstats",
    "tf_train": "bench_transformer_train",
    # ISSUE 17: the fc-epilogue A/B — same workload with the ffn and
    # projection fc+bias+act chains fused onto the Pallas fc_epilogue
    # kernel; rides right after the baseline leg so an on-chip window
    # banks the A/B pair together (the convep precedent)
    "tf_train_fcep": "bench_transformer_train_fcep",
    # ISSUE 8: the same transformer step as ONE pjit program over
    # every attached device (dp x tp MeshPlan, ZeRO-3 + tp specs,
    # flash under shard_map); on a single chip this degrades to a
    # 1-device mesh — still the gspmd compile path, so the leg stays
    # an honest liveness check everywhere
    "tf_train_gspmd": "bench_transformer_train_gspmd",
    # ISSUE 14: the tp-sharded serving-inference step (MeshPlan slice,
    # column-parallel fc weights, one jit with in/out NamedShardings)
    # — the graph a mesh-sliced ReplicaPool replica serves; degrades
    # to tp1 on a single chip like tf_train_gspmd
    "serving_tp_sharded": "bench_serving_tp_sharded",
    "bert_train": "bench_bert_train",
    "dfm_train": "bench_deepfm_train",
    "infer": "bench_resnet50_infer",
    "vgg_infer": "bench_vgg16_infer",
    "longctx": "bench_longctx_train",
    "longctx_d128": "bench_longctx_train_d128",
    # ISSUE 7: LLM continuous decode through the paged KV-cache +
    # flash_decode step — tokens/s/chip + inter-token p50/p99 vs
    # concurrent streams; rides after the longctx legs (same kernel
    # family)
    "llm_decode": "bench_llm_decode",
    # ISSUE 11: decode act II — the speculative verify loop
    # (acceptance-rate x tokens/s) and the chunked-prefill join
    # (inter-token p99 while a 32k prompt joins); the prefix-shared
    # row rides the plain llm_decode leg via its prefix_share kwarg
    "llm_decode_spec": "bench_llm_decode_spec",
    "llm_decode_chunked_join": "bench_llm_decode_chunked_join",
    # the reference's cifar10 fp16 table rows (float16_benchmark.md
    # :56-74) — cheap bf16 legs, so they ride ahead of int8
    "vgg_cifar": "bench_vgg16_cifar_infer",
    "rn32_cifar": "bench_resnet32_cifar_infer",
    # int8 LAST: on 2026-07-31 its on-chip compile died with a backend
    # UNAVAILABLE; at the end a repeat costs only this leg
    "infer_i8": "bench_resnet50_infer_int8",
    # ISSUE 5: int8 activations across layer boundaries (fused
    # per-channel requantize through BN-fold bias + ReLU) — the A/B
    # against the row above; very last, same reasoning
    "infer_i8_inter": "bench_resnet50_infer_int8_interlayer",
}

# tiny CPU shapes of every leg: what the tests run to check a leg's
# build/dispatch plumbing (tests/test_models.py et al.).  Off the chip
# every kernel's auto-impl is its XLA form, so these check the graph
# rewrites and the dispatch, never a kernel.  main() does not use
# them: it measures on the chip at full size or not at all.
_TINY = {
    "rn_train": dict(batch=8, chain=2),
    "rn_train_convep": dict(batch=8, chain=2),
    "rn_train_convbnstats": dict(batch=8, chain=2),
    "tf_train": dict(batch=2, seq=128, chain=2),
    "tf_train_fcep": dict(batch=2, seq=128, chain=2),
    "tf_train_gspmd": dict(batch=2, seq=128, chain=2),
    "serving_tp_sharded": dict(batch=2, in_dim=16, hidden=32,
                               depth=2, out_dim=16, chain=2),
    "bert_train": dict(batch=1, seq=128, chain=1),
    "dfm_train": dict(batch=256, chain=3),
    "infer": dict(batch=8, chain=3),
    "infer_i8": dict(batch=2, chain=1),
    "infer_i8_inter": dict(batch=2, chain=1),
    "vgg_infer": dict(batch=4, chain=2),
    "vgg_cifar": dict(batch=16, chain=2),
    "rn32_cifar": dict(batch=32, chain=2),
    "longctx": dict(batch=1, heads=2, seq=512, chain=1),
    "longctx_d128": dict(batch=1, heads=2, seq=512, head_dim=32,
                         chain=1),
    "llm_decode": dict(streams=2, prefill_len=8, gen_tokens=4,
                       heads=2, head_dim=32, page_size=8, vocab=256),
    "llm_decode_spec": dict(streams=2, spec_k=2, prefill_len=8,
                            gen_tokens=3, heads=2, head_dim=32,
                            page_size=8, vocab=64, draft_heads=2,
                            draft_head_dim=8),
    "llm_decode_chunked_join": dict(streams=2, join_prompt=64,
                                    chunk=16, prefill_len=8,
                                    gen_tokens=6, heads=2,
                                    head_dim=32, page_size=8,
                                    vocab=64),
}

# per-leg wall budget, first compile included
_LEG_TIMEOUT_S = 1800

_ROWS_FILE = "chiprun_out/bench_rows.json"


def _assert_on_chip():
    """The check `place` cannot make (core/types.py): the default
    device is a TPU chip.  With _chain_timed's check that the stepped
    state lives on the default device, a leg proves where it ran."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            "bench: the default device is %s (%s), not a TPU chip — "
            "bench.py measures on the chip or not at all"
            % (dev.platform, dev.device_kind))


def _run_leg_child(leg, kwargs):
    """Entry for `bench.py --leg`: run one bench leg on the chip, print
    its dict as the last stdout line."""
    import paddle_tpu as fluid

    _assert_on_chip()
    fluid.enable_compile_cache()
    res = globals()[_LEG_FUNCS[leg]](**kwargs)
    print("LEGRESULT " + json.dumps(res))


def _run_leg(leg, kwargs, timeout_s=_LEG_TIMEOUT_S):
    """Run one leg in a subprocess; returns (result_dict | None,
    detail)."""
    import subprocess
    import sys

    cmd = [sys.executable, __file__, "--leg", leg,
           "--kwargs", json.dumps(kwargs)]
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return None, "timeout>%ds" % timeout_s
    if out.returncode != 0:
        return None, "exit=%d %s" % (out.returncode,
                                     (out.stderr or "")[-300:].strip())
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("LEGRESULT "):
            return json.loads(line[len("LEGRESULT "):]), "ok"
    return None, "no LEGRESULT in output"


def _default_platform(timeout_s=300):
    """Platform of JAX's default device, asked of a CHILD so the parent
    never touches JAX (it would hold the chip against every leg).  One
    question, no retry: no answer is no chip."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.devices()[0].platform)"],
        capture_output=True, text=True, timeout=timeout_s)
    if out.returncode != 0:
        raise RuntimeError("bench: jax found no device: %s"
                           % (out.stderr or "")[-300:].strip())
    return out.stdout.strip().splitlines()[-1]


def _epilogue_marker(row):
    """Canonical epilogue-workload marker of a bench row (ISSUE 17).

    New rows carry the fused-anchor list in row["epilogue"] (e.g.
    "fc"); legacy banked rows carry the per-flag bools
    (conv_epilogue / conv_bn_stats / int8_interlayer) that predate the
    unified pass — this derives the SAME canonical string from either
    spelling, so banked baselines keep matching their reruns across
    the marker migration."""
    ep = row.get("epilogue")
    if ep:
        return str(ep)
    parts = []
    if row.get("conv_epilogue"):
        parts.append("conv")
    if row.get("conv_bn_stats"):
        parts.append("conv_bn")
    if row.get("int8_interlayer"):
        parts.append("int8")
    return "+".join(parts)


def _workload_sig(key, row):
    """Workload identity of a bench row, independent of key spelling.

    The FAMILY is the key with every shape tag (_mbN/_seqN/_hN/_dN/
    _blkN), graph-variant tag (_s2d/_convep/_cmp_pool/_bn1p/
    _fastpath) and _DEGRADED decoration stripped; the shape and the
    graph variant are then re-keyed from the row's OWN metadata
    (batch/seq/heads/head_dim + the variant marker fields every
    variant leg records).  The three epilogue-fusion flags collapse
    into ONE canonical marker (_epilogue_marker) so old per-flag rows
    and new stage-list rows land in the same slot.  Two rows with
    equal signatures are the same measurement slot: a fresh live one
    always supersedes a banked one, however either key happens to be
    spelled."""
    import re

    fam = re.sub(r"_DEGRADED.*$", "", key)
    fam = re.sub(r"_(?:mb|seq|h|d|blk|str|spec_k)\d+", "", fam)
    fam = re.sub(r"_(?:s2d|convep|convbnstats|fcep|cmp_pool|bn1p|"
                 r"fastpath|packed|hp2|fusedadam|interlayer|int8kv|"
                 r"gspmd|prefix_shared|chunked_join|disagg|tp\d+)"
                 r"(?=_|$)",
                 "", fam)
    return (fam, row.get("batch"), row.get("seq"), row.get("heads"),
            row.get("head_dim"), bool(row.get("s2d_stem")),
            _epilogue_marker(row),
            row.get("maxpool_grad") or "",
            bool(row.get("conv_bn_folded")),
            bool(row.get("packed_stats")), bool(row.get("head_pack")),
            bool(row.get("fused_adam")),
            row.get("streams"), bool(row.get("kv_int8")),
            bool(row.get("paged")),
            row.get("spec_k"), row.get("prefix_shared"),
            bool(row.get("chunked_join")),
            bool(row.get("gspmd")), row.get("dp"), row.get("tp"),
            row.get("devices"),
            bool(row.get("serving_sharded")),
            bool(row.get("disagg")))


def main():
    import os
    import sys

    platform = _default_platform()
    if platform != "tpu":
        print("bench: the default device is %r, not a TPU chip — no leg "
              "run, nothing written" % platform, file=sys.stderr)
        return 1

    results, details = {}, {}
    for leg in _LEG_FUNCS:
        results[leg], details[leg] = _run_leg(leg, {})
        print("leg %-10s %s" % (
            leg, json.dumps(results[leg]) if results[leg]
            else details[leg]), file=sys.stderr)

    rn = results["rn_train"]
    headline = rn["mfu_pct"] if rn else None

    def infer_row(leg, baseline_ms):
        r = results[leg]
        if r is None:
            return {"error": details[leg]}
        row = dict(r)
        row["vs_v100_fp16_baseline"] = round(
            baseline_ms / r["ms_per_batch"], 3)
        return row

    def row(leg):
        return results[leg] if results[leg] is not None else \
            {"error": details[leg]}

    # the verdict-r4 "re-key" rule: the s2d-stem graph is a different
    # workload variant, so its rows/metric must say so in the KEY, not
    # only in a buried s2d_stem field (a dashboard diffing rounds by
    # key must not read the stem flip as a same-workload perf change)
    rn_s2d = "_s2d" if (rn or {}).get("s2d_stem") else ""
    extras = {
        "resnet50_train" + rn_s2d: row("rn_train"),
        "resnet50_train_convep": row("rn_train_convep"),
        "resnet50_train_convbnstats": row("rn_train_convbnstats"),
        "transformer_base_train": row("tf_train"),
        "transformer_base_train_fcep": row("tf_train_fcep"),
        "transformer_base_train_gspmd": row("tf_train_gspmd"),
        "serving_tp_sharded": row("serving_tp_sharded"),
        "bert_base_train_seq512": row("bert_train"),
        "deepfm_ctr_train": row("dfm_train"),
        "resnet50_infer_bf16_mb128":
            infer_row("infer", BASELINE_INFER_MS),
        "resnet50_infer_int8_mb128": row("infer_i8"),
        "resnet50_infer_int8_interlayer_mb128": row("infer_i8_inter"),
        "vgg16_infer_bf16_mb64":
            infer_row("vgg_infer", BASELINE_VGG16_MB64_MS),
        "vgg16_cifar10_infer_bf16_mb512":
            infer_row("vgg_cifar", BASELINE_VGG16_CIFAR_MS),
        "resnet32_cifar10_infer_bf16_mb512":
            infer_row("rn32_cifar", BASELINE_RN32_CIFAR_MS),
        "longctx_flash_train_seq32768": row("longctx"),
        "longctx_flash_train_seq32768_d128": row("longctx_d128"),
        "llm_decode_flash_str64": row("llm_decode"),
        "llm_decode_spec_k4_flash_str64": row("llm_decode_spec"),
        "llm_decode_chunked_join_flash":
            row("llm_decode_chunked_join"),
    }
    metric = "resnet50_bf16_train_mfu_pct_mb128" + rn_s2d
    full = {
        "metric": metric,
        "value": headline,
        "unit": "% of chip peak (bf16)",
        # >=1.0 means the 50%-MFU north star is met
        "vs_baseline": None if headline is None
        else round(headline / (100 * MFU_TARGET), 4),
        "extras": extras,
    }
    # stdout carries ONE compact JSON line (the full extras block once
    # outgrew the driver's tail capture); the complete row set goes
    # where the chip tool brings files back from — never into docs/
    rows_file = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), _ROWS_FILE)
    os.makedirs(os.path.dirname(rows_file), exist_ok=True)
    with open(rows_file, "w") as f:
        json.dump(full, f, indent=1, sort_keys=True)
    failed = [leg for leg, r in results.items() if r is None]
    print(json.dumps({
        "metric": metric,
        "value": headline,
        "unit": full["unit"],
        "vs_baseline": full["vs_baseline"],
        "rows_file": _ROWS_FILE,
        "n_rows": len(extras),
        "failed_legs": failed,
    }))
    if failed:
        print("FAILED legs: %s" % failed, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--leg", choices=sorted(_LEG_FUNCS))
    ap.add_argument("--kwargs", default="{}")
    a = ap.parse_args()
    if a.leg:
        _run_leg_child(a.leg, json.loads(a.kwargs))
    else:
        import sys

        sys.exit(main())
