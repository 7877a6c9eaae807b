"""Compile every gate program for the TPU — on the CPU, without a chip.

Why this exists: Pallas interpret mode (what CPU tests run) never
enforces Mosaic's TPU block-mapping rules, so a kernel can pass the
whole suite and still be rejected on the chip.  That exact failure
shipped once: a [1, bq] lse block spec crashed the first on-hardware
transformer step while 546 CPU tests were green.

The chip's compiler is installed here and compiles for a chip that is
DESCRIBED and not attached (on-chip-measurement guide, section 2, third
rehearsal): jax.experimental.topologies describes a `v5e:2x2`, and
`jit(step).lower(avals placed on a described device).compile()` raises
what the chip's compiler would raise.  This tool builds the programs
of tools/gate_programs.py at real size and compiles each
that way: one-chip programs for described device 0, the sharded
programs over a mesh of the four described devices.

Scope honesty: a compile asks the whole compiler — Mosaic's lowering
rules, VMEM limits, HBM fit of the one program (memory_analysis() is in
the report), kernels that cannot be partitioned.  It runs nothing, so
it says nothing about results or times, and it counts one program at a
time, not what else a process keeps on the device.  A compile that
passes is not a chip run: chip_smoke.py is.  Only one process at a time
can describe the topology (a second aborts on libtpu's lock file), and
such compiles write persistent-cache entries nothing can read back, so
the compile cache is switched off around them.

Usage:  python tools/tpu_lowering_check.py [--fast] [workload ...]
Exit code 0 iff every selected workload compiles.  JSON report on
stdout.  --fast skips the two slowest builds (resnet50 train, bert).

Reference analog: the reference gates kernels per-platform at build
time via REGISTER_OP_CUDA_KERNEL + CI on GPU machines
(paddle/fluid/framework/op_registry.h:237); here the gate is the
chip's own compiler, asked ahead of the chip.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import functools
import json
import math
import os
import re
import sys
import time

# the builders run their startup programs on the CPU; the compile
# targets the described chip.  The compiler logs under /tmp otherwise.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax

TOPOLOGY = "v5e:2x2"


@functools.lru_cache(maxsize=None)
def described_devices():
    """The four devices of a described, not attached, v5e:2x2."""
    from jax.experimental import topologies

    return tuple(topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY).devices)


@contextlib.contextmanager
def compile_cache_off():
    """A described-topology compile is written to the persistent cache
    but cannot be read back without a chip (the next one warns and
    compiles again) — switch the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def compile_for_chip(fn, args, on_mesh=False):
    """`fn` (jitted, or jittable) compiled for the described chip, not
    run.  `args`: its arguments as arrays or ShapeDtypeStructs; only
    shapes and dtypes are used.  They are placed on described device 0
    — or, `on_mesh`, passed bare to a function that was jitted with
    in_shardings over a mesh of described_devices().  Returns the
    compiled executable (memory_analysis(), as_text())."""
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    where = None if on_mesh else \
        SingleDeviceSharding(described_devices()[0])

    def aval(x):
        dtype = x.dtype if hasattr(x, "dtype") else np.asarray(x).dtype
        return jax.ShapeDtypeStruct(np.shape(x), dtype, sharding=where)

    fn = fn if hasattr(fn, "lower") else jax.jit(fn)
    with compile_cache_off():
        return fn.lower(*jax.tree_util.tree_map(aval, args)).compile()


# workloads whose builder takes the described devices and jits ONE
# program with in/out NamedShardings over a mesh of them
ON_MESH = ("transformer_train_gspmd", "serving_tp_sharded")


def _workloads():
    from tools import gate_programs as progs

    return {
        "transformer_train": lambda: progs._build_transformer_train(
            32, 512)[:3],
        # the looped decoder at the cell's sizes (4,096 tokens, 6
        # layers x 4 passes over one set of weights, the 49,152-wide
        # head four times): its memory_analysis() says whether the
        # step fits before chip time is spent, and token-major flash at
        # head size 128 has no other program here
        "ouro_train": lambda: progs._build_ouro_train(1, 4096)[:3],
        # the 2024-26 decoder block at the cell's sizes: flash at head
        # sizes 192 / 128 head-major, the grouped matmuls, a recompute
        # segment a layer
        "xing4_train": lambda: progs._build_xing4_train(1, 4096)[:3],
        # the DeepSeek-V2 block at the cell's sizes (2 x 4,096 tokens):
        # the grouped matmuls at an expert width of 1,408 = 11 x 128
        # (taken whole) and 768 rows an expert, the softmax router
        # and its balance loss crossing recompute segments
        "dsv2_train": lambda: progs._build_dsv2_train(2, 4096)[:3],
        # the hybrid state-space decoder at the cell's sizes (1 x 8,192
        # tokens, nine Mamba-2 layers and one grouped-KV attention
        # layer, 772 M parameters): whether 12.35 GB of state and the
        # step's activations fit, and that each scan's forward kernel
        # runs once (ONE_SSD_FWD_AN_OP)
        "granite_train": lambda: progs._build_granite_train(1, 8192)[:3],
        # the published head sizes, state size and chunk (the kernels'
        # geometry), one period of layers, everything else narrow
        "granite_train_tiny": lambda: progs._build_granite_train(
            1, 512, hidden_size=256, num_attention_heads=4,
            num_key_value_heads=2, mamba_n_heads=4,
            shared_intermediate_size=512, vocab_size=512)[:3],
        # the KDA / latent-attention hybrid at the cell's sizes (1 x
        # 4,096 tokens, six KDA layers and one gated MLA layer over a
        # 512-wide group-limited router, 822 M parameters): whether
        # 13.15 GB of state and the step's activations fit
        # (STEP_BYTES_MAX), and that each scan's forward kernel runs
        # once (ONE_KDA_FWD_AN_OP)
        "ling3_train": lambda: progs._build_ling3_train(1, 4096)[:3],
        # the published head sizes, chunking, router (512 outputs in 8
        # groups) and expert width, one period of layers, everything
        # else narrow
        "ling3_train_tiny": lambda: progs._build_ling3_train(
            1, 512, hidden_size=256, num_attention_heads=2,
            kv_lora_rank=64, intermediate_size=512, vocab_size=512)[:3],
        # the gated-convolution / attention hybrid at the cell's sizes
        # (1 x 8,192 tokens, four conv layers and one grouped-KV
        # attention layer with rotary K at 8 heads, 16 of 64 experts
        # held at width 1,536, 788 M parameters): whether 12.6 GB of
        # state and the step's activations fit (STEP_BYTES_MAX), and
        # that the gated convolution reads its projection in place
        # (GATED_CONV_IN_PLACE)
        "lfm2_train": lambda: progs._build_lfm2_train(1, 8192)[:3],
        # the published head size, taps, router (64 outputs) and
        # expert width, the cell's five layers, everything else narrow
        "lfm2_train_tiny": lambda: progs._build_lfm2_train(
            1, 512, hidden_size=256, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=512,
            vocab_size=512)[:3],
        # one tensor- and expert-parallel rank of Solar-Open2-250B at
        # the cell's sizes (1 x 8,192 tokens at hidden 4,096: one gated
        # attention layer at 8 / 1 heads of 128 and three KDA layers at
        # 8 heads on the unbounded-decay path, 8 of 320 experts held at
        # width 1,280, 841 M parameters): whether 13.45 GB of state and
        # the step's activations fit (STEP_BYTES_MAX), and that each
        # scan's forward kernel runs once (ONE_KDA_FWD_AN_OP)
        "solar_open2_train": lambda: progs._build_solar_open2_train(
            1, 8192)[:3],
        # the published head sizes, chunking, taps, router (320
        # outputs) and expert width, one period of layers, everything
        # else narrow
        "solar_open2_train_tiny": lambda: progs._build_solar_open2_train(
            1, 512, hidden_size=256, num_attention_heads=2,
            kda_heads_held=2, vocab_size=512)[:3],
        # one expert-parallel rank of Mellum2-12B-A2.5B at the cell's
        # sizes (1 x 16,384 tokens at hidden 2,304: three window layers
        # of 1,024 keys on the band grid and one YaRN full-attention
        # layer at 32 / 4 heads of 128, 16 of 64 experts held at width
        # 896, 595 M parameters): whether 9.52 GB of state and the
        # step's activations at 16,384 tokens fit (STEP_BYTES_MAX), and
        # that each flash op's forward kernel runs once, the window
        # layers' under their own name (ONE_FLASH_FWD_AN_OP)
        "mellum2_train": lambda: progs._build_mellum2_train(
            1, 16384)[:3],
        # the published head size, group of 8, window (1,024 of 2,048
        # tokens: a band of the grid), YaRN numbers, router (64
        # outputs) and expert width, one period of layers, everything
        # else narrow
        "mellum2_train_tiny": lambda: progs._build_mellum2_train(
            1, 2048, hidden_size=256, num_attention_heads=8,
            num_key_value_heads=1, vocab_size=512)[:3],
        # EvaByte's first four layers at the cell's sizes (1 x 8,192
        # bytes at hidden 4,096, 32 heads of 128, four windows of 2,048
        # in chunks of 16, SwiGLU 11,008, eight heads of 320 ids, 821 M
        # parameters): whether 13.14 GB of state and the step's
        # activations fit (STEP_BYTES_MAX), that every EVA kernel runs
        # once an op and that no score array a head exists
        # (EVA_KERNELS_AN_OP)
        "evabyte_train": lambda: progs._build_evabyte_train(1, 8192)[:3],
        # the published head size, window and chunk, two windows,
        # everything else narrow (three heads: a hidden size that is
        # neither a window's keys nor the 256 chunk keys)
        "evabyte_train_tiny": lambda: progs._build_evabyte_train(
            1, 4096, hidden_size=384, num_attention_heads=3,
            num_key_value_heads=3, intermediate_size=512)[:3],
        # both at the cells' depth and head sizes, narrow and short
        # (256 tokens; seconds to compile): what is checked is how many
        # kernels the step holds, not whether it fits
        # (ONE_FLASH_FWD_AN_OP)
        "ouro_train_tiny": lambda: progs._build_ouro_train(
            1, 256, hidden_size=256, num_attention_heads=2,
            head_dim=128, num_key_value_heads=2, intermediate_size=512,
            vocab_size=512)[:3],
        "xing4_train_tiny": lambda: progs._build_xing4_train(
            1, 256, hidden_size=256, num_attention_heads=2,
            q_lora_rank=64, kv_lora_rank=64, intermediate_size=512,
            moe_intermediate_size=128, n_routed_experts=2,
            vocab_size=512)[:3],
        # the published expert width (1,408: one block through
        # Mosaic) and head sizes, everything else narrow and short
        "dsv2_train_tiny": lambda: progs._build_dsv2_train(
            2, 256, hidden_size=256, num_attention_heads=2,
            kv_lora_rank=64, intermediate_size=512, n_routed_experts=2,
            held_experts=[0, 1], vocab_size=512)[:3],
        "resnet50_train": lambda: progs._build_resnet50_train(128)[:3],
        "resnet50_train_s2d": lambda: progs._build_resnet50_train(
            128, s2d=True)[:3],
        # fused conv-epilogue Pallas graphs (ops/pallas_conv.py):
        # interpret-mode tests never enforce Mosaic's tiling/lowering
        # rules, so the convep A/B legs must compile here BEFORE a
        # chip call is spent on them (the flash [1,bq] lse lesson)
        "resnet50_train_convep": lambda: progs._build_resnet50_train(
            128, conv_epilogue=True)[:3],
        "resnet50_infer_convep": lambda: _infer(
            progs, "resnet", 128, conv_epilogue=True),
        # conv+BN-stats train-chain fusion (ISSUE 4): the stat sibling
        # outputs' (1, bco) blocks and the one-pass normalize kernel's
        # row blocks are exactly the construct class Mosaic may reject
        # while interpret mode stays green
        "resnet50_train_convbnstats": lambda:
            progs._build_resnet50_train(128, conv_bn_stats=True)[:3],
        # the fused multi-tensor Adam tail (optimizer.py
        # Adam(fuse=True)): concat/split over every param must lower
        # for tpu before the batch-slide A/B leg runs
        "transformer_train_fusedadam": lambda:
            progs._build_transformer_train(8, 512, fused_adam=True)[:3],
        # ISSUE 17: the unified-epilogue fc anchor — the fused
        # matmul+bias+residual+act kernel's (bm, bn) output blocks and
        # full-K operand blocks are new Mosaic surface the plain mul
        # lowering never sees (the conv workloads above gate the conv
        # anchors of the same stage grammar)
        "transformer_train_fcep": lambda:
            progs._build_transformer_train(8, 512,
                                           fc_epilogue=True)[:3],
        # ISSUE 17: the greedy logits tail (the epilogue grammar's
        # terminal argmax stage, shared by the decode engine's step,
        # draft and verify sweeps) over a vocab-width bf16 row block
        "decode_greedy_tail": lambda: _decode_greedy_tail(),
        # ISSUE 8: the gspmd-sharded train step — ONE jit with in/out
        # NamedShardings over a dp x tp mesh, ZeRO-3/tp specs on the
        # weights and the flash kernels under shard_map.  shard_map
        # imposes its own Mosaic constraints (per-shard block shapes:
        # B/dp rows, H/tp heads) that the single-device transformer
        # lowering never sees.  Built over the four DESCRIBED devices:
        # dp2 x tp2, the mesh `chip_smoke.py --chips 4` runs.
        "transformer_train_gspmd": lambda:
            progs._build_transformer_train(
                8, 512, gspmd=True, tp=2,
                devices=described_devices())[:3],
        # ISSUE 14: the tp-sharded serving-INFERENCE graph — one jit
        # with in/out NamedShardings over a dp1 x tp2 slice mesh,
        # column-parallel fc weights + the inter-layer all-gathers
        # the SPMD partitioner inserts: SPMD surface the unsharded
        # predictor lowering never sees.  Over two described devices.
        "serving_tp_sharded": lambda: progs._build_serving_tp_sharded(
            tp=2, devices=described_devices())[:3],
        # ISSUE 14: the disagg decode graph — the flash_decode step
        # over handoff-fragmented block tables (pages strided across
        # the pool in prefill-completion order).  The kernel walks
        # the table through scalar prefetch either way.
        "llm_decode_disagg": lambda: progs._build_llm_decode(
            streams=8, prefill_len=64, heads=8, head_dim=128,
            page_size=128, disagg=True),
        "bert_train": lambda: progs._build_bert_train(8, 512)[:3],
        "deepfm_train": lambda: progs._build_deepfm_train(2048)[:3],
        "resnet50_infer_int8": lambda:
            progs._build_resnet50_infer_int8(128)[:3],
        # ISSUE 5: the int8-interlayer graph — s8-in convs, raw-s32
        # accumulator outputs and the fused requantize epilogue are
        # exactly the lowering surface Mosaic/XLA:TPU may reject while
        # the CPU suite stays green
        "resnet50_infer_int8_interlayer": lambda:
            progs._build_resnet50_infer_int8(
                128, int8_activations=True)[:3],
        # ISSUE 7: the paged-KV flash-decode step — scalar-prefetch
        # block-table index maps, the (1, hpb, page_size, d) page
        # blocks, the int8-page convert and the head-packed pairing
        # are exactly the construct class Mosaic may reject while the
        # interpret suite stays green; every variant flag compiles
        # here
        "llm_decode": lambda: progs._build_llm_decode(
            streams=8, prefill_len=64, heads=8, head_dim=128,
            page_size=128),
        "llm_decode_d64_hp2": lambda: progs._build_llm_decode(
            streams=8, prefill_len=64, heads=8, head_dim=64,
            page_size=128, head_pack=True),
        "llm_decode_int8kv": lambda: progs._build_llm_decode(
            streams=8, prefill_len=64, heads=8, head_dim=128,
            page_size=128, kv_int8=True),
        "llm_decode_bf16": lambda: _llm_decode_bf16(progs),
        # ISSUE 11c: the q-len-(k+1) speculative VERIFY step — the
        # per-row causal mask (min(kv_len, kv_len-R+1+row) over a row
        # iota) and the 16-sublane query block at R > 8 are new
        # Mosaic surface the q-len-1 gate never sees
        "llm_decode_spec_k4": lambda: progs._build_llm_decode(
            streams=8, prefill_len=64, heads=8, head_dim=128,
            page_size=128, spec_k=4),
        "llm_decode_spec_k8": lambda: progs._build_llm_decode(
            streams=8, prefill_len=64, heads=8, head_dim=128,
            page_size=128, spec_k=8),
        "resnet50_infer": lambda: _infer(progs, "resnet", 128),
        "vgg16_infer": lambda: _infer(progs, "vgg", 64),
        "vgg16_cifar_infer": lambda: _infer(progs, "vgg_cifar", 512),
        "resnet32_cifar_infer": lambda: _infer(progs, "rn32_cifar",
                                               512),
        "longctx_train": lambda: progs._build_longctx_train()[:3],
    }


def _decode_greedy_tail():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.epilogue import greedy_logits_tail

    fn = jax.jit(lambda state, feed: greedy_logits_tail(
        feed["logits"]))
    feed = {"logits": jax.ShapeDtypeStruct((8, 32000), jnp.bfloat16)}
    return fn, {}, feed


def _llm_decode_bf16(progs):
    import jax.numpy as jnp

    return progs._build_llm_decode(
        streams=8, prefill_len=64, heads=8, head_dim=64,
        page_size=128, dtype=jnp.bfloat16)


def _infer(progs, which, batch, conv_epilogue=False):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(0)
    if which == "resnet":
        from paddle_tpu.models.resnet import resnet50 as build

        feed = lambda: {  # noqa: E731
            "image": jnp.asarray(
                rng.rand(batch, 3, 224, 224).astype(np.float32),
                jnp.bfloat16),
            "label": jnp.zeros((batch, 1), jnp.int32)}
    elif which == "rn32_cifar":
        from paddle_tpu.models.resnet import resnet_cifar10 as build

        feed = lambda: {  # noqa: E731
            "image": jnp.asarray(
                rng.rand(batch, 3, 32, 32).astype(np.float32),
                jnp.bfloat16),
            "label": jnp.zeros((batch, 1), jnp.int32)}
    elif which == "vgg_cifar":
        from paddle_tpu.models.vgg import vgg

        def build(is_test):
            return vgg(16, class_dim=10, img_shape=(3, 32, 32),
                       is_test=is_test)

        feed = lambda: {  # noqa: E731
            "image": jnp.asarray(
                rng.rand(batch, 3, 32, 32).astype(np.float32),
                jnp.bfloat16)}
    else:
        from paddle_tpu.models.vgg import vgg16 as build

        feed = lambda: {  # noqa: E731
            "image": jnp.asarray(
                rng.rand(batch, 3, 224, 224).astype(np.float32),
                jnp.bfloat16)}
    return progs._build_infer(lambda: build(is_test=True), feed,
                              "logits",
                              conv_epilogue=conv_epilogue)[:3]


FAST_SKIP = ("resnet50_train", "bert_train", "ouro_train",
             "xing4_train", "dsv2_train", "granite_train", "ling3_train",
             "lfm2_train", "solar_open2_train", "mellum2_train",
             "evabyte_train")

# the steps whose attention takes q, k and v token-major, [B, T, H*d]
# as the projections leave them: their compiled step may hold no head
# split or merge
NO_HEAD_LAYOUT_COPIES = ("transformer_train", "transformer_train_gspmd",
                         "ouro_train")


def head_layout_copies(hlo_text):
    """`copy` instructions of rank-4 float arrays in the ENTRY computation
    of a compiled module: the head splits and merges ([B, T, H, d] <->
    [B, H, T, d], each made in two hops) of a step whose attention is
    fed head-major.  96 in the 64 x 512 Transformer step before flash
    attention took token-major operands (PERF.md, PR 31), 10 ms of its
    99."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    return len(re.findall(
        r" = (?:bf16|f16|f32)\[\d+(?:,\d+){3}\]\S* copy\(", entry))


def token_relayouts(hlo_text, tokens):
    """`copy`, `transpose` and `reshape` instructions (not the
    asynchronous copies between memory spaces) of float arrays with an
    axis of `tokens` in the ENTRY computation of a compiled module:
    the relayouts of a projection-sized array.  A rotary op that
    reshapes the lanes to find an entry's partner costs one each for q
    and k between the projection and the flash call (PERF.md, PR 54);
    ops/pallas_rotary.py costs none."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    return len(re.findall(
        r" = (?:bf16|f16|f32)\[(?:\d+,)*%d(?:,\d+)*\]\S* "
        r"(?:copy|transpose|reshape)\(" % tokens, entry))


# the training steps whose every flash_attention op has a grad that
# reads the forward's Out and LSE, as a grad op of its own or inside a
# recompute segment: the forward kernel runs once an op (an op with a
# window its own, pt_flash_win_fwd, and the one-sweep backward
# pt_flash_win_bwd_dkv: `window_flash_ops`)
ONE_FLASH_FWD_AN_OP = ("transformer_train", "transformer_train_gspmd",
                       "ouro_train", "ouro_train_tiny", "xing4_train",
                       "xing4_train_tiny", "dsv2_train",
                       "dsv2_train_tiny", "granite_train",
                       "granite_train_tiny", "ling3_train",
                       "ling3_train_tiny", "lfm2_train",
                       "lfm2_train_tiny", "solar_open2_train",
                       "solar_open2_train_tiny", "mellum2_train",
                       "mellum2_train_tiny")

# the training steps whose every ssd_scan op has a grad that reads the
# forward's Y and chunk-start states inside its recompute segment: the
# forward kernel runs once an op (9 in the cell's step; 18 if the
# segment replayed it, 27 if the grad op ran it again too), and the
# backward kernel once
ONE_SSD_FWD_AN_OP = ("granite_train", "granite_train_tiny")


# the training steps whose every kda_scan op has a grad that reads the
# forward's O, block-start states and chunk inverses inside its
# recompute segment: the forward kernel runs once an op (6 in the
# cell's step; 12 if the segment replayed it, 18 if the grad op ran it
# again too), and the backward kernel once
ONE_KDA_FWD_AN_OP = ("ling3_train", "ling3_train_tiny",
                     "solar_open2_train", "solar_open2_train_tiny")


# the training steps whose every causal_conv1d op runs the kernels of
# ops/pallas_conv1d.py: pt_conv1d_fwd twice an op (the forward pass and
# its recompute segment's replay: the op keeps no output, so a segment
# binds nothing) and pt_conv1d_bwd once (9 ops a granite step: 18 + 9;
# 18 a ling3 step: 36 + 18; 4 an lfm2 step, gated: 8 + 4), and the XLA
# graph's float32 pad of X is gone from the op's scope
CONV1D_KERNELS = ("granite_train", "granite_train_tiny", "ling3_train",
                  "ling3_train_tiny", "lfm2_train", "lfm2_train_tiny",
                  "solar_open2_train", "solar_open2_train_tiny")

# the training steps whose causal_conv1d ops are gated -> their tokens
# T: the kernels read the thirds of the [T, 3 C] projection in place
# and write its gradient as one array, so under the scope pt_gated_conv
# stands no pad, no concatenate, and no slice, copy or fusion that
# yields an array of T rows (the thirds split off, the gates' products
# or the three gradients joined: the XLA composition's)
GATED_CONV_IN_PLACE = {"lfm2_train": 8192, "lfm2_train_tiny": 512}


# the training steps whose rotary_embedding ops run the kernel of
# ops/pallas_rotary.py wherever it can tile X (H D whole lane tiles:
# every q and k; not the latent attention's one shared key a token):
# pt_rotary three times an op, the forward pass, its recompute
# segment's replay, and the backward the same kernel at the negative
# angle (the op keeps nothing, so a segment binds nothing)
ROTARY_KERNEL = ("xing4_train", "xing4_train_tiny", "ouro_train",
                 "ouro_train_tiny", "dsv2_train", "dsv2_train_tiny",
                 "ling3_train", "ling3_train_tiny", "lfm2_train",
                 "lfm2_train_tiny", "mellum2_train", "mellum2_train_tiny",
                 "evabyte_train", "evabyte_train_tiny")

# the training steps whose every layer mixes by EVA attention, an
# eva_pool and an eva_attention op each: the six Mosaic calls (the
# summariser, a window's causal flash, the staircase; forward and
# backward) ONCE an op, since a recompute segment's replay takes the
# saved summaries, Out and LSE, and no float array of [.., T, W],
# [.., T, T/c] or, a window a row, [.., W, W]: a head's scores stay in
# VMEM in both directions
EVA_KERNELS_AN_OP = {
    "evabyte_train": ((8192, 2048), (8192, 512), (2048, 2048)),
    "evabyte_train_tiny": ((4096, 2048), (4096, 256), (2048, 2048))}
EVA_KERNELS = ("pt_eva_pool_fwd", "pt_eva_pool_bwd", "pt_eva_chunk_fwd",
               "pt_eva_chunk_bwd", "pt_flash_fwd", "pt_flash_bwd_dkv")


def rotary_kernel_ops(program):
    """The rotary_embedding ops of a program that pt_rotary can tile."""
    from paddle_tpu.ops import pallas_rotary

    block = program.global_block()
    n = 0
    for op in block.ops:
        if op.type != "rotary_embedding":
            continue
        shape = block.var(op.inputs["X"][0]).shape
        heads = op.attrs.get("n_head")
        width, d = (shape[2], shape[2] // heads) if heads \
            else (shape[2] * shape[3], shape[3])
        n += pallas_rotary.blocks(shape[1], width, d) is not None
    return n


# the training steps whose every moe_experts op combines by token
# through the kernel of ops/pallas_moe_combine.py: pt_moe_combine twice
# an op (the forward's combine and d x) and a third time where the
# recompute segment's backward reads the layer's output (xing4: the
# stream mix after it; in dsv2 and ling3 the replay's combine is dead
# code and the compiler drops it)
MOE_COMBINE_KERNEL = ("xing4_train", "xing4_train_tiny", "dsv2_train",
                      "dsv2_train_tiny", "ling3_train", "ling3_train_tiny",
                      "lfm2_train", "lfm2_train_tiny",
                      "solar_open2_train", "solar_open2_train_tiny",
                      "mellum2_train", "mellum2_train_tiny")


# the training steps whose hyper-connections run the kernels of
# ops/pallas_mhc.py -> the elements of a stream-sized array there
# (X [B, n, T, C]; Y, U [B, T C]): under the scope pt_mhc no XLA
# fusion, copy, convert or transpose yields one (the XLA composition's
# float32 casts, broadcast products and copies of whole stream arrays),
# and each kernel is called as often as the segments run it
MHC_STREAMS_IN_KERNELS = {
    "xing4_train": (4 * 4096 * 3584, 4096 * 3584),
    "xing4_train_tiny": (4 * 256 * 256, 256 * 256)}
# 10 hyper-connections in 5 recompute segments: mhc_pre's forward in
# the forward pass and in its segment's replay; mhc_post's too, but a
# segment's last, whose output nothing of the segment reads; each
# backward once (the first compile's counts, PR 52)
MHC_KERNEL_CALLS = {"pt_mhc_pre_fwd": 20, "pt_mhc_post_fwd": 15,
                    "pt_mhc_pre_bwd": 10, "pt_mhc_post_bwd": 10}


def mhc_stream_moves(hlo_text, sizes):
    """Instructions of a compiled module under the op_name scope
    pt_mhc, outside the kernels, that yield an array of one of `sizes`
    elements: `fusion`, `copy`, `convert` and `transpose`.  The
    coefficients' transposes and the weight's parts (a few MB) do not
    count."""
    found = []
    for result, kind in re.findall(
            r"^\s*(?:ROOT )?%?[\w.\-]+ = ([^\n]*?) "
            r"(fusion|copy|convert|transpose)\("
            r'[^\n]*op_name="[^"]*pt_mhc[^"]*"', hlo_text, re.M):
        for dims in re.findall(r"\w+\[([\d,]+)\]", result):
            if math.prod(map(int, dims.split(","))) in sizes:
                found.append("%s %s" % (kind, result))
                break
    return found


def conv_scope_pads(hlo_text):
    """`pad` instructions of a compiled module under the op_name scope
    pt_causal_conv1d: the XLA graph's left-padded float32 copy of X
    (and its gradient's), which the kernels' halo replaces."""
    return len(re.findall(
        r'^[^\n]* pad\([^\n]*op_name="[^"]*pt_causal_conv1d[^"]*"',
        hlo_text, re.M))


def gated_conv_copies(hlo_text, rows):
    """Instructions of a compiled module under the op_name scope
    pt_gated_conv that move a [., rows, .] array outside the kernels:
    every `pad` and `concatenate`, and a `slice`, `copy` or fusion that
    yields an array of `rows` rows.  The filter's transpose and the sum
    of dW over the batch (a few KB) do not count."""
    under = r'[^\n]*op_name="[^"]*pt_gated_conv[^"]*"'
    return len(re.findall(
        r"^[^\n]* (?:pad|concatenate)\(" + under, hlo_text, re.M)) \
        + len(re.findall(
            r"^[^=\n]* = [^=\n]*?\[\d+,%d,\d+\][^=\n]*? "
            r"(?:slice|copy|fusion)\(" % rows + under, hlo_text, re.M))


def arrays_of(hlo_text, *trailing):
    """The float arrays of a compiled module whose last two dims are
    one of `trailing` ((rows, columns) pairs), whatever leads them, as
    the distinct shapes found: a score array of EVA attention a head
    would be [.., T, W] (a window's keys; [.., W, W] on the window
    part's reshape) or [.., T, T/c] (the chunk keys); the kernels keep
    both in VMEM (PERF.md, PR 55)."""
    return sorted(set(re.findall(
        r"(?:bf16|f16|f32)\[(?:\d+,)*(?:%s)\]" % "|".join(
            "%d,%d" % pair for pair in trailing), hlo_text)))


def kernel_calls(hlo_text):
    """{kernel name: Mosaic calls} of a compiled module.  The TPU
    compiler names a call after the kernel (`pl.pallas_call(name=)`,
    PERF.md section 3) and numbers the copies (`pt_flash_fwd.7`)."""
    return dict(collections.Counter(re.findall(
        r"^\s*(?:ROOT )?%?([\w-]+?)(?:\.\d+)* = [^\n]*"
        r'custom_call_target="tpu_custom_call"', hlo_text, re.M)))


# the steps with expert layers -> the rows M of `_group_layout`'s
# worst-case layout, (ceil(N k / 256) + held) x 256: of the work by
# padded row only the kernels may stand outside moe_experts' loops over
# the live rows (PR 37)
ROW_WORK_IN_LOOPS = {"dsv2_train": 51200, "dsv2_train_tiny": 3584}

# memory_analysis() bytes (argument + output + temp - alias) a step may
# take.  dsv2_train: PR 37 reads 9,613,623,296 (PR 36: 9,429,541,888;
# the live peak fell, the compiler's packing of it rose, and moves by
# megabytes with the order of the step's ops: PERF.md)
# PR 43 reads 9,672,605,696 with the combine a kernel: the live peak
# stands (8,892,879,922 -> 8,893,076,538 bytes at the same program
# point, a replayed grouped matmul: one plan array more), the heap the
# compiler packs the temporaries into grew 3,192,799,744 ->
# 3,250,685,440 (buffer assignment of both modules, PERF.md PR 43)
STEP_BYTES_MAX = {"dsv2_train": 9_700_000_000,
                  # PR 51 reads 12,713,077,248; 40 MB more (ISSUE 52):
                  # the stream mixes' kernels leave no stream-sized
                  # float32 temporary to raise it
                  "xing4_train": 12_753_077_248,
                  # the driver's ceiling for the cell (ISSUE 45):
                  # step_hbm_gb between 4 and 15.5; the one lever above
                  # 15.0 is the vocabulary at an eighth
                  "lfm2_train": 15_000_000_000,
                  # PR 41 reads 13,062,109,696: 9.87 GB of weights and
                  # float32 Adam moments, 3.20 GB of gradients and a
                  # segment's activations
                  "ling3_train": 13_100_000_000,
                  # PR 49 reads 15,325,295,104 (the chip too, to the
                  # byte): 10.09 GB of weights and float32 Adam
                  # moments, 5.23 GB of gradients and a segment's
                  # activations at 8,192 tokens x hidden 4,096; the
                  # chip holds 15.75 GiB = 16.9e9
                  "solar_open2_train": 15_400_000_000,
                  # PR 53 reads 13,356,171,776: 7.14 GB of weights and
                  # float32 Adam moments, 6.21 GB of gradients, the
                  # [16384, 24576] float32 logits with their gradient
                  # and a segment's replay at 16,384 tokens
                  "mellum2_train": 13_600_000_000,
                  # ISSUE 55's gate: 13.14 GB of weights, gradients and
                  # float32 Adam moments, the float32 stream at the
                  # layer boundaries and one segment's replay at 8,192
                  # bytes x hidden 4,096 (the [8192, 11008] SwiGLU
                  # arrays); the chip holds 15.75 GiB = 16.9e9
                  "evabyte_train": 15_900_000_000}

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", re.M)
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")


def rows_outside_loops(hlo_text, rows):
    """`fusion`, `gather` and `copy` instructions of a compiled module
    that yield a float array of `rows` rows ([rows, width]) and are NOT
    in a `while` body or a computation one calls: array work that walks
    the worst-case layout of an expert layer whatever is live.  Before
    PR 37 the `dsv2` step held the gathers, SwiGLU and sums round the
    grouped matmuls so, 51,200 rows each.  Kernels (custom calls) and
    the loops themselves do not count; nor do the layout's index
    vectors (integers)."""
    heads = list(_COMPUTATION.finditer(hlo_text))
    bodies = {m.group(1): hlo_text[m.end():(
        heads[i + 1].start() if i + 1 < len(heads) else len(hlo_text))]
        for i, m in enumerate(heads)}
    looped = set(re.findall(r"body=%?([\w.\-]+)", hlo_text))
    todo = list(looped)
    while todo:
        for callee in _CALLED.findall(bodies.get(todo.pop(), "")):
            if callee not in looped:
                looped.add(callee)
                todo.append(callee)
    row_array = re.compile(
        r"^\s*(?:ROOT )?%%?[\w.\-]+ = [^=\n]*?(?:bf16|f16|f32)\[%d,\d+"
        r"[^=\n]*? (?:fusion|gather|copy)\(" % rows, re.M)
    return sum(len(row_array.findall(body))
               for name, body in bodies.items()
               if name not in looped
               and not name.startswith("fused_computation"))


def check_workload(name, build):
    """Build the gate program and compile its jitted step for the
    described chip.  Returns (ok, detail, seconds); detail of a compile
    that passed is its memory_analysis() and kernel count, for the
    NO_HEAD_LAYOUT_COPIES steps `head_layout_copies`, which fails the
    workload unless it is 0, and for the ONE_FLASH_FWD_AN_OP steps
    `kernel_calls` and the program's `flash_ops`, which fail it unless
    `pt_flash_fwd` is called once an op (a recompute segment that
    replays the op holds a second call: 10 for 5 in `xing4`, 48 for 24
    in `ouro` before PR 33), and for the ONE_SSD_FWD_AN_OP steps
    `ssd_ops`, the same of `pt_ssd_fwd` and `pt_ssd_bwd`, and for the
    ONE_KDA_FWD_AN_OP steps `kda_ops`, the same of `pt_kda_fwd` and
    `pt_kda_bwd`; for the CONV1D_KERNELS steps `conv1d_ops` and
    `conv_scope_pads`, which fail it unless `pt_conv1d_fwd` is called
    twice and `pt_conv1d_bwd` once a causal_conv1d op and no `pad`
    stands under the op's scope; for the GATED_CONV_IN_PLACE steps
    `gated_conv_copies`, which fails it unless no array of the step's
    tokens is moved under the scope pt_gated_conv outside the kernels;
    for the MOE_COMBINE_KERNEL steps
    `moe_ops`, which fail it unless `pt_moe_combine` is called two or
    three times a moe_experts op; for the MHC_STREAMS_IN_KERNELS steps
    `mhc_stream_moves`, which fails it unless no stream-sized array is
    made under the scope pt_mhc outside the kernels and the four
    kernels are called MHC_KERNEL_CALLS times; for the ROTARY_KERNEL
    steps `rotary_kernel_ops`, which fails it unless pt_rotary is
    called three times an op it can tile; for the
    ROW_WORK_IN_LOOPS steps
    `rows_outside_loops`, which fails the workload unless it is 0, and
    for the STEP_BYTES_MAX steps `step_bytes`, which fails it above
    the limit."""
    t0 = time.time()
    # Force the Pallas path during tracing: impl auto-detection sees a
    # CPU device in this process, but the program we must validate is
    # the one traced ON THE CHIP (where _on_tpu() is True).
    import paddle_tpu.ops.pallas_kernels as pk

    orig = pk._on_tpu
    pk._on_tpu = lambda: True
    # flag hygiene: variant builds set process-global flags; reset to
    # defaults so a variant workload can never leak into the next
    # build's trace
    from paddle_tpu.flags import set_flags

    set_flags({"fc_epilogue": "off", "gspmd": False,
               "serving_sharded": False})
    try:
        fn, state, feed = build()
        exe = compile_for_chip(fn, (state, feed),
                               on_mesh=name in ON_MESH)
        mem = exe.memory_analysis()
        text = exe.as_text()
        detail = {
            "tpu_custom_calls": text.count(
                'custom_call_target="tpu_custom_call"'),
            **{k: getattr(mem, k + "_size_in_bytes") for k in (
                "temp", "argument", "output", "alias",
                "generated_code")}}
        ok = True
        if name in NO_HEAD_LAYOUT_COPIES:
            detail["head_layout_copies"] = head_layout_copies(text)
            ok &= not detail["head_layout_copies"]
        if name in ONE_FLASH_FWD_AN_OP:
            from paddle_tpu import framework

            flash = [op for op in
                     framework.default_main_program().global_block().ops
                     if op.type == "flash_attention"]
            windowed = sum(bool(op.attrs.get("window")) for op in flash)
            detail["flash_ops"] = len(flash) - windowed
            detail["kernel_calls"] = kernel_calls(text)
            ok &= len(flash) > 0 and detail["kernel_calls"].get(
                "pt_flash_fwd", 0) == detail["flash_ops"]
            if windowed:
                detail["window_flash_ops"] = windowed
                ok &= detail["kernel_calls"].get("pt_flash_win_fwd") \
                    == detail["kernel_calls"].get("pt_flash_win_bwd_dkv") \
                    == windowed
        if name in ONE_SSD_FWD_AN_OP:
            from paddle_tpu import framework

            detail["ssd_ops"] = sum(
                op.type == "ssd_scan" for op in
                framework.default_main_program().global_block().ops)
            ok &= detail["kernel_calls"].get("pt_ssd_fwd") \
                == detail["kernel_calls"].get("pt_ssd_bwd") \
                == detail["ssd_ops"] > 0
        if name in ONE_KDA_FWD_AN_OP:
            from paddle_tpu import framework

            detail["kda_ops"] = sum(
                op.type == "kda_scan" for op in
                framework.default_main_program().global_block().ops)
            ok &= detail["kernel_calls"].get("pt_kda_fwd") \
                == detail["kernel_calls"].get("pt_kda_bwd") \
                == detail["kda_ops"] > 0
        if name in CONV1D_KERNELS:
            from paddle_tpu import framework

            detail["conv1d_ops"] = sum(
                op.type == "causal_conv1d" for op in
                framework.default_main_program().global_block().ops)
            detail["conv_scope_pads"] = conv_scope_pads(text)
            ok &= detail["kernel_calls"].get("pt_conv1d_fwd") \
                == 2 * detail["kernel_calls"].get("pt_conv1d_bwd", 0) \
                == 2 * detail["conv1d_ops"] > 0
            ok &= not detail["conv_scope_pads"]
        if name in GATED_CONV_IN_PLACE:
            detail["gated_conv_copies"] = gated_conv_copies(
                text, GATED_CONV_IN_PLACE[name])
            ok &= not detail["gated_conv_copies"]
        if name in MOE_COMBINE_KERNEL:
            from paddle_tpu import framework

            detail["moe_ops"] = sum(
                op.type == "moe_experts" for op in
                framework.default_main_program().global_block().ops)
            ok &= 0 < 2 * detail["moe_ops"] \
                <= detail["kernel_calls"].get("pt_moe_combine", 0) \
                <= 3 * detail["moe_ops"]
        if name in EVA_KERNELS_AN_OP:
            from paddle_tpu import framework

            detail["kernel_calls"] = kernel_calls(text)
            detail["eva_ops"] = sum(
                op.type == "eva_attention" for op in
                framework.default_main_program().global_block().ops)
            detail["eva_score_arrays"] = arrays_of(
                text, *EVA_KERNELS_AN_OP[name])
            ok &= detail["eva_ops"] > 0 and all(
                detail["kernel_calls"].get(k) == detail["eva_ops"]
                for k in EVA_KERNELS)
            ok &= not detail["eva_score_arrays"]
        if name in ROTARY_KERNEL:
            from paddle_tpu import framework

            detail["rotary_kernel_ops"] = rotary_kernel_ops(
                framework.default_main_program())
            ok &= 0 < 3 * detail["rotary_kernel_ops"] \
                == detail["kernel_calls"].get("pt_rotary")
        if name in MHC_STREAMS_IN_KERNELS:
            detail["mhc_stream_moves"] = mhc_stream_moves(
                text, MHC_STREAMS_IN_KERNELS[name])
            ok &= not detail["mhc_stream_moves"]
            ok &= all(detail["kernel_calls"].get(k) == v
                      for k, v in MHC_KERNEL_CALLS.items())
        if name in ROW_WORK_IN_LOOPS:
            detail["rows_outside_loops"] = rows_outside_loops(
                text, ROW_WORK_IN_LOOPS[name])
            ok &= not detail["rows_outside_loops"]
        if name in STEP_BYTES_MAX:
            detail["step_bytes"] = detail["temp"] + detail["argument"] \
                + detail["output"] - detail["alias"]
            ok &= detail["step_bytes"] <= STEP_BYTES_MAX[name]
        return ok, detail, time.time() - t0
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        msg = "%s: %s" % (type(e).__name__, str(e)[:400])
        return False, msg, time.time() - t0
    finally:
        pk._on_tpu = orig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("workloads", nargs="*",
                    help="subset to check (default: all)")
    ap.add_argument("--fast", action="store_true",
                    help="skip the slowest builds (%s)"
                         % ", ".join(FAST_SKIP))
    args = ap.parse_args(argv)

    table = _workloads()
    names = args.workloads or [
        n for n in table
        if not (args.fast and n in FAST_SKIP)]
    unknown = [n for n in names if n not in table]
    if unknown:
        ap.error("unknown workloads: %s (have: %s)"
                 % (unknown, list(table)))

    report, ok_all = {}, True
    for n in names:
        ok, detail, secs = check_workload(n, table[n])
        report[n] = {"ok": ok, "detail": detail,
                     "seconds": round(secs, 1)}
        ok_all &= ok
        print("  %-22s %s (%.1fs) %s"
              % (n, "OK" if ok else "FAIL", secs, detail),
              file=sys.stderr)
    print(json.dumps({"all_ok": ok_all, "workloads": report}))
    return 0 if ok_all else 1


if __name__ == "__main__":
    sys.path.insert(0, ".")
    sys.exit(main())
