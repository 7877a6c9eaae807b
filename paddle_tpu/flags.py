"""Typed flag/config system.

Reference parity (SURVEY.md §5 "Config / flag system"): the reference
scatters gflags DEFINE_* through C++ (executor.cc:40, allocator_strategy.cc,
gpu_info.cc) re-exported to Python by whitelist (__init__.py:124
__bootstrap__ -> core.init_gflags).  Here ONE typed registry replaces the
three idioms; every flag reads an env override ``PADDLE_TPU_<NAME>`` at
import, mirroring the reference's env-driven bootstrap.
"""

from __future__ import annotations

import os
from typing import Any

_REGISTRY: dict = {}


class _Flag:
    __slots__ = ("name", "type", "value", "help")

    def __init__(self, name, type_, default, help_):
        self.name = name
        self.type = type_
        self.value = default
        self.help = help_


def _coerce(type_, raw: str):
    if type_ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return type_(raw)


def define_flag(name: str, default: Any, help_: str = ""):
    type_ = type(default)
    env = os.environ.get(f"PADDLE_TPU_{name.upper()}")
    value = _coerce(type_, env) if env is not None else default
    _REGISTRY[name] = _Flag(name, type_, value, help_)


def get_flag(name: str):
    return _REGISTRY[name].value


def set_flags(flags: dict):
    """reference fluid.set_flags analog."""
    for name, value in flags.items():
        f = _REGISTRY.get(name)
        if f is None:
            raise KeyError(f"unknown flag '{name}'")
        if not isinstance(value, f.type):
            value = _coerce(f.type, str(value))
        f.value = value


def all_flags():
    return {name: f.value for name, f in _REGISTRY.items()}


# ---------------------------------------------------------------------------
# core flags (reference counterparts noted)
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False,
            "sweep op outputs for NaN/Inf after each interpreted op "
            "(reference FLAGS_check_nan_inf, operator.cc:953)")
define_flag("benchmark", False,
            "block after each op to localize async failures "
            "(reference FLAGS_benchmark, operator.cc:949)")
define_flag("profile_ops", False,
            "record a host span per interpreted op "
            "(reference platform/profiler RecordEvent around op Run)")
define_flag("eager_delete_tensor_gb", 0.0,
            "GC threshold placeholder (XLA owns buffers; reference "
            "executor GC flag)")
define_flag("maxpool_grad_algo", "sas",
            "max-pool backward: 'sas' = XLA's select_and_scatter vjp "
            "(routes dy to one maximum); 'compare' = k*k shifted "
            "compare-and-route passes, routing dy to EVERY tied "
            "maximum — a different, still-valid subgradient (ties are "
            "common on post-ReLU inputs where the window max is 0); "
            "candidate when select_and_scatter lowers slowly")
define_flag("conv_epilogue", "off",
            "fused conv+bias+residual+ReLU Pallas kernel "
            "(ops/pallas_conv.py) for NHWC conv2d: 'off' = plain XLA "
            "conv (default; zero behavior change), 'on' = Pallas "
            "kernel on TPU / XLA composite elsewhere, 'pallas' / "
            "'interpret' / 'xla' force one impl ('interpret' runs the "
            "kernel under the Pallas interpreter for CPU parity "
            "tests).  Built for the rn50 HBM-bound diagnosis: ~9.3 "
            "GB/step of residual/ReLU glue XLA won't fuse into its "
            "conv custom-calls (VERDICT r5)")
define_flag("conv_bn_stats", "off",
            "fused conv+BN(train) Pallas path (ops/pallas_conv.py "
            "conv2d_bn_stats / bn_normalize_epilogue) for the rewritten "
            "conv2d_bn_train op: 'off' = the exact unfused composite "
            "(default; zero behavior change — conv, _moments_1pass "
            "stats, normalize, residual, relu), 'on' = two one-pass "
            "Pallas kernels on TPU / unfused composite elsewhere, "
            "'pallas' / 'interpret' / 'xla' force one impl.  The TRAIN-"
            "side sibling of conv_epilogue: BN batch stats sit between "
            "conv and residual add, so the train chain re-reads the "
            "conv output twice (moments, then normalize); the stats "
            "ride out of the conv kernel as sibling outputs and ONE "
            "fused normalize+residual+ReLU pass finishes the chain "
            "(ROADMAP rn50 >=50% MFU item, ISSUE 4)")
define_flag("fc_epilogue", "off",
            "fused matmul+bias+residual+act Pallas kernel "
            "(ops/epilogue.py fc_epilogue) for the fc/mul chains the "
            "unified epilogue transpiler rewrites (ISSUE 17): 'off' = "
            "the exact unfused composite (default; zero behavior "
            "change — mul, elementwise_add, act as discrete ops), "
            "'on' = Pallas kernel on TPU / unfused composite "
            "elsewhere, 'pallas' / 'interpret' / 'xla' force one impl "
            "('interpret' runs the kernel under the Pallas interpreter "
            "for CPU parity tests).  The matmul sibling of "
            "conv_epilogue — covers the transformer train graph's "
            "fc+bias+relu/gelu tails (the Adam-tail diagnosis's "
            "missing A/B leg)")
define_flag("int8_interlayer", False,
            "int8 end-to-end activation flow (ISSUE 5): "
            "convert_to_int8_execution folds, for every quantized-op -> "
            "quantized-op edge, the producer's dequant + folded-BN "
            "shift + ReLU + the consumer's quant into ONE per-channel "
            "requantize op, so the tensor that hits HBM between layers "
            "is int8 instead of bf16/f32 (~30%% traffic cut on the "
            "HBM-bound int8 infer row).  Default off: flag-off graphs "
            "are bit-identical to the calibrated int8 path (asserted "
            "in tests/test_quantization.py); flip per-call via "
            "convert_to_int8_execution(int8_activations=True)")
define_flag("paged_decode", False,
            "LLM decode KV-cache strategy (ISSUE 7): False = the "
            "validated dense lax.scan decode loop (decode.py "
            "beam_search/greedy_search; default, zero behavior "
            "change — flag-off decode is bit-identical to the "
            "pre-paged scan loop, asserted in tests/test_decode.py); "
            "True = the host-stepped paged path: decode runs one "
            "device step per token with an early all-finished exit, "
            "so the step fn may carry a paged KV-cache "
            "(ops/paged_kv.PagedKVCache) and attend via flash_decode "
            "— thousands of ragged concurrent sequences share ONE "
            "preallocated HBM page pool instead of re-running "
            "full-prefix attention per step")
define_flag("kv_int8", False,
            "paged KV-cache storage dtype: False = the model dtype "
            "(f32/bf16; default), True = int8 pages with per-channel "
            "(head, dim) scales riding the PR-5 requantize contract "
            "(q = clip(round(x/s*127)), dequant-in-kernel x_hat = "
            "q*s/127) — 2-4x less HBM per cached token and 2-4x less "
            "decode-step K/V streaming traffic.  Accuracy asserted "
            "against the f32 KV path (top-1 agreement, "
            "tests/test_decode.py; docs/DECODE.md accuracy bar)")
define_flag("prefill_chunk", 0,
            "chunked prefill for the continuous-decode engine "
            "(ISSUE 11a): 0 = whole-prompt prefill (default; the "
            "validated PR-7 path — a long prompt's projections run as "
            "one pow2-padded call before the sequence joins), N > 0 = "
            "prompts longer than N tokens prefill in fixed N-token "
            "chunks INTERLEAVED with decode iterations (one chunk per "
            "iteration, chunk shape always padded to exactly N — one "
            "compile), so a 32k-token join never stretches running "
            "streams' inter-token p99 (the PR-10 decode_inter_token "
            "SLO is the acceptance instrument).  Chunked-prefill "
            "output is bit-identical to whole-prefill (asserted in "
            "tests/test_decode_act2.py)")
define_flag("kv_share", False,
            "copy-on-write prefix sharing in the paged KV-cache "
            "(ISSUE 11b): False = every sequence owns its pages "
            "(default; the validated PR-7 allocator, zero behavior "
            "change), True = per-page refcounts plus a radix tree "
            "over block tables so beams (PagedKVCache.fork) AND "
            "requests with a common token prefix share physical "
            "full pages — a shared system prompt amortizes its "
            "prefill to zero.  Appends into a shared page copy-on-"
            "write through the atomic alloc path; the zero-leak "
            "invariant generalizes to free + unique(in_use) == "
            "num_pages; shared-decode output is bit-identical "
            "(array_equal) to unshared since the kernel reads the "
            "same physical bytes (docs/DECODE.md)")
define_flag("spec_k", 0,
            "lossless speculative decoding for the continuous-decode "
            "engine (ISSUE 11c): 0 = one token per decode iteration "
            "(default; the validated PR-7 step), k > 0 = a small "
            "draft model proposes k tokens per iteration, ONE "
            "batched flash_decode verify step (q-len-(k+1) "
            "generalization of the split-K-over-pages kernel) scores "
            "them, greedy acceptance takes the longest agreeing "
            "prefix (decode.spec_accept_length), and rejection is a "
            "page-pointer rewind through PagedKVCache.truncate — so "
            "speculative greedy output is token-for-token identical "
            "to non-speculative greedy (asserted), with "
            "acceptance-rate x tokens/s reported per bench row")
define_flag("gspmd", False,
            "GSPMD pod-scale front-end (ISSUE 8): False = the "
            "validated per-module parallelism paths (default, zero "
            "behavior change — shard_program() is a no-op and the "
            "compiled step is bit-identical to never calling it, "
            "asserted in tests/test_gspmd.py); True = "
            "transpiler.shard_program(plan) maps per-var "
            "PartitionSpec annotations on the Program IR to "
            "NamedShardings over a dp/tp/pp MeshPlan and emits ONE "
            "jitted train step (jax.jit with in/out shardings — the "
            "modern pjit) covering fwd+bwd+optimizer: ZeRO-3 is a "
            "parameter/optimizer-state sharding spec (params sharded "
            "on dp, gathered by the XLA SPMD partitioner), tensor "
            "parallelism is tp PartitionSpecs on the existing layers, "
            "and flash attention runs under shard_map on the same "
            "mesh (docs/GSPMD.md)")
define_flag("tracing", False,
            "request-scoped structured tracing (ISSUE 9, "
            "observability/tracing.py): False = off (default; every "
            "span site reduces to ONE module-global None check — the "
            "disabled-cost contract asserted in "
            "tests/test_observability.py); True = spans with "
            "trace-id/span-id propagation are recorded into a bounded "
            "ring: a serving request carries one trace id submit -> "
            "admission -> batch -> replica -> Predictor.run -> "
            "delivery, decode sequences span join -> step -> retire, "
            "and the id rides the RPC envelope so pserver handler "
            "spans join the caller's trace.  Export: chrome-trace "
            "JSON merged by tools/timeline.py.  Head sampling "
            "(ISSUE 10): PADDLE_TPU_TRACE_SAMPLE / "
            "ServingConfig.trace_sample in [0.0, 1.0] decides ONCE "
            "per trace id (deterministic hash, inherited by children "
            "and the RPC envelope — no partial traces); 0.0 is wire- "
            "and cost-identical to flag-off; with the flag on, Pallas "
            "kernel entries and executor steps also emit "
            "jax.profiler annotations carrying the trace id "
            "(observability/device_trace.py, docs/OBSERVABILITY.md)")
define_flag("serving_sharded", False,
            "mesh-sliced serving replicas (ISSUE 14): False = every "
            "serving replica is one whole-model predictor on one "
            "device (default; the validated PR-6..13 pool, zero "
            "behavior change — Predictor.shard() is a no-op and "
            "ReplicaPool ignores its mesh_plan), True = a MeshPlan "
            "describes an INFERENCE replica: ReplicaPool carves the "
            "device set into plan-sized slices, each replica's "
            "predictor tp-shards its fc weights COLUMN-parallel over "
            "the slice (parallel/gspmd.py annotate_tp_inference -> "
            "CompiledProgram.with_sharding_rules), so one pool serves "
            "a model that doesn't fit one chip's HBM.  Column-only "
            "(output-dim) splits keep every contraction full-width — "
            "the sharded replica's outputs are bit-identical "
            "(array_equal) to the unsharded predictor, asserted on "
            "the tp2 CPU mesh (docs/SERVING.md, docs/GSPMD.md)")
define_flag("disagg_prefill", False,
            "disaggregated prefill/decode serving tiers (ISSUE 14): "
            "False = the validated single-tier continuous-decode "
            "engine (default; each decode replica prefills its own "
            "joins — zero behavior change), True = "
            "serving.DecodeServer splits into a PREFILL pool "
            "(compute-bound: prompt projections + page writes) and a "
            "DECODE pool (BW-bound iteration loop) behind ONE "
            "admission plane; a finished prefill hands its sequence "
            "to the decode tier as a PAGE-LIST transfer — block-table "
            "entries + per-page refcounts through "
            "PagedKVCache.detach/adopt, never a full-KV tensor copy — "
            "with typed HandoffError, deadline propagation across the "
            "tier boundary, and exactly-once accounting when a "
            "replica on either side dies mid-handoff "
            "(docs/SERVING.md handoff state machine)")
define_flag("ir_verify", "off",
            "IR verifier gating every transpiler pass (ISSUE 15, "
            "paddle_tpu/analysis/, docs/ANALYSIS.md): 'off' = default "
            "(zero behavior change — checked_pass is one flag read "
            "and the wrapped pass runs untouched, bit-identity "
            "asserted in tests/test_ir_verifier.py); 'on' = the "
            "structural Program/Block/Op verifier runs before AND "
            "after every transpiler pass (def-before-use, registered "
            "op types with their attr schemas, slot validity, "
            "dangling/duplicate vars, grad-op pairing) raising typed "
            "VerifierError diagnostics that name block/op-index/var "
            "and the guilty pass; 'full' = 'on' plus the static "
            "shape/dtype inference check after each pass.  The test "
            "suite forces 'on' (tests/conftest.py) so every parity "
            "test doubles as a verifier soak; ci.sh runs the gate "
            "workloads under 'full' via tools/verifier_sweep.py")
define_flag("int8_conv_algo", "conv",
            "conv2d_int8 lowering: 'conv' = integer "
            "conv_general_dilated; 'im2col' = pad/slice/concat + one "
            "s8xs8->s32 dot_general (bit-identical; escape hatch for "
            "backends where the integer conv hits a bad compile path)")
