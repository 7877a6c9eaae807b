"""The `ling-3.0-flash-vl` configuration's benchmark files on the CPU,
in a file of their own (a `model_config` PR adds files and entries and
edits none): builders/ling3_flops.py against the hand-worked numbers of
ISSUE 41 and a hand count at a tiny shape, the configuration against
the catalog row's published numbers, reference/ling3.py against the
program at tiny size (and against the repository's copy), the cell kind
end to end through the harness, and BENCHMARK.json's entries of PR 41,
looked up BY NAME and held with `<=`: a later PR appends after them.

Tolerances as in test_reference.py: float32 1e-4 (the same mathematics
in another order), AMP 2e-2 at these sizes (a loss over 128 tokens).
"""

import io
import json
import os
import shutil

import numpy as np
import pytest

from conftest import BENCH, CHECKOUT

import flops
import harness

CELL = "ling3_flash_train_s4k"
# the catalog row `Ling-3.0-flash-VL` (architectures.jsonl beside the
# model-configs guide): its `source_url` and the numbers of its
# `config`, copied here so that the test reads nothing outside the
# checkout
SOURCE = ("https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/"
          "main/config.json")
PUBLISHED = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560,
    "intermediate_size": 6144, "first_k_dense_replace": 2,
    "max_position_embeddings": 131072, "moe_intermediate_size": 768,
    "num_experts_per_tok": 8, "num_attention_heads": 32,
    "q_lora_rank": None, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts": 512,
    "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False,
    "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]

TINY = {
    "builder": "ling3", "reference": "ling3", "param_prefix": "ling3",
    "hidden_size": 128, "num_attention_heads": 4, "head_dim": 32,
    "num_key_value_heads": 4, "q_lora_rank": None, "kv_lora_rank": 32,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "rope_theta": 6000000, "max_position_embeddings": 4096,
    "rms_norm_eps": 1e-6, "intermediate_size": 256,
    "moe_intermediate_size": 64,
    "moe_shared_expert_intermediate_size": 64, "num_experts": 4,
    "num_experts_published": 16, "held_experts": [0, 1, 2, 3],
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
    "num_hidden_layers": 3, "layer_group_size": 3,
    "first_k_dense_replace": 1, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "kda_safe_gate": True, "linear_silu": True,
    "gated_attention_proj_granularity_type": "head_wise",
    "kda_chunk_size": 16, "kda_block_chunks": 2, "vocab_size": 128,
    "initializer_range": 0.02, "amp": True, "learning_rate": 1e-3,
    "recompute": True,
    # off the chip the kernel entries resolve to their XLA forms: the
    # chunked scan, plain attention and the grouped matmuls in
    # jax.numpy; a recompute segment differentiates its replay of them
    # and calls no grad op
    "kernel_impls": {"flash_attention": "xla", "kda_scan": "xla",
                     "moe_gmm": "xla", "moe_route_scoring": "sigmoid",
                     "moe_route_groups": "2of4"},
    "reference_rtol": 2e-2,
}


def _load(kind, name):
    return harness._load_file(os.path.join(BENCH, kind, name + ".py"))


def _config():
    return json.load(open(os.path.join(BENCH, "configs",
                                       "ling-3.0-flash-vl.json")))


# -- builders/ling3_flops.py --------------------------------------------------

def test_parameters_at_the_cells_sizes():
    """ISSUE 41's table, from the functions: what multiplies a token,
    by part."""
    w, config = _load("builders", "ling3_flops"), _config()
    assert w.layer_kinds(config) == ["kda"] * 5 + ["mla", "kda"]
    parts = w.matmul_params(config)
    # W_q, W_k, W_v, W_a, W_o 5 x 2560 x 4096, w_beta and w_gate
    kda = 5 * 2560 * 4096 + 2 * 2560 * 32
    assert kda == 52_592_640 and parts["kda_proj"] == 6 * kda
    mla = 2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560 + 2560 * 32
    assert mla == 31_965_184 and parts["mla_proj"] == mla
    assert parts["dense_ffn"] == 3 * 2560 * 6144 == 47_185_920
    assert parts["shared_expert"] == 6 * 3 * 2560 * 768
    assert parts["router"] == 6 * 2560 * 512
    # 8 experts a token over 512, 8 held: an eighth of an expert a token
    assert parts["routed_experts"] == 6 * 0.125 * 3 * 2560 * 768
    assert parts["head"] == 2560 * 19648 == 50_298_880


def test_flops_per_token_at_the_cells_sizes():
    w, config = _load("builders", "ling3_flops"), _config()
    fwd = w.forward_flops_per_token(config, 4096)
    in_mflop = {k: round(v / 1e6) for k, v in fwd.items()}
    assert in_mflop == {"kda_proj": 631, "mla_proj": 64, "dense_ffn": 94,
                        "shared_expert": 71, "routed_experts": 9,
                        "router": 16, "head": 101, "flash": 42, "kda": 27}
    # a token and head of a KDA layer, forward: 5 C D + 6 D^2 + 2 C^2/3
    assert w.kda_flops_per_token(config) == pytest.approx(
        32 * (5 * 64 * 128 + 6 * 128 * 128 + 2 * 64 * 64 / 3))
    assert w.kda_flops_per_token(config, backward=True) \
        == 32 * (10 * 64 * 128 + 12 * 128 * 128 + 2 * 64 * 64)
    total = w.train_flops_per_token(config, 4096)
    # 3 x everything but the scan, whose backward is its own count
    assert total == pytest.approx(
        3 * (sum(fwd.values()) - fwd["kda"]) + fwd["kda"]
        + 6 * w.kda_flops_per_token(config, backward=True))
    assert total / 1e9 == pytest.approx(3.17, abs=0.01)


def test_kda_step_against_a_hand_count_at_a_tiny_shape():
    """One KDA layer, 2 heads of 8, chunks of 4 and blocks of 2 chunks,
    16 tokens: every product of the WY form written out."""
    w = _load("builders", "ling3_flops")
    config = {"layer_group_size": 2, "num_hidden_layers": 1,
              "num_attention_heads": 2, "head_dim": 8,
              "kda_chunk_size": 4, "kda_block_chunks": 2}
    c, d, h, tokens = 4, 8, 2, 16
    chunks = tokens // c
    # forward, a chunk and head (a triangular product half its square)
    fwd = (2 * (2 * c * c * d / 2)          # M and P
           + 2 * c ** 3 / 3                 # the triangular inverse
           + 2 * (2 * c * c * d / 2)        # W = T (beta Kg), U = T (beta V)
           + 2 * (2 * c * d * d)            # W Z^T, Qg Z^T
           + 2 * c * c * d / 2              # P Ut
           + 2 * c * d * d)                 # Ut^T Kend
    bwd = ((2 * c * c * d / 2 + 2 * c * d * d)   # dUt
           + 2 * c * c * d / 2                   # dP
           + 2 * (2 * c * d * d)                 # dQg, dKend
           + 2 * (2 * c * d * d)                 # dZ's two products
           + 2 * c * d * d                       # dW
           + 2 * (2 * c * c * d / 2)             # dT's two products
           + 2 * (2 * c * c * d / 2)             # dVb, dKb
           + 2 * (2 * c ** 3 / 2)                # T^T dT T^T
           + 4 * (2 * c * c * d / 2))            # dleft, dright of M and P
    flops_, nbytes = w.kda_step(config, 1, tokens)
    assert flops_ == pytest.approx(h * chunks * (fwd + bwd))
    act, decay, beta = tokens * h * d * 2, tokens * h * d * 4, tokens * h * 4
    states = (tokens // 8) * h * d * d * 4
    # forward: Q K V in, G, beta, O out, states out; backward: Q K V G
    # beta dO states in, dQ dK dV dG dbeta out
    assert nbytes == (4 * act + decay + beta + states) \
        + (4 * act + decay + beta + states + 3 * act + decay + beta)
    # no KDA layer, no work
    assert w.kda_step(dict(config, layer_group_size=1), 1, tokens) \
        == (0.0, 0.0)


def test_kernel_work_at_the_cells_sizes():
    w, config = _load("builders", "ling3_flops"), _config()
    flops_, nbytes = w.kda_step(config, 1, 4096)
    # six layers of 32 heads over 4,096 tokens
    assert flops_ / 1e9 == pytest.approx(337.2, abs=0.1)
    # a layer: Q, K, V, O forward and Q, K, V, dO, dQ, dK, dV backward
    # 11 x 33.55 MB, G twice and dG 3 x 67.1 MB, the states written and
    # read 2 x 33.55 MB, beta twice and d beta 3 x 0.5 MB
    assert nbytes / 6 / 1e6 == pytest.approx(639.1, abs=0.1)
    least_s, bound = flops.roofline_seconds(
        flops_, nbytes, {"bf16_flops_per_s": 197e12,
                         "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and least_s * 1e3 == pytest.approx(4.68,
                                                                abs=0.01)


def test_config_against_the_published():
    """Every number of the catalog row's `config` as published, but the
    four cuts; no width among them; the two clamp lists kept whole."""
    config = _config()
    assert config["source"] == SOURCE
    differs = [k for k, v in PUBLISHED.items()
               if k not in config or config[k] != v]
    assert sorted(differs) == sorted(REDUCED)
    assert [config[k] for k in REDUCED] == [7, 1, 8, 19648]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["num_experts_published"] == PUBLISHED["num_experts"]
    assert config["held_experts"] == list(range(8))
    assert {k: config["published"][k] for k in REDUCED} \
        == {k: PUBLISHED[k] for k in REDUCED}
    assert sorted(config["reduced_why"]) == sorted(REDUCED)
    # no clamp in a layer that is kept, and no placeholder id in the slice
    assert not any(config["expert_swiglu_limit_list"][:7])
    assert not any(config["share_expert_swiglu_limit_list"][:7])
    assert min(PUBLISHED[k] for k in PUBLISHED if k.endswith("_token")) \
        >= config["vocab_size"]
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in spec["configs"]
                 if c["name"] == "ling-3.0-flash-vl")
    assert entry["reduced"] == REDUCED
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmarks/configs/ling-3.0-flash-vl.json"
    assert config["kernel_impls"] == {
        "kda_scan": "pallas", "kda_scan_grad": "saved",
        "flash_attention": "pallas", "moe_gmm": "pallas",
        "moe_route_scoring": "sigmoid", "moe_route_groups": "4of8"}
    for key in ("deployment", "assumed", "reference_rtol_why"):
        assert config[key]
    for key in ("layer_kinds", "kda_gate", "kda_conv", "kda_qk_norm",
                "kda_output", "mla_gate", "router", "head", "kda_chunking",
                "initializer", "optimizer", "adam_moments", "recompute"):
        assert config["assumed"][key], key
    for word in ("vision_tower", "multi_token_prediction"):
        assert config["published"][word]
    for word in ("64 chips", "over 8 of them", "64 rows", "4096",
                 "821.8 M", "13.15 GB"):
        assert word in config["deployment"], word
    assert 0 < config["reference_rtol"] < 1e-3


# -- reference/ling3.py against the program -----------------------------------

@pytest.mark.parametrize("amp,recompute,rtol", [
    (False, False, 1e-4), (False, True, 1e-4), (True, True, 2e-2)])
def test_ling3_reference(amp, recompute, rtol):
    import jax

    import paddle_tpu as fluid

    config = dict(TINY, amp=amp, recompute=recompute)
    kind = _load("kinds", "train_steps")
    kind._fresh_programs()
    np.random.seed(0)
    built = _load("builders", "ling3").build(
        config, {"batch": 2, "seq_len": 64}, flops)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = built["make_batch"](np.random.default_rng(0))
    assert batch[0].shape == (2, 64, 1) and batch[0].max() < 128
    assert (batch[1][:, :-1] == batch[0][:, 1:]).all()
    ref = _load("reference", "ling3")
    params = ref.read_params(config, kind._scope_get)
    want = ref.loss(params, batch, config)
    if not amp:
        # the controls compute another loss (before the step donates
        # and changes the weights): the two wrong models outside the
        # program's distance (6e-5 and more at N(0, 0.02) matrices, a
        # zero selection bias and 128 tokens; tests/test_ling3_model.py
        # holds them at wider weights), the layers in bfloat16 inside
        # the AMP limit
        for variant in ("no_erase", "no_group_limit"):
            wrong = ref.loss(params, batch, config, variant=variant)
            assert abs(wrong - want) > 2e-5 * want, variant
        assert ref.loss(params, batch, config, dtype="bfloat16") \
            == pytest.approx(want, rel=2e-2)
    got, = exe.run(built["compiled"],
                   feed=dict(zip(["src_ids", "tgt_label"], batch)),
                   fetch_list=[built["loss"]])
    assert float(np.asarray(got).reshape(-1)[0]) == \
        pytest.approx(want, rel=rtol)
    assert 0.9 * np.log(128) < want < 1.1 * np.log(128)
    assert built["items_per_step"] == 128 and built["flops_per_item"] > 0
    assert set(built["kernel_work"]) == {"kda", "mla_flash", "moe_gmm"}
    for work in built["kernel_work"].values():
        assert work["flops"] > 0 and work["bytes"] > 0
    # the reference reads every parameter the program has, and the
    # routers' selection biases besides
    names = {p.name for p in fluid.default_main_program().all_parameters()}
    read = set(jax.tree_util.tree_leaves(ref.param_names(config)))
    assert names <= read
    assert all(n.endswith("router_bias.w") for n in read - names)


def test_benchmark_reference_is_the_repositorys():
    with open(os.path.join(BENCH, "reference", "ling3.py")) as f, \
            open(os.path.join(CHECKOUT, "paddle_tpu", "models",
                              "ling3_reference.py")) as g:
        assert f.read() == g.read()


# -- the cell kind end to end on the CPU --------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A temporary benchmark root with the benchmark's code, a tiny
    ling3 configuration and one cell (test_rehearsal.py's way)."""
    from test_rehearsal import CODE, _metric

    root = tmp_path_factory.mktemp("checkout")
    bench = root / "benchmarks"
    bench.mkdir()
    for name in CODE:
        src = os.path.join(BENCH, name)
        (shutil.copytree if os.path.isdir(src) else shutil.copy)(
            src, bench / name)
    (bench / "peaks.json").write_text(json.dumps({
        "source": "made up for the CPU rehearsal",
        "kinds": {"cpu": {"bf16_flops_per_s": 1e12,
                          "hbm_bytes_per_s": 1e11}}}))
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    (bench / "configs" / "tiny-ling3.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_seq.json").write_text(json.dumps(
        {"kind": "train_steps", "batch": 2, "seq_len": 64,
         "rate_metric": "tokens_per_s"}))
    cells = ["c_ling3"]
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny-ling3", "source": "test",
                     "reduced": [],
                     "file": "benchmarks/configs/tiny-ling3.json",
                     "why": "test"}],
        "workloads": [{"name": "c_ling3", "config": "tiny-ling3",
                       "traffic": "tiny_seq", "chips": 4, "why": "test"}],
        "end_to_end": [
            {"name": "tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": cells},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        # every per-layer metric the real cell is listed under
        "per_layer": [
            _metric(e["name"], e["unit"], e["source"], e["layer"],
                    e["moves"], cells)
            for e in spec["per_layer"] if CELL in e["workloads"]]}))
    return str(root)


def _run(root, trace, **over):
    path = os.path.join(root, "benchmarks", "configs", "tiny-ling3.json")
    if over:
        with open(path, "w") as f:
            json.dump(dict(TINY, **over), f)
    out = io.StringIO()
    try:
        result = harness.run_cell(root, "c_ling3", seed=2147483999,
                                  seconds=0.5, trace=trace, platform="cpu",
                                  out=out)
    finally:
        if over:
            with open(path, "w") as f:
                json.dump(TINY, f)
    return result, [json.loads(x)
                    for x in out.getvalue().strip().splitlines()[:-1]]


def test_cell_end_to_end_line(root):
    result, earlier = _run(root, trace=0)
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert result["correct"], (checks["checks"], checks["wrong_impls"],
                               checks["kernel_impls"])
    used = checks["kernel_impls"]
    assert {k.split(":")[0] for k in used} >= {
        "flash_attention", "kda_scan", "moe_gmm", "moe_route_groups"}
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    fixed = checks["fixed_batch_losses"]
    assert fixed[0] > fixed[1] > fixed[2]


def test_the_named_impls_decide_correct(root):
    """The configuration of the real cell names the Pallas scan, its
    grad on the saved states, the Pallas flash and grouped matmuls and
    the 4-of-8 group limit: a run of the XLA forms, of a backward that
    did not run on the saved states (here the segment differentiates
    its replay and the grad op never runs), or of another group limit
    (all of which happen here, off the chip and at a tiny router) is
    not correct, whatever its loss."""
    result, earlier = _run(root, trace=0,
                           kernel_impls=_config()["kernel_impls"])
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert not result["correct"]
    assert checks["checks"]["kernel_impls"] is False
    assert checks["wrong_impls"] == {
        "flash_attention": ["xla"], "kda_scan": ["xla"],
        "kda_scan_grad": [], "moe_gmm": ["xla"],
        "moe_route_groups": ["2of4"]}
    assert checks["checks"]["reference"] is True


def test_cell_per_layer_line(root):
    result, _ = _run(root, trace=1)
    assert result["correct"]
    # no device plane in a CPU trace: the readers of named kernels
    # (kda_ms, kda_roofline, mla_flash_roofline, flash_*, moe_gmm_*)
    # and of trace categories return nothing and the line leaves them
    # out; the stat rings are read without the trace's help
    assert {"feed_wait_ms", "feed_put_ms", "feed_put_in_run_ms",
            "enqueue_ms", "run_prepare_ms", "run_fetch_ms", "step_p50_ms",
            "mfu_pct", "step_hbm_gb", "build_s", "compile_s",
            "first_call_s"} <= set(result["metrics"])
    assert not {"kda_ms", "kda_roofline", "mla_flash_roofline",
                "moe_gmm_ms"} & set(result["metrics"])


def test_new_readers_return_nothing_where_the_trace_has_no_such_kernel():
    """On the parent's program, and on every cell without a KDA layer,
    the trace holds no pt_kda_* call and the work has no `kda` entry:
    the readers return None and raise nothing."""
    for name in ("kda_ms", "kda_roofline"):
        read = _load("layer_metrics", name).read
        assert read({"trace": None, "work": {"kernel_work": {}},
                     "clocks": {}}) is None
        no_kda = {"first": 0, "devices": [
            {"op_ns": {"mosaic:pt_flash_fwd": 5e6}, "steps": 2}]}
        assert read({"trace": no_kda, "work": {"kernel_work": {}},
                     "clocks": {}}) is None


def test_new_readers_on_a_made_up_trace():
    """2 steps, 30 ms of pt_kda_fwd and 50 ms of pt_kda_bwd: 40 ms a
    step, and against work whose least time is 4 ms a share of 10%."""
    trace = {"first": 0, "devices": [{"op_ns": {
        "mosaic:pt_kda_fwd": 30e6, "mosaic:pt_kda_bwd": 50e6,
        "mosaic:pt_flash_fwd": 7e6}, "steps": 2}]}
    m = {"trace": trace, "chips": 1, "flops": flops,
         "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
         "work": {"kernel_work": {"kda": {"flops": 4e9, "bytes": 1e8}}}}
    assert _load("layer_metrics", "kda_ms").read(m) == pytest.approx(40.0)
    assert _load("layer_metrics", "kda_roofline").read(m) \
        == pytest.approx(10.0)


# -- BENCHMARK.json's entries of PR 41 ----------------------------------------

def test_benchmark_entries():
    """By name, not by position, and `<=`: a later PR appends cells and
    metrics after these and may append this cell to further lists."""
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="ling-3.0-flash-vl",
                        traffic="train_s4k_b1", chips=1)
    assert len(cell["why"]) <= 200
    for word in ("4096", "7 of 42", "KDA", "64 rows an expert"):
        assert word in cell["why"], word
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    job = json.load(open(os.path.join(BENCH, "traffic",
                                      "train_s4k_b1.json")))
    assert (job["kind"], job["batch"], job["seq_len"],
            job["rate_metric"]) == ("train_steps", 1, 4096,
                                    "tokens_per_s")
    config = _config()
    assert job["seq_len"] % (config["kda_chunk_size"]
                             * config["kda_block_chunks"]) == 0
    reports = {e["name"] for e in spec["per_layer"]
               if CELL in e.get("workloads", ())}
    assert {
        "feed_wait_ms", "enqueue_ms", "step_p50_ms", "device_idle_pct",
        "mfu_pct", "step_hbm_gb", "build_s", "compile_s", "first_call_s",
        "matmul_ms", "flash_fwd_ms", "flash_bwd_ms", "run_prepare_ms",
        "run_fetch_ms", "feed_put_ms", "feed_put_in_run_ms",
        "other_fusion_ms", "copy_ms", "mla_flash_roofline", "moe_gmm_ms",
        "moe_gmm_roofline", "moe_gmm_tile_us", "moe_gmm_roofline_live",
        "moe_live_tiles", "moe_live_tiles_window", "kda_ms",
        "kda_roofline"} <= reports
    # no state-space scan and no token-major flash metric here
    assert not {n for n in reports if n.startswith(("ssd_", "gqa_"))}
    assert "flash_roofline" not in reports and "flash_ms" not in reports
    for name in ("kda_ms", "kda_roofline"):
        e = next(e for e in spec["per_layer"] if e["name"] == name)
        assert e == dict(e, layer="kernels", moves="tokens_per_s",
                         source="device_trace")
        assert {CELL} <= set(e["workloads"])
        assert e["unit"] == ("ms" if name.endswith("_ms") else "%")
    for e in spec["per_layer"]:
        if CELL in e.get("workloads", ()):
            assert e["moves"] in ("tokens_per_s", "setup_s")
            assert callable(_load("layer_metrics",
                                  e["name"].split(".")[0]).read)
    assert CELL in next(e for e in spec["end_to_end"]
                        if e["name"] == "tokens_per_s")["workloads"]
    # every share of a roofline or of a peak that moves tokens_per_s and
    # that this cell's kernels feed is reported here
    assert {"mfu_pct", "mla_flash_roofline", "moe_gmm_roofline",
            "kda_roofline"} <= reports
