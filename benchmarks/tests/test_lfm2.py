"""The `lfm2-24b-a2b` configuration's benchmark files on the CPU, in a
file of their own (a `model_config` PR adds files and entries and edits
none): builders/lfm2_flops.py against the hand-worked numbers of ISSUE
45 and a hand count at a tiny shape, the configuration against the
catalog row's published numbers, reference/lfm2.py against the program
at tiny size, the cell kind end to end through the harness, the new
reader gated_conv_roofline, and BENCHMARK.json's entries of PR 45,
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

CELL = "lfm2_24b_train_s8k"
CONFIG = "lfm2-24b-a2b"
# the catalog row `LFM2-24B-A2B` (architectures.jsonl beside the
# model-configs guide): its `source_url` and its `config`, copied here
# so that the test reads nothing outside the checkout
SOURCE = ("https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/"
          "config.json")
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": (["conv", "conv"]
                    + ["full_attention", "conv", "conv", "conv"] * 9
                    + ["full_attention", "conv"]),
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
}
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts",
           "vocab_size"]

TINY = {
    "builder": "lfm2", "reference": "lfm2", "param_prefix": "lfm2",
    "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 256,
    "moe_intermediate_size": 64, "num_experts": 4,
    "num_experts_published": 16, "held_experts": [0, 1, 2, 3],
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "num_hidden_layers": 5, "num_dense_layers": 1,
    "kept_layers": [0, 2, 3, 4, 5],
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv"],
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "vocab_size": 128, "initializer_range": 0.02, "amp": True,
    "learning_rate": 1e-3, "recompute": True,
    # off the chip the kernel entries resolve to their XLA forms: the
    # gates as products round the XLA convolution, plain attention on
    # K and V repeated to the query heads, the grouped matmuls in
    # jax.numpy
    "kernel_impls": {"causal_conv1d": "xla", "causal_conv1d_gates": "xla",
                     "flash_attention": "xla",
                     "flash_attention_kv_heads": "repeated",
                     "moe_gmm": "xla", "moe_route_scoring": "sigmoid"},
    "reference_rtol": 2e-2,
}


def _load(kind, name):
    return harness._load_file(os.path.join(BENCH, kind, name + ".py"))


def _config():
    return json.load(open(os.path.join(BENCH, "configs",
                                       CONFIG + ".json")))


# -- builders/lfm2_flops.py ---------------------------------------------------

def test_parameters_at_the_cells_sizes():
    """ISSUE 45's arithmetic of the cut, from the functions."""
    w, config = _load("builders", "lfm2_flops"), _config()
    assert w.layer_kinds(config) == ["conv", "full_attention", "conv",
                                     "conv", "conv"]
    conv = w.layer_params(config, "conv", True)
    assert conv["mixer"] == 2048 * 6144 + 2048 * 2048 == 16_777_216
    assert conv["ffn"] == 3 * 2048 * 11776 == 72_351_744
    assert conv["other"] == 2048 * 3 + 2 * 2048 and conv["router"] == 0
    assert sum(conv.values()) == 89_139_200
    attn = w.layer_params(config, "full_attention", False)
    assert attn["mixer"] == 2 * 2048 * 2048 + 2 * 2048 * 512 \
        == 10_485_760
    assert attn["other"] == 2 * 64 + 2 * 2048
    assert attn["ffn"] == 16 * 3 * 2048 * 1536 == 150_994_944
    assert attn["router"] == 2048 * 64
    assert sum(attn.values()) == 161_616_000
    assert sum(w.layer_params(config, "conv", False).values()) \
        == 167_913_472
    # 89.14 + 161.62 + 3 x 167.91 M, the tied slice 33.55 M, one norm
    assert w.n_params(config) == 89_139_200 + 161_616_000 \
        + 3 * 167_913_472 + 16384 * 2048 + 2048 == 788_052_096
    assert round(w.n_params(config) / 1e6, 1) == 788.1
    assert w.n_params(config) * 16 / 1e9 == pytest.approx(12.61, abs=0.01)


def test_flops_per_token_at_the_cells_sizes():
    w, config = _load("builders", "lfm2_flops"), _config()
    fwd = w.forward_flops_per_token(config, 8192)
    in_mflop = {k: round(v / 1e6, 1) for k, v in fwd.items()}
    assert in_mflop == {"dense_ffn": 144.7, "conv_proj": 134.2,
                        "attention_proj": 21.0, "routed_experts": 75.5,
                        "router": 1.0, "head": 67.1, "flash": 33.6}
    total = sum(fwd.values())
    assert total / 1e6 == pytest.approx(477.1, abs=0.1)
    shares = {k: round(100 * v / total) for k, v in fwd.items()}
    # the cell's `why`: 30 / 28 / 16 / 14 / 7 / 4
    assert shares == {"dense_ffn": 30, "conv_proj": 28,
                      "routed_experts": 16, "head": 14, "flash": 7,
                      "attention_proj": 4, "router": 0}
    # 4 experts a token over 64, 16 held: ONE expert a token here
    assert fwd["routed_experts"] == 2.0 * 4 * 1 * 3 * 2048 * 1536
    assert w.train_flops_per_token(config, 8192) == 3 * total
    assert 3 * total / 1e9 == pytest.approx(1.431, abs=0.001)


def test_gated_conv_step_against_a_hand_count_at_a_tiny_shape():
    """One conv layer, 4 channels, 3 taps, 8 tokens, float32."""
    w = _load("builders", "lfm2_flops")
    config = {"layer_types": ["conv", "full_attention"],
              "num_hidden_layers": 2, "hidden_size": 4, "conv_L_cache": 3}
    els = 8 * 4
    fwd = 1 + (3 + 2) + 1                # B x; 3 products, 2 sums; C c
    bwd = (1 + 5) + 2 + 5 + 2 + 2 * 3    # again; dC, dc; taps; dB, dx; dW
    ops, nbytes = w.gated_conv_step(config, 1, 8, bytes_per_el=4)
    assert ops == els * (fwd + bwd) == els * 28
    # forward B, C, x in and y out; backward B, C, x, dy in, dB, dC, dx out
    assert nbytes == els * 4 * (4 + 7)
    assert w.gated_conv_step(dict(config, kept_layers=[1],
                                  num_hidden_layers=1), 1, 8) == (0.0, 0.0)


def test_kernel_work_at_the_cells_sizes():
    w, config = _load("builders", "lfm2_flops"), _config()
    ops, nbytes = w.gated_conv_step(config, 1, 8192)
    # four conv layers (layer 0 and three of the period); a layer: 134
    # MB forward, 235 MB backward
    assert nbytes / 4 / 1e6 == pytest.approx(134.2 + 234.9, abs=0.1)
    least_s, bound = flops.roofline_seconds(
        ops, nbytes, {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9})
    assert bound == "memory"
    assert least_s * 1e3 / 4 == pytest.approx(0.45, abs=0.005)
    fops, fbytes = w.gqa_flash_step(config, 1, 8192, flops)
    # granite-4.0-h-micro's attention layer exactly: 32 / 8 heads of 64
    g = _load("builders", "granite_hybrid_flops")
    granite = json.load(open(os.path.join(
        BENCH, "configs", "granite-4.0-h-micro.json")))
    assert (fops, fbytes) == g.gqa_flash_step(granite, 1, 8192, flops)
    assert fops == 3 * 0.5 * 4 * 32 * 8192 * 8192 * 64


def test_config_against_the_published():
    """Every entry of the catalog row's `config` as published, but the
    four cuts; no width among them; `layer_types` kept whole."""
    config = _config()
    assert config["source"] == SOURCE
    differs = [k for k, v in PUBLISHED.items()
               if k not in config or config[k] != v]
    assert sorted(differs) == sorted(REDUCED)
    assert [config[k] for k in REDUCED] == [5, 1, 16, 16384]
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert config["num_experts_published"] == PUBLISHED["num_experts"]
    assert config["held_experts"] == list(range(16))
    assert config["kept_layers"] == [0, 2, 3, 4, 5]
    assert len(config["layer_types"]) == 40
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert sorted(config["reduced_why"]) == sorted(REDUCED)
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    assert len(entry["why"]) <= 200
    assert config["kernel_impls"] == {
        "causal_conv1d": "pallas", "causal_conv1d_gates": "fused",
        "flash_attention": "pallas",
        "flash_attention_kv_heads": "grouped", "moe_gmm": "pallas",
        "moe_route_scoring": "sigmoid"}
    for key in ("deployment", "assumed", "reference_rtol_why"):
        assert config[key]
    for key in ("tie_embedding", "projection_order", "head_dim", "router",
                "initialization", "norms", "optimizer", "recompute"):
        assert config["assumed"][key], key
    for word in ("Four chips", "16 of the 64 experts", "ten pipeline",
                 "WITHOUT", "788.05 M", "12.61 GB"):
        assert word in config["deployment"], word
    assert 0 < config["reference_rtol"] < 1e-3


# -- reference/lfm2.py against the program ------------------------------------

@pytest.mark.parametrize("amp,recompute,rtol", [
    (False, False, 1e-4), (False, True, 1e-4), (True, True, 2e-2)])
def test_lfm2_reference(amp, recompute, rtol):
    import jax

    import paddle_tpu as fluid

    config = dict(TINY, amp=amp, recompute=recompute)
    kind = _load("kinds", "train_steps")
    kind._fresh_programs()
    np.random.seed(0)
    built = _load("builders", "lfm2").build(
        config, {"batch": 2, "seq_len": 64}, flops)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = built["make_batch"](np.random.default_rng(0))
    assert batch[0].shape == (2, 64, 1) and batch[0].max() < 128
    assert (batch[1][:, :-1] == batch[0][:, 1:]).all()
    ref = _load("reference", "lfm2")
    params = ref.read_params(config, kind._scope_get)
    want = ref.loss(params, batch, config)
    if not amp:
        # the controls compute another loss (before the step donates
        # and changes the weights): a wrong model outside the program's
        # distance, the layers in bfloat16 inside the AMP limit
        wrong = ref.loss(params, batch, config, variant="no_input_gate")
        assert abs(wrong - want) > 2e-5 * want
        assert ref.loss(params, batch, config, dtype="bfloat16") \
            == pytest.approx(want, rel=2e-2)
    got, = exe.run(built["compiled"],
                   feed=dict(zip(["src_ids", "tgt_label"], batch)),
                   fetch_list=[built["loss"]])
    assert float(np.asarray(got).reshape(-1)[0]) == \
        pytest.approx(want, rel=rtol)
    assert 0.9 * np.log(128) < want < 1.1 * np.log(128)
    assert built["items_per_step"] == 128 and built["flops_per_item"] > 0
    assert set(built["kernel_work"]) == {"gated_conv", "gqa_flash",
                                         "moe_gmm"}
    for work in built["kernel_work"].values():
        assert work["flops"] > 0 and work["bytes"] > 0
    # the reference reads every parameter the program has, and the
    # routers' selection biases besides
    names = {p.name for p in fluid.default_main_program().all_parameters()}
    read = set(jax.tree_util.tree_leaves(ref.param_names(config)))
    assert names <= read
    assert all(n.endswith("router_bias.w") for n in read - names)


# -- the cell kind end to end on the CPU --------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A temporary benchmark root with the benchmark's code, a tiny
    lfm2 configuration and one cell (test_rehearsal.py's way)."""
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
    (bench / "configs" / "tiny-lfm2.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_seq.json").write_text(json.dumps(
        {"kind": "train_steps", "batch": 2, "seq_len": 64,
         "rate_metric": "tokens_per_s"}))
    cells = ["c_lfm2"]
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny-lfm2", "source": "test",
                     "reduced": [],
                     "file": "benchmarks/configs/tiny-lfm2.json",
                     "why": "test"}],
        "workloads": [{"name": "c_lfm2", "config": "tiny-lfm2",
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
    path = os.path.join(root, "benchmarks", "configs", "tiny-lfm2.json")
    if over:
        with open(path, "w") as f:
            json.dump(dict(TINY, **over), f)
    out = io.StringIO()
    try:
        result = harness.run_cell(root, "c_lfm2", seed=2147483999,
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
        "causal_conv1d", "causal_conv1d_gates", "flash_attention",
        "flash_attention_kv_heads", "moe_gmm", "moe_route_scoring"}
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    fixed = checks["fixed_batch_losses"]
    assert fixed[0] > fixed[1] > fixed[2]


def test_the_named_impls_decide_correct(root):
    """The configuration of the real cell names the Pallas convolution
    with its gates inside, the Pallas flash reading K and V in place
    and the Pallas grouped matmuls: a run of the XLA forms (which is
    what runs here, off the chip) is not correct, whatever its loss."""
    result, earlier = _run(root, trace=0,
                           kernel_impls=_config()["kernel_impls"])
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert not result["correct"]
    assert checks["checks"]["kernel_impls"] is False
    assert checks["wrong_impls"] == {
        "causal_conv1d": ["xla"], "causal_conv1d_gates": ["xla"],
        "flash_attention": ["xla"],
        "flash_attention_kv_heads": ["repeated"], "moe_gmm": ["xla"]}
    assert checks["checks"]["reference"] is True


def test_cell_per_layer_line(root):
    result, _ = _run(root, trace=1)
    assert result["correct"]
    # no device plane in a CPU trace: the readers of named kernels
    # (gated_conv_roofline, conv1d_ms, gqa_flash_roofline, flash_*,
    # moe_gmm_*) and of trace categories return nothing and the line
    # leaves them out; the stat rings are read without the trace's help
    assert {"feed_wait_ms", "feed_put_ms", "feed_put_in_run_ms",
            "enqueue_ms", "run_prepare_ms", "run_fetch_ms", "step_p50_ms",
            "mfu_pct", "step_hbm_gb", "build_s", "compile_s",
            "first_call_s"} <= set(result["metrics"])
    assert not {"gated_conv_roofline", "conv1d_ms", "gqa_flash_roofline",
                "moe_gmm_ms"} & set(result["metrics"])


def test_the_new_reader_returns_nothing_where_there_is_nothing_to_read():
    """On the parent's program, and in every cell whose convolutions
    have no gates (granite4, ling3: their traces DO hold pt_conv1d_*
    calls), the work has no `gated_conv` entry: the reader returns
    None and raises nothing; nor where the work is there and the trace
    holds no such call."""
    read = _load("layer_metrics", "gated_conv_roofline").read
    assert read({"trace": None, "work": {"kernel_work": {}},
                 "clocks": {}}) is None
    ungated = {"first": 0, "devices": [
        {"op_ns": {"mosaic:pt_conv1d_fwd": 5e6,
                   "mosaic:pt_conv1d_bwd": 4e6}, "steps": 2}]}
    assert read({"trace": ungated, "work": {"kernel_work": {
        "ssd": {"flops": 1.0, "bytes": 1.0}}}, "clocks": {}}) is None
    no_conv = {"first": 0, "devices": [
        {"op_ns": {"mosaic:pt_flash_fwd": 5e6}, "steps": 2}]}
    assert read({"trace": no_conv, "work": {"kernel_work": {
        "gated_conv": {"flops": 1.0, "bytes": 1.0}}}, "chips": 1,
        "flops": flops, "peaks": {"bf16_flops_per_s": 1e12,
                                  "hbm_bytes_per_s": 1e11}}) is None


def test_the_new_reader_on_a_made_up_trace():
    """2 steps, 6 ms of pt_conv1d_fwd and 4 ms of pt_conv1d_bwd: 5 ms a
    step, and against work whose least time is 2 ms (by its bytes) a
    share of 40%."""
    trace = {"first": 0, "devices": [{"op_ns": {
        "mosaic:pt_conv1d_fwd": 6e6, "mosaic:pt_conv1d_bwd": 4e6,
        "mosaic:pt_flash_fwd": 7e6}, "steps": 2}]}
    m = {"trace": trace, "chips": 1, "flops": flops,
         "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
         "work": {"kernel_work": {"gated_conv": {"flops": 1e8,
                                                 "bytes": 2e8}}}}
    assert _load("layer_metrics", "gated_conv_roofline").read(m) \
        == pytest.approx(40.0)
    assert _load("layer_metrics", "conv1d_ms").read(m) \
        == pytest.approx(5.0)


# -- BENCHMARK.json's entries of PR 45 ----------------------------------------

def test_benchmark_entries():
    """By name, not by position, and `<=`: a later PR appends cells and
    metrics after these and may append this cell to further lists."""
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="train_s8k_b1_ep",
                        chips=1)
    assert len(cell["why"]) <= 200
    for word in ("8192", "EP-4", "512 rows each", "2048 deployed",
                 "gated conv 2% of time"):
        assert word in cell["why"], word
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    job = json.load(open(os.path.join(BENCH, "traffic",
                                      "train_s8k_b1_ep.json")))
    assert (job["kind"], job["batch"], job["seq_len"],
            job["rate_metric"]) == ("train_steps", 1, 8192,
                                    "tokens_per_s")
    # 32,768 pairs a step: 512 rows a held expert under a uniform router
    config = _config()
    assert job["batch"] * job["seq_len"] * config["num_experts_per_tok"] \
        // config["num_experts_published"] == 512
    reports = {e["name"] for e in spec["per_layer"]
               if CELL in e.get("workloads", ())}
    assert {
        "feed_wait_ms", "enqueue_ms", "step_p50_ms", "device_idle_pct",
        "mfu_pct", "step_hbm_gb", "build_s", "compile_s", "first_call_s",
        "matmul_ms", "flash_fwd_ms", "flash_bwd_ms", "run_prepare_ms",
        "run_fetch_ms", "other_fusion_ms", "copy_ms", "gqa_flash_roofline",
        "moe_gmm_ms", "moe_gmm_roofline", "moe_gmm_tile_us",
        "moe_gmm_roofline_live", "moe_live_tiles", "moe_live_tiles_window",
        "moe_combine_ms", "conv1d_ms", "gated_conv_roofline"} <= reports
    # no scan, no latent attention, no token-major-only flash metric
    assert not {n for n in reports if n.startswith(("ssd_", "kda_",
                                                    "mla_"))}
    assert "flash_roofline" not in reports and "flash_ms" not in reports
    e = next(e for e in spec["per_layer"]
             if e["name"] == "gated_conv_roofline")
    assert e == dict(e, layer="kernels", moves="tokens_per_s",
                     source="device_trace", unit="%", better="higher")
    # where its reader finds something to read: this cell, and not the
    # cells whose convolutions have no gates
    assert {CELL} <= set(e["workloads"])
    assert not {"granite4_h_micro_train_b1", "ling3_flash_train_s4k"} \
        & set(e["workloads"])
    for e in spec["per_layer"]:
        if CELL in e.get("workloads", ()):
            assert e["moves"] in ("tokens_per_s", "setup_s")
            assert callable(_load("layer_metrics",
                                  e["name"].split(".")[0]).read)
    assert CELL in next(e for e in spec["end_to_end"]
                        if e["name"] == "tokens_per_s")["workloads"]
    # every share of a roofline or of a peak that moves tokens_per_s and
    # that this cell's kernels feed is reported here
    assert {"mfu_pct", "gqa_flash_roofline", "moe_gmm_roofline",
            "moe_gmm_roofline_live", "gated_conv_roofline"} <= reports
