"""The `solar-open2-250b` configuration's benchmark files on the CPU, in
a file of their own (a `model_config` PR adds files and entries and
edits none): builders/solar_open2_flops.py against the hand-worked
numbers of ISSUE 49 and hand counts from shapes, the configuration
against the catalog row's published numbers, reference/solar_open2.py
against the program at tiny size, the cell kind end to end through the
harness, the new reader kda_head_chunk_us, and BENCHMARK.json's entries
of PR 49, looked up BY NAME and held with `<=`: a later PR appends
after them.

Tolerances as in test_reference.py: float32 1e-4 (the same mathematics
in another order), AMP 2e-2 at these sizes (a loss over 128 tokens).
"""

import importlib.util
import io
import json
import os
import shutil

import numpy as np
import pytest

from conftest import BENCH, CHECKOUT

import flops
import harness

CELL = "solar_open2_train_s8k"
CONFIG = "solar-open2-250b"
# the catalog row `Solar-Open2-250B` (architectures.jsonl beside the
# model-configs guide): its `source_url` and its `config`, copied here
# so that the test reads nothing outside the checkout
SOURCE = ("https://huggingface.co/upstage/Solar-Open2-250B/blob/main/"
          "config.json")
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48,
    "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
    "vocab_size": 196608, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8,
}
REDUCED = ["num_hidden_layers", "num_attention_heads",
           "num_key_value_heads", "linear_attn_config.num_heads",
           "n_routed_experts", "vocab_size"]

TINY = {
    "builder": "solar_open2", "reference": "solar_open2",
    "param_prefix": "solar", "hidden_size": 128, "head_dim": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 32,
                           "num_heads": 8, "num_kv_heads": None},
    "kda_heads_held": 4, "rms_norm_eps": 1e-5,
    "moe_intermediate_size": 64, "n_routed_experts": 4,
    "n_routed_experts_published": 16, "held_experts": [0, 1, 2, 3],
    "n_shared_experts": 1, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "first_k_dense_replace": 0, "num_hidden_layers": 4,
    "gqa_interval": 3, "gqa_layers": [0, 4], "use_rope": False,
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "tie_word_embeddings": False,
    "kda_chunk_size": 16, "kda_block_chunks": 2, "vocab_size": 128,
    "initializer_range": 0.02, "amp": True, "learning_rate": 1e-3,
    "recompute": True,
    # off the chip the kernel entries resolve to their XLA forms: the
    # scan in jax.numpy (every pair's own decay: exact whatever the
    # attr says, which is counted as given), plain attention, the
    # grouped matmuls in jax.numpy
    # (inside a recompute segment the XLA scan is differentiated with
    # its segment and counts no `kda_scan_grad`)
    "kernel_impls": {"kda_scan": "xla", "kda_scan_decay": "unbounded",
                     "flash_attention": "xla", "moe_gmm": "xla",
                     "moe_route_scoring": "sigmoid"},
    "reference_rtol": 2e-2,
}


def _load(kind, name):
    return harness._load_file(os.path.join(BENCH, kind, name + ".py"))


def _config():
    return json.load(open(os.path.join(BENCH, "configs",
                                       CONFIG + ".json")))


# -- builders/solar_open2_flops.py ---------------------------------------------

def test_parameters_at_the_cells_sizes():
    """ISSUE 49's arithmetic of the cut, from the functions."""
    w, config = _load("builders", "solar_open2_flops"), _config()
    assert w.layer_kinds(config) == ["gqa", "kda", "kda", "kda"]
    assert w.kda_heads(config) == 8
    parts = w.parameters(config)
    kda = (3 * 4096 * 1024 + 1024 * 4096 + 2 * 4096 * 128
           + 2 * 128 * 1024 + 4096 * 8 + 3 * 1024 * 4 + 8 + 1024 + 128)
    assert kda == 18_134_152
    assert parts["kda_mixer"] == 3 * kda
    assert parts["gqa_mixer"] == 3 * 4096 * 1024 + 2 * 4096 * 128 \
        == 13_631_488
    expert = 3 * 4096 * 1280
    assert parts["shared_expert"] == 4 * expert == 4 * 15_728_640
    assert parts["router"] == 4 * 4096 * 320
    assert parts["routed_experts"] == 4 * 8 * expert
    # an expert layer's feed-forward: 142.87 M
    assert expert + 4096 * 320 + 8 * expert == 142_868_480
    assert parts["embedding"] == parts["head"] == 24576 * 4096
    assert parts["norms"] == 9 * 4096
    total = sum(parts.values())
    assert total == 840_871_320
    # ISSUE 49: 840.8 M to the 0.1 M, 13.45 GB at 16 bytes
    assert abs(total - 840.8e6) < 0.1e6
    assert total * 16 / 1e9 == pytest.approx(13.45, abs=0.01)


def test_flops_per_token_at_the_cells_sizes():
    w, config = _load("builders", "solar_open2_flops"), _config()
    fwd = w.forward_flops_per_token(config, 8192)
    in_mflop = {k: round(v / 1e6, 1) for k, v in fwd.items()}
    assert in_mflop == {"kda_proj": 108.7, "gqa_proj": 27.3,
                        "shared_expert": 125.8, "routed_experts": 25.2,
                        "router": 10.5, "head": 201.3, "flash": 16.8,
                        "kda": 3.4}
    total = sum(fwd.values())
    assert total / 1e9 == pytest.approx(0.519, abs=0.001)
    shares = {k: round(100 * v / total) for k, v in fwd.items()}
    # the cell's `why` and ISSUE 49's make-up
    assert shares == {"head": 39, "shared_expert": 24, "kda_proj": 21,
                      "routed_experts": 5, "gqa_proj": 5, "flash": 3,
                      "router": 2, "kda": 1}
    # 8 experts a token over 320, 8 held: a fifth of an expert a token
    # in each of the four layers
    assert fwd["routed_experts"] == pytest.approx(
        4 * 2.0 * 8 * 8 / 320 * 3 * 4096 * 1280)
    # causal attention at 8 query heads of 128: T H 2 d
    assert fwd["flash"] == 8192 * 8 * 2 * 128
    # a head and token of the WY form forward: 5 C D + 6 D^2 + 2 C^2/3
    per_head = 5 * 64 * 128 + 6 * 128 * 128 + 2 * 64 * 64 / 3
    assert w.kda_flops_per_token(config) == pytest.approx(8 * per_head)
    assert fwd["kda"] == pytest.approx(3 * 8 * per_head)
    back = 10 * 64 * 128 + 12 * 128 * 128 + 2 * 64 * 64
    assert w.kda_flops_per_token(config, backward=True) == 8 * back
    assert w.train_flops_per_token(config, 8192) == pytest.approx(
        3 * (total - fwd["kda"]) + fwd["kda"] + 3 * 8 * back)
    assert w.train_flops_per_token(config, 8192) / 1e9 \
        == pytest.approx(1.56, abs=0.01)


def test_kernel_work_at_the_cells_sizes():
    w, config = _load("builders", "solar_open2_flops"), _config()
    # three KDA layers x 1 sequence x 8 heads x 8192 / 64
    assert w.kda_head_chunks(config, 1, 8192) == 3 * 8 * 128 == 3072
    ops, nbytes = w.kda_step(config, 1, 8192)
    tokens, h, d = 8192, 8, 128
    act, decay, beta = tokens * h * d * 2, tokens * h * d * 4, tokens * h * 4
    states = tokens // 256 * h * d * d * 4
    assert nbytes == 3 * ((4 * act + decay + beta + states)
                          + (7 * act + 2 * decay + 2 * beta + states))
    per_head = (5 * 64 * 128 + 6 * 128 * 128 + 2 * 64 * 64 / 3) \
        + (10 * 64 * 128 + 12 * 128 * 128 + 2 * 64 * 64)
    assert ops == pytest.approx(3 * tokens * h * per_head)
    # the count ling3's builder makes, at this block's heads and tokens
    ling3 = _load("builders", "ling3_flops")
    like = {"layer_group_size": 4, "num_hidden_layers": 3,
            "num_attention_heads": 8, "head_dim": 128}
    assert ling3.kda_step(like, 1, 8192) == pytest.approx((ops, nbytes))
    least_s, bound = flops.roofline_seconds(
        ops, nbytes, {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9})
    assert bound == "memory"
    assert least_s * 1e3 == pytest.approx(1.17, abs=0.01)
    fops, fbytes = w.gqa_flash_step(config, 1, 8192, flops)
    assert fops == 3 * 0.5 * 4 * 8 * 8192 * 8192 * 128
    row = 8192 * 128 * 2
    assert fbytes == (2 * 8 + 2 * 1) * row + (4 * 8 + 4 * 1) * row
    kernels = _load("builders", "xing4_flops")
    gops, _ = kernels.gmm_step(8192, 8, 8, 320, 4096, 1280, 4)
    # 1,638.4 rows a layer in expectation: 204.8 an expert
    assert kernels.routed_rows(8192, 8, 8, 320) == pytest.approx(1638.4)
    assert gops == pytest.approx(4 * 3 * 6 * 1638.4 * 4096 * 1280)


def test_config_against_the_published():
    """Every entry of the catalog row's `config` as published, but the
    cuts; no width among them; the nested group and the list copied
    whole; the heads held beside the published counts."""
    config = _config()
    assert config["source"] == SOURCE
    differs = [k for k, v in PUBLISHED.items()
               if k not in config or config[k] != v]
    top_level = [k for k in REDUCED if "." not in k]
    assert sorted(differs) == sorted(top_level)
    assert [config[k] for k in top_level] == [4, 8, 1, 8, 24576]
    # the nested group whole; the count held in a key of this repo's
    assert config["linear_attn_config"] == PUBLISHED["linear_attn_config"]
    assert config["kda_heads_held"] == 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["vocab_size"] == 192 * 128
    assert config["n_routed_experts_published"] == 320
    assert config["held_experts"] == list(range(8))
    # the published group of 8 query heads a KV head is kept
    assert config["num_attention_heads"] // config["num_key_value_heads"] \
        == PUBLISHED["num_attention_heads"] \
        // PUBLISHED["num_key_value_heads"]
    assert config["published"] == {
        "num_hidden_layers": 48, "num_attention_heads": 64,
        "num_key_value_heads": 8, "linear_attn_config.num_heads": 64,
        "n_routed_experts": 320, "vocab_size": 196608}
    assert sorted(config["reduced_why"]) == sorted(REDUCED)
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    assert len(entry["why"]) <= 200
    assert config["kernel_impls"] == {
        "kda_scan": "pallas", "kda_scan_grad": "saved",
        "kda_scan_decay": "unbounded", "flash_attention": "pallas",
        "moe_gmm": "pallas", "moe_route_scoring": "sigmoid"}
    for key in ("deployment", "assumed", "reference_rtol_why"):
        assert config[key]
    for key in ("layer_kinds", "gqa", "kda_conv", "kda_qk_norm",
                "kda_gate", "kda_beta", "kda_output", "router", "head",
                "kda_chunking", "initializer", "optimizer", "recompute"):
        assert config["assumed"][key], key
    for word in ("40 chips", "tensor-parallel over 8", "8 x 5 = 40",
                 "Twelve pipeline", "WITHOUT", "840.87 M", "13.45 GB",
                 "205 rows", "1,024"):
        assert word in config["deployment"], word
    assert 0 < config["reference_rtol"] < 1e-3


# -- reference/solar_open2.py against the program ------------------------------

@pytest.mark.parametrize("amp,recompute,rtol", [
    (False, False, 1e-4), (False, True, 1e-4), (True, True, 2e-2)])
def test_solar_open2_reference(amp, recompute, rtol):
    import jax

    import paddle_tpu as fluid

    config = dict(TINY, amp=amp, recompute=recompute)
    kind = _load("kinds", "train_steps")
    kind._fresh_programs()
    np.random.seed(0)
    built = _load("builders", "solar_open2").build(
        config, {"batch": 2, "seq_len": 64}, flops)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = built["make_batch"](np.random.default_rng(0))
    assert batch[0].shape == (2, 64, 1) and batch[0].max() < 128
    assert (batch[1][:, :-1] == batch[0][:, 1:]).all()
    ref = _load("reference", "solar_open2")
    params = ref.read_params(config, kind._scope_get)
    want = ref.loss(params, batch, config)
    if not amp:
        # the controls compute another loss (before the step donates
        # and changes the weights): a wrong model outside the program's
        # distance, the layers in bfloat16 inside the AMP limit
        wrong = ref.loss(params, batch, config, variant="no_gqa_gate")
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
    assert set(built["kernel_work"]) == {"kda", "gqa_flash", "moe_gmm"}
    for work in built["kernel_work"].values():
        assert work["flops"] > 0 and work["bytes"] > 0
    # three KDA layers x 2 sequences x 4 held heads x 64 / 16
    assert built["kernel_work"]["kda"]["head_chunks"] == 3 * 2 * 4 * 4
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
    solar_open2 configuration and one cell (test_rehearsal.py's way)."""
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
    (bench / "configs" / "tiny-solar.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_seq.json").write_text(json.dumps(
        {"kind": "train_steps", "batch": 2, "seq_len": 64,
         "rate_metric": "tokens_per_s"}))
    cells = ["c_solar"]
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny-solar", "source": "test",
                     "reduced": [],
                     "file": "benchmarks/configs/tiny-solar.json",
                     "why": "test"}],
        "workloads": [{"name": "c_solar", "config": "tiny-solar",
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
    path = os.path.join(root, "benchmarks", "configs", "tiny-solar.json")
    if over:
        with open(path, "w") as f:
            json.dump(dict(TINY, **over), f)
    out = io.StringIO()
    try:
        result = harness.run_cell(root, "c_solar", seed=2147483999,
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
        "kda_scan", "kda_scan_decay", "kda_gate_form", "flash_attention",
        "moe_gmm", "moe_route_scoring"}
    assert "kda_scan_decay:bounded" not in used
    assert "kda_gate_form:softplus" in used
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    fixed = checks["fixed_batch_losses"]
    assert fixed[0] > fixed[1] > fixed[2]


def test_the_named_impls_decide_correct(root):
    """The configuration of the real cell names the Pallas scan with
    its saved backward on the unbounded path, the Pallas flash and the
    Pallas grouped matmuls: a run of the XLA forms (which is what runs
    here, off the chip) is not correct, whatever its loss; nor would a
    scan built on the bounded path be."""
    result, earlier = _run(root, trace=0,
                           kernel_impls=_config()["kernel_impls"])
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert not result["correct"]
    assert checks["checks"]["kernel_impls"] is False
    assert checks["wrong_impls"] == {
        "kda_scan": ["xla"], "kda_scan_grad": [],
        "flash_attention": ["xla"], "moe_gmm": ["xla"]}
    assert checks["checks"]["reference"] is True
    result, earlier = _run(root, trace=0, kernel_impls=dict(
        TINY["kernel_impls"], kda_scan_decay="bounded"))
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert not result["correct"]
    assert checks["wrong_impls"] == {"kda_scan_decay": ["unbounded"]}


def test_cell_per_layer_line(root):
    result, _ = _run(root, trace=1)
    assert result["correct"]
    # no device plane in a CPU trace: the readers of named kernels
    # (kda_*, kda_head_chunk_us, conv1d_ms, gqa_flash_roofline, flash_*,
    # moe_gmm_*) and of trace categories return nothing and the line
    # leaves them out; the stat rings are read without the trace's help
    assert {"feed_wait_ms", "feed_put_ms", "feed_put_in_run_ms",
            "enqueue_ms", "run_prepare_ms", "run_fetch_ms", "step_p50_ms",
            "mfu_pct", "step_hbm_gb", "build_s", "compile_s",
            "first_call_s"} <= set(result["metrics"])
    assert not {"kda_ms", "kda_head_chunk_us", "kda_roofline",
                "conv1d_ms", "gqa_flash_roofline", "moe_gmm_ms"} \
        & set(result["metrics"])


VARIANTS = ["beta_not_doubled", "g_clamped", "no_gqa_gate",
            "kda_gate_a_head"]


def test_the_controls_tool_reads_program_and_wrong_models(root, tmp_path):
    """tools/reference_controls.py --logits on the tiny cell: a row a
    seed with the loop kind's own comparison beside each control, and
    the logits of the program's forward pass and of each control
    against the reference's.  The program (AMP) lies nearer the
    reference than the three wrong models whose effect reaches the
    logits at seeded weights; no token of a seeded gate reaches -5, so
    the clamp reads exactly nothing (the kernel's exactness there is
    tests/test_kda_scan.py's and tools/kda_unbounded_chip.py's)."""
    spec = importlib.util.spec_from_file_location(
        "reference_controls",
        os.path.join(CHECKOUT, "tools", "reference_controls.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = str(tmp_path / "rows.json")
    assert tool.main(["--root", root, "--cell", "c_solar", "--seeds",
                      "2147484001", "--variants", *VARIANTS,
                      "--logits", "4", "--out", out]) == 0
    row, = json.load(open(out))
    names = {"program", "bfloat16", *VARIANTS}
    assert set(row["logits_rms_share"]) == set(row["correct"]) == names
    assert row["rtol"] == TINY["reference_rtol"]
    assert row["correct"]["program"]
    assert row["correct"] == {k: v <= row["rtol"]
                              for k, v in row["rel_diff"].items()}
    share = row["logits_rms_share"]
    assert share["g_clamped"] == 0
    assert all(share["program"] < share[v] for v in VARIANTS
               if v != "g_clamped"), share
    # no limit on the logits in the tiny configuration: none applied
    assert "logits_correct" not in row
    # with one, between the program and the wrong models: the program
    # inside, each wrong model whose effect reaches the logits outside;
    # a limit the program misses is the tool's exit code
    path = os.path.join(root, "benchmarks", "configs", "tiny-solar.json")
    try:
        for limit, code in ((0.1, 0), (0.001, 1)):
            with open(path, "w") as f:
                json.dump(dict(TINY, reference_logits_rms=limit), f)
            assert tool.main(["--root", root, "--cell", "c_solar",
                              "--seeds", "2147484001", "--variants",
                              "beta_not_doubled", "no_gqa_gate",
                              "--logits", "4", "--out", out]) == code
            row, = json.load(open(out))
            assert row["logits_limit"] == limit
            assert row["logits_correct"] == {
                "program": code == 0, "bfloat16": code == 0,
                "beta_not_doubled": False, "no_gqa_gate": False}
    finally:
        with open(path, "w") as f:
            json.dump(TINY, f)


def test_the_cells_two_limits_lie_where_the_chip_read():
    """`reference_rtol` three times the largest first loss the chip
    read; `reference_logits_rms` between the program's logits and the
    bfloat16 reference's, with room on both sides (the readings:
    PERF.md section 6, PR 49, and `reference_rtol_why`)."""
    config = _config()
    assert 2.9 * 1.173e-4 <= config["reference_rtol"] <= 3.1 * 1.173e-4
    limit = config["reference_logits_rms"]
    assert 1.5 * 0.0369 <= limit <= 0.0908 / 1.5


def test_the_new_reader_returns_nothing_where_there_is_nothing_to_read():
    """On the parent's program (no trace of the kernels), in a cell
    whose builder gives no count (`ling3`: its trace DOES hold pt_kda_*
    calls), and where the count is there and the trace holds no such
    call: None, and nothing raised."""
    read = _load("layer_metrics", "kda_head_chunk_us").read
    assert read({"trace": None, "work": {"kernel_work": {}},
                 "clocks": {}}) is None
    kda = {"first": 0, "devices": [
        {"op_ns": {"mosaic:pt_kda_fwd": 5e6, "mosaic:pt_kda_bwd": 4e6},
         "steps": 2}]}
    assert read({"trace": kda, "work": {"kernel_work": {
        "kda": {"flops": 1.0, "bytes": 1.0}}}}) is None
    assert read({"trace": kda, "work": {"kernel_work": {
        "mla_flash": {"flops": 1.0, "bytes": 1.0}}}}) is None
    no_kda = {"first": 0, "devices": [
        {"op_ns": {"mosaic:pt_flash_fwd": 5e6}, "steps": 2}]}
    assert read({"trace": no_kda, "work": {"kernel_work": {
        "kda": {"flops": 1.0, "bytes": 1.0, "head_chunks": 10}}}}) is None


def test_the_new_reader_on_a_made_up_trace():
    """2 steps, 6 ms of pt_kda_fwd and 10 ms of pt_kda_bwd: 8 ms a step
    over 2,000 head-chunks is 4 us each; kda_ms and the scan's share of
    its roofline read the same calls."""
    trace = {"first": 0, "devices": [{"op_ns": {
        "mosaic:pt_kda_fwd": 6e6, "mosaic:pt_kda_bwd": 10e6,
        "mosaic:pt_flash_fwd": 7e6}, "steps": 2}]}
    m = {"trace": trace, "chips": 1, "flops": flops,
         "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
         "work": {"kernel_work": {"kda": {
             "flops": 1e8, "bytes": 2e8, "head_chunks": 2000}}}}
    assert _load("layer_metrics", "kda_head_chunk_us").read(m) \
        == pytest.approx(4.0)
    assert _load("layer_metrics", "kda_ms").read(m) == pytest.approx(8.0)
    # least time 2 ms by the bytes: a quarter
    assert _load("layer_metrics", "kda_roofline").read(m) \
        == pytest.approx(25.0)


# -- BENCHMARK.json's entries of PR 49 ----------------------------------------

def test_benchmark_entries():
    """By name, not by position, and `<=`: a later PR appends cells and
    metrics after these and may append this cell to further lists."""
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG,
                        traffic="train_s8k_b1_tp8ep40", chips=1)
    assert len(cell["why"]) <= 200
    # the headroom the review of PR 49 asked to be said here: the
    # step's bytes beside the chip's
    for word in ("8192", "TP-8 x EP-40", "4 of 48 layers",
                 "over a rank's", "15.33 of the chip's 16.91 GB"):
        assert word in cell["why"], word
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    job = json.load(open(os.path.join(BENCH, "traffic",
                                      "train_s8k_b1_tp8ep40.json")))
    assert (job["kind"], job["batch"], job["seq_len"],
            job["rate_metric"]) == ("train_steps", 1, 8192,
                                    "tokens_per_s")
    # 65,536 pairs a step: 204.8 rows a held expert under a uniform
    # router, a fifth of the deployment's 1,024
    config = _config()
    assert job["batch"] * job["seq_len"] * config["num_experts_per_tok"] \
        / config["n_routed_experts_published"] == 204.8
    reports = {e["name"] for e in spec["per_layer"]
               if CELL in e.get("workloads", ())}
    assert {
        "feed_wait_ms", "enqueue_ms", "step_p50_ms", "device_idle_pct",
        "mfu_pct", "step_hbm_gb", "run_prepare_ms", "run_fetch_ms",
        "feed_put_ms", "feed_put_in_run_ms", "build_s", "compile_s",
        "first_call_s", "matmul_ms", "other_fusion_ms", "copy_ms",
        "flash_fwd_ms", "flash_bwd_ms", "kda_ms", "kda_fwd_ms",
        "kda_bwd_ms", "kda_roofline", "conv1d_ms", "gqa_flash_roofline",
        "moe_gmm_ms", "moe_gmm_roofline", "moe_gmm_roofline_live",
        "moe_gmm_tile_us", "moe_live_tiles", "moe_live_tiles_window",
        "moe_combine_ms", "kda_head_chunk_us"} <= reports
    # no state-space scan, no latent attention, no gated convolution
    assert not {n for n in reports if n.startswith(("ssd_", "mla_",
                                                    "gated_conv"))}
    assert "flash_roofline" not in reports and "flash_ms" not in reports
    e = next(e for e in spec["per_layer"]
             if e["name"] == "kda_head_chunk_us")
    assert e == dict(e, layer="kernels", moves="tokens_per_s",
                     source="device_trace", unit="us", better="lower")
    # where its reader finds something to read: this cell, and not the
    # cell whose builder gives no count
    assert {CELL} <= set(e["workloads"])
    assert "ling3_flash_train_s4k" not in e["workloads"]
    for e in spec["per_layer"]:
        if CELL in e.get("workloads", ()):
            assert e["moves"] in ("tokens_per_s", "setup_s")
            assert callable(_load("layer_metrics",
                                  e["name"].split(".")[0]).read)
    assert CELL in next(e for e in spec["end_to_end"]
                        if e["name"] == "tokens_per_s")["workloads"]
    # every share of a roofline or of a peak that moves tokens_per_s and
    # that this cell's kernels feed is reported here
    assert {"mfu_pct", "kda_roofline", "gqa_flash_roofline",
            "moe_gmm_roofline", "moe_gmm_roofline_live"} <= reports
