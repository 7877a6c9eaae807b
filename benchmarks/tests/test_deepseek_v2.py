"""The `deepseek-v2-lite` configuration's benchmark files on the CPU, in
a file of their own (a `model_config` PR adds files and entries and
edits none, so the cases ISSUE 34 asked for in test_reference.py and
test_flops.py are here): builders/deepseek_v2_flops.py against
hand-worked values, the configuration against the catalog row's
published numbers, reference/deepseek_v2.py against the program at
tiny size, the cell kind end to end through the harness, and
BENCHMARK.json's entries of PR 34.

Tolerances as in test_reference.py: float32 1e-4 (the same mathematics
in another order), AMP 2e-2 at these sizes (a loss over 64 tokens).
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

CELL = "dsv2_lite_train_s4k"
# the catalog row `DeepSeek-V2-Lite` (architectures.jsonl beside the
# model-configs guide): its `source_url` and its `config`, copied here
# so that the test reads nothing outside the checkout
SOURCE = ("https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
          "config.json")
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "greedy", "v_head_dim": 128, "vocab_size": 102400,
}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]

TINY = {
    "builder": "deepseek_v2", "reference": "deepseek_v2",
    "param_prefix": "dsv2", "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "n_routed_experts_published": 8, "held_experts": [0, 1, 2, 3],
    "n_shared_experts": 2, "num_experts_per_tok": 3,
    "norm_topk_prob": False, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "aux_loss_alpha": 0.001,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "vocab_size": 128, "initializer_range": 0.02, "amp": True,
    "learning_rate": 1e-3, "recompute": True,
    # off the chip the kernel entries resolve to their XLA forms
    "kernel_impls": {"flash_attention": "xla", "moe_gmm": "xla",
                     "moe_route_scoring": "softmax"},
    "reference_rtol": 2e-2,
}


def _load(kind, name):
    return harness._load_file(os.path.join(BENCH, kind, name + ".py"))


def _config():
    return json.load(open(os.path.join(BENCH, "configs",
                                       "deepseek-v2-lite.json")))


# -- builders/deepseek_v2_flops.py --------------------------------------------

def test_flops_per_token_at_the_cells_sizes():
    w, config = _load("builders", "deepseek_v2_flops"), _config()
    params = w.matmul_params(config)
    # a layer's attention: q 2048 x 16 x 192, kv_a 2048 x (512 + 64),
    # kv_b 512 x 16 x 256, o 2048 x 2048
    attention = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert attention == 13_762_560
    assert params["attention"] == 5 * attention
    assert params["dense_ffn"] == 3 * 2048 * 10944 == 67_239_936
    expert = 3 * 2048 * 1408
    assert expert == 8_650_752
    # two shared experts a layer, four expert layers
    assert params["shared_experts"] == 4 * 2 * expert
    # a token meets 6 x 8 / 64 = 0.75 of a held expert in expectation
    assert params["routed_experts"] == 4 * 0.75 * expert
    assert params["router"] == 4 * 2048 * 64
    assert params["head"] == 2048 * 12800
    fwd = w.forward_flops_per_token(config, 4096)
    # causal flash: half of 2 T (192 + 128) a head, 16 heads, 5 layers
    assert fwd["flash"] == 4096 * 16 * 320 * 5 == 104_857_600
    in_mflop = {k: round(v / 1e6, 1) for k, v in fwd.items()}
    assert in_mflop == {"attention": 137.6, "flash": 104.9,
                        "dense_ffn": 134.5, "shared_experts": 138.4,
                        "routed_experts": 51.9, "router": 1.0,
                        "head": 52.4}
    total = sum(fwd.values())
    assert total == pytest.approx(620.8e6, rel=1e-3)
    assert w.train_flops_per_token(config, 4096) == 3 * total \
        == pytest.approx(1.862e9, rel=1e-3)
    # latent attention 39% (flash 17%), the expert layers 31%, the
    # dense layer 22%, the head 8%
    share = {k: v / total for k, v in fwd.items()}
    assert share["attention"] + share["flash"] == pytest.approx(0.39,
                                                                abs=0.005)
    assert share["flash"] == pytest.approx(0.17, abs=0.005)
    assert share["shared_experts"] + share["routed_experts"] \
        + share["router"] == pytest.approx(0.31, abs=0.005)
    assert share["dense_ffn"] == pytest.approx(0.22, abs=0.005)
    assert share["head"] == pytest.approx(0.08, abs=0.005)
    # a rank for the query: two projections in the place of one
    ranked = w.matmul_params(dict(config, q_lora_rank=768))
    assert ranked["attention"] - params["attention"] == \
        5 * (2048 * 768 + 768 * 3072 - 2048 * 3072)


def test_kernel_work_at_the_cells_sizes():
    k = _load("builders", "xing4_flops")
    # 5 causal calls of 2 x 16 heads x 4096^2 at 192 / 128, fwd + bwd
    step, nbytes = k.flash_step(2, 16, 4096, 192, 128, 5)
    fwd = 2 * 2 * 16 * 4096 * 4096 * 320 / 2
    assert step == 5 * 3 * fwd == pytest.approx(2.577e12, rel=1e-3)
    assert step / 8192 == 3 * _load(
        "builders", "deepseek_v2_flops").forward_flops_per_token(
            _config(), 4096)["flash"]
    # each held expert sees 8192 x 6 / 64 = 768 rows in expectation
    assert k.routed_rows(8192, 6, 8, 64) == 8 * 768
    gflops, gbytes = k.gmm_step(8192, 6, 8, 64, 2048, 1408, 4)
    assert gflops == 4 * 3 * 6 * 6144 * 2048 * 1408 \
        == pytest.approx(1.276e12, rel=1e-3)
    # forward: rows in and out and the three stacks once; backward: the
    # rows, their gradients in and out, the stacks and their gradients
    weights, rows = 3 * 8 * 2048 * 1408, 6144 * 2048
    assert gbytes == 4 * 2 * ((2 * rows + weights)
                              + (3 * rows + 2 * weights))
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.roofline_seconds(gflops, gbytes, peak)
    # 768 rows an expert: over the ridge, where xing4's 256 sit on it
    assert bound == "compute" and least == pytest.approx(6.5e-3, rel=2e-2)


# -- the configuration against the catalog row --------------------------------

def test_config_against_the_published():
    """Every key of the catalog row's `config` as published, but the
    three cuts; no width among them."""
    config = _config()
    assert config["source"] == SOURCE
    differs = [k for k, v in PUBLISHED.items()
               if k not in config or config[k] != v]
    assert sorted(differs) == sorted(REDUCED)
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 12800)
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert sorted(config["reduced_why"]) == sorted(REDUCED)
    assert config["held_experts"] == list(range(8))
    assert config["n_routed_experts"] == len(config["held_experts"])
    assert config["n_routed_experts_published"] == 64
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["q_lora_rank"] is None
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in spec["configs"]
                 if c["name"] == "deepseek-v2-lite")
    assert entry["reduced"] == REDUCED
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmarks/configs/deepseek-v2-lite.json"
    assert config["kernel_impls"] == {
        "flash_attention": "pallas", "moe_gmm": "pallas",
        "moe_route_scoring": "softmax"}
    for key in ("deployment", "assumed", "reference_rtol_why"):
        assert config[key]
    assert "768" in config["deployment"] and "3072" in config["deployment"]
    assert config["aux_loss_alpha"] == 0.001
    assert "aux_loss_alpha" in config["assumed"]
    # 535.0 M parameters: every matrix, the embedding, 2 norms a layer,
    # the kv latent's norm and the final norm
    w = _load("builders", "deepseek_v2_flops")
    attention = w.matmul_params(config)["attention"] // 5
    expert = 3 * 2048 * 1408
    dense_layer = attention + 3 * 2048 * 10944
    expert_layer = attention + 2048 * 64 + 2 * expert + 8 * expert
    assert dense_layer == pytest.approx(81.0e6, rel=1e-3)
    assert expert_layer == pytest.approx(100.4e6, rel=1e-3)
    n = dense_layer + 4 * expert_layer + 2 * 12800 * 2048 \
        + 5 * (2 * 2048 + 512) + 2048
    assert n == pytest.approx(535.0e6, rel=1e-3)


# -- reference/deepseek_v2.py against the program -----------------------------

@pytest.mark.parametrize("amp,recompute,held,rtol", [
    (False, False, [0, 1, 2, 3], 1e-4), (False, True, [5, 2], 1e-4),
    (True, True, [0, 1, 2, 3], 2e-2)])
def test_deepseek_v2_reference(amp, recompute, held, rtol):
    import paddle_tpu as fluid

    config = dict(TINY, amp=amp, recompute=recompute, held_experts=held,
                  n_routed_experts=len(held))
    kind = _load("kinds", "train_steps")
    kind._fresh_programs()
    np.random.seed(0)
    built = _load("builders", "deepseek_v2").build(
        config, {"batch": 2, "seq_len": 32}, flops)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = built["make_batch"](np.random.default_rng(0))
    ref = _load("reference", "deepseek_v2")
    want = ref.loss(ref.read_params(config, kind._scope_get), batch, config)
    got, = exe.run(built["compiled"],
                   feed=dict(zip(["src_ids", "tgt_label"], batch)),
                   fetch_list=[built["loss"]])
    assert float(np.asarray(got).reshape(-1)[0]) == \
        pytest.approx(want, rel=rtol)
    # a loss over 128 classes at random weights, plus two expert
    # layers' balance losses of about alpha each
    assert 0.9 * np.log(128) < want < 1.1 * np.log(128)
    assert built["items_per_step"] == 64 and built["flops_per_item"] > 0
    assert set(built["kernel_work"]) == {"mla_flash", "moe_gmm"}
    # the reference reads every parameter the program has
    names = {p.name for p in fluid.default_main_program().all_parameters()}
    import jax

    assert names == set(jax.tree_util.tree_leaves(ref.param_names(config)))


def test_reference_without_the_balance_loss_is_the_cross_entropy():
    import jax.numpy as jnp

    import paddle_tpu as fluid

    kind = _load("kinds", "train_steps")
    kind._fresh_programs()
    np.random.seed(0)
    _load("builders", "deepseek_v2").build(
        dict(TINY), {"batch": 2, "seq_len": 32}, flops)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    ref = _load("reference", "deepseek_v2")
    params = ref.read_params(TINY, kind._scope_get)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 128, (2, 32)),
                      jnp.int32)
    labels = jnp.roll(ids, -1, 1)
    ce, aux = ref.loss_terms(params, ids, labels, TINY)
    assert float(aux) == pytest.approx(2 * 0.001, rel=0.05)
    ce0, aux0 = ref.loss_terms(params, ids, labels,
                               dict(TINY, seq_aux=False))
    assert float(aux0) == 0.0 and float(ce0) == float(ce)
    assert float(ref.batch_loss(params, ids, labels, TINY)) \
        == pytest.approx(float(ce) + float(aux), rel=1e-6)


def test_benchmark_reference_is_the_repositorys():
    with open(os.path.join(BENCH, "reference", "deepseek_v2.py")) as f, \
            open(os.path.join(CHECKOUT, "paddle_tpu", "models",
                              "deepseek_v2_reference.py")) as g:
        assert f.read() == g.read()


# -- the cell kind end to end on the CPU --------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A temporary benchmark root with the benchmark's code, a tiny
    deepseek_v2 configuration and one cell (test_rehearsal.py's way)."""
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
    (bench / "configs" / "tiny-dsv2.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_seq.json").write_text(json.dumps(
        {"kind": "train_steps", "batch": 4, "seq_len": 16,
         "rate_metric": "tokens_per_s"}))
    cells = ["c_dsv2"]
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny-dsv2", "source": "test", "reduced": [],
                     "file": "benchmarks/configs/tiny-dsv2.json",
                     "why": "test"}],
        "workloads": [{"name": "c_dsv2", "config": "tiny-dsv2",
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
    if over:
        path = os.path.join(root, "benchmarks", "configs", "tiny-dsv2.json")
        with open(path, "w") as f:
            json.dump(dict(TINY, **over), f)
    out = io.StringIO()
    try:
        result = harness.run_cell(root, "c_dsv2", seed=2147483999,
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
    assert result["correct"], checks
    # what the configuration names is what ran, and nothing else of
    # those kernels: two expert layers' routers, all softmax
    used = checks["kernel_impls"]
    assert {k.split(":")[0] for k in used} >= {
        "flash_attention", "moe_gmm", "moe_route_scoring"}
    assert used["moe_route_scoring:softmax"] == used["moe_gmm:xla"] > 0
    assert "moe_route_scoring:sigmoid" not in used
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    record = json.load(open(os.path.join(
        root, "benchmarks", "out", "c_dsv2.seed2147483999.trace0.json")))
    # the step's bytes and text came from the executable that ran
    assert record["memory"]["recompiled"] == 0
    fixed = checks["fixed_batch_losses"]
    assert fixed[0] > fixed[1] > fixed[2]


def test_a_sigmoid_router_fails_the_cell(root):
    """`correct` names the scoring: the same program routed by sigmoid
    runs, and the kernel_impls check refuses it."""
    result, earlier = _run(
        root, trace=0,
        kernel_impls=dict(TINY["kernel_impls"],
                          moe_route_scoring="sigmoid"))
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert not result["correct"]
    assert checks["checks"]["kernel_impls"] is False
    assert checks["wrong_impls"] == {"moe_route_scoring": ["softmax"]}
    assert checks["checks"]["reference"] is True


def test_cell_per_layer_line(root):
    result, _ = _run(root, trace=1)
    assert result["correct"]
    # no device plane in a CPU trace: the readers of named kernels and
    # of trace categories return nothing and the line leaves them out;
    # the rest read the host clock, the program's record and counters
    assert set(result["metrics"]) == {
        "feed_wait_ms", "feed_put_ms", "feed_put_in_run_ms", "enqueue_ms",
        "run_prepare_ms", "run_fetch_ms", "step_p50_ms", "mfu_pct",
        "step_hbm_gb", "build_s", "compile_s", "first_call_s"}


# -- BENCHMARK.json's entries of PR 34 ----------------------------------------

def test_benchmark_entries():
    """By name, not by position: a later PR appends after these."""
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="deepseek-v2-lite",
                        traffic="train_s4k_b2", chips=1)
    assert len(cell["why"]) <= 200
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    job = json.load(open(os.path.join(BENCH, "traffic",
                                      "train_s4k_b2.json")))
    assert (job["kind"], job["batch"], job["seq_len"],
            job["rate_metric"]) == ("train_steps", 2, 4096, "tokens_per_s")
    reports = {e["name"] for e in spec["per_layer"]
               if CELL in e.get("workloads", ())}
    # the step holds flash AND grouped-matmul Mosaic calls: the readers
    # by kernel name apply, the readers of EVERY Mosaic call do not
    assert reports == {
        "feed_wait_ms", "feed_put_ms", "feed_put_in_run_ms", "enqueue_ms",
        "run_prepare_ms", "run_fetch_ms", "step_p50_ms",
        "device_idle_pct", "mfu_pct", "step_hbm_gb", "build_s",
        "compile_s", "first_call_s", "matmul_ms", "flash_fwd_ms",
        "flash_bwd_ms", "mla_flash_roofline", "moe_gmm_ms",
        "moe_gmm_roofline", "other_fusion_ms", "copy_ms"}
    cells = [w["name"] for w in spec["workloads"]]
    for e in spec["per_layer"]:
        if CELL in e.get("workloads", ()):
            assert e["moves"] in ("tokens_per_s", "setup_s")
            assert callable(_load("layer_metrics",
                                  e["name"].split(".")[0]).read)
            # appended after the cells the benchmark had
            assert e["workloads"].index(CELL) == len(
                [c for c in e["workloads"]
                 if cells.index(c) < cells.index(CELL)])
        # every cell reports setup_s: its metrics name every cell
        if e["moves"] == "setup_s":
            assert e["workloads"] == cells
    assert CELL in next(e for e in spec["end_to_end"]
                        if e["name"] == "tokens_per_s")["workloads"]
