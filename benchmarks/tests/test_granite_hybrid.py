"""The `granite-4.0-h-micro` configuration's benchmark files on the CPU,
in a file of their own (a `model_config` PR adds files and entries and
edits none): builders/granite_hybrid_flops.py against the hand-worked
numbers of ISSUE 38, the configuration against the catalog row's
published numbers, reference/granite_hybrid.py against the program at
tiny size (and against the repository's copy), the cell kind end to end
through the harness, and BENCHMARK.json's entries of PR 38, looked up
BY NAME and held with `<=`: a later PR appends after them.

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

CELL = "granite4_h_micro_train_b1"
# the catalog row `granite-4.0-h-micro` (architectures.jsonl beside the
# model-configs guide): its `source_url` and the numbers of its
# `config`, copied here so that the test reads nothing outside the
# checkout
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/"
          "main/config.json")
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}
REDUCED = ["num_hidden_layers", "vocab_size"]

TINY = {
    "builder": "granite_hybrid", "reference": "granite_hybrid",
    "param_prefix": "granite", "hidden_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 4, "mamba_d_head": 64, "mamba_d_state": 32,
    "mamba_d_conv": 4, "mamba_chunk_size": 16, "mamba_conv_bias": True,
    "mamba_n_groups": 1, "mamba_proj_bias": False,
    "attention_bias": False, "shared_intermediate_size": 256,
    "num_hidden_layers": 3,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "vocab_size": 128, "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
    "logits_scaling": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "initializer_range": 0.02,
    "amp": True, "learning_rate": 1e-3, "recompute": True,
    # off the chip the kernel entries resolve to their XLA forms: the
    # chunked scan in jax.numpy and plain attention on K and V
    # repeated; a recompute segment differentiates its replay of them
    # and calls no grad op
    "kernel_impls": {"flash_attention": "xla", "ssd_scan": "xla",
                     "flash_attention_kv_heads": "repeated"},
    "reference_rtol": 2e-2,
}


def _load(kind, name):
    return harness._load_file(os.path.join(BENCH, kind, name + ".py"))


def _config():
    return json.load(open(os.path.join(BENCH, "configs",
                                       "granite-4.0-h-micro.json")))


# -- builders/granite_hybrid_flops.py ----------------------------------------

def test_parameters_at_the_cells_sizes():
    w, config = _load("builders", "granite_hybrid_flops"), _config()
    mamba = w.layer_params(config, "mamba")
    # in_proj 2048 x (4096 + 4352 + 64) and out_proj 4096 x 2048
    assert mamba["mixer"] == 2048 * 8512 + 4096 * 2048 == 25_821_184
    assert mamba["ffn"] == 3 * 2048 * 8192 == 50_331_648
    # conv 4352 x 4 + 4352, A_log / D / dt_bias, gated norm, two norms
    assert mamba["other"] == 4352 * 5 + 3 * 64 + 4096 + 2 * 2048
    assert sum(mamba.values()) == 76_182_976          # 76.18 M
    attention = w.layer_params(config, "attention")
    # q and o 2048 x 2048, k and v 2048 x 512
    assert attention["mixer"] == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert sum(attention.values()) == 60_821_504      # 60.82 M
    assert w.layer_kinds(config) == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    n = w.n_params(config)
    assert n == 9 * 76_182_976 + 60_821_504 + 12544 * 2048 + 2048 \
        == 772_160_448                                # 772.2 M
    # float32 master, gradient and two Adam moments
    assert 16 * n / 1e9 == pytest.approx(12.35, abs=0.005)
    # whole, the tied matrix does not fit
    whole = n + (100352 - 12544) * 2048
    assert 16 * whole / 1e9 == pytest.approx(15.2, abs=0.05)


def test_flops_per_token_at_the_cells_sizes():
    w, config = _load("builders", "granite_hybrid_flops"), _config()
    fwd = w.forward_flops_per_token(config, 8192)
    in_mflop = {k: round(v / 1e6) for k, v in fwd.items()}
    assert in_mflop == {"ffn": 1007, "mamba_proj": 465,
                        "attention_proj": 21, "head": 51, "scan": 38,
                        "flash": 34}
    # the scan, a token and layer: C B^T once (2 x 256 x 128), and per
    # head the masked product (2 x 256 x 64), the chunk's state and the
    # state's output (2 x 64 x 128 each)
    assert w.scan_flops_per_token(config) == 65_536 + 64 * (32_768 + 32_768)
    assert fwd["scan"] == 9 * 4_259_840
    # causal flash: half of 2 x 2 T d a head, 32 heads, one layer
    assert fwd["flash"] == 2 * 8192 * 2048
    total = sum(fwd.values())
    assert total == pytest.approx(1616e6, rel=1e-3)
    assert w.train_flops_per_token(config, 8192) == 3 * total \
        == pytest.approx(4.85e9, rel=1e-3)
    assert 3 * total * 8192 == pytest.approx(39.7e12, rel=1e-3)
    share = {k: v / total for k, v in fwd.items()}
    assert share["ffn"] == pytest.approx(0.62, abs=0.005)
    assert share["mamba_proj"] + share["attention_proj"] \
        == pytest.approx(0.30, abs=0.005)
    assert share["scan"] == pytest.approx(0.024, abs=0.0005)
    assert share["flash"] == pytest.approx(0.02, abs=0.005)
    assert share["head"] == pytest.approx(0.03, abs=0.005)


def test_kernel_work_at_the_cells_sizes():
    w, config = _load("builders", "granite_hybrid_flops"), _config()
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops, nbytes = w.ssd_step(config, 1, 8192)
    # forward 4.26 MFLOP a token and layer; backward C B^T again and
    # the two products of its gradient (6 x 256 x 128), per head dy x^T
    # and the masked product's transpose (4 x 256 x 64) and two
    # products each for the state and its output (8 x 64 x 128)
    bwd = 6 * 256 * 128 + 64 * (4 * 256 * 64 + 8 * 64 * 128)
    assert ops == 9 * 8192 * (4_259_840 + bwd)
    # the chunk-start states: float32 [32, 4096, 128] a layer, written
    # once forward and read once backward
    states = 4 * 32 * 4096 * 128
    assert states == 67_108_864
    x, bc, dt = 8192 * 4096 * 2, 8192 * 128 * 2, 8192 * 64 * 4
    forward = 2 * x + 2 * bc + dt + states
    backward = 2 * (x + 2 * bc) + 2 * x + 2 * dt + states
    assert nbytes == 9 * (forward + backward)
    least, bound = flops.roofline_seconds(ops, nbytes, peak)
    # memory-bound by the float32 states and the 4096-wide X, Y, dY, dX
    assert bound == "memory" and least == pytest.approx(6.1e-3, rel=2e-2)
    fops, fbytes = w.gqa_flash_step(config, 1, 8192, flops)
    # causal, forward + backward = 3 x (2 x 2 x 32 x 8192^2 x 64 / 2)
    assert fops == 3 * 2 * 2 * 32 * 8192 * 8192 * 64 / 2 \
        == 3 * 8192 * w.forward_flops_per_token(config, 8192)["flash"]
    # K and V read once a KV head: 8, not 32
    row = 8192 * 64 * 2
    assert fbytes == (2 * 32 + 2 * 8) * row + (4 * 32 + 4 * 8) * row
    repeated = (4 * 32) * row + (8 * 32) * row
    assert fbytes < repeated
    least, bound = flops.roofline_seconds(fops, fbytes, peak)
    assert bound == "compute" and least == pytest.approx(4.19e-3, rel=1e-2)


# -- the configuration against the catalog row -------------------------------

def test_config_against_the_published():
    """Every number of the catalog row's `config` as published, but the
    two cuts; no width among them; `layer_types` kept whole."""
    config = _config()
    assert config["source"] == SOURCE
    differs = [k for k, v in PUBLISHED.items()
               if k not in config or config[k] != v]
    assert sorted(differs) == sorted(REDUCED)
    assert (config["num_hidden_layers"], config["vocab_size"]) \
        == (10, 12544)
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert sorted(config["reduced_why"]) == sorted(REDUCED)
    kinds = config["layer_types"]
    assert len(kinds) == 40
    assert [i for i, k in enumerate(kinds) if k == "attention"] \
        == [5, 15, 25, 35]
    assert set(kinds) == {"mamba", "attention"}
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in spec["configs"]
                 if c["name"] == "granite-4.0-h-micro")
    assert entry["reduced"] == REDUCED
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmarks/configs/granite-4.0-h-micro.json"
    assert config["kernel_impls"] == {
        "flash_attention": "pallas", "ssd_scan": "pallas",
        "ssd_scan_grad": "saved", "flash_attention_kv_heads": "grouped"}
    for key in ("deployment", "assumed", "reference_rtol_why"):
        assert config[key]
    for key in ("time_step_limit", "initialization", "optimizer"):
        assert key in config["assumed"]
    assert "[1, 16]" in config["assumed"]["initialization"]
    assert "772.2 M" in config["deployment"]
    assert "12.35 GB" in config["deployment"]
    assert 0 < config["reference_rtol"] < 1e-3


# -- reference/granite_hybrid.py against the program --------------------------

@pytest.mark.parametrize("amp,recompute,rtol", [
    (False, False, 1e-4), (False, True, 1e-4), (True, True, 2e-2)])
def test_granite_hybrid_reference(amp, recompute, rtol):
    import jax

    import paddle_tpu as fluid

    config = dict(TINY, amp=amp, recompute=recompute)
    kind = _load("kinds", "train_steps")
    kind._fresh_programs()
    np.random.seed(0)
    built = _load("builders", "granite_hybrid").build(
        config, {"batch": 2, "seq_len": 64}, flops)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = built["make_batch"](np.random.default_rng(0))
    assert batch[0].shape == (2, 64, 1) and batch[0].max() < 128
    assert (batch[1][:, :-1] == batch[0][:, 1:]).all()
    ref = _load("reference", "granite_hybrid")
    want = ref.loss(ref.read_params(config, kind._scope_get), batch, config)
    got, = exe.run(built["compiled"],
                   feed=dict(zip(["src_ids", "tgt_label"], batch)),
                   fetch_list=[built["loss"]])
    assert float(np.asarray(got).reshape(-1)[0]) == \
        pytest.approx(want, rel=rtol)
    assert 0.9 * np.log(128) < want < 1.1 * np.log(128)
    assert built["items_per_step"] == 128 and built["flops_per_item"] > 0
    assert set(built["kernel_work"]) == {"ssd", "gqa_flash"}
    for work in built["kernel_work"].values():
        assert work["flops"] > 0 and work["bytes"] > 0
    # the reference reads every parameter the program has
    names = {p.name for p in fluid.default_main_program().all_parameters()}
    assert names == set(jax.tree_util.tree_leaves(ref.param_names(config)))


def test_benchmark_reference_is_the_repositorys():
    with open(os.path.join(BENCH, "reference", "granite_hybrid.py")) as f, \
            open(os.path.join(CHECKOUT, "paddle_tpu", "models",
                              "granite_hybrid_reference.py")) as g:
        assert f.read() == g.read()


# -- the cell kind end to end on the CPU --------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A temporary benchmark root with the benchmark's code, a tiny
    granite_hybrid configuration and one cell (test_rehearsal.py's
    way)."""
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
    (bench / "configs" / "tiny-granite.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_seq.json").write_text(json.dumps(
        {"kind": "train_steps", "batch": 2, "seq_len": 64,
         "rate_metric": "tokens_per_s"}))
    cells = ["c_granite"]
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny-granite", "source": "test",
                     "reduced": [],
                     "file": "benchmarks/configs/tiny-granite.json",
                     "why": "test"}],
        "workloads": [{"name": "c_granite", "config": "tiny-granite",
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
    path = os.path.join(root, "benchmarks", "configs", "tiny-granite.json")
    if over:
        with open(path, "w") as f:
            json.dump(dict(TINY, **over), f)
    out = io.StringIO()
    try:
        result = harness.run_cell(root, "c_granite", seed=2147483999,
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
        "flash_attention", "ssd_scan", "flash_attention_kv_heads"}
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    fixed = checks["fixed_batch_losses"]
    assert fixed[0] > fixed[1] > fixed[2]


def test_the_named_impls_decide_correct(root):
    """The configuration of the real cell names the Pallas scan, its
    grad on the saved states and K and V read in place: a run of the
    XLA scan, of a backward that did not run on the saved states (here
    the segment differentiates its replay and the grad op never runs),
    or of plain attention on repeated K and V (all of which happen
    here, off the chip) is not correct, whatever its loss."""
    result, earlier = _run(root, trace=0,
                           kernel_impls=_config()["kernel_impls"])
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert not result["correct"]
    assert checks["checks"]["kernel_impls"] is False
    assert checks["wrong_impls"] == {
        "flash_attention": ["xla"], "ssd_scan": ["xla"],
        "ssd_scan_grad": [],
        "flash_attention_kv_heads": ["repeated"]}
    assert checks["checks"]["reference"] is True


def test_cell_per_layer_line(root):
    result, _ = _run(root, trace=1)
    assert result["correct"]
    # no device plane in a CPU trace: the readers of named kernels
    # (ssd_ms, ssd_roofline, gqa_flash_roofline, flash_*) and of trace
    # categories return nothing and the line leaves them out
    assert set(result["metrics"]) == {
        "feed_wait_ms", "feed_put_ms", "feed_put_in_run_ms", "enqueue_ms",
        "run_prepare_ms", "run_fetch_ms", "step_p50_ms", "mfu_pct",
        "step_hbm_gb", "build_s", "compile_s", "first_call_s"}


def test_new_readers_return_nothing_where_the_trace_has_no_such_kernel():
    """On the parent's program, and on every cell without a scan, the
    trace holds no pt_ssd_* call and the work has no `ssd` entry: the
    readers return None and raise nothing."""
    for name in ("ssd_ms", "ssd_roofline", "gqa_flash_roofline"):
        read = _load("layer_metrics", name).read
        assert read({"trace": None, "work": {"kernel_work": {}},
                     "clocks": {}}) is None


# -- BENCHMARK.json's entries of PR 38 ----------------------------------------

def test_benchmark_entries():
    """By name, not by position, and `<=`: a later PR appends cells and
    metrics after these and may append this cell to further lists."""
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="granite-4.0-h-micro",
                        traffic="train_s8k_b1", chips=1)
    assert len(cell["why"]) <= 200
    for word in ("8192", "10 of 40", "1/8", "scan 2.4%"):
        assert word in cell["why"], word
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    job = json.load(open(os.path.join(BENCH, "traffic",
                                      "train_s8k_b1.json")))
    assert (job["kind"], job["batch"], job["rate_metric"]) \
        == ("train_steps", 1, "tokens_per_s")
    # 8192 unless the chip said it does not fit: then 4096, and why
    assert job["seq_len"] == 8192 or (
        job["seq_len"] == 4096 and "does not fit" in job["why"])
    assert job["seq_len"] % _config()["mamba_chunk_size"] == 0
    reports = {e["name"] for e in spec["per_layer"]
               if CELL in e.get("workloads", ())}
    assert {
        "feed_wait_ms", "feed_put_ms", "feed_put_in_run_ms", "enqueue_ms",
        "run_prepare_ms", "run_fetch_ms", "step_p50_ms",
        "device_idle_pct", "mfu_pct", "step_hbm_gb", "build_s",
        "compile_s", "first_call_s", "matmul_ms", "flash_fwd_ms",
        "flash_bwd_ms", "other_fusion_ms", "copy_ms", "ssd_ms",
        "ssd_roofline", "gqa_flash_roofline"} <= reports
    # no expert layer and no latent attention here
    assert not {n for n in reports if n.startswith(("moe_", "mla_"))}
    for name in ("ssd_ms", "ssd_roofline", "gqa_flash_roofline"):
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
