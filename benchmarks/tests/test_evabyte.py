"""The `evabyte-6.5b` configuration's benchmark files on the CPU, in a
file of their own (a `model_config` PR adds files and entries and edits
none): builders/evabyte_flops.py against the hand-worked numbers of
ISSUE 55 and a brute-force count of allowed pairs, the configuration
against the catalog row's published numbers, reference/evabyte.py
against the program at tiny size, the cell kind end to end through the
harness, the four new readers eva_ms, eva_roofline, eva_pool_ms and
eva_pool_roofline, and BENCHMARK.json's entries of PR 55, looked up BY
NAME and held with `<=`: a later PR appends after them.

Tolerances as in test_reference.py: float32 1e-4 (the same mathematics
in another order), AMP 2e-2 at these sizes (a loss over 64 positions).
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

CELL = "evabyte_6_5b_train_s8k"
CONFIG = "evabyte-6.5b"
# the catalog row `EvaByte` (architectures.jsonl beside the
# model-configs guide): its `source_url` and its `config`, copied here
# so that the test reads nothing outside the checkout
SOURCE = "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096,
    "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
    "intermediate_size": 11008, "lazy_init": True,
    "max_position_embeddings": 32768, "max_seq_length": 32768,
    "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32,
    "num_key_value_heads": 32, "num_pred_heads": 8,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
    "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048,
}
REDUCED = ["num_hidden_layers"]

TINY = {
    "builder": "evabyte", "reference": "evabyte",
    "param_prefix": "evabyte", "attention_class": "eva",
    "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 192,
    "num_hidden_layers": 2, "window_size": 16, "chunk_size": 4,
    "num_pred_heads": 3, "vocab_size": 40, "rope_theta": 100000,
    "rms_norm_eps": 1e-5, "norm_add_unit_offset": True,
    "attention_bias": False, "hidden_act": "silu",
    "tie_word_embeddings": False, "initializer_range": 0.02, "amp": True,
    "learning_rate": 1e-3, "recompute": True,
    # off the chip the EVA entries resolve to their XLA forms
    "kernel_impls": {"eva_attention": "xla", "eva_pool": "xla"},
    "reference_rtol": 2e-2,
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _load(kind, name):
    return harness._load_file(os.path.join(BENCH, kind, name + ".py"))


def _config():
    return json.load(open(os.path.join(BENCH, "configs",
                                       CONFIG + ".json")))


# -- builders/evabyte_flops.py -------------------------------------------------

def test_parameters_at_the_cells_sizes_and_the_published_total():
    """ISSUE 55's arithmetic of the cut, from the functions; and the
    uncut model's, which reproduces the name's 6.5 B."""
    w, config = _load("builders", "evabyte_flops"), _config()
    layer = w.layer_params(config)
    assert layer == {"mixer": 4 * 4096 ** 2, "ffn": 3 * 4096 * 11008,
                     "norms": 8192, "eva": 2 * 32 * 128}
    assert layer["mixer"] == 67_108_864 and layer["ffn"] == 135_266_304
    assert sum(layer.values()) == 202_391_552
    assert w.n_params(config) == 4 * 202_391_552 + 320 * 4096 \
        + 4096 * 8 * 320 + 4096 == 821_366_784
    assert w.n_params(config) * 16 / 1e9 == pytest.approx(13.14, abs=0.01)
    # the driver's five layers do not fit the chip's 16.9 GB less the
    # activations; four do
    assert (5 * 202_391_552 + 11_800_576) * 16 / 1e9 \
        == pytest.approx(16.4, abs=0.05)
    whole = dict(config, num_hidden_layers=32)
    assert w.n_params(whole) / 1e9 == pytest.approx(6.488, abs=0.001)


@pytest.mark.parametrize("t,w,c", [(16, 16, 4), (64, 16, 4), (40, 16, 8),
                                   (12, 16, 2), (96, 32, 1), (8, 8, 8),
                                   (8192, 2048, 16)])
def test_allowed_pairs_against_a_brute_force_count(t, w, c):
    work = _load("builders", "evabyte_flops")
    config = {"window_size": w, "chunk_size": c}
    i = np.arange(t)[:, None]
    tok = np.arange(t)[None, :]
    tokens = int(((tok // w == i // w) & (tok <= i)).sum())
    chunks = int(((np.arange(-(-t // c))[None, :] * c) // w
                  < i // w).sum())
    assert work.eva_pairs(config, t) == (tokens, chunks)
    # the issue's sums: min(i mod W + 1, W) and 128 floor(i / W) a query
    assert tokens == sum(min(r % w + 1, w) for r in range(t))
    assert chunks == sum((w // c) * (r // w) for r in range(t))


def test_flops_per_token_at_the_cells_sizes():
    w, config = _load("builders", "evabyte_flops"), _config()
    fwd = w.forward_flops_per_token(config, 8192)
    in_mflop = {k: round(v / 1e6, 1) for k, v in fwd.items()}
    assert in_mflop == {"attention_proj": 536.9, "ffn": 1082.1,
                        "head": 21.0, "eva_window": 67.1,
                        "eva_chunks": 12.6, "eva_pool": 0.1}
    # a query meets 1,024.5 window keys and 192 chunk keys on average,
    # 16,384 FLOP a pair: 19.9 MFLOP a layer against 404.8 of matrices
    tokens, chunks = w.eva_pairs(config, 8192)
    assert tokens / 8192 == 1024.5 and chunks / 8192 == 192
    assert (fwd["eva_window"] + fwd["eva_chunks"]) / 4 / 1e6 \
        == pytest.approx(19.93, abs=0.01)
    assert (fwd["attention_proj"] + fwd["ffn"]) / 4 / 1e6 \
        == pytest.approx(404.8, abs=0.05)
    total = w.train_flops_per_token(config, 8192)
    assert total == 3 * sum(fwd.values())
    assert total / 1e9 == pytest.approx(5.16, abs=0.005)
    assert 3 * (fwd["eva_window"] + fwd["eva_chunks"]) / total \
        == pytest.approx(0.046, abs=0.001)
    # 42.3 TFLOP a step
    assert total * 8192 / 1e12 == pytest.approx(42.3, abs=0.05)
    # at the model's 32k the share would be 7.3% (ISSUE 55 says 7.5)
    at_32k = w.forward_flops_per_token(config, 32768)
    assert (at_32k["eva_window"] + at_32k["eva_chunks"]) \
        / sum(at_32k.values()) == pytest.approx(0.073, abs=0.001)


def test_kernel_work_at_the_cells_sizes():
    w, config = _load("builders", "evabyte_flops"), _config()
    ops, nbytes = w.eva_step(config, 1, 8192)
    tokens, chunks = w.eva_pairs(config, 8192)
    assert ops == 4 * 3 * 4 * 128 * 32 * (tokens + chunks)
    # 12 rows of 8,192 x 4,096 bf16 and 6 of their sixteenth, a layer
    assert nbytes == 4 * (12 + 6 / 16) * 8192 * 4096 * 2
    least_s, bound = flops.roofline_seconds(ops, nbytes, PEAKS)
    assert bound == "compute"
    assert least_s * 1e3 == pytest.approx(9.95, abs=0.01)
    # the allowed pairs are 0.30 of a full causal layer's
    assert (tokens + chunks) / (8192 * 8193 / 2) \
        == pytest.approx(0.297, abs=0.001)
    ops, nbytes = w.eva_pool_step(config, 1, 8192)
    assert nbytes == 4 * (6 + 4 / 16) * 8192 * 4096 * 2
    least_s, bound = flops.roofline_seconds(ops, nbytes, PEAKS)
    assert bound == "memory"
    assert least_s * 1e3 == pytest.approx(2.05, abs=0.01)


def test_config_against_the_published():
    """Every entry of the catalog row's `config` as published, but the
    one cut; no width among them."""
    config = _config()
    assert config["source"] == SOURCE
    differs = [k for k, v in PUBLISHED.items()
               if k not in config or config[k] != v]
    assert differs == REDUCED
    assert config["num_hidden_layers"] == 4
    assert config["published"] == {"num_hidden_layers": 32}
    assert sorted(config["reduced_why"]) == REDUCED
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    assert len(entry["why"]) <= 200
    assert config["kernel_impls"] == {
        "eva_attention": "pallas", "eva_pool": "pallas",
        "flash_attention": "pallas",
        "flash_attention_layout": "token_major"}
    for key in ("deployment", "assumed", "reference_rtol_why"):
        assert config[key]
    for key in ("pooling_logits", "pooling_init", "rotary", "heads",
                "norms", "initializer", "optimizer", "recompute",
                "adam_moments", "staircase_blocks"):
        assert config["assumed"][key], key
    for word in ("eight pipeline stages of four", "821,366,784",
                 "13.14 GB", "WITHOUT"):
        assert word in config["deployment"], word
    assert 0 < config["reference_rtol"] < 1e-3
    assert 0 < config["reference_logits_rms"] < 0.2


# -- reference/evabyte.py against the program ----------------------------------

@pytest.mark.parametrize("amp,recompute,rtol", [
    (False, False, 1e-4), (False, True, 1e-4), (True, True, 2e-2)])
def test_evabyte_reference(amp, recompute, rtol):
    import jax

    import paddle_tpu as fluid

    config = dict(TINY, amp=amp, recompute=recompute)
    kind = _load("kinds", "train_steps")
    kind._fresh_programs()
    np.random.seed(0)
    built = _load("builders", "evabyte").build(
        config, {"batch": 2, "seq_len": 64}, flops)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = built["make_batch"](np.random.default_rng(0))
    assert batch[0].shape == (2, 64, 1) and batch[0].max() < 40
    assert batch[1].shape == (2, 64, 3, 1)
    # head p's label at t is the byte at t + 1 + p
    for p in range(3):
        assert (batch[1][:, :63 - p, p] == batch[0][:, 1 + p:]).all()
    ref = _load("reference", "evabyte")
    params = ref.read_params(config, kind._scope_get)
    want = ref.loss(params, batch, config)
    if not amp:
        assert ref.loss(params, batch, config, dtype="bfloat16") \
            == pytest.approx(want, rel=2e-2)
        assert ref.logits(params, batch, config, every=8).shape \
            == (2, 8, 3, 40)
    got, = exe.run(built["compiled"],
                   feed=dict(zip(["src_ids", "tgt_label"], batch)),
                   fetch_list=[built["loss"]])
    assert float(np.asarray(got).reshape(-1)[0]) == \
        pytest.approx(want, rel=rtol)
    assert 0.9 * np.log(40) < want < 1.1 * np.log(40)
    assert built["items_per_step"] == 128 and built["flops_per_item"] > 0
    assert tuple(built["logits"].shape[-2:]) == (3, 40)
    assert set(built["kernel_work"]) == {"eva", "eva_pool"}
    for work in built["kernel_work"].values():
        assert work["flops"] > 0 and work["bytes"] > 0
    names = {p.name for p in fluid.default_main_program().all_parameters()}
    assert names == set(jax.tree_util.tree_leaves(ref.param_names(config)))


# -- the cell kind end to end on the CPU --------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A temporary benchmark root with the benchmark's code, a tiny
    evabyte configuration and one cell (test_rehearsal.py's way)."""
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
    (bench / "configs" / "tiny-evabyte.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_bytes.json").write_text(json.dumps(
        {"kind": "train_steps", "batch": 2, "seq_len": 64,
         "rate_metric": "tokens_per_s"}))
    cells = ["c_evabyte"]
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny-evabyte", "source": "test",
                     "reduced": [],
                     "file": "benchmarks/configs/tiny-evabyte.json",
                     "why": "test"}],
        "workloads": [{"name": "c_evabyte", "config": "tiny-evabyte",
                       "traffic": "tiny_bytes", "chips": 4,
                       "why": "test"}],
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
    path = os.path.join(root, "benchmarks", "configs", "tiny-evabyte.json")
    if over:
        with open(path, "w") as f:
            json.dump(dict(TINY, **over), f)
    out = io.StringIO()
    try:
        result = harness.run_cell(root, "c_evabyte", seed=2147483999,
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
    assert {k.split(":")[0] for k in checks["kernel_impls"]} >= {
        "eva_attention", "eva_pool"}
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    fixed = checks["fixed_batch_losses"]
    assert fixed[0] > fixed[1] > fixed[2]


def test_the_named_impls_decide_correct(root):
    """The configuration of the real cell names the Pallas kernels: a
    run of the XLA forms (which is what runs here, off the chip) is not
    correct, whatever its loss."""
    result, earlier = _run(root, trace=0,
                           kernel_impls=_config()["kernel_impls"])
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert not result["correct"]
    assert checks["checks"]["kernel_impls"] is False
    assert checks["wrong_impls"] == {
        "eva_attention": ["xla"], "eva_pool": ["xla"],
        "flash_attention": [], "flash_attention_layout": []}
    assert checks["checks"]["reference"] is True


def test_cell_per_layer_line(root):
    result, _ = _run(root, trace=1)
    assert result["correct"]
    # no device plane in a CPU trace: the readers of named kernels
    # (eva_*, flash_*, rotary_ms) and of trace categories return
    # nothing and the line leaves them out
    assert {"feed_wait_ms", "feed_put_ms", "feed_put_in_run_ms",
            "enqueue_ms", "run_prepare_ms", "run_fetch_ms", "step_p50_ms",
            "mfu_pct", "step_hbm_gb", "build_s", "compile_s",
            "first_call_s"} <= set(result["metrics"])
    assert not {"eva_ms", "eva_roofline", "eva_pool_ms",
                "eva_pool_roofline", "flash_fwd_ms"} \
        & set(result["metrics"])


def test_the_controls_tool_reads_program_and_wrong_models(root, tmp_path):
    """tools/reference_controls.py --variants all --logits on the tiny
    cell: the program (AMP) lies nearer the reference than each of the
    four wrong models; with a limit on the logits between them the
    program is inside and each wrong model outside, and a limit the
    program misses is the tool's exit code."""
    spec = importlib.util.spec_from_file_location(
        "reference_controls",
        os.path.join(CHECKOUT, "tools", "reference_controls.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    variants = list(_load("reference", "evabyte").VARIANTS)
    assert variants == ["mean_pool", "no_chunks", "sliding_window",
                        "one_head"]
    out = str(tmp_path / "rows.json")
    path = os.path.join(root, "benchmarks", "configs", "tiny-evabyte.json")
    # N(0, 0.2): scores that are not flat, so the key sets and the
    # pooling show in the logits
    wide = dict(TINY, initializer_range=0.2)
    try:
        for limit, code in ((0.05, 0), (0.0005, 1)):
            with open(path, "w") as f:
                json.dump(dict(wide, reference_logits_rms=limit), f)
            assert tool.main(["--root", root, "--cell", "c_evabyte",
                              "--seeds", "2147484001", "--variants",
                              "all", "--logits", "4", "--out", out]) == code
            row, = json.load(open(out))
            names = {"program", "bfloat16", *variants}
            assert set(row["logits_rms_share"]) == set(row["correct"]) \
                == names
            share = row["logits_rms_share"]
            assert all(share["program"] < share[v] for v in variants), share
            assert row["logits_limit"] == limit
            assert row["logits_correct"] == dict(
                {v: False for v in variants},
                program=code == 0, bfloat16=code == 0), share
    finally:
        with open(path, "w") as f:
            json.dump(TINY, f)


def test_the_cells_two_limits_lie_where_the_chip_read():
    """`reference_rtol` three to four times the largest first loss the
    chip read; `reference_logits_rms` between the program's largest
    reading and the bfloat16 reference's smallest (the readings:
    PERF.md section 6, PR 55, and `reference_rtol_why`)."""
    config = _config()
    assert 3.0 * 3.05e-5 <= config["reference_rtol"] <= 4.0 * 3.05e-5
    assert 0.01999 < config["reference_logits_rms"] < 0.03218
    for reading in ("30.5", "0.01999", "0.03218", "mean_pool 0.1260"):
        assert reading in config["reference_rtol_why"], reading


# -- the four readers -----------------------------------------------------------

def _measurement(op_ns, kernel_work):
    return {"trace": {"first": 0, "devices": [{"op_ns": op_ns,
                                               "steps": 2}]},
            "chips": 1, "flops": flops, "clocks": {},
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
            "work": {"kernel_work": kernel_work}}


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """On the parent's program (no pt_eva_* call, no `eva` work) and in
    every cell without EVA (their traces DO hold pt_flash_* calls) the
    readers return None and raise nothing; nor where the work is there
    and the trace holds no such call."""
    readers = {n: _load("layer_metrics", n).read
               for n in ("eva_ms", "eva_roofline", "eva_pool_ms",
                         "eva_pool_roofline")}
    empty = {"trace": None, "work": {"kernel_work": {}}, "clocks": {}}
    assert all(r(empty) is None for r in readers.values())
    parent = _measurement({"mosaic:pt_flash_fwd": 5e6,
                           "mosaic:pt_flash_bwd_dkv": 9e6},
                          {"gqa_flash": {"flops": 1.0, "bytes": 1.0}})
    assert all(r(parent) is None for r in readers.values())
    parent["work"]["kernel_work"].update(
        eva={"flops": 1.0, "bytes": 1.0},
        eva_pool={"flops": 1.0, "bytes": 1.0})
    assert all(r(parent) is None for r in readers.values())


def test_the_new_readers_on_a_made_up_trace():
    """2 steps: 6 + 10 ms of the window part's flash calls and 1 + 3 ms
    of the staircase make eva_ms 10 ms a step; against work whose least
    time is 2.5 ms (by its operations) a share of 25%.  The summariser:
    2 + 2 ms, 2 ms a step, against 1 ms (by its bytes) 50%."""
    m = _measurement(
        {"mosaic:pt_flash_fwd": 6e6, "mosaic:pt_flash_bwd_dkv": 10e6,
         "mosaic:pt_eva_chunk_fwd": 1e6, "mosaic:pt_eva_chunk_bwd": 3e6,
         "mosaic:pt_eva_pool_fwd": 2e6, "mosaic:pt_eva_pool_bwd": 2e6,
         "mosaic:pt_rotary": 9e6},
        {"eva": {"flops": 2.5e9, "bytes": 1e7},
         "eva_pool": {"flops": 1e6, "bytes": 1e8}})
    read = {n: _load("layer_metrics", n).read(m)
            for n in ("eva_ms", "eva_roofline", "eva_pool_ms",
                      "eva_pool_roofline")}
    assert read == {"eva_ms": pytest.approx(10.0),
                    "eva_roofline": pytest.approx(25.0),
                    "eva_pool_ms": pytest.approx(2.0),
                    "eva_pool_roofline": pytest.approx(50.0)}
    # another kernel's name (the rotary above, a name nothing builds) is
    # not the aggregation's
    m["trace"]["devices"][0]["op_ns"]["mosaic:pt_eva_bwd"] = 4e6
    assert _load("layer_metrics", "eva_ms").read(m) == pytest.approx(10.0)


# -- BENCHMARK.json's entries of PR 55 ----------------------------------------

def test_benchmark_entries():
    """By name, not by position, and `<=`: a later PR appends cells and
    metrics after these and may append this cell to further lists."""
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="train_s8k_b1_bytes",
                        chips=1)
    assert len(cell["why"]) <= 200
    for word in ("8192 bytes", "32k", "16 bytes a parameter", "4.6%"):
        assert word in cell["why"], word
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    job = json.load(open(os.path.join(BENCH, "traffic",
                                      "train_s8k_b1_bytes.json")))
    assert (job["kind"], job["batch"], job["seq_len"],
            job["rate_metric"], job["reduced"]) == (
        "train_steps", 1, 8192, "tokens_per_s", {})
    reports = {e["name"] for e in spec["per_layer"]
               if CELL in e.get("workloads", ())}
    assert {
        "feed_wait_ms", "enqueue_ms", "step_p50_ms", "device_idle_pct",
        "mfu_pct", "step_hbm_gb", "build_s", "compile_s", "first_call_s",
        "step_trace_s", "step_lower_s", "step_compile_s",
        "run_prepare_ms", "run_fetch_ms", "feed_put_ms",
        "feed_put_in_run_ms", "matmul_ms", "flash_fwd_ms", "flash_bwd_ms",
        "other_fusion_ms", "copy_ms", "rotary_ms", "eva_ms",
        "eva_roofline", "eva_pool_ms", "eva_pool_roofline"} <= reports
    # no scan, no convolution, no experts, no grouped or latent heads;
    # not the readers of ALL Mosaic calls
    assert not {n for n in reports if n.startswith((
        "ssd_", "kda_", "mla_", "conv", "gated_", "mhc_", "moe_", "gqa_",
        "window_"))}
    assert "flash_roofline" not in reports and "flash_ms" not in reports
    for name, unit, better in (("eva_ms", "ms", "lower"),
                               ("eva_roofline", "%", "higher"),
                               ("eva_pool_ms", "ms", "lower"),
                               ("eva_pool_roofline", "%", "higher")):
        e = next(e for e in spec["per_layer"] if e["name"] == name)
        assert e == dict(e, layer="kernels", moves="tokens_per_s",
                         source="device_trace", unit=unit, better=better)
        assert {CELL} <= set(e["workloads"])
        assert not {w["name"] for w in spec["workloads"]
                    if w["config"] != CONFIG} & set(e["workloads"])
    for e in spec["per_layer"]:
        if CELL in e.get("workloads", ()):
            assert e["moves"] in ("tokens_per_s", "setup_s")
            assert callable(_load("layer_metrics",
                                  e["name"].split(".")[0]).read)
    assert CELL in next(e for e in spec["end_to_end"]
                        if e["name"] == "tokens_per_s")["workloads"]
    assert len(spec["configs"]) >= 11 and len(spec["workloads"]) >= 13
