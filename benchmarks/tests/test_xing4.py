"""The `xing4.0-29b-a4b` configuration's benchmark files on the CPU, in a
file of their own (a `model_config` PR adds files and entries and edits
none): builders/xing4_flops.py against hand-worked values, the
configuration against the published numbers, reference/xing4.py against
the program at tiny size, the cell kind end to end through the harness,
and BENCHMARK.json's entries of PR 27.

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

CELL = "xing4_29b_train_s4k"
F32_RTOL, AMP_RTOL = 1e-4, 2e-2

TINY = {
    "builder": "xing4", "reference": "xing4", "param_prefix": "xing",
    "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "n_routed_experts_published": 8, "held_experts": [0, 1, 2, 3],
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.0, "hc_mult": 4,
    "hc_sinkhorn_iters": 5, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "vocab_size": 128, "initializer_range": 0.02, "amp": True,
    "learning_rate": 1e-3, "recompute": True,
    # off the chip the kernel entries resolve to their XLA forms
    "kernel_impls": {"flash_attention": "xla", "moe_gmm": "xla"},
    "reference_rtol": 2e-2,
}


def _load(kind, name):
    return harness._load_file(os.path.join(BENCH, kind, name + ".py"))


# -- builders/xing4_flops.py: the xing4_0 block -------------------------------

def _xing4_flops():
    return harness._load_file(os.path.join(BENCH, "builders",
                                           "xing4_flops.py"))


def _xing4_config():
    return json.load(open(os.path.join(BENCH, "configs",
                                       "xing4.0-29b-a4b.json")))


def test_flash_work_at_two_head_sizes():
    w = _xing4_flops()
    b, h, t = 1, 32, 4096
    # causal forward: QK^T at 192 and PV at 128, half the square
    fwd = w.flash_flops(b, h, t, t, 192, 128, causal=True)
    assert fwd == b * h * t * t * (192 + 128) == 171_798_691_840
    assert w.flash_flops(b, h, t, t, 192, 128, causal=False) == 2 * fwd
    assert w.flash_flops(b, h, t, t, 192, 128, causal=True,
                         backward=True) == 2 * fwd
    # at equal sizes it is flops.py's count
    assert w.flash_flops(4, 8, 8192, 8192, 64, 64, causal=True) == \
        flops.flash_attention_flops(4, 8, 8192, 8192, 64, causal=True)
    assert w.flash_bytes(4, 8, 8192, 8192, 64, 64, 2, backward=True) == \
        flops.flash_attention_bytes(4, 8, 8192, 8192, 64, 2, backward=True)
    # bytes: q, k at 192 and v, o at 128, bf16; backward moves each twice
    els = b * h * t * (192 + 192 + 128 + 128)
    assert w.flash_bytes(b, h, t, t, 192, 128, 2) == els * 2
    assert w.flash_bytes(b, h, t, t, 192, 128, 2, backward=True) == els * 4
    # five layers: 5 x 3 x forward = 2.58e12, 629 MFLOP a token
    step, nbytes = w.flash_step(b, h, t, 192, 128, 5)
    assert step == 15 * fwd
    assert step / t == pytest.approx(629.1e6, rel=1e-3)
    assert nbytes == 5 * 3 * els * 2


def test_grouped_matmul_work():
    w = _xing4_flops()
    # 4096 tokens x 4 experts each x 8 held of 64: 2048 rows a layer,
    # 256 an expert
    rows = w.routed_rows(4096, 4, 8, 64)
    assert rows == 2048
    fwd = w.gmm_flops(rows, 3584, 1024)
    assert fwd == 3 * 2 * 2048 * 3584 * 1024 == 45_097_156_608
    assert w.gmm_flops(rows, 3584, 1024, backward=True) == 2 * fwd
    weights = 3 * 8 * 3584 * 1024
    assert w.gmm_bytes(rows, 8, 3584, 1024, 2) == \
        (2 * rows * 3584 + weights) * 2
    assert w.gmm_bytes(rows, 8, 3584, 1024, 2, backward=True) == \
        (3 * rows * 3584 + 2 * weights) * 2
    step, nbytes = w.gmm_step(4096, 4, 8, 64, 3584, 1024, 4)
    assert step == 4 * 3 * fwd == pytest.approx(5.41e11, rel=1e-3)
    # at 256 rows an expert the weights' bytes bound the forward
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    _, bound = flops.roofline_seconds(
        fwd, w.gmm_bytes(rows, 8, 3584, 1024, 2), peak)
    assert bound == "memory"


def test_stream_mix_bytes():
    w = _xing4_flops()
    # a token and sublayer: (3 n + 2) C elements forward, twice that
    # backward, bf16: 3 x 14 x 3584 x 2 = 301,056 bytes
    assert w.mhc_mix_bytes(1, 4, 3584, 1) == 301_056
    # the cell: 4096 tokens, 10 sublayers: 12.3 GB
    assert w.mhc_mix_bytes(4096, 4, 3584, 10) == \
        pytest.approx(12.33e9, rel=1e-3)


def test_xing4_per_token():
    w, config = _xing4_flops(), _xing4_config()
    parts = w.matmul_params(config)
    # attention: q_a 3584x768, q_b 768x6144, kv_a 3584x576,
    # kv_b 512x8192, o 4096x3584 = 28.4 M a layer
    assert parts["attention"] == 5 * (
        2_752_512 + 4_718_592 + 2_064_384 + 4_194_304 + 14_680_064)
    assert parts["dense_ffn"] == 3 * 3584 * 9216          # one dense layer
    # an expert layer: shared 11.0 M, router 3584x64, and 4 x 8/64 = half
    # a routed expert a token in expectation
    expert = 3 * 3584 * 1024
    assert parts["expert_ffn"] == 4 * (expert + 3584 * 64 + 0.5 * expert)
    assert parts["mhc"] == 5 * 2 * 14336 * 24
    assert parts["head"] == 3584 * 16384
    # 2.85 GFLOP a token at 4096, flash 22% of it
    total = w.train_flops_per_token(config, 4096)
    assert total == pytest.approx(2.850e9, rel=2e-3)
    attn = 3 * 4096 * 32 * 320 * 5
    assert total == 6 * sum(parts.values()) + attn
    assert attn / total == pytest.approx(0.22, abs=0.005)


def test_xing4_config_against_the_published():
    """Every width as published; only the four cuts differ."""
    config = _xing4_config()
    published = {
        "hidden_size": 3584, "intermediate_size": 9216,
        "moe_intermediate_size": 1024, "num_attention_heads": 32,
        "num_key_value_heads": 32, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_experts_per_tok": 4, "n_shared_experts": 1, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "routed_scaling_factor": 2,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "n_group": 1, "topk_group": 1, "rope_theta": 10000}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["rope_scaling"]["factor"] == 64
    assert config["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "n_routed_experts": 64, "vocab_size": 131072,
        "num_nextn_predict_layers": 1}
    spec = json.load(open(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json")))
    entry = next(c for c in spec["configs"]
                 if c["name"] == "xing4.0-29b-a4b")
    assert sorted(entry["reduced"]) == sorted(config["published"])
    assert sorted(config["reduced_why"]) == sorted(config["published"])
    # the router keeps its 64 outputs; 8 experts are held
    assert config["n_routed_experts_published"] == 64
    assert config["held_experts"] == list(range(8))
    assert config["n_routed_experts"] == len(config["held_experts"])
    # 758.5 M parameters
    w = _xing4_flops()
    n = sum(w.matmul_params(dict(config, num_experts_per_tok=64)).values()) \
        + 16384 * 3584
    assert n == pytest.approx(758.5e6, rel=2e-3)


# -- reference/xing4.py against the program -----------------------------------

@pytest.mark.parametrize("amp,recompute,layers,rtol", [
    (False, False, 4, F32_RTOL), (False, True, 3, F32_RTOL),
    (True, True, 3, AMP_RTOL)])
def test_xing4_reference(amp, recompute, layers, rtol):
    import paddle_tpu as fluid

    config = dict(TINY, amp=amp, recompute=recompute,
                  num_hidden_layers=layers, hc_sinkhorn_iters=20,
                  first_k_dense_replace=layers - 2)
    kind = _load("kinds", "train_steps")
    kind._fresh_programs()
    np.random.seed(0)
    built = _load("builders", "xing4").build(
        config, {"batch": 2, "seq_len": 32}, flops)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = built["make_batch"](np.random.default_rng(0))
    ref = _load("reference", "xing4")
    want = ref.loss(ref.read_params(config, kind._scope_get), batch, config)
    got, = exe.run(built["compiled"],
                   feed=dict(zip(["src_ids", "tgt_label"], batch)),
                   fetch_list=[built["loss"]])
    assert float(np.asarray(got).reshape(-1)[0]) == \
        pytest.approx(want, rel=rtol)
    # a loss over 128 classes at random weights
    assert 0.5 * np.log(128) < want < 2 * np.log(128)
    assert built["items_per_step"] == 64 and built["flops_per_item"] > 0
    assert set(built["kernel_work"]) == {"mla_flash", "moe_gmm", "mhc_mix"}


def test_benchmark_reference_is_the_repositorys():
    """benchmarks/reference/xing4.py is a copy of the reference the
    repository's own tests compare the ops with."""
    with open(os.path.join(BENCH, "reference", "xing4.py")) as f, \
            open(os.path.join(CHECKOUT, "paddle_tpu", "models",
                              "xing4_reference.py")) as g:
        assert f.read() == g.read()


# -- the cell kind end to end on the CPU --------------------------------------

NEW_METRICS = (("mla_flash_roofline", "%"), ("moe_gmm_ms", "ms"),
               ("moe_gmm_roofline", "%"), ("other_fusion_ms", "ms"),
               ("copy_ms", "ms"))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A temporary benchmark root with the benchmark's code, a tiny
    xing4 configuration and one cell (test_rehearsal.py's way)."""
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
    (bench / "configs" / "tiny-xing.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_seq.json").write_text(json.dumps(
        {"kind": "train_steps", "batch": 4, "seq_len": 16,
         "rate_metric": "tokens_per_s"}))
    cells = ["c_xing"]
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny-xing", "source": "test", "reduced": [],
                     "file": "benchmarks/configs/tiny-xing.json",
                     "why": "test"}],
        "workloads": [{"name": "c_xing", "config": "tiny-xing",
                       "traffic": "tiny_seq", "chips": 4, "why": "test"}],
        "end_to_end": [
            {"name": "tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": cells},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": [
            _metric("step_p50_ms", "ms", "host_clock", "entry",
                    "tokens_per_s", cells),
            _metric("mfu_pct", "%", "host_clock", "device",
                    "tokens_per_s", cells),
            _metric("step_hbm_gb", "GB", "program_counter", "device",
                    "tokens_per_s", cells),
            _metric("build_s", "s", "host_clock", "build and compile",
                    "setup_s", cells),
            _metric("flash_fwd_ms", "ms", "device_trace", "kernels",
                    "tokens_per_s", cells),
        ] + [_metric(name, unit, "device_trace", "kernels",
                     "tokens_per_s", cells)
             for name, unit in NEW_METRICS]}))
    return str(root)


def _run(root, trace):
    out = io.StringIO()
    result = harness.run_cell(root, "c_xing", seed=2147483999, seconds=0.5,
                              trace=trace, platform="cpu", out=out)
    return result, [json.loads(x)
                    for x in out.getvalue().strip().splitlines()[:-1]]


def test_cell_end_to_end_line(root):
    result, earlier = _run(root, trace=0)
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert result["correct"], checks
    # counted at trace time, 4 traces an op: its shape rule at build, the
    # forward, and two by the recompute segment's jax.vjp; 3 layers, 2 of
    # them with experts
    assert checks["kernel_impls"] == {"flash_attention:xla": 12,
                                      "moe_gmm:xla": 8}
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    record = json.load(open(os.path.join(
        root, "benchmarks", "out", "c_xing.seed2147483999.trace0.json")))
    # the step's bytes and text came from the executable that ran
    assert record["memory"]["recompiled"] == 0
    fixed = checks["fixed_batch_losses"]
    assert fixed[0] > fixed[1] > fixed[2]


def test_cell_per_layer_line(root):
    result, _ = _run(root, trace=1)
    assert result["correct"]
    # no device plane in a CPU trace: the readers of named kernels and of
    # trace categories return nothing and the line leaves them out
    assert set(result["metrics"]) == {"step_p50_ms", "mfu_pct",
                                      "step_hbm_gb", "build_s"}


@pytest.mark.parametrize("name", [n for n, _ in NEW_METRICS])
def test_new_readers_on_a_recorded_trace(name):
    """The readers against a measurement made by hand: named Mosaic
    calls and categories as trace_reduce.reduce() gives them."""
    tr = harness._load_file(os.path.join(BENCH, "trace_reduce.py"))
    steps = 4
    r = {"steps": steps,
         "op_ns": {"mosaic:pt_flash_fwd": 8e6 * steps,
                   "mosaic:pt_flash_bwd_dq": 4e6 * steps,
                   "mosaic:pt_flash_bwd_dkv": 8e6 * steps,
                   "mosaic:pt_gmm_fwd": 3e6 * steps,
                   "mosaic:pt_gmm_bwd_dx": 1e6 * steps,
                   "mosaic:pt_gmm_bwd_dw": 1e6 * steps},
         "category_ns": {"fusion": 7e6 * steps, "copy": 2e6 * steps,
                         "mosaic": 25e6 * steps}}
    m = {"trace": {"devices": {"/device:TPU:0": r},
                   "first": "/device:TPU:0"},
         "tr": tr, "flops": flops, "chips": 1,
         "peaks": {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9},
         "work": {"kernel_work": {
             # 2 ms of compute at the peak; 1 ms of bytes
             "mla_flash": {"flops": 0.4e12, "bytes": 0.8e9},
             # 0.5 ms of compute; 1 ms of bytes: memory-bound
             "moe_gmm": {"flops": 0.1e12, "bytes": 0.8e9}}}}
    want = {"mla_flash_roofline": 2.0 / 20.0 * 100, "moe_gmm_ms": 5.0,
            "moe_gmm_roofline": 1.0 / 5.0 * 100, "other_fusion_ms": 7.0,
            "copy_ms": 2.0}
    reader = _load("layer_metrics", name)
    assert reader.read(m) == pytest.approx(want[name])
    # a parent without the kernels, or a CPU trace: nothing to read
    if name.startswith(("mla", "moe")):
        r["op_ns"] = {"mosaic:pt_other": 1e6}
        assert reader.read(m) is None
    assert reader.read(dict(m, trace=None)) is None


# -- BENCHMARK.json's entries of PR 27 -----------------------------------------

def test_benchmark_entries():
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    cell = spec["workloads"][-1]
    assert cell == dict(cell, name=CELL, config="xing4.0-29b-a4b",
                        traffic="train_s4k_b1", chips=1)
    assert len(cell["why"]) <= 200
    entry = spec["configs"][-1]
    assert entry["name"] == "xing4.0-29b-a4b"
    assert entry["file"] == "benchmarks/configs/xing4.0-29b-a4b.json"
    job = json.load(open(os.path.join(BENCH, "traffic",
                                      "train_s4k_b1.json")))
    assert (job["kind"], job["batch"], job["seq_len"],
            job["rate_metric"]) == ("train_steps", 1, 4096, "tokens_per_s")
    # appended last, each reading a named kernel or a trace category
    later = spec["per_layer"][-5:]
    assert [e["name"] for e in later] == [n for n, _ in NEW_METRICS]
    layers = {e["layer"] for e in spec["per_layer"][:-5]}
    for e in later:
        assert e["workloads"] == [CELL] and e["layer"] in layers
        assert e["moves"] == "tokens_per_s"
        assert callable(_load("layer_metrics", e["name"]).read)
    reports = {e["name"] for e in spec["per_layer"]
               if CELL in e.get("workloads", ())}
    assert reports >= {"feed_wait_ms", "feed_put_ms", "feed_put_in_run_ms",
                       "run_prepare_ms", "enqueue_ms", "run_fetch_ms",
                       "step_p50_ms", "device_idle_pct", "mfu_pct",
                       "step_hbm_gb", "matmul_ms", "flash_fwd_ms",
                       "flash_bwd_ms", "build_s", "compile_s",
                       "first_call_s"}
    # the step holds a second kind of Mosaic call: not on the lists of
    # the metrics that read EVERY Mosaic call
    assert not reports & {"flash_ms", "flash_roofline"}
    # every cell reports setup_s: its three metrics name all five cells
    cells = [w["name"] for w in spec["workloads"]]
    for e in spec["per_layer"]:
        if e["moves"] == "setup_s":
            assert e["workloads"] == cells
    assert CELL in next(e for e in spec["end_to_end"]
                        if e["name"] == "tokens_per_s")["workloads"]
