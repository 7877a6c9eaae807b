"""The `ouro-2.6b` configuration's benchmark files on the CPU, in a file
of their own (a `model_config` PR adds files and entries and edits
none): builders/ouro_flops.py against hand-worked values, the
configuration against the catalog row's published numbers,
reference/ouro.py against the program at tiny size, the cell kind end
to end through the harness, and BENCHMARK.json's entries of PR 32.

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

CELL = "ouro_2_6b_train_s4k"
# the catalog row `Ouro-2.6B` (architectures.jsonl beside the
# model-configs guide): its `source_url` and its `config`, copied here
# so that the test reads nothing outside the checkout
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}

TINY = {
    "builder": "ouro", "reference": "ouro", "param_prefix": "ouro",
    "hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
    "num_key_value_heads": 4, "intermediate_size": 160, "vocab_size": 128,
    "num_hidden_layers": 2, "total_ut_steps": 4, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "rope_scaling": None,
    "initializer_range": 0.02, "exit_entropy_beta": 0.05, "amp": True,
    "learning_rate": 1e-3, "recompute": True,
    # off the chip the flash entry resolves to its XLA form, which
    # takes token-major operands through a transpose inside the op
    "kernel_impls": {"flash_attention": "xla",
                     "flash_attention_layout": "head_major"},
    "reference_rtol": 2e-2,
}


def _load(kind, name):
    return harness._load_file(os.path.join(BENCH, kind, name + ".py"))


def _config():
    return json.load(open(os.path.join(BENCH, "configs",
                                       "ouro-2.6b.json")))


# -- builders/ouro_flops.py ---------------------------------------------------

def test_flops_per_token_at_the_cells_sizes():
    w, config = _load("builders", "ouro_flops"), _config()
    # a layer: q, k, v, o 4 x 2048^2 and gate, up, down 3 x 2048 x 5632
    assert w.layer_matmul_params(config) == \
        4 * 2048 * 2048 + 3 * 2048 * 5632 == 51_380_224
    parts = w.parts_per_token(config, 4096)
    # 6 layers x 4 passes = 24 executions of a layer
    assert parts["layers"] == 6 * 51_380_224 * 24 == 7_398_752_256
    # causal attention: 16.8 MFLOP a token forward, x 3, x 24
    assert parts["flash"] == 3 * (2 * 2048 * 4096) * 24 == 1_207_959_552
    # the head after every pass: 100.66 M parameters, x 6, x 4
    assert parts["heads"] == 6 * 2048 * 49152 * 4 == 2_415_919_104
    assert parts["exit_gate"] == 6 * 2048 * 3
    total = w.train_flops_per_token(config, 4096)
    assert total == sum(parts.values())
    assert total == pytest.approx(11.02e9, rel=1e-3)
    # the looped stack is 78% of it, the four heads 22%
    assert (parts["layers"] + parts["flash"]) / total == \
        pytest.approx(0.78, abs=0.005)
    # R = 1 is a plain decoder of the same layers
    plain = w.parts_per_token(dict(config, total_ut_steps=1), 4096)
    assert plain["layers"] * 4 == parts["layers"]
    assert plain["exit_gate"] == 0


def test_flash_work_at_the_cells_sizes():
    # 24 causal calls of 16 heads x 4096^2 x 128, forward + backward
    fwd = flops.flash_attention_flops(1, 16, 4096, 4096, 128, causal=True)
    assert fwd == 2 * 16 * 4096 * 4096 * 128 == 68_719_476_736
    step, nbytes = flops.transformer_flash_step(1, 16, 4096, 128, 24)
    assert step == 24 * 3 * fwd
    assert step / 4096 == _load("builders", "ouro_flops").parts_per_token(
        _config(), 4096)["flash"]
    # q, k, v, o once forward (4 operands), 8 backward, bf16
    assert nbytes == 24 * 12 * 16 * 4096 * 128 * 2
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.roofline_seconds(step, nbytes, peak)
    assert bound == "compute" and least == pytest.approx(25.1e-3, rel=1e-2)


# -- the configuration against the catalog row --------------------------------

def test_config_against_the_published():
    """Every key of the catalog row's `config` as published, but
    num_hidden_layers."""
    config = _config()
    assert config["source"] == SOURCE
    differs = [k for k, v in PUBLISHED.items() if config.get(k) != v]
    assert differs == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 6
    assert config["published"] == {"num_hidden_layers": 48}
    assert sorted(config["reduced_why"]) == ["num_hidden_layers"]
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in spec["configs"] if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == SOURCE
    assert config["kernel_impls"] == {
        "flash_attention": "pallas",
        "flash_attention_layout": "token_major"}
    for key in ("deployment", "assumed", "reference_rtol_why"):
        assert config[key]
    # 509.7 M parameters: 6 layers (with their four norms), embedding,
    # head, final norm, the gate's weight and bias
    layer = _load("builders", "ouro_flops").layer_matmul_params(config) \
        + 4 * 2048
    n = 6 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert n == pytest.approx(509.7e6, rel=1e-3)


# -- reference/ouro.py against the program ------------------------------------

@pytest.mark.parametrize("amp,recompute,passes,rtol", [
    (False, False, 1, 1e-4), (False, True, 4, 1e-4), (True, True, 2, 2e-2)])
def test_ouro_reference(amp, recompute, passes, rtol):
    import paddle_tpu as fluid

    config = dict(TINY, amp=amp, recompute=recompute,
                  total_ut_steps=passes)
    kind = _load("kinds", "train_steps")
    kind._fresh_programs()
    np.random.seed(0)
    built = _load("builders", "ouro").build(
        config, {"batch": 2, "seq_len": 32}, flops)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = built["make_batch"](np.random.default_rng(0))
    ref = _load("reference", "ouro")
    want = ref.loss(ref.read_params(config, kind._scope_get), batch, config)
    got, = exe.run(built["compiled"],
                   feed=dict(zip(["src_ids", "tgt_label"], batch)),
                   fetch_list=[built["loss"]])
    assert float(np.asarray(got).reshape(-1)[0]) == \
        pytest.approx(want, rel=rtol)
    # a loss over 128 classes at random weights, less the entropy term
    assert 0.9 * np.log(128) < want < 1.1 * np.log(128)
    assert built["items_per_step"] == 64 and built["flops_per_item"] > 0
    assert set(built["kernel_work"]) == {"flash"}
    # every parameter once, whatever R is
    names = [p.name for p in fluid.default_main_program().all_parameters()]
    assert len(names) == len(set(names)) == 25 + 2 * (passes > 1)


def test_benchmark_reference_is_the_repositorys():
    with open(os.path.join(BENCH, "reference", "ouro.py")) as f, \
            open(os.path.join(CHECKOUT, "paddle_tpu", "models",
                              "ouro_reference.py")) as g:
        assert f.read() == g.read()


# -- the cell kind end to end on the CPU --------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A temporary benchmark root with the benchmark's code, a tiny
    ouro configuration and one cell (test_rehearsal.py's way)."""
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
    (bench / "configs" / "tiny-ouro.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_seq.json").write_text(json.dumps(
        {"kind": "train_steps", "batch": 4, "seq_len": 16,
         "rate_metric": "tokens_per_s"}))
    cells = ["c_ouro"]
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny-ouro", "source": "test", "reduced": [],
                     "file": "benchmarks/configs/tiny-ouro.json",
                     "why": "test"}],
        "workloads": [{"name": "c_ouro", "config": "tiny-ouro",
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


def _run(root, trace):
    out = io.StringIO()
    result = harness.run_cell(root, "c_ouro", seed=2147483999, seconds=0.5,
                              trace=trace, platform="cpu", out=out)
    return result, [json.loads(x)
                    for x in out.getvalue().strip().splitlines()[:-1]]


def test_cell_end_to_end_line(root):
    result, earlier = _run(root, trace=0)
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert result["correct"], checks
    # counted at trace time, 4 traces an op (two while the program is
    # built, the step's forward, its segment's replay); 2 layers x 4
    # passes.  The layout is counted by the forward entry alone
    assert checks["kernel_impls"] == {"flash_attention:xla": 32,
                                      "flash_attention_layout:head_major":
                                      32}
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    record = json.load(open(os.path.join(
        root, "benchmarks", "out", "c_ouro.seed2147483999.trace0.json")))
    # the step's bytes and text came from the executable that ran
    assert record["memory"]["recompiled"] == 0
    fixed = checks["fixed_batch_losses"]
    assert fixed[0] > fixed[1] > fixed[2]


def test_cell_per_layer_line(root):
    result, _ = _run(root, trace=1)
    assert result["correct"]
    # no device plane in a CPU trace: the readers of the trace return
    # nothing and the line leaves them out; the rest read the host
    # clock, the program's record and its counters
    assert set(result["metrics"]) == {
        "feed_wait_ms", "feed_put_ms", "feed_put_in_run_ms", "enqueue_ms",
        "run_prepare_ms", "run_fetch_ms", "step_p50_ms", "mfu_pct",
        "step_hbm_gb", "build_s", "compile_s", "first_call_s"}


# -- BENCHMARK.json's entries of PR 32 ----------------------------------------

def test_benchmark_entries():
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    cell = spec["workloads"][-1]
    assert cell == dict(cell, name=CELL, config="ouro-2.6b",
                        traffic="train_s4k_b1_full_vocab", chips=1)
    assert len(cell["why"]) <= 200
    assert len(spec["workloads"]) == 6
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] == \
        ["tfm_base_train_dp2tp2"]
    entry = spec["configs"][-1]
    assert entry["name"] == "ouro-2.6b"
    assert entry["file"] == "benchmarks/configs/ouro-2.6b.json"
    job = json.load(open(os.path.join(BENCH, "traffic",
                                      "train_s4k_b1_full_vocab.json")))
    assert (job["kind"], job["batch"], job["seq_len"],
            job["rate_metric"]) == ("train_steps", 1, 4096, "tokens_per_s")
    reports = {e["name"] for e in spec["per_layer"]
               if CELL in e.get("workloads", ())}
    # the step's only Mosaic calls are flash: the readers of EVERY
    # Mosaic call apply, the readers of the grouped matmuls do not
    assert reports == {
        "feed_wait_ms", "feed_put_ms", "feed_put_in_run_ms", "enqueue_ms",
        "run_prepare_ms", "run_fetch_ms", "step_p50_ms",
        "device_idle_pct", "mfu_pct", "step_hbm_gb", "build_s",
        "compile_s", "first_call_s", "matmul_ms", "flash_ms",
        "flash_fwd_ms", "flash_bwd_ms", "flash_roofline",
        "other_fusion_ms", "copy_ms"}
    for e in spec["per_layer"]:
        if CELL in e.get("workloads", ()):
            assert e["workloads"][-1] == CELL      # appended, last
            assert callable(_load("layer_metrics",
                                  e["name"].split(".")[0]).read)
        # every cell reports setup_s: its metrics name all six cells
        if e["moves"] == "setup_s":
            assert e["workloads"] == [w["name"] for w in spec["workloads"]]
    assert next(e for e in spec["end_to_end"]
                if e["name"] == "tokens_per_s")["workloads"][-1] == CELL
