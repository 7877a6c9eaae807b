"""The `mellum2-12b-a2.5b` configuration's benchmark files on the CPU, in
a file of their own (a `model_config` PR adds files and entries and
edits none): builders/mellum2_flops.py against the hand-worked numbers
of ISSUE 53 and a brute-force count of allowed pairs, the configuration
against the catalog row's published numbers, reference/mellum2.py
against the program at tiny size, the cell kind end to end through the
harness, the new readers window_flash_ms and window_flash_roofline, and
BENCHMARK.json's entries of PR 53, looked up BY NAME and held with
`<=`: a later PR appends after them.

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

CELL = "mellum2_12b_train_s16k"
CONFIG = "mellum2-12b-a2.5b"
# the catalog row `Mellum2-12B-A2.5B-Instruct` (architectures.jsonl
# beside the model-configs guide): its `source_url` and its `config`,
# copied here so that the test reads nothing outside the checkout
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 7,
    "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": ROPE, "sliding_window": 1024,
    "tie_word_embeddings": False, "vocab_size": 98304,
    "use_sliding_window": True,
}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]

TINY = {
    "builder": "mellum2", "reference": "mellum2",
    "param_prefix": "mellum2", "hidden_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "moe_intermediate_size": 64, "num_experts": 4,
    "num_experts_published": 16, "held_experts": [0, 1, 2, 3],
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "num_hidden_layers": 4, "kept_layers": [0, 1, 2, 3],
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 2,
    "mlp_layer_types": ["sparse"] * 8, "sliding_window": 16,
    "use_sliding_window": True, "max_window_layers": 0,
    "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "rope_parameters": dict(ROPE, full_attention=dict(
        ROPE["full_attention"], original_max_position_embeddings=16)),
    "tie_word_embeddings": False, "vocab_size": 128,
    "initializer_range": 0.02, "amp": True, "learning_rate": 1e-3,
    "recompute": True,
    # off the chip the kernel entries resolve to their XLA forms: plain
    # attention with the window on K and V repeated to the query heads
    # (no grid, so no `flash_attention_window` series), the grouped
    # matmuls in jax.numpy
    "kernel_impls": {"flash_attention": "xla",
                     "flash_attention_kv_heads": "repeated",
                     "moe_gmm": "xla", "moe_route_scoring": "softmax"},
    "reference_rtol": 2e-2,
}


def _load(kind, name):
    return harness._load_file(os.path.join(BENCH, kind, name + ".py"))


def _config():
    return json.load(open(os.path.join(BENCH, "configs",
                                       CONFIG + ".json")))


# -- builders/mellum2_flops.py -------------------------------------------------

def test_parameters_at_the_cells_sizes_and_the_published_totals():
    """ISSUE 53's arithmetic of the cut, from the functions; and the
    uncut model's, which reproduce the name's 12B-A2.5B."""
    w, config = _load("builders", "mellum2_flops"), _config()
    assert w.layer_kinds(config) == ["sliding_attention"] * 3 \
        + ["full_attention"]
    layer = w.layer_params(config)
    assert layer["mixer"] == 2 * 2304 * 4096 + 2 * 2304 * 512 \
        == 21_233_664
    assert layer["router"] == 2304 * 64 == 147_456
    assert layer["ffn"] == 16 * 3 * 2304 * 896 == 16 * 6_193_152
    assert layer["other"] == 2 * 2304
    assert sum(layer.values()) == 120_476_160
    assert w.n_params(config) == 4 * 120_476_160 + 2 * 24576 * 2304 \
        + 2304 == 595_153_152
    assert round(w.n_params(config) / 1e6, 1) == 595.2
    assert w.n_params(config) * 16 / 1e9 == pytest.approx(9.52, abs=0.01)
    assert w.n_params(config) * 12 / 1e9 == pytest.approx(7.14, abs=0.01)
    whole = dict(config, num_hidden_layers=28, kept_layers=None,
                 num_experts=64, vocab_size=98304)
    assert sum(w.layer_params(whole).values()) / 1e6 \
        == pytest.approx(417.75, abs=0.01)
    assert w.n_params(whole) / 1e9 == pytest.approx(12.15, abs=0.005)
    active = 28 * (21_233_664 + 147_456 + 8 * 6_193_152 + 4608) \
        + 2 * 98304 * 2304 + 2304
    assert active / 1e9 == pytest.approx(2.43, abs=0.01)


@pytest.mark.parametrize("t,w", [(1, 1), (7, 3), (16, 16), (16, 40),
                                 (50, 1), (64, 24), (200, 96), (33, 0)])
def test_allowed_pairs_against_a_brute_force_count(t, w):
    work = _load("builders", "mellum2_flops")
    i, j = np.indices((t, t))
    allowed = j <= i
    if w:
        allowed &= j > i - w
    assert work.allowed_pairs(t, w) == int(allowed.sum())
    assert work.allowed_pairs(t, w) == sum(
        min(r + 1, w or t) for r in range(t))


def test_window_flash_step_against_a_brute_force_count():
    """Two window layers and one full one, 3 query heads on 1 KV head
    of 8, 40 tokens under a window of 12, 2 sequences, float32."""
    w = _load("builders", "mellum2_flops")
    config = {"layer_types": ["sliding_attention", "full_attention",
                              "sliding_attention"],
              "num_hidden_layers": 3, "num_attention_heads": 3,
              "num_key_value_heads": 1, "head_dim": 8,
              "sliding_window": 12}
    i, j = np.indices((40, 40))
    band = int(((j <= i) & (j > i - 12)).sum())
    half = int((j <= i).sum())
    ops, nbytes = w.window_flash_step(config, 2, 40, bytes_per_el=4)
    # q . k and p v, 2 d each, a pair and query head; backward twice
    assert ops == 2 * 2 * 3 * band * (2 * 8 + 2 * 8) * 3
    # forward q, o at 3 heads and k, v at 1; backward q, o, dO, dq at 3
    # and k, v, dk, dv at 1
    row = 2 * 40 * 8 * 4
    assert nbytes == 2 * ((2 * 3 + 2 * 1) + (4 * 3 + 4 * 1)) * row
    full_ops, full_bytes = w.gqa_flash_step(config, 2, 40, bytes_per_el=4)
    assert full_ops == 2 * 3 * half * 32 * 3
    assert full_bytes == nbytes / 2
    # no window layer, no window work
    assert w.window_flash_step(dict(config, kept_layers=[1],
                                    num_hidden_layers=1), 2, 40) \
        == (0.0, 0.0)


def test_flops_per_token_at_the_cells_sizes():
    w, config = _load("builders", "mellum2_flops"), _config()
    fwd = w.forward_flops_per_token(config, 16384)
    in_mflop = {k: round(v / 1e6, 1) for k, v in fwd.items()}
    # the issue's count: projections 170, head 113, experts 99 (2 of a
    # token's 8 pairs meet a held expert), the full layer's flash 134
    # and the three window layers' 49 together
    assert in_mflop == {"attention_proj": 169.9, "routed_experts": 99.1,
                        "router": 1.2, "head": 113.2,
                        "window_flash": 48.8, "full_flash": 134.2}
    total = sum(fwd.values())
    assert total / 1e9 == pytest.approx(0.566, abs=0.001)
    assert w.train_flops_per_token(config, 16384) == 3 * total
    assert 3 * total / 1e9 == pytest.approx(1.70, abs=0.005)
    # the band is never counted as the half square: a mask without a
    # skip would cost the three layers 403 M
    assert 3 * 4 * 4096 * (16384 + 1) / 2 / 1e6 \
        == pytest.approx(402.7, abs=0.1)
    assert fwd["window_flash"] / 3 == pytest.approx(
        4 * 4096 * (1024 - 1024 * 1023 / 2 / 16384), rel=1e-9)
    # a window layer's allowed pairs: an eighth of the full layer's
    assert fwd["full_flash"] / (fwd["window_flash"] / 3) \
        == pytest.approx(8.25, abs=0.01)
    assert fwd["routed_experts"] == 2.0 * 4 * 2 * 3 * 2304 * 896


def test_kernel_work_at_the_cells_sizes():
    w, config = _load("builders", "mellum2_flops"), _config()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops, nbytes = w.window_flash_step(config, 1, 16384)
    assert ops == 3 * 3 * 4 * 128 * 32 * w.allowed_pairs(16384, 1024)
    # (2 x 32 + 2 x 4) + (4 x 32 + 4 x 4) rows of 16,384 x 128 bf16
    assert nbytes == 3 * 216 * 16384 * 128 * 2
    least_s, bound = flops.roofline_seconds(ops, nbytes, peaks)
    assert bound == "compute"
    assert least_s * 1e3 / 3 == pytest.approx(4.06, abs=0.01)
    full_ops, full_bytes = w.gqa_flash_step(config, 1, 16384)
    assert full_bytes == nbytes / 3
    # flops.py's causal count, of the full layer ALONE
    assert full_ops == pytest.approx(
        3 * flops.flash_attention_flops(1, 32, 16384, 16384, 128, True),
        rel=1e-4)
    least_s, bound = flops.roofline_seconds(full_ops, full_bytes, peaks)
    assert bound == "compute"
    assert least_s * 1e3 == pytest.approx(33.5, abs=0.05)


def test_config_against_the_published():
    """Every entry of the catalog row's `config` as published, but the
    three cuts; no width among them; `layer_types`, `mlp_layer_types`
    and `rope_parameters` kept whole."""
    config = _config()
    assert config["source"] == SOURCE
    differs = [k for k, v in PUBLISHED.items()
               if k not in config or config[k] != v]
    assert sorted(differs) == sorted(REDUCED)
    assert [config[k] for k in REDUCED] == [4, 16, 24576]
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert config["num_experts_published"] == PUBLISHED["num_experts"]
    assert config["held_experts"] == list(range(16))
    assert config["kept_layers"] == [0, 1, 2, 3]
    assert len(config["layer_types"]) == 28
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert sorted(config["reduced_why"]) == sorted(REDUCED)
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    assert len(entry["why"]) <= 200
    assert config["kernel_impls"] == {
        "flash_attention": "pallas", "flash_attention_window": "band",
        "moe_gmm": "pallas", "moe_route_scoring": "softmax"}
    for key in ("deployment", "assumed", "reference_rtol_why"):
        assert config[key]
    for key in ("layer_kinds", "attention", "rotary", "window", "router",
                "head", "prediction_head", "initializer", "optimizer",
                "recompute", "window_blocks", "adam_moments"):
        assert config["assumed"][key], key
    for word in ("Four chips", "16 of the 64 experts", "pipeline stages of four",
                 "WITHOUT", "595.15 M", "9.52 GB"):
        assert word in config["deployment"], word
    assert 0 < config["reference_rtol"] < 1e-3
    assert 0 < config["reference_logits_rms"] < 0.2


# -- reference/mellum2.py against the program ---------------------------------

@pytest.mark.parametrize("amp,recompute,rtol", [
    (False, False, 1e-4), (False, True, 1e-4), (True, True, 2e-2)])
def test_mellum2_reference(amp, recompute, rtol):
    import jax

    import paddle_tpu as fluid

    config = dict(TINY, amp=amp, recompute=recompute)
    kind = _load("kinds", "train_steps")
    kind._fresh_programs()
    np.random.seed(0)
    built = _load("builders", "mellum2").build(
        config, {"batch": 2, "seq_len": 64}, flops)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = built["make_batch"](np.random.default_rng(0))
    assert batch[0].shape == (2, 64, 1) and batch[0].max() < 128
    assert (batch[1][:, :-1] == batch[0][:, 1:]).all()
    ref = _load("reference", "mellum2")
    params = ref.read_params(config, kind._scope_get)
    want = ref.loss(params, batch, config)
    if not amp:
        # the controls compute another loss (before the step donates
        # and changes the weights): the layers in bfloat16 inside the
        # AMP limit
        assert ref.loss(params, batch, config, dtype="bfloat16") \
            == pytest.approx(want, rel=2e-2)
        assert ref.logits(params, batch, config, every=8).shape \
            == (2, 8, 128)
    got, = exe.run(built["compiled"],
                   feed=dict(zip(["src_ids", "tgt_label"], batch)),
                   fetch_list=[built["loss"]])
    assert float(np.asarray(got).reshape(-1)[0]) == \
        pytest.approx(want, rel=rtol)
    assert 0.9 * np.log(128) < want < 1.1 * np.log(128)
    assert built["items_per_step"] == 128 and built["flops_per_item"] > 0
    assert built["logits"].shape[-1] == 128
    assert set(built["kernel_work"]) == {"window_flash", "gqa_flash",
                                         "moe_gmm"}
    for work in built["kernel_work"].values():
        assert work["flops"] > 0 and work["bytes"] > 0
    # the reference reads every parameter the program has, no other
    names = {p.name for p in fluid.default_main_program().all_parameters()}
    assert names == set(jax.tree_util.tree_leaves(ref.param_names(config)))


# -- the cell kind end to end on the CPU --------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A temporary benchmark root with the benchmark's code, a tiny
    mellum2 configuration and one cell (test_rehearsal.py's way)."""
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
    (bench / "configs" / "tiny-mellum2.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_seq.json").write_text(json.dumps(
        {"kind": "train_steps", "batch": 2, "seq_len": 64,
         "rate_metric": "tokens_per_s"}))
    cells = ["c_mellum2"]
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny-mellum2", "source": "test",
                     "reduced": [],
                     "file": "benchmarks/configs/tiny-mellum2.json",
                     "why": "test"}],
        "workloads": [{"name": "c_mellum2", "config": "tiny-mellum2",
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
    path = os.path.join(root, "benchmarks", "configs", "tiny-mellum2.json")
    if over:
        with open(path, "w") as f:
            json.dump(dict(TINY, **over), f)
    out = io.StringIO()
    try:
        result = harness.run_cell(root, "c_mellum2", seed=2147483999,
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
        "flash_attention", "flash_attention_kv_heads", "moe_gmm",
        "moe_route_scoring"}
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    fixed = checks["fixed_batch_losses"]
    assert fixed[0] > fixed[1] > fixed[2]


def test_the_named_impls_decide_correct(root):
    """The configuration of the real cell names the Pallas flash
    kernels, the BAND grid of the window layers' calls and the Pallas
    grouped matmuls: a run of the XLA forms (which is what runs here,
    off the chip, and counts no window grid at all) is not correct,
    whatever its loss."""
    result, earlier = _run(root, trace=0,
                           kernel_impls=_config()["kernel_impls"])
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert not result["correct"]
    assert checks["checks"]["kernel_impls"] is False
    assert checks["wrong_impls"] == {
        "flash_attention": ["xla"], "flash_attention_window": [],
        "moe_gmm": ["xla"]}
    assert checks["checks"]["reference"] is True


def test_cell_per_layer_line(root):
    result, _ = _run(root, trace=1)
    assert result["correct"]
    # no device plane in a CPU trace: the readers of named kernels
    # (window_flash_*, gqa_flash_roofline, flash_*, moe_gmm_*) and of
    # trace categories return nothing and the line leaves them out; the
    # stat rings are read without the trace's help
    assert {"feed_wait_ms", "feed_put_ms", "feed_put_in_run_ms",
            "enqueue_ms", "run_prepare_ms", "run_fetch_ms", "step_p50_ms",
            "mfu_pct", "step_hbm_gb", "build_s", "compile_s",
            "first_call_s"} <= set(result["metrics"])
    assert not {"window_flash_ms", "window_flash_roofline",
                "gqa_flash_roofline", "moe_gmm_ms"} \
        & set(result["metrics"])


def test_the_controls_tool_reads_program_and_wrong_models(root, tmp_path):
    """tools/reference_controls.py --variants all --logits on the tiny
    cell: a row a seed with the loop kind's own comparison beside each
    control, and the logits of the program's forward pass and of each
    control against the reference's.  The program (AMP) lies nearer the
    reference than each of the three wrong models; with a limit on the
    logits between them the program is inside and each wrong model
    outside, and a limit the program misses is the tool's exit code."""
    spec = importlib.util.spec_from_file_location(
        "reference_controls",
        os.path.join(CHECKOUT, "tools", "reference_controls.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    variants = list(_load("reference", "mellum2").VARIANTS)
    assert variants == ["no_window", "no_yarn", "gates_not_renormalised"]
    out = str(tmp_path / "rows.json")
    path = os.path.join(root, "benchmarks", "configs", "tiny-mellum2.json")
    # N(0, 0.2): scores that are not flat, so the mask and the
    # frequencies show in the logits
    wide = dict(TINY, initializer_range=0.2)
    try:
        for limit, code in ((0.2, 0), (0.001, 1)):
            with open(path, "w") as f:
                json.dump(dict(wide, reference_logits_rms=limit), f)
            assert tool.main(["--root", root, "--cell", "c_mellum2",
                              "--seeds", "2147484001", "--variants",
                              "all", "--logits", "4", "--out", out]) == code
            row, = json.load(open(out))
            names = {"program", "bfloat16", *variants}
            assert set(row["logits_rms_share"]) == set(row["correct"]) \
                == names
            assert row["correct"] == {k: v <= row["rtol"]
                                      for k, v in row["rel_diff"].items()}
            share = row["logits_rms_share"]
            assert all(share["program"] < share[v] for v in variants), share
            assert row["logits_limit"] == limit
            assert row["logits_correct"] == dict(
                {v: False for v in variants},
                program=code == 0, bfloat16=code == 0)
    finally:
        with open(path, "w") as f:
            json.dump(TINY, f)


def test_the_cells_two_limits_lie_where_the_chip_read():
    """`reference_rtol` three to four times the largest first loss the
    chip read over 21 seeds; `reference_logits_rms` between the
    program's largest reading and the bfloat16 reference's smallest
    (the readings: PERF.md section 6, PR 53, and `reference_rtol_why`)."""
    config = _config()
    assert 3.0 * 1.724e-5 <= config["reference_rtol"] <= 4.0 * 1.724e-5
    assert 0.010252 < config["reference_logits_rms"] < 0.012256
    for reading in ("17.2", "0.01025", "0.01226", "no_window 1.161"):
        assert reading in config["reference_rtol_why"], reading


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """On the parent's program (no pt_flash_win_* call, no
    `window_flash` work) and in every cell without a window layer
    (their traces DO hold pt_flash_* calls) the readers return None and
    raise nothing; nor where the work is there and the trace holds no
    such call."""
    ms = _load("layer_metrics", "window_flash_ms").read
    share = _load("layer_metrics", "window_flash_roofline").read
    empty = {"trace": None, "work": {"kernel_work": {}}, "clocks": {}}
    assert ms(empty) is None and share(empty) is None
    causal = {"first": 0, "devices": [
        {"op_ns": {"mosaic:pt_flash_fwd": 5e6,
                   "mosaic:pt_flash_bwd_dkv": 9e6}, "steps": 2}]}
    parent = {"trace": causal, "clocks": {}, "chips": 1, "flops": flops,
              "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
              "work": {"kernel_work": {"gqa_flash": {"flops": 1.0,
                                                     "bytes": 1.0}}}}
    assert ms(parent) is None and share(parent) is None
    parent["work"]["kernel_work"]["window_flash"] = {"flops": 1.0,
                                                     "bytes": 1.0}
    assert share(parent) is None


def test_the_new_readers_on_a_made_up_trace():
    """2 steps, 6 ms of pt_flash_win_fwd and 10 ms of
    pt_flash_win_bwd_dkv: 8 ms a step, beside the full layer's calls,
    which neither reader counts; against work whose least time is 2 ms
    (by its operations) a share of 25%.  And gqa_flash_roofline reads
    the full layer's calls alone."""
    trace = {"first": 0, "devices": [{"op_ns": {
        "mosaic:pt_flash_win_fwd": 6e6, "mosaic:pt_flash_win_bwd_dkv": 10e6,
        "mosaic:pt_flash_fwd": 7e6, "mosaic:pt_flash_bwd_dkv": 13e6},
        "steps": 2}]}
    m = {"trace": trace, "chips": 1, "flops": flops,
         "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
         "work": {"kernel_work": {
             "window_flash": {"flops": 2e9, "bytes": 1e8},
             "gqa_flash": {"flops": 5e9, "bytes": 1e8}}}}
    assert _load("layer_metrics", "window_flash_ms").read(m) \
        == pytest.approx(8.0)
    assert _load("layer_metrics", "window_flash_roofline").read(m) \
        == pytest.approx(25.0)
    assert _load("layer_metrics", "gqa_flash_roofline").read(m) \
        == pytest.approx(50.0)
    # the two-sweep backward's dq call is the window layers' too
    trace["devices"][0]["op_ns"]["mosaic:pt_flash_win_bwd_dq"] = 4e6
    assert _load("layer_metrics", "window_flash_ms").read(m) \
        == pytest.approx(10.0)


# -- BENCHMARK.json's entries of PR 53 ----------------------------------------

def test_benchmark_entries():
    """By name, not by position, and `<=`: a later PR appends cells and
    metrics after these and may append this cell to further lists."""
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="train_s16k_b1_ep4",
                        chips=1)
    assert len(cell["why"]) <= 200
    for word in ("16384", "EP-4", "2048 rows", "8192 deployed", "4x"):
        assert word in cell["why"], word
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    job = json.load(open(os.path.join(BENCH, "traffic",
                                      "train_s16k_b1_ep4.json")))
    assert (job["kind"], job["batch"], job["seq_len"],
            job["rate_metric"], job["reduced"]) == (
        "train_steps", 1, 16384, "tokens_per_s", {})
    # 131,072 pairs a step: 2,048 rows a held expert under a uniform
    # router
    config = _config()
    pairs = job["batch"] * job["seq_len"] * config["num_experts_per_tok"]
    assert pairs == 131072
    assert pairs // config["num_experts_published"] == 2048
    reports = {e["name"] for e in spec["per_layer"]
               if CELL in e.get("workloads", ())}
    assert {
        "feed_wait_ms", "enqueue_ms", "step_p50_ms", "device_idle_pct",
        "mfu_pct", "step_hbm_gb", "build_s", "compile_s", "first_call_s",
        "matmul_ms", "flash_fwd_ms", "flash_bwd_ms", "run_prepare_ms",
        "run_fetch_ms", "feed_put_ms", "feed_put_in_run_ms",
        "other_fusion_ms", "copy_ms", "gqa_flash_roofline", "moe_gmm_ms",
        "moe_gmm_roofline", "moe_gmm_tile_us", "moe_gmm_roofline_live",
        "moe_live_tiles", "moe_live_tiles_window", "moe_combine_ms",
        "step_trace_s", "step_lower_s", "step_compile_s",
        "window_flash_ms", "window_flash_roofline"} <= reports
    # no scan, no convolution, no latent attention, no token-major-only
    # flash metric
    assert not {n for n in reports if n.startswith((
        "ssd_", "kda_", "mla_", "conv", "gated_", "mhc_"))}
    assert "flash_roofline" not in reports and "flash_ms" not in reports
    for name, unit, better in (("window_flash_ms", "ms", "lower"),
                               ("window_flash_roofline", "%", "higher")):
        e = next(e for e in spec["per_layer"] if e["name"] == name)
        assert e == dict(e, layer="kernels", moves="tokens_per_s",
                         source="device_trace", unit=unit, better=better)
        # where its reader finds something to read: this cell, and no
        # cell without a window layer
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
    # every share of a roofline or of a peak that moves tokens_per_s and
    # that this cell's kernels feed is reported here
    assert {"mfu_pct", "gqa_flash_roofline", "moe_gmm_roofline",
            "moe_gmm_roofline_live", "window_flash_roofline"} <= reports
