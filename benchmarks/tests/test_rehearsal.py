"""Every loop and builder end to end on the CPU through the harness's
library entry, at tiny sizes.  The test writes its own configuration,
job and BENCHMARK.json files into a temporary benchmark root (run.py
has no size or platform option), copies the benchmark's code beside
them, and adds one configuration, one job and one per-layer metric of
its own: files and entries are enough, no code of the harness changes.

Nothing here is a measurement: a CPU run gives no time, rate or
utilization worth writing down.
"""

import io
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, CHECKOUT

CODE = ("harness.py", "observe.py", "trace_reduce.py", "flops.py",
        "builders", "kinds", "reference", "layer_metrics")

TINY_TFM = {
    "builder": "transformer_lm", "reference": "transformer_lm",
    "n_layer": 2, "d_model": 64, "d_inner": 128, "n_head": 2,
    "vocab_size": 128, "dropout_rate": 0.0, "label_smooth_eps": 0.0,
    "amp": True, "learning_rate": 1e-3, "param_prefix": "tfm",
    # off the chip the kernel entry resolves to its XLA form
    "kernel_impls": {"flash_attention": "xla"},
    "reference_rtol": 2e-2,
}
TINY_RN = {
    "builder": "resnet", "reference": "resnet", "depth": 18,
    "image_size": 64, "num_classes": 10, "momentum": 0.9,
    "weight_decay": 1e-4, "learning_rate": 0.01, "nhwc": True,
    "amp": True, "reference_rtol": 2e-2,
}
JOB = {"kind": "train_steps"}
JOBS = {
    "tiny_seq": dict(JOB, batch=4, seq_len=16, rate_metric="tokens_per_s"),
    "tiny_sharded": dict(JOB, batch=8, seq_len=16,
                         mesh={"dp": 2, "tp": 2},
                         rate_metric="tokens_per_s"),
    # batch norm over a handful of samples amplifies bf16 rounding: 16
    # images of 64^2 leave 64 samples a channel in the last stage
    "tiny_images": dict(JOB, batch=16, rate_metric="images_per_s"),
    # the job a later PR would add as a file of its own
    "tiny_added": dict(JOB, batch=2, seq_len=8,
                       rate_metric="tokens_per_s"),
}
CELLS = [
    ("c_seq", "tiny-tfm", "tiny_seq", 4),
    ("c_sharded", "tiny-tfm", "tiny_sharded", 4),
    ("c_images", "tiny-rn", "tiny_images", 4),
    ("c_added", "tiny-tfm-added", "tiny_added", 4),
]
TOKENS = ["c_seq", "c_sharded", "c_added"]


def _metric(name, unit, source, layer, moves, workloads=None):
    m = {"name": name, "unit": unit, "better": "lower", "source": source,
         "layer": layer, "moves": moves}
    if workloads:
        m["workloads"] = workloads
    return m


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "benchmarks"
    bench.mkdir()
    for name in CODE:
        src = os.path.join(BENCH, name)
        (shutil.copytree if os.path.isdir(src) else shutil.copy)(
            src, bench / name)
    # the CPU is no device of the real peaks table: a table of the
    # test's own, so that nothing here reads as a chip's number
    (bench / "peaks.json").write_text(json.dumps({
        "source": "made up for the CPU rehearsal",
        "kinds": {"cpu": {"bf16_flops_per_s": 1e12,
                          "hbm_bytes_per_s": 1e11}}}))
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    configs = {"tiny-tfm": TINY_TFM, "tiny-rn": TINY_RN,
               "tiny-tfm-added": dict(TINY_TFM, n_layer=1)}
    for name, cfg in configs.items():
        (bench / "configs" / (name + ".json")).write_text(json.dumps(cfg))
    for name, job in JOBS.items():
        (bench / "traffic" / (name + ".json")).write_text(json.dumps(job))
    # the per-layer metric a later PR would add as a file of its own
    (bench / "layer_metrics" / "steps_counted.py").write_text(
        "def read(m):\n    return len(m['clocks']['step_s'])\n")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": n, "source": "test", "reduced": [],
                     "file": "benchmarks/configs/%s.json" % n,
                     "why": "test"} for n in configs],
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": k,
                       "why": "test"} for n, c, t, k in CELLS],
        "end_to_end": [
            {"name": "tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": TOKENS},
            {"name": "images_per_s", "unit": "images/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["c_images"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": [
            _metric("step_p50_ms", "ms", "host_clock", "entry",
                    "tokens_per_s", TOKENS),
            _metric("step_p50_ms.img", "ms", "host_clock", "entry",
                    "images_per_s", ["c_images"]),
            _metric("enqueue_ms", "ms", "program_counter", "entry",
                    "tokens_per_s", TOKENS),
            _metric("feed_wait_ms", "ms", "host_clock", "input",
                    "tokens_per_s", TOKENS),
            _metric("mfu_pct", "%", "host_clock", "device",
                    "tokens_per_s", TOKENS),
            _metric("step_hbm_gb", "GB", "program_counter", "device",
                    "tokens_per_s", TOKENS),
            _metric("build_s", "s", "host_clock", "build and compile",
                    "setup_s"),
            _metric("compile_s", "s", "program_counter",
                    "build and compile", "setup_s"),
            # device-trace metrics: a CPU trace has no device plane, so
            # their readers return nothing and the line leaves them out
            _metric("device_idle_pct", "%", "device_trace", "device",
                    "tokens_per_s", TOKENS),
            _metric("flash_ms", "ms", "device_trace", "kernels",
                    "tokens_per_s", TOKENS),
            _metric("flash_roofline", "%", "device_trace", "kernels",
                    "tokens_per_s", TOKENS),
            _metric("matmul_ms", "ms", "device_trace", "kernels",
                    "tokens_per_s", TOKENS),
            _metric("conv_ms", "ms", "device_trace", "kernels",
                    "images_per_s", ["c_images"]),
            _metric("collective_ms", "ms", "device_trace", "sharding",
                    "tokens_per_s", ["c_sharded"]),
            _metric("collective_exposed_ms", "ms", "device_trace",
                    "sharding", "tokens_per_s", ["c_sharded"]),
            _metric("steps_counted", "steps", "host_clock", "entry",
                    "tokens_per_s", ["c_added"]),
        ]}))
    return str(root)


def _run(root, cell, trace):
    import harness

    out = io.StringIO()
    result = harness.run_cell(root, cell, seed=3, seconds=0.5,
                              trace=trace, platform="cpu", out=out)
    lines = out.getvalue().strip().splitlines()
    assert json.loads(lines[-1]) == json.loads(json.dumps(result))
    return result, [json.loads(x) for x in lines[:-1]]


@pytest.mark.parametrize("cell", [c[0] for c in CELLS])
def test_end_to_end_line(root, cell):
    result, earlier = _run(root, cell, trace=0)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] >= 1
    rate = "images_per_s" if cell == "c_images" else "tokens_per_s"
    assert set(result["metrics"]) == {rate, "setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 4
    # the same seed gives the same losses
    again, _ = _run(root, cell, trace=0)
    rec = os.path.join(root, "benchmarks", "out",
                       "%s.seed3.trace0.json" % cell)
    record = json.load(open(rec))
    assert record["losses"]["warmup"] == next(
        e for e in earlier if e.get("event") == "losses")["warmup"]
    # the step's bytes and text came from the executable that ran
    assert record["memory"]["recompiled"] == 0
    assert record["memory"]["step_bytes"] == \
        result["device"]["memory_peak_bytes"]


@pytest.mark.parametrize("cell", [c[0] for c in CELLS])
def test_per_layer_line(root, cell):
    result, _ = _run(root, cell, trace=1)
    assert result["correct"]
    got = set(result["metrics"])
    suffix = ".img" if cell == "c_images" else ""
    want = {"step_p50_ms" + suffix, "build_s", "compile_s"}
    if cell != "c_images":
        want |= {"enqueue_ms", "feed_wait_ms", "mfu_pct", "step_hbm_gb"}
    if cell == "c_added":
        want.add("steps_counted")
        assert result["metrics"]["steps_counted"]["value"] == \
            result["attempted"]
    # no device plane in a CPU trace: no device metric, no busy_s
    assert got == want
    assert "busy_s" not in result["device"]
    assert not os.path.exists(os.path.join(
        root, "benchmarks", "out", "_trace_" + cell))


def test_sharded_state_is_checked(root):
    _, earlier = _run(root, "c_sharded", trace=0)
    checks = next(e for e in earlier if e.get("event") == "correctness")
    assert checks["checks"]["on_declared_sharding"] is True
    assert "flash_attention_gspmd:shard_map" in checks["kernel_impls"]


def test_wrong_chip_count_is_refused(root):
    import harness

    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(spec_path))
    spec["workloads"].append({"name": "c_one", "config": "tiny-tfm",
                              "traffic": "tiny_seq", "chips": 1,
                              "why": "test"})
    json.dump(spec, open(spec_path, "w"))
    with pytest.raises(harness.Refused, match="needs 1 chip"):
        _run(root, "c_one", trace=0)
    with pytest.raises(harness.Refused, match="no workload named"):
        _run(root, "no_such_cell", trace=0)


def test_run_py_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tfm_base_train_s512", "--seed", "0", "--seconds", "1",
         "--trace", "0"], cwd=CHECKOUT, env=env, capture_output=True,
        text=True, timeout=600)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(line.startswith('{"correct"')
                   for line in p.stdout.splitlines())
