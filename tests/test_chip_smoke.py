"""chip_smoke.py rehearsed without the chip, and the no-fallback rules
it rests on.

Rehearsal (i) of the on-chip-measurement guide: the phases of
chip_smoke.py end to end at tiny size on the CPU, Pallas kernels in
interpret mode.  The steering (the device check, the sizes, interpret
mode, XLA attention inside the compiled step) happens HERE, through
arguments chip_smoke's phases already take — never through an option
of the program.  Rehearsal (ii), the four-chip phase on virtual
devices, is the gspmd case below.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

_ROOT = str(pathlib.Path(__file__).resolve().parents[1])
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

TINY = {
    # off the chip flash_attention's auto-impl is its XLA form, so the
    # compiled step holds no kernel
    "transformer": dict(batch=2, seq=32, steps=3, n_layer=1,
                        custom_calls=0),
    "resnet": dict(batch=2, image=32, steps=2, custom_calls=0),
    "serve": dict(vocab=96, d_model=32, num_heads=2, head_dim=128,
                  page_size=16, n_requests=3, prompt_min=3,
                  prompt_max=20, new_tokens=4),
    "kernels": dict(flash=(1, 2, 128, 64),
                    decode=dict(batch=2, heads=2, head_dim=128,
                                page_size=16, max_pages=2),
                    conv3x3=(1, 8, 8, 8, 8), conv1x1=(1, 8, 8, 8, 16),
                    fc=(64, 32, 128)),
    "gspmd": dict(batch=4, seq=32, steps=2, n_layer=1, dp=2, tp=2,
                  custom_calls=0),
}


@pytest.fixture
def smoke():
    import chip_smoke

    return chip_smoke


@pytest.mark.parametrize("phase", [
    "train_transformer", "train_resnet", "serve_float32",
    "serve_bfloat16", "kernels", "gspmd"])
def test_phase_rehearsal_tiny_cpu(smoke, phase, capsys, monkeypatch):
    watch = smoke.CompileWatch()
    if phase == "train_transformer":
        smoke.phase_train_transformer(TINY["transformer"], watch, "cpu",
                                      flash_impl="xla")
    elif phase == "train_resnet":
        smoke.phase_train_resnet(TINY["resnet"], watch, "cpu")
    elif phase.startswith("serve_"):
        smoke.phase_serve(TINY["serve"], phase[len("serve_"):], watch,
                          impl="interpret", expect="interpret")
    elif phase == "kernels":
        smoke.phase_kernels(TINY["kernels"], watch, impl="interpret",
                            expect="interpret")
    else:
        # rehearsal (ii): dp2 x tp2 over four of the virtual devices
        import jax

        build = smoke.build_gspmd_transformer
        monkeypatch.setattr(
            smoke, "build_gspmd_transformer",
            lambda cfg, sharded: build(cfg, sharded, jax.devices()[:4]))
        smoke.phase_gspmd(TINY["gspmd"], watch, "cpu", flash_impl="xla")
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.splitlines()]
    assert lines and all("phase" in ln for ln in lines), lines


def test_sharded_step_compiles_once(smoke):
    """A step over a mesh is traced and compiled ONCE: the state the
    startup program made is placed on the declared shardings before
    the first call, so step 2 — fed step 1's outputs, whose avals
    carry the mesh — hits the same executable (the four-chip v5e run
    compiled it twice)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer

    x = layers.data("x", shape=[16], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    loss = layers.mean(layers.square_error_cost(
        layers.fc(x, size=1), y))
    optimizer.Adam(1e-3).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    compiled = fluid.CompiledProgram(
        fluid.default_main_program()).with_data_parallel(
            loss_name=loss.name)
    feed = {"x": np.ones((8, 16), np.float32),
            "y": np.ones((8, 1), np.float32)}
    watch = smoke.CompileWatch()
    exe.run(compiled, feed=feed, fetch_list=[loss])
    after_first = watch.snapshot()
    for _ in range(2):
        exe.run(compiled, feed=feed, fetch_list=[loss])
    assert watch.since(after_first)["compiles"] == 0


def test_failed_impl_expectation_fails_the_phase(smoke):
    """A phase that names the Pallas path and got another fails: off
    the chip every auto-impl is the XLA form."""
    with pytest.raises(smoke.SmokeFailure, match="resolved to"):
        smoke.phase_kernels(TINY["kernels"], smoke.CompileWatch(),
                            impl=None, expect="pallas")


def _run_smoke(args=(), env=None, cwd=_ROOT):
    e = dict(os.environ)
    e.pop("XLA_FLAGS", None)
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, env=e, cwd=cwd, timeout=300)


def test_no_accelerator_exits_nonzero_and_prints_no_result():
    r = _run_smoke(env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    verdict = [json.loads(ln) for ln in r.stderr.splitlines()
               if ln.startswith('{"ok"')]
    assert verdict and verdict[-1]["ok"] is False
    assert "no accelerator" in verdict[-1]["error"]


def test_last_line_contract(smoke, monkeypatch, capsys):
    """ok + device.platform/kind/count and no other key, as the last
    stdout line; the device as JAX reports it."""
    import jax

    monkeypatch.setattr(
        smoke, "phase_device", lambda count: {
            "platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices())})
    monkeypatch.setattr(smoke, "run", lambda *a, **k: None)
    assert smoke.main([]) == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    assert list(last) == ["ok", "device"]
    assert list(last["device"]) == ["platform", "kind", "count"]


def test_failing_phase_is_a_failed_run(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "phase_device", lambda count: {
        "platform": "cpu", "kind": "cpu", "count": 1})

    def boom(*a, **k):
        raise smoke.SmokeFailure("phase x did not hold")

    monkeypatch.setattr(smoke, "run", boom)
    with pytest.raises(smoke.SmokeFailure):
        smoke.main([])
    cap = capsys.readouterr()
    assert not any(ln.startswith('{"ok"') for ln in
                   cap.out.splitlines())
    assert json.loads(cap.err.splitlines()[0])["ok"] is False


# -- the compile cache is placed from outside --------------------------------

_CACHE_PROBE = (
    "import jax, paddle_tpu as f\n"
    "calls = []\n"
    "orig = jax.config.update\n"
    "jax.config.update = lambda k, v: (calls.append(k), orig(k, v))[1]\n"
    "d = f.enable_compile_cache()\n"
    "print(repr((d, 'jax_compilation_cache_dir' in calls,\n"
    "            jax.config.jax_compilation_cache_dir)))\n")


@pytest.fixture(scope="module")
def cache_probes(tmp_path_factory):
    """The three cases, each in a process of its own (conftest keeps
    the cache off in this one), started together."""
    given = str(tmp_path_factory.mktemp("cache") / "x")
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base.update(JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    envs = {"given": dict(base, JAX_COMPILATION_CACHE_DIR=given),
            "default": base, "default_again": base}
    procs = {k: subprocess.Popen(
        [sys.executable, "-c", _CACHE_PROBE], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, env=e, cwd=_ROOT)
        for k, e in envs.items()}
    out = {"given_dir": given}
    for k, p in procs.items():
        stdout, stderr = p.communicate(timeout=300)
        assert p.returncode == 0, stderr[-2000:]
        out[k] = eval(stdout.strip().splitlines()[-1])
    return out


def test_cache_dir_from_outside_sets_no_directory_in_code(cache_probes):
    given = cache_probes["given_dir"]
    assert cache_probes["given"] == (given, False, given)


def test_cache_dir_default_is_the_checkout(cache_probes):
    want = os.path.join(_ROOT, ".jax_cache")
    assert cache_probes["default"] == (want, True, want)


def test_cache_dir_default_is_the_same_from_two_processes(cache_probes):
    assert cache_probes["default_again"] == cache_probes["default"]


def test_cache_off_means_no_cache_dir():
    """tests/conftest.py keeps the cache off for the CPU suite, and the
    resolver says so instead of naming a directory nothing uses."""
    import paddle_tpu as fluid

    assert fluid.compile_cache_dir() is None
    assert fluid.enable_compile_cache() is None


# -- no silent fallback -------------------------------------------------------

def test_on_tpu_lets_a_backend_failure_through(monkeypatch):
    from paddle_tpu.ops import pallas_kernels as pk

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(pk.jax, "devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        pk._on_tpu()


def test_flash_lse_never_picks_interpret_by_itself():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import flash_attention_lse

    q = jnp.zeros((1, 1, 128, 64), jnp.float32)
    with pytest.raises(ValueError, match="'pallas' or 'interpret'"):
        flash_attention_lse(q, q, q, impl=None)


def test_benchmark_without_a_chip_exits_2_and_prints_nothing():
    """The one benchmark's "no chip, no number": benchmarks/run.py on
    the CPU exits 2 before it builds anything, with an empty stdout."""
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks", "run.py"),
         "--workload", "tfm_base_train_s512", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=e, cwd=_ROOT, timeout=300)
    assert r.returncode == 2, r.stderr[-400:]
    assert r.stdout == ""
    assert "no accelerator" in r.stderr


# -- one benchmark, one record ------------------------------------------------

# what the tree no longer holds: the root benchmark script, its chip
# rows under docs/, the CPU perf gate and its baseline.  Spelled in
# pieces so that this file does not name them.
_GONE = [r"(?<!\w)bench" + r"\.py", "bench" + "_onchip",
         "perf" + "_sentinel", "perf_baseline" + "_cpu"]
# the histories may name what went; the driver's root records are not
# the builder's; benchmarks/ is the benchmark's own to edit
_HISTORIES = {"CHANGES.md", "ROADMAP.md", "PERF.md", "ISSUE.md"}
_DRIVERS = ("BENCH_r0", "MULTICHIP_r0", "BASELINE.", "PROGRESS.jsonl",
            "COPYCHECK.json", "PERF_LEDGER.jsonl")
_SCANNED_DIRS = ("paddle_tpu", "tools", "tests", "docs", "examples",
                 ".claude")


def _builder_files():
    for name in sorted(os.listdir(_ROOT)):
        path = os.path.join(_ROOT, name)
        if os.path.isfile(path) and name not in _HISTORIES \
                and not name.startswith(_DRIVERS):
            yield path
    for d in _SCANNED_DIRS:
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(_ROOT, d)):
            dirnames[:] = [x for x in dirnames if x != "__pycache__"]
            for fn in sorted(filenames):
                yield os.path.join(dirpath, fn)


def test_no_file_names_the_old_benchmark_or_the_cpu_perf_gate():
    import re

    gone = re.compile("|".join(_GONE))
    hits = []
    for path in _builder_files():
        try:
            with open(path, encoding="utf-8") as f:
                text = f.read()
        except UnicodeDecodeError:
            continue                       # a binary: names nothing
        hits += ["%s:%d: %s" % (os.path.relpath(path, _ROOT), n,
                                line.strip()[:100])
                 for n, line in enumerate(text.splitlines(), 1)
                 if gone.search(line)]
    assert not hits, "\n".join(hits[:40])
