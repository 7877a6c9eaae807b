"""The always-on step record (observability/step_record.py) and the
stable device-side kernel names (ISSUE 24).  No test here asserts a
duration: stamps are checked for order only."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu.core.scope import Scope
from paddle_tpu.observability import (device_trace, metrics, step_record,
                                      tracing)
from paddle_tpu.reader import DeviceFeeder

RUN_STAMPS = ["enter", "feeds", "state", "key", "built", "conformed",
              "dispatched", "committed", "returned", "done"]


@pytest.fixture(autouse=True)
def _empty_ring():
    step_record.clear()
    yield
    step_record.clear()


def _tiny_program():
    x = layers.data("x", shape=[4], dtype="float32")
    loss = layers.mean(layers.fc(x, size=3))
    optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    return exe, compiled, loss


def _feed():
    return {"x": np.ones((2, 4), np.float32)}


def test_five_runs_leave_five_ordered_run_records():
    exe, compiled, loss = _tiny_program()
    exe.run(fluid.default_startup_program())
    fetched = [True, False, True, True, False]
    for f in fetched:
        exe.run(compiled, feed=_feed(), fetch_list=[loss], return_numpy=f)
    recs = step_record.records("run")
    assert len(recs) == 5
    assert [r["first_call"] for r in recs] == [True] + [False] * 4
    assert [r["fetched"] for r in recs] == fetched
    assert {r["program"] for r in recs} == {id(compiled)}
    assert [r["seq"] for r in recs] == sorted(r["seq"] for r in recs)
    for r in recs:
        stamps = [r[k] for k in RUN_STAMPS]
        assert stamps == sorted(stamps), r
        assert (r["built"] == r["key"]) == (not r["first_call"])
        assert (r["returned"] == r["committed"]) == (not r["fetched"])
    # the startup program is a plain Program: Executor interprets it,
    # CompiledProgram._run never sees it
    assert step_record.records("put") == []


def test_raising_step_still_leaves_its_record():
    exe, compiled, loss = _tiny_program()
    # no startup program run in this scope: the state walk raises
    with pytest.raises(RuntimeError, match="uninitialized"):
        exe.run(compiled, feed=_feed(), fetch_list=[loss], scope=Scope())
    (rec,) = step_record.records("run")
    assert rec["enter"] <= rec["feeds"]
    assert "state" not in rec and "returned" not in rec
    assert rec["feeds"] <= rec["done"]
    assert rec["first_call"] is False and rec["fetched"] is True


def test_step_seconds_histogram_observes_dispatched_minus_conformed():
    exe, compiled, loss = _tiny_program()
    exe.run(fluid.default_startup_program())
    hist = metrics.registry().get("paddle_tpu_executor_step_seconds")

    def count_sum():
        series = [s for _, s in hist.items()]
        return (sum(s["count"] for s in series),
                sum(s["sum"] for s in series))

    c0, s0 = count_sum()
    for _ in range(3):
        exe.run(compiled, feed=_feed(), fetch_list=[loss])
    c1, s1 = count_sum()
    recs = step_record.records("run")
    assert c1 - c0 == 3
    want = sum(r["dispatched"] - r["conformed"] for r in recs) * 1e-9
    assert s1 - s0 == pytest.approx(want, rel=1e-6)


def test_feeder_leaves_one_put_record_a_batch():
    n = 5
    batches = [{"a": np.zeros((i + 1, 3), np.float32),
                "b": np.zeros((i + 1,), np.int64)} for i in range(n)]
    got = list(DeviceFeeder(iter(batches), capacity=2))
    assert len(got) == n
    puts = step_record.records("put")
    assert len(puts) == n
    assert [p["bytes"] for p in puts] == \
        [(i + 1) * 3 * 4 + (i + 1) * 8 for i in range(n)]
    consumer = {r["thread"] for r in step_record.records("next")}
    for p in puts:
        assert p["start"] <= p["end"]
        assert p["host_wait"] >= 0 and p["dev_wait"] >= 0
        assert p["thread"] not in consumer     # the transfer thread
    nexts = step_record.records("next")
    assert len(nexts) == n + 1                 # the last one met END
    assert all(r["start"] <= r["done"] for r in nexts)


def test_ring_is_bounded_and_reads_are_copies():
    for i in range(step_record.MAXLEN + 10):
        step_record.Record("run", i=i).done()
    recs = step_record.records()
    assert len(recs) == step_record.MAXLEN
    assert recs[0]["i"] == 10 and recs[-1]["i"] == step_record.MAXLEN + 9
    recs[0]["i"] = -1                          # a copy, not the ring's
    assert step_record.records()[0]["i"] == 10
    assert step_record.records("put") == []
    step_record.clear()
    assert step_record.records() == []


# ---------------------------------------------------------------------------
# the first call's parts (ISSUE 51)
# ---------------------------------------------------------------------------

PARTS = ("trace_ns", "lower_ns", "compile_ns")


def test_first_call_record_holds_its_three_parts():
    """The call that misses the jit cache says what it spent tracing,
    lowering and compiling, the three disjoint and inside `dispatched -
    conformed`; a call that hits the cache holds none; a `.lower()`
    from outside a run runs `step`'s body again and writes nowhere."""
    exe, compiled, loss = _tiny_program()
    exe.run(fluid.default_startup_program())
    for _ in range(3):
        exe.run(compiled, feed=_feed(), fetch_list=[loss])
    first, *later = step_record.records("run")
    assert first["first_call"] is True
    assert all(isinstance(first[k], int) and first[k] > 0 for k in PARTS)
    assert first["cache_hit"] is False     # conftest keeps the cache off
    assert sum(first[k] for k in PARTS) \
        <= first["dispatched"] - first["conformed"]
    for rec in later:
        assert rec["first_call"] is False
        assert not {"cache_hit", *PARTS} & set(rec)
    # a second shape is a second first call, with parts of its own
    exe.run(compiled, feed={"x": np.ones((3, 4), np.float32)},
            fetch_list=[loss])
    again = step_record.records("run")[-1]
    assert again["first_call"] is True and again["trace_ns"] > 0

    before = step_record.records()
    traced = []
    import paddle_tpu.core.compiler as compiler_mod
    real = compiler_mod._run_block_symbolic

    def spy(*a, **kw):
        traced.append(1)
        return real(*a, **kw)

    compiler_mod._run_block_symbolic = spy
    try:
        step, = [fn for key, fn in compiled._cache.items()
                 if callable(fn) and key[0][0][1] == (2, 4)]
        state = {n: jax.ShapeDtypeStruct(np.shape(v), v.dtype)
                 for n, v in ((n, fluid.global_scope().find_var(n).get())
                              for n in compiled._persistable_names)}
        # other avals than the run's: jit traces the body anew
        step.lower(state, {"x": jax.ShapeDtypeStruct((5, 4), jnp.float32)})
    finally:
        compiler_mod._run_block_symbolic = real
    assert traced == [1]
    assert step_record.records() == before


def test_first_call_parts_listen_on_their_own_thread_only():
    """The listeners add what fires on the thread of the first call
    under way, outside its trace: another thread's compile, and a
    compile inside the trace (the trace's own time), add nothing."""
    import threading

    rec = step_record.Record("run", first_call=True)
    seen = {}

    def fn():
        step_record._on_duration(
            "/jax/core/compile/backend_compile_duration", 2.0)
        step_record._on_duration(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.5)
        step_record._on_duration("/jax/core/compile/other", 9.0)
        step_record._on_event("/jax/compilation_cache/cache_hits")
        t = threading.Thread(target=step_record._on_duration, args=(
            "/jax/core/compile/backend_compile_duration", 7.0))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        with step_record.first_call_trace():
            step_record._on_duration(
                "/jax/core/compile/backend_compile_duration", 3.0)
        seen.update(rec.fields)
        return "out"

    assert step_record.first_call(rec, fn)() == "out"
    assert seen["compile_ns"] == 2_000_000_000
    assert seen["lower_ns"] == 500_000_000
    assert seen["cache_hit"] is True and seen["trace_ns"] > 0
    # after the call: nothing listens, nothing is written
    step_record._on_duration(
        "/jax/core/compile/backend_compile_duration", 5.0)
    with step_record.first_call_trace():
        pass
    assert rec.fields["compile_ns"] == 2_000_000_000
    assert rec.fields["trace_ns"] == seen["trace_ns"]


def test_run_phases_are_annotated_once_each_under_the_grammar(
        monkeypatch):
    """_run is cut into phases once: the stamps and the profiler
    annotations come from the same calls, every annotation is closed,
    none carries the harness's `bm:` prefix or a colon."""
    opened, closed = [], []

    class Spy:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            closed.append(self.name)

    monkeypatch.setattr(
        device_trace, "session_annotation",
        lambda kernel, trace_id=None:
        Spy(device_trace.annotation_name(kernel, trace_id)))
    exe, compiled, loss = _tiny_program()
    exe.run(fluid.default_startup_program())
    exe.run(compiled, feed=_feed(), fetch_list=[loss])
    want = ["pt#executor.%s#-" % p
            for p in ("prepare", "dispatch", "commit", "fetch")]
    assert opened == want and closed == want
    del opened[:], closed[:]
    exe.run(compiled, feed=_feed(), fetch_list=[loss],
            return_numpy=False)
    assert opened == want[:3] and closed == want[:3]
    del opened[:], closed[:]
    list(DeviceFeeder(iter([{"a": np.zeros(2, np.float32)}])))
    assert opened == ["pt#feeder.put#-"] == closed
    for name in want + ["pt#feeder.put#-"]:
        assert ":" not in name
        assert device_trace.parse_annotation(name) is not None


# ---------------------------------------------------------------------------
# stable device-side kernel names
# ---------------------------------------------------------------------------

def _pallas_names(jaxpr, out=None):
    """Names of every pallas_call equation, nested jaxprs included."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_names(sub, out)
    return out


def _flash_fwd():
    from paddle_tpu.ops.pallas_kernels import flash_attention

    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    return lambda q: flash_attention(q, q, q, causal=True,
                                     impl="interpret"), (q,)


def _flash_grad():
    f, args = _flash_fwd()
    return jax.grad(lambda q: f(q).sum()), args


def _conv_ep():
    from paddle_tpu.ops.pallas_conv import conv2d_epilogue

    x = jnp.ones((1, 8, 8, 8), jnp.float32)
    w = jnp.ones((16, 8, 3, 3), jnp.float32)
    return lambda x, w: conv2d_epilogue(
        x, w, None, None, strides=(1, 1), paddings=(1, 1), act="relu",
        impl="interpret"), (x, w)


def _conv_bn_act():
    from paddle_tpu.ops.pallas_conv import conv2d_bn_act

    x = jnp.ones((2, 8, 8, 8), jnp.float32)
    w = jnp.ones((16, 8, 3, 3), jnp.float32)
    g = jnp.ones((16,), jnp.float32)
    return lambda x, w, g: conv2d_bn_act(
        x, w, g, g, strides=(1, 1), paddings=(1, 1), act="relu",
        impl="interpret"), (x, w, g)


def _fc_ep():
    from paddle_tpu.ops.epilogue import fc_epilogue

    x = jnp.ones((16, 128), jnp.float32)
    w = jnp.ones((128, 128), jnp.float32)
    b = jnp.ones((128,), jnp.float32)
    return lambda x, w, b: fc_epilogue(x, w, b, None, act="relu",
                                       impl="interpret"), (x, w, b)


def _flash_decode():
    from paddle_tpu.ops.pallas_kernels import flash_decode

    q = jnp.ones((2, 2, 64), jnp.float32)
    pages = jnp.ones((4, 2, 16, 64), jnp.float32)
    tables = jnp.array([[0, 1], [2, 3]], jnp.int32)
    lens = jnp.array([20, 9], jnp.int32)
    return lambda q, p: flash_decode(q, p, p, tables, lens,
                                     impl="interpret"), (q, pages)


@pytest.mark.parametrize("make,want", [
    (_flash_fwd, ["pt_flash_fwd"]),
    (_flash_grad, ["pt_flash_fwd", "pt_flash_bwd_dkv"]),
    (_conv_ep, ["pt_conv_ep"]),
    (_conv_bn_act, ["pt_conv_stats", "pt_bn_apply"]),
    (_fc_ep, ["pt_fc_ep"]),
    (_flash_decode, ["pt_flash_decode"]),
], ids=["flash_fwd", "flash_grad", "conv_ep", "conv_bn_act", "fc_ep",
        "flash_decode"])
def test_pallas_calls_carry_their_kernel_name(make, want):
    f, args = make()
    names = _pallas_names(jax.make_jaxpr(f)(*args).jaxpr)
    assert names == want
    for n in names:
        # benchmarks/trace_reduce.group_name strips a trailing [.\d]+
        assert not n[-1].isdigit() and not n.endswith(".")


def test_kernel_name_is_innermost_in_forward_and_backward():
    """The TPU compiler names the Mosaic call after the innermost
    name-stack element: the kernel's name, not jax's transform wrapper
    (`jvp(...)`, `transpose(jvp(...))`), which the outer `pt` scope of
    the kernel entry takes."""
    f, args = _flash_grad()
    text = jax.jit(f).lower(*args).as_text(debug_info=True)
    assert "jvp(pt)" in text and "transpose(jvp(pt))" in text
    for name in ("pt_flash_fwd", "pt_flash_bwd_dkv"):
        assert '%s/pallas_call"' % name in text, name
        assert "(%s)" % name not in text


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_compiled_text_does_not_depend_on_the_tracing_flag(impl):
    from paddle_tpu.ops.pallas_kernels import flash_attention

    q = jnp.ones((1, 1, 128, 64), jnp.float32)

    def text():
        # a fresh function each time: nothing is served from a cache
        return jax.jit(lambda q: flash_attention(
            q, q, q, causal=True, impl=impl)).lower(q).compile().as_text()

    assert tracing.maybe_tracer() is None
    texts = []
    try:
        for flag in (False, True):
            if flag:
                tracing.start_tracing()
            texts.append(text())   # one call line: same source locations
    finally:
        tracing.stop_tracing()
    off, on = texts
    assert "pt_flash_attention" not in on
    assert on == off
