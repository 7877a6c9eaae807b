"""flash_attention saves Out and LSE; flash_attention_grad reads them.

The forward kernel runs once a layer (ISSUE 25): the forward op writes
its per-row log-sum-exp as an IR variable, `append_backward` binds it
and Out on the registered grad op, and that op calls the two backward
kernels directly.  A grad op that cannot see both slots, or an impl
with no kernel, keeps `jax.vjp` over the forward.

All on the CPU: the kernels in interpret mode, which a test asks for by
steering `_auto_impl` (the program has no option for it), or the XLA
form.
"""

import collections
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework, layers, optimizer
from paddle_tpu.backward import append_backward
from paddle_tpu.core.compiler import _TraceEnv, _run_block_symbolic
from paddle_tpu.core.program import Program
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.flags import set_flags
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.parallel import env as penv

# the one backward sweep carries the dk/dv sweep's name (it is that
# sweep, now writing dq too); pt_flash_bwd_dq is the two-sweep fallback's
KERNELS = ("pt_flash_fwd", "pt_flash_bwd_dkv")
GRADS = ["q@GRAD", "k@GRAD", "v@GRAD"]


@pytest.fixture
def interpret(monkeypatch):
    """What the chip's `pallas` is to the IR op, on the CPU."""
    monkeypatch.setattr(pk, "_auto_impl", lambda: "interpret")


def _impl_counts():
    c = pk._M_KERNEL_IMPL
    return collections.Counter(
        {(lbl["kernel"], lbl["impl"]): int(v) for lbl, v in c.items()})


def _since(before):
    now = _impl_counts()
    return {k: now[k] - before[k] for k in now if now[k] - before[k]}


def _feed(b=2, h=2, tq=32, tk=32, d=8, seed=0):
    rng = np.random.RandomState(seed)
    return {"q": rng.randn(b, h, tq, d).astype(np.float32),
            "k": rng.randn(b, h, tk, d).astype(np.float32),
            "v": rng.randn(b, h, tk, d).astype(np.float32)}


def _attention_net(feed, n_layers=1, segmented=False, **kw):
    """n_layers flash_attention ops chained through Q, a scalar loss,
    and the backward `append_backward` builds; segmented: a scale after
    every layer, its output a recompute checkpoint.  Returns the
    program."""
    x = None
    for name, val in feed.items():
        var = layers.data(name, shape=list(val.shape[1:]),
                          dtype="float32")
        var.stop_gradient = False
        x = var if name == "q" else x
    k, v = (framework.default_main_program().global_block().var(n)
            for n in ("k", "v"))
    checkpoints = []
    for _ in range(n_layers):
        x = layers.flash_attention(x, k, v, **kw)
        if segmented:
            x = layers.scale(x, scale=1.5)
            checkpoints.append(x)
    loss = layers.mean(layers.square(x))
    append_backward(loss, checkpoints=checkpoints or None)
    return framework.default_main_program()


def _run(prog, feed, fetch):
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        return exe.run(fluid.CompiledProgram(prog), feed=feed,
                       fetch_list=list(fetch))


def _pallas_eqns(fn, *args):
    """The pallas_call equations in the jaxpr of fn(*args), nested
    jaxprs (jit, checkpoint, shard_map, custom_vjp) included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple))
                            else [val]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _pallas_calls(fn, *args):
    """{pallas_call name: count} in the jaxpr of fn(*args)."""
    return dict(collections.Counter(
        eqn.params["name"] for eqn in _pallas_eqns(fn, *args)))


def _pallas_grids(fn, *args):
    """The grid of every pallas_call in the jaxpr of fn(*args)."""
    return [tuple(eqn.params["grid_mapping"].grid)
            for eqn in _pallas_eqns(fn, *args)]


def _kernel_calls(prog, feed):
    """{pallas_call name: count} in the jaxpr of the whole block."""
    def step(feeds):
        env = _TraceEnv()
        env.update(feeds)
        _run_block_symbolic(prog, 0, env)
        return [env[g] for g in GRADS]

    return _pallas_calls(step, feed)


def _unbind_saved(prog):
    """The grad ops as a program from before the slots has them."""
    n = 0
    for op in prog.global_block().ops:
        if op.type == "flash_attention_grad":
            del op.inputs["Out"], op.inputs["LSE"]
            n += 1
    return n


def _plain_lse(feed, causal):
    q, k = jnp.asarray(feed["q"]), jnp.asarray(feed["k"])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    if causal:
        tq, tk = s.shape[-2:]
        keep = (jnp.arange(tq)[:, None] + (tk - tq)
                >= jnp.arange(tk)[None, :])
        s = jnp.where(keep, s, -1e30)
    return np.asarray(jax.nn.logsumexp(s, axis=-1))


# -- (a) the forward kernel runs once a layer -------------------------------

@pytest.mark.parametrize("n_layers", [1, 3])
def test_one_forward_kernel_a_layer(interpret, n_layers):
    feed = _feed()
    prog = _attention_net(feed, n_layers=n_layers, causal=True)
    gops = [op for op in prog.global_block().ops
            if op.type == "flash_attention_grad"]
    assert len(gops) == n_layers
    assert all(op.inputs.get("Out") and op.inputs.get("LSE")
               for op in gops)
    assert _kernel_calls(prog, feed) == dict.fromkeys(KERNELS, n_layers)
    # and what it was before: the vjp runs the forward kernel again
    assert _unbind_saved(prog) == n_layers
    calls = _kernel_calls(prog, feed)
    assert calls == {"pt_flash_fwd": 2 * n_layers,
                     "pt_flash_bwd_dkv": n_layers}


# -- (b) same numbers -------------------------------------------------------

@pytest.mark.parametrize("shape,kw", [
    (dict(tq=32, tk=32), dict(causal=False)),
    (dict(tq=32, tk=32), dict(causal=True, block_q=16, block_k=16)),
    (dict(tq=16, tk=48), dict(causal=True, block_q=8, block_k=16)),
    (dict(tq=40, tk=40), dict(causal=True, block_q=16, block_k=16)),
], ids=["full", "causal", "tq_ne_tk", "padded"])
def test_saved_equals_recompute_bit_for_bit(interpret, shape, kw):
    feed = _feed(**shape)
    prog = _attention_net(feed, **kw)
    before = _impl_counts()
    saved = _run(prog, feed, GRADS)
    # a causal entry says whether its grid has a step above the
    # diagonal (ISSUE 48): 16 rows on 48 keys at blocks of 8 / 16 has
    # none; a call that is not causal says nothing
    fetch = () if not kw["causal"] else (
        "flash_attention_causal_fetch",
        "all_live" if shape["tq"] < shape["tk"] else "held")
    assert _since(before) == {("flash_attention", "interpret"): 1,
                              ("flash_attention_grad", "saved"): 1,
                              ("flash_attention_bwd", "fused"): 1,
                              ("flash_attention_layout", "head_major"): 2,
                              **({fetch: 2} if fetch else {})}
    _unbind_saved(prog)
    before = _impl_counts()
    recomputed = _run(prog, feed, GRADS)
    assert _since(before) == {("flash_attention", "interpret"): 2,
                              ("flash_attention_grad", "recompute"): 1,
                              ("flash_attention_bwd", "fused"): 1,
                              ("flash_attention_layout", "head_major"): 2,
                              **({fetch: 3} if fetch else {})}
    for name, a, b in zip(GRADS, saved, recomputed):
        assert np.array_equal(a, b), name
        assert np.abs(a).max() > 0, name


def test_xla_impl_keeps_the_vjp():
    """No kernel, no residual worth saving: the grad op differentiates
    plain attention, bound slots or not, and says so."""
    feed = _feed()
    prog = _attention_net(feed, causal=True)
    before = _impl_counts()
    bound = _run(prog, feed, GRADS)
    assert _since(before)[("flash_attention_grad", "recompute")] == 1
    assert ("flash_attention_grad", "saved") not in _since(before)
    _unbind_saved(prog)
    for a, b in zip(bound, _run(prog, feed, GRADS)):
        assert np.array_equal(a, b)


# -- (c) LSE is the log-sum-exp, on every impl ------------------------------

@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("causal", [False, True])
def test_lse_is_logsumexp_of_the_scores(monkeypatch, impl, causal):
    monkeypatch.setattr(pk, "_auto_impl", lambda: impl)
    feed = _feed(tq=24, tk=40)      # pads under the kernel's blocks
    q, k, v = (layers.data(n, shape=list(a.shape[1:]), dtype="float32")
               for n, a in feed.items())
    layers.flash_attention(q, k, v, causal=causal, block_q=16,
                           block_k=16)
    prog = framework.default_main_program()
    op = prog.global_block().ops[-1]
    lse_var = prog.global_block().var(op.outputs["LSE"][0])
    assert lse_var.stop_gradient and lse_var.dtype == "float32"
    assert tuple(lse_var.shape) == (-1, 2, 24)
    (lse,) = _run(prog, feed, [lse_var.name])
    assert lse.shape == (2, 2, 24) and lse.dtype == np.float32
    np.testing.assert_allclose(lse, _plain_lse(feed, causal),
                               rtol=1e-5, atol=1e-5)


def test_lse_stays_float32_under_amp(interpret):
    from paddle_tpu.contrib import mixed_precision

    feed = _feed()
    q, k, v = (layers.data(n, shape=list(a.shape[1:]), dtype="float32")
               for n, a in feed.items())
    proj = layers.fc(q, size=8, num_flatten_dims=3, bias_attr=False)
    out = layers.flash_attention(proj, k, v, causal=True)
    loss = layers.mean(layers.square(out))
    mixed_precision.decorate(optimizer.SGD(0.1),
                             dest_dtype="bfloat16").minimize(loss)
    prog = framework.default_main_program()
    fwd = next(op for op in prog.global_block().ops
               if op.type == "flash_attention")
    gop = next(op for op in prog.global_block().ops
               if op.type == "flash_attention_grad")
    assert gop.inputs["LSE"] == fwd.outputs["LSE"]
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        exe.run(framework.default_startup_program())
        before = _impl_counts()
        o, lse, _ = exe.run(
            fluid.CompiledProgram(prog), feed=feed,
            fetch_list=[fwd.outputs["Out"][0], fwd.outputs["LSE"][0],
                        loss], return_numpy=False)
    assert _since(before)[("flash_attention_grad", "saved")] == 1
    assert o.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    assert np.isfinite(np.asarray(lse)).all()


# -- (d) programs from before the slot --------------------------------------

def test_program_serialized_without_lse_still_runs(interpret):
    feed = _feed()
    prog = _attention_net(feed, causal=True)
    want = _run(prog, feed, GRADS)
    desc = json.loads(prog.to_bytes())
    block = desc["blocks"][0]
    for op in block["ops"]:
        if op["type"] == "flash_attention":
            lse_name, = op["outputs"].pop("LSE")
        if op["type"] == "flash_attention_grad":
            del op["inputs"]["Out"], op["inputs"]["LSE"]
    block["vars"] = [v for v in block["vars"] if v["name"] != lse_name]
    old = Program.parse_from_bytes(json.dumps(desc).encode())
    assert lse_name not in old.global_block().vars
    from paddle_tpu.analysis.verifier import verify

    assert verify(old, feeds=list(feed)) == []
    for a, b in zip(want, _run(old, feed, GRADS)):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_hand_built_grad_op_without_residuals_runs(monkeypatch, impl):
    from test_ir_verifier import _attention_program

    monkeypatch.setattr(pk, "_auto_impl", lambda: impl)
    prog = _attention_program(batch=2, heads=2, batch_axis="",
                              head_axis="")
    feed = _feed(tq=128, tk=128, d=64)
    # the hand-built op feeds Out itself as Out@GRAD
    (dq,) = _run(prog, feed, ["q@GRAD"])
    q, k, v = (jnp.asarray(feed[n]) for n in "qkv")

    def f(a):
        return pk._plain_attention(a, k, v, False, 64 ** -0.5)

    o, vjp = jax.vjp(f, q)
    np.testing.assert_allclose(dq, np.asarray(vjp(o)[0]), atol=2e-5)


# -- (e) under the dp2 x tp2 tags -------------------------------------------

def test_saved_path_under_shard_map_matches_one_device(interpret):
    from paddle_tpu.parallel.gspmd import MeshPlan
    from paddle_tpu.transpiler import shard_program

    feed = _feed(b=4, h=4, tq=32, tk=32)
    prog = _attention_net(feed, causal=True)
    want = _run(prog, feed, GRADS)
    try:
        set_flags({"gspmd": True})
        compiled = shard_program(
            fluid.CompiledProgram(prog), MeshPlan(dp=2, tp=2),
            devices=jax.devices()[:4])
        tags = [(op.attrs.get("gspmd_batch_axis"),
                 op.attrs.get("gspmd_head_axis"))
                for op in prog.global_block().ops
                if op.type.startswith("flash_attention")]
        assert tags == [("dp", "tp")] * 2
        before = _impl_counts()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            got = exe.run(compiled, feed=feed, fetch_list=GRADS)
        # both ops in shard_map, one forward, the backward from it
        assert _since(before) == {
            ("flash_attention", "interpret"): 1,
            ("flash_attention_grad", "saved"): 1,
            ("flash_attention_bwd", "fused"): 1,
            ("flash_attention_layout", "head_major"): 2,
            ("flash_attention_causal_fetch", "all_live"): 2,
            ("flash_attention_gspmd", "shard_map"): 2}
    finally:
        set_flags({"gspmd": False})
        penv.reset()
    for name, a, b in zip(GRADS, got, want):
        assert np.array_equal(np.asarray(a), b), name


# -- (f) inference programs ---------------------------------------------------

def test_inference_program_ignores_lse(tmp_path, interpret):
    feed = _feed()
    q, k, v = (layers.data(n, shape=list(a.shape[1:]), dtype="float32")
               for n, a in feed.items())
    att = layers.flash_attention(q, k, v, causal=True)
    pred = layers.fc(layers.reshape(att, [-1, 2 * 32 * 8]), size=3)
    loss = layers.mean(layers.square(pred))
    main = framework.default_main_program()
    test_prog = main.clone(for_test=True)
    optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    (want,) = exe.run(test_prog, feed=feed, fetch_list=[pred])
    fluid.io.save_inference_model(str(tmp_path), list(feed), [pred], exe)
    with scope_guard(Scope()):
        prog, feeds, fetches = fluid.io.load_inference_model(
            str(tmp_path), exe)
        assert not any(op.type.endswith("_grad")
                       for op in prog.global_block().ops)
        (got,) = exe.run(prog, feed=feed, fetch_list=fetches)
        (got_c,) = exe.run(fluid.CompiledProgram(prog), feed=feed,
                           fetch_list=fetches)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got_c, want, rtol=1e-5, atol=1e-6)


# -- the counter, on the model the benchmark trains ---------------------------

def test_transformer_step_counts_saved_six_times(interpret):
    from paddle_tpu.models.transformer import transformer_encoder_model

    model = transformer_encoder_model(
        vocab_size=32, max_len=8, d_model=16, n_head=2, d_inner=32,
        n_layer=6, dropout_rate=0.0, param_prefix="tfm")
    optimizer.Adam(1e-3).minimize(model["loss"])
    prog = framework.default_main_program()
    ids = np.random.RandomState(0).randint(0, 32, (2, 8, 1)) \
        .astype(np.int64)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    before = _impl_counts()
    (loss,) = exe.run(fluid.CompiledProgram(prog),
                      feed={"src_ids": ids, "tgt_label": ids},
                      fetch_list=[model["loss"]])
    assert np.isfinite(loss).all()
    # heads of 8 lanes: the op transposes inside (`_flash_layout`)
    assert _since(before) == {("flash_attention", "interpret"): 6,
                              ("flash_attention_grad", "saved"): 6,
                              ("flash_attention_bwd", "fused"): 6,
                              ("flash_attention_layout", "head_major"): 12,
                              ("flash_attention_causal_fetch",
                               "all_live"): 12}


def test_transformer_step_at_the_cells_head_size_is_token_major(interpret):
    """8 heads of 64 lanes, the `tfm_base_*` cells' attention: every
    layer's forward and backward address the heads in place."""
    from paddle_tpu.models.transformer import transformer_encoder_model

    model = transformer_encoder_model(
        vocab_size=32, max_len=16, d_model=512, n_head=8, d_inner=32,
        n_layer=2, dropout_rate=0.0, param_prefix="tfm")
    optimizer.Adam(1e-3).minimize(model["loss"])
    prog = framework.default_main_program()
    ids = np.random.RandomState(0).randint(0, 32, (1, 16, 1)) \
        .astype(np.int64)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    before = _impl_counts()
    (loss,) = exe.run(fluid.CompiledProgram(prog),
                      feed={"src_ids": ids, "tgt_label": ids},
                      fetch_list=[model["loss"]])
    assert np.isfinite(loss).all()
    assert _since(before) == {("flash_attention", "interpret"): 2,
                              ("flash_attention_grad", "saved"): 2,
                              ("flash_attention_bwd", "fused"): 2,
                              ("flash_attention_layout", "token_major"): 4,
                              ("flash_attention_causal_fetch",
                               "all_live"): 4}


# -- token-major operands: the op on [B, T, H*d] ------------------------------

def _token_major(feed):
    """[B, H, T, D] feeds as the projections leave them: [B, T, H*D]."""
    return {n: np.ascontiguousarray(x.transpose(0, 2, 1, 3)).reshape(
        x.shape[0], x.shape[2], -1) for n, x in feed.items()}


@pytest.mark.parametrize("h,d,layout", [(2, 64, "token_major"),
                                        (2, 128, "token_major"),
                                        (3, 64, "head_major"),
                                        (4, 32, "head_major")])
def test_rank3_op_binds_its_residuals_and_matches_rank4(interpret, h, d,
                                                        layout):
    """`append_backward` on a flash_attention that takes [B, T, H*d]:
    Out and LSE bound on the grad op, LSE [B, H, Tq], the backward on
    the saved residuals; the numbers of the rank-4 op on the same
    heads."""
    feed4 = _feed(b=2, h=h, tq=32, tk=32, d=d)
    kw = dict(causal=True, block_q=16, block_k=16)
    want = _run(_attention_net(feed4, **kw), feed4, GRADS)
    framework.switch_main_program(Program())
    feed3 = _token_major(feed4)
    prog = _attention_net(feed3, n_head=h, **kw)
    (grad_op,) = [op for op in prog.global_block().ops
                  if op.type == "flash_attention_grad"]
    (fwd_op,) = [op for op in prog.global_block().ops
                 if op.type == "flash_attention"]
    assert grad_op.inputs["Out"] == fwd_op.outputs["Out"]
    assert grad_op.inputs["LSE"] == fwd_op.outputs["LSE"]
    assert fwd_op.attrs["heads"] == grad_op.attrs["heads"] == h
    block = prog.global_block()
    assert tuple(block.var(fwd_op.outputs["Out"][0]).shape[1:]) \
        == (32, h * d)
    assert tuple(block.var(fwd_op.outputs["LSE"][0]).shape[1:]) == (h, 32)
    before = _impl_counts()
    got = _run(prog, feed3, GRADS)
    assert _since(before) == {("flash_attention", "interpret"): 1,
                              ("flash_attention_grad", "saved"): 1,
                              ("flash_attention_bwd", "fused"): 1,
                              ("flash_attention_layout", layout): 2,
                              ("flash_attention_causal_fetch", "held"): 2}
    assert _kernel_calls(prog, feed3) == dict.fromkeys(KERNELS, 1)
    for name, a, w in zip(GRADS, got, want):
        w = _token_major({name: w})[name]
        assert a.shape == w.shape, name
        if layout == "head_major":
            assert np.array_equal(a, w), name
        else:       # delta's sum in another order: a few ulps
            np.testing.assert_allclose(a, w, atol=2e-6 * np.abs(w).max(),
                                       err_msg=name)


# -- inside a recompute segment (ISSUE 33) ------------------------------------
# The segment's backward binds the forward's Out and LSE (`Saved`), its
# replay takes them for the op's outputs, and the op's backward is the
# registered grad op: the forward kernel runs once a layer here too.

def _segment_ops(prog):
    return [op for op in prog.global_block().ops
            if op.type == "recompute_segment_grad"]


def _unbind_segments(prog):
    """The segment ops as a program from before the `Saved` slot has
    them.  Returns how many names were bound."""
    n = 0
    for op in _segment_ops(prog):
        n += len(op.inputs.pop("Saved", ()))
        op.attrs.pop("saved_names", None)
    return n


@pytest.mark.parametrize("n_layers", [1, 3])
def test_segment_runs_one_forward_kernel_a_layer(interpret, n_layers):
    feed = _feed()
    prog = _attention_net(feed, n_layers, segmented=True, causal=True)
    flash = [op for op in prog.global_block().ops
             if op.type == "flash_attention"]
    bound = [op.inputs["Saved"] for op in _segment_ops(prog)
             if "Saved" in op.inputs]
    # the segment that holds layer i binds that layer's Out and LSE
    assert sorted(bound) == sorted(
        op.outputs["Out"] + op.outputs["LSE"] for op in flash)
    before = _impl_counts()
    assert _kernel_calls(prog, feed) == dict.fromkeys(KERNELS, n_layers)
    assert _since(before) == {
        ("flash_attention", "interpret"): n_layers,
        ("flash_attention_grad", "saved"): n_layers,
        ("flash_attention_bwd", "fused"): n_layers,
        ("flash_attention_layout", "head_major"): 2 * n_layers,
        ("flash_attention_causal_fetch", "all_live"): 2 * n_layers}
    # and what it was before: the replay runs the forward kernel again.
    # The jaxpr holds it twice a segment, in the primal of the replay's
    # vjp, which nothing reads and XLA drops, and in the replay the
    # backward differentiates: 2 a layer in the compiled step
    assert _unbind_segments(prog) == 2 * n_layers
    before = _impl_counts()
    assert _kernel_calls(prog, feed) == {"pt_flash_fwd": 3 * n_layers,
                                         "pt_flash_bwd_dkv": n_layers}
    assert ("flash_attention_grad", "saved") not in _since(before)


def _projected(x, width, name):
    from paddle_tpu.param_attr import ParamAttr

    return layers.fc(x, size=width, num_flatten_dims=2, bias_attr=False,
                     param_attr=ParamAttr(name=name + ".w"))


def _mla_layer(x, i, heads=1, d_qk=192, d_v=128):
    """Head-major attention at latent attention's two head sizes."""
    def split(t, d):
        t = layers.reshape(t, [0, 0, heads, d])
        return layers.transpose(t, [0, 2, 1, 3])

    q, k = (split(_projected(x, heads * d_qk, "l%d_%s" % (i, n)), d_qk)
            for n in "qk")
    v = split(_projected(x, heads * d_v, "l%d_v" % i), d_v)
    out = layers.flash_attention(q, k, v, causal=True, block_q=16,
                                 block_k=16)
    out = layers.reshape(layers.transpose(out, [0, 2, 1, 3]),
                         [0, 0, heads * d_v])
    return layers.elementwise_add(x, _projected(out, 32, "l%d_o" % i))


def _shared_layer(x, i, heads=2, d=64):
    """Token-major attention; every execution reads ONE set of
    weights (names without i)."""
    q, k, v = (_projected(x, heads * d, "shared_" + n) for n in "qkv")
    out = layers.flash_attention(q, k, v, causal=True, n_head=heads,
                                 block_q=16, block_k=16)
    return layers.elementwise_add(x, _projected(out, 32, "shared_o"))


def _train_five(layer, n_layers, unbind):
    """Five SGD steps under RecomputeOptimizer, one segment a layer
    execution.  Returns (losses, first-step parameter gradients by
    name, impl counts, segment ops with names bound)."""
    x = layers.data("x", shape=[32, 32], dtype="float32")
    h, checkpoints = x, []
    for i in range(n_layers):
        h = layer(h, i)
        checkpoints.append(h)
    loss = layers.mean(layers.square(h))
    opt = optimizer.RecomputeOptimizer(optimizer.SGD(0.5))
    opt._set_checkpoints(checkpoints)
    _, params_grads = opt.minimize(loss)
    prog = framework.default_main_program()
    bound = sum("Saved" in op.inputs for op in _segment_ops(prog))
    if unbind:
        _unbind_segments(prog)
    grads = sorted(g.name for _, g in params_grads)
    feed = {"x": np.random.RandomState(1).randn(2, 32, 32)
            .astype(np.float32)}
    exe = fluid.Executor(fluid.CPUPlace())
    np.random.seed(3)           # initializers draw from np.random
    with scope_guard(Scope()):
        exe.run(framework.default_startup_program())
        compiled = fluid.CompiledProgram(prog)
        before = _impl_counts()
        first = exe.run(compiled, feed=feed, fetch_list=[loss] + grads)
        used = _since(before)
        losses = [first[0]] + [
            exe.run(compiled, feed=feed, fetch_list=[loss])[0]
            for _ in range(4)]
    return losses, dict(zip(grads, first[1:])), used, bound


@pytest.mark.parametrize("layer,layout,n_grads", [
    (_mla_layer, "head_major", 8), (_shared_layer, "token_major", 4),
], ids=["head_major_192_128", "token_major_shared_weight"])
def test_segment_saved_equals_replay_bit_for_bit(fresh_programs_factory,
                                                 interpret, layer, layout,
                                                 n_grads):
    runs = []
    for unbind in (False, True):
        with fresh_programs_factory():
            runs.append(_train_five(layer, 2, unbind))
    (losses, grads, used, bound), (losses_r, grads_r, used_r, _) = runs
    assert bound == 2
    assert used[("flash_attention_grad", "saved")] == 2
    assert used[("flash_attention_layout", layout)] == 4
    assert ("flash_attention_grad", "saved") not in used_r
    # the replay traced the forward entry once more a segment
    assert used_r[("flash_attention", "interpret")] \
        == used[("flash_attention", "interpret")] + 2
    assert len(grads) == n_grads and set(grads) == set(grads_r)
    for name in grads:
        assert np.array_equal(grads[name], grads_r[name]), name
        assert np.abs(grads[name]).max() > 0, name
    assert len(losses) == 5 and losses[4] < losses[0]
    for a, b in zip(losses, losses_r):
        assert np.array_equal(a, b)


def test_xla_impl_keeps_the_replay_in_a_segment():
    """No kernel, no residual worth saving: the names are bound (the
    program does not know its impl) and the replay is what it was,
    trace for trace; the grad op is not called and nothing is counted
    in its name."""
    feed = _feed()
    prog = _attention_net(feed, 2, segmented=True, causal=True)
    assert sum("Saved" in op.inputs for op in _segment_ops(prog)) == 2

    def step(feeds):
        env = _TraceEnv()
        env.update(feeds)
        _run_block_symbolic(prog, 0, env)
        return [env[g] for g in GRADS]

    before = _impl_counts()
    bound_trace = str(jax.make_jaxpr(step)(feed))
    bound = _run(prog, feed, GRADS)
    assert not any(k == "flash_attention_grad" for k, _ in _since(before))
    _unbind_segments(prog)
    assert str(jax.make_jaxpr(step)(feed)) == bound_trace
    for a, b in zip(bound, _run(prog, feed, GRADS)):
        assert np.array_equal(a, b)


def test_segment_desc_serialized_without_saved_still_runs(interpret):
    feed = _feed()
    prog = _attention_net(feed, 2, segmented=True, causal=True)
    want = _run(prog, feed, GRADS)
    desc = json.loads(prog.to_bytes())
    stripped = 0
    for op in desc["blocks"][0]["ops"]:
        if op["type"] == "recompute_segment_grad":
            stripped += len(op["inputs"].pop("Saved", ()))
            op["attrs"].pop("saved_names", None)
    assert stripped == 4
    old = Program.parse_from_bytes(json.dumps(desc).encode())
    from paddle_tpu.analysis.verifier import verify

    assert verify(old, feeds=list(feed)) == []
    before = _impl_counts()
    for a, b in zip(want, _run(old, feed, GRADS)):
        assert np.array_equal(a, b)
    assert ("flash_attention_grad", "saved") not in _since(before)
