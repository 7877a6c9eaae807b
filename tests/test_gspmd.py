"""GSPMD pod-scale front-end (ISSUE 8; parallel/gspmd.py +
transpiler/sharding_transpiler.py) on the virtual 8-device CPU mesh.

Contract under test (docs/GSPMD.md):
  - MeshPlan / PartitionSpec annotations round-trip through the
    Program IR (serialization, clone, compiled-program fingerprint);
  - ONE jitted train step with in/out NamedShardings (fwd+bwd+Adam)
    over a dp x tp mesh is numerically tight vs the unsharded step
    (loss + grads + params after N steps);
  - ZeRO-3 expressed as annotations matches parallel/zero.py's rule
    closure, and params/accumulators are REALLY dim-sharded on device;
  - flag-off (`gspmd` default) is bit-identical to never calling
    shard_program;
  - ElasticTrainer kill-and-resume reproduces the sharded trajectory
    bit-exact from checkpoints.
"""

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework, layers, optimizer, unique_name
from paddle_tpu.core.program import Program
from paddle_tpu.core import scope as scope_mod
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.flags import set_flags
from paddle_tpu.models.transformer import transformer_encoder_model
from paddle_tpu.parallel import env as penv
from paddle_tpu.parallel.gspmd import (MeshPlan, annotate_zero3,
                                       partition_spec_of)
from paddle_tpu.transpiler import ShardingTranspiler, shard_program


@pytest.fixture(autouse=True)
def gspmd_hygiene():
    """The gspmd flag and the global mesh are process state; no test
    may leak them into the next."""
    yield
    set_flags({"gspmd": False})
    penv.reset()


TINY = dict(vocab_size=128, max_len=16, d_model=32, n_head=4,
            d_inner=64, n_layer=2, dropout_rate=0.0,
            param_prefix="tfm")


def _fresh():
    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    penv.reset()


def _feed(step):
    rng = np.random.RandomState(100 + step)
    ids = rng.randint(0, TINY["vocab_size"], (8, 16, 1)).astype(np.int64)
    return {"src_ids": ids, "tgt_label": ids}


def _build_tiny(gspmd, plan=None, **shard_kw):
    """Tiny transformer + Adam; returns (compiled, loss_var, main)."""
    _fresh()
    set_flags({"gspmd": gspmd})
    model = transformer_encoder_model(**TINY)
    optimizer.Adam(1e-3).minimize(model["loss"])
    main = framework.default_main_program()
    compiled = fluid.CompiledProgram(main)
    if gspmd:
        compiled = shard_program(
            compiled, plan or MeshPlan(dp=4, tp=2),
            loss_name=model["loss"].name, min_size=256, **shard_kw)
    return compiled, model["loss"], main


def _train(compiled, loss, main, steps=3, fetch_extra=()):
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        np.random.seed(11)
        exe.run(framework.default_startup_program())
        losses, extra = [], []
        for s in range(steps):
            out = exe.run(compiled, feed=_feed(s),
                          fetch_list=[loss] + list(fetch_extra))
            losses.append(float(np.asarray(out[0])))
            extra.append([np.asarray(v) for v in out[1:]])
        sc = scope_mod._global_scope
        params = {v.name: np.asarray(sc.find_var(v.name).get())
                  for v in main.all_parameters()}
    return losses, params, extra


# ---------------------------------------------------------------------------
# MeshPlan + annotation round-trip
# ---------------------------------------------------------------------------

def test_meshplan_basics():
    plan = MeshPlan(dp=4, tp=2)
    assert plan.axis_names == ("dp", "tp", "pp")
    assert plan.shape == (4, 2, 1)
    assert plan.size() == 8
    assert plan.axis_size("tp") == 2
    assert plan.axis_size("nope") == 1          # unknown = factor 1
    mesh = plan.build_mesh()
    assert tuple(mesh.axis_names) == ("dp", "tp", "pp")
    assert MeshPlan.from_mesh(mesh) == plan
    assert MeshPlan.from_dict(plan.to_dict()) == plan
    from jax.sharding import PartitionSpec as P

    assert plan.spec("dp", None) == P("dp", None)
    with pytest.raises(ValueError, match="not in"):
        plan.spec("bogus")
    with pytest.raises(ValueError, match="needs"):
        MeshPlan(dp=3).build_mesh()


def test_annotation_roundtrip_through_ir():
    _fresh()
    x = layers.data("x", shape=[64], dtype="float32")
    pred = layers.fc(x, 32, bias_attr=False)
    main = framework.default_main_program()
    w = main.all_parameters()[0]
    # nested tuple entry (a dim sharded over two axes) survives the
    # JSON round-trip as tuples, not lists
    w.set_sharding((("dp", "tp"), None))
    restored = Program.parse_from_bytes(main.to_bytes())
    rv = restored.global_block().vars[w.name]
    assert rv.sharding == (("dp", "tp"), None)
    # clone keeps it too
    assert main.clone().global_block().vars[w.name].sharding == \
        (("dp", "tp"), None)
    plan = MeshPlan(dp=4, tp=2)
    from jax.sharding import PartitionSpec as P

    assert partition_spec_of(rv, plan) == P(("dp", "tp"), None)
    # 64 rows / (4*2) divides; a plan it doesn't divide -> replicated
    assert partition_spec_of(rv, MeshPlan(dp=48)) is None
    with pytest.raises(ValueError, match="not in"):
        partition_spec_of(rv, MeshPlan.from_dict(
            {"axes": {"dp": 8}, "data_axis": "dp"}))


def test_annotation_changes_compiled_fingerprint():
    from paddle_tpu.core.compiler import _program_fingerprint

    _fresh()
    x = layers.data("x", shape=[16], dtype="float32")
    layers.fc(x, 8, bias_attr=False)
    main = framework.default_main_program()
    fp0 = _program_fingerprint(main)
    main.all_parameters()[0].set_sharding(("dp", None))
    fp1 = _program_fingerprint(main)
    assert fp0 != fp1, \
        "a sharding annotation edit must invalidate the jit cache"


def test_accumulator_inherits_param_annotation():
    _fresh()
    x = layers.data("x", shape=[64], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    pred = layers.fc(x, 1, bias_attr=False)
    main = framework.default_main_program()
    main.all_parameters()[0].set_sharding(("dp", None))
    loss = layers.mean(layers.square_error_cost(pred, y))
    optimizer.Adam(0.01).minimize(loss)
    gb = main.global_block()
    pname = main.all_parameters()[0].name
    moments = [v for n, v in gb.vars.items()
               if n.startswith(pname + "_moment")]
    assert len(moments) == 2
    for m in moments:
        assert m.sharding == ("dp", None), m.name
    # beta-pow [1] accumulators keep their own shape: no inherit
    betas = [v for n, v in gb.vars.items()
             if n.startswith(pname + "_beta")]
    assert betas and all(b.sharding is None for b in betas)


# ---------------------------------------------------------------------------
# flag-off bit-identity
# ---------------------------------------------------------------------------

def test_flag_off_bit_identity():
    """With the `gspmd` flag at its default (off), shard_program must
    be a complete no-op: same object back, no annotations, no op
    attrs, and the executed step bit-identical to never calling it."""
    base_losses, base_params, _ = _train(*_build_tiny(False))

    _fresh()
    set_flags({"gspmd": False})
    model = transformer_encoder_model(**TINY)
    optimizer.Adam(1e-3).minimize(model["loss"])
    main = framework.default_main_program()
    before = main.to_bytes()
    compiled = fluid.CompiledProgram(main)
    out = shard_program(compiled, MeshPlan(dp=4, tp=2),
                        loss_name=model["loss"].name, min_size=256)
    assert out is compiled
    assert main.to_bytes() == before, \
        "flag-off shard_program may not touch the IR"
    assert compiled._mesh is None and \
        compiled._param_sharding_fn is None
    off_losses, off_params, _ = _train(compiled, model["loss"], main)
    assert off_losses == base_losses
    for n in base_params:
        assert np.array_equal(off_params[n], base_params[n]), n


# ---------------------------------------------------------------------------
# pjit-vs-unsharded parity (the acceptance leg)
# ---------------------------------------------------------------------------

def test_pjit_step_matches_unsharded():
    """ONE jitted step with in/out NamedShardings over dp=4 x tp=2
    (ZeRO-3 + Megatron tp + flash under shard_map) vs the plain
    single-program jit: losses each step, a sampled gradient, and
    every parameter after N steps agree allclose-tight."""
    main0 = _build_tiny(False)
    gnames = ["tfm_l0_self_q.w@GRAD", "tfm_out_fc.w@GRAD"]
    base_losses, base_params, base_grads = _train(
        *main0, fetch_extra=gnames)

    compiled, loss, main = _build_tiny(True)
    # the transpiler really annotated + tagged
    gb = main.global_block()
    assert gb.vars["tfm_l0_self_q.w"].sharding == ("dp", "tp")
    assert gb.vars["tfm_l0_ffn_fc2.w"].sharding == ("tp", "dp")
    assert any(op.attrs.get("gspmd_batch_axis") == "dp"
               for b in main.blocks for op in b.ops
               if op.type == "flash_attention")
    g_losses, g_params, g_grads = _train(compiled, loss, main,
                                         fetch_extra=gnames)
    np.testing.assert_allclose(g_losses, base_losses, rtol=2e-5,
                               atol=1e-6)
    for s in range(len(base_grads)):
        for gn, a, b in zip(gnames, g_grads[s], base_grads[s]):
            np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-6,
                                       err_msg=f"step {s} {gn}")
    for n in base_params:
        np.testing.assert_allclose(g_params[n], base_params[n],
                                   rtol=5e-4, atol=1e-5, err_msg=n)


def test_params_and_state_sharded_on_device():
    """The pjit step's claim is per-device memory 1/shards: committed
    weights and Adam moments must REALLY be dim-sharded over the
    mesh (companion to test_parallelism's ZeRO assertions)."""
    compiled, loss, main = _build_tiny(True)
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        np.random.seed(11)
        exe.run(framework.default_startup_program())
        exe.run(compiled, feed=_feed(0), fetch_list=[loss])
        sc = scope_mod._global_scope
        qw = sc.find_var("tfm_l0_self_q.w").get()
        # (32, 32) weight over dp=4 x tp=2 -> (8, 16) per device
        assert qw.addressable_shards[0].data.shape == (8, 16)
        gb = main.global_block()
        mname = next(n for n in gb.vars
                     if n.startswith("tfm_l0_self_q.w_moment1"))
        m = sc.find_var(mname).get()
        assert m.addressable_shards[0].data.shape == (8, 16)
        # embedding: ZeRO-3 dim0 over dp only -> (32, 32) of (128, 32)
        emb = sc.find_var("tfm_emb.w").get()
        assert emb.addressable_shards[0].data.shape == (32, 32)


# ---------------------------------------------------------------------------
# ZeRO-3 as spec vs parallel/zero.py
# ---------------------------------------------------------------------------

def test_zero3_spec_matches_zero_py():
    """The annotation path (ZeRO-3 as IR specs through shard_program)
    must train identically to zero.py's rule closure through
    with_sharding_rules — the refactor that retires the bespoke path
    keeps its numbers."""
    from paddle_tpu.parallel.zero import zero_sharding_rules

    W = np.random.RandomState(7).randn(16, 1).astype(np.float32)

    def build(mode):
        _fresh()
        set_flags({"gspmd": mode == "gspmd"})
        x = layers.data("x", shape=[16], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        optimizer.Adam(0.05).minimize(loss)
        main = framework.default_main_program()
        exe = fluid.Executor()
        with scope_guard(Scope()):
            np.random.seed(42)
            exe.run(fluid.default_startup_program())
            if mode == "gspmd":
                compiled = shard_program(
                    fluid.CompiledProgram(main), MeshPlan(dp=8),
                    loss_name=loss.name, min_size=4)
            elif mode == "zero":
                mesh = penv.set_mesh(penv.make_mesh(
                    shape=(8,), axis_names=("dp",)))
                compiled = fluid.CompiledProgram(main) \
                    .with_data_parallel(loss_name=loss.name,
                                        mesh=mesh) \
                    .with_sharding_rules(zero_sharding_rules(
                        stage=3, axis="dp", min_size=4, program=main))
            else:
                compiled = fluid.CompiledProgram(main) \
                    .with_data_parallel(loss_name=loss.name)
            losses = []
            r2 = np.random.RandomState(8)
            for _ in range(8):
                bx = r2.rand(32, 16).astype(np.float32)
                lv, = exe.run(compiled, feed={"x": bx, "y": bx @ W},
                              fetch_list=[loss])
                losses.append(float(np.asarray(lv)))
            # the gspmd path shards the weight exactly like zero-3
            pname = main.all_parameters()[0].name
            arr = scope_mod._global_scope.find_var(pname).get()
            rows = arr.addressable_shards[0].data.shape[0]
        return losses, rows, arr.shape[0]

    z_losses, z_rows, z_n = build("zero")
    g_losses, g_rows, g_n = build("gspmd")
    np.testing.assert_allclose(g_losses, z_losses, rtol=1e-5)
    assert g_rows == z_rows == z_n // 8


# ---------------------------------------------------------------------------
# ElasticTrainer kill-and-resume on the sharded trajectory
# ---------------------------------------------------------------------------

def test_elastic_kill_resume_bit_parity(tmp_path):
    """A killed-and-relaunched trainer resumes the gspmd-sharded
    trajectory bit-exact: orbax checkpoints save the sharded state
    per-shard (StandardSave of jax.Arrays), resume restores it into a
    fresh scope and the remaining steps reproduce the uninterrupted
    run's parameters bit-for-bit (step-keyed data)."""
    from paddle_tpu.contrib.checkpoint import AsyncCheckpointer
    from paddle_tpu.distributed.elastic import ElasticTrainer

    n_steps, save_every, crash_after = 10, 5, 7

    def run(ckdir, stop_at=None, resume=False):
        compiled, loss, main = _build_tiny(True)
        ck = AsyncCheckpointer(str(ckdir))
        el = ElasticTrainer(ck, save_every=save_every, program=main,
                            wait_each_save=True)
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            np.random.seed(11)
            exe.run(framework.default_startup_program())
            start = el.resume() if resume else 0
            if resume:
                assert start == save_every, start
            for s in range(start, stop_at or n_steps):
                exe.run(compiled, feed=_feed(s), fetch_list=[loss])
                el.step_done(s)
            el.finish()
            sc = scope_mod._global_scope
            params = {v.name: np.asarray(sc.find_var(v.name).get())
                      for v in main.all_parameters()}
        ck.close()
        return params

    full = run(tmp_path / "full")
    # crash: steps [0, 7) land a checkpoint at 5; the relaunch
    # restores step 5 and replays 5..10
    run(tmp_path / "crash", stop_at=crash_after)
    resumed = run(tmp_path / "crash", resume=True)
    for n, v in full.items():
        assert np.array_equal(resumed[n], v), \
            f"param {n} diverged after kill-and-resume"


# ---------------------------------------------------------------------------
# serving prewarm (cold-start satellite)
# ---------------------------------------------------------------------------

def test_serving_prewarm_buckets(tmp_path):
    """ServingConfig(prewarm=True) compiles every (replica, bucket)
    entry at start(): the predictor's compile cache holds the full
    bucket set before any request, and the first request formed is
    served from a warm bucket."""
    from paddle_tpu import inference, serving

    x = layers.data("x", shape=[4], dtype="float32")
    pred = layers.fc(x, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    mdir = str(tmp_path / "model")
    fluid.io.save_inference_model(mdir, ["x"], [pred], exe)

    def factory(i):
        return inference.create_predictor(inference.Config(mdir))

    cfg = serving.ServingConfig(n_replicas=1, max_batch=4,
                                prewarm=True)
    srv = serving.InferenceServer(factory, cfg)
    try:
        srv.start()
        rep = srv.pool.replicas[0].predictor
        # every bucket shape compiled at start: (1, 2, 4)
        assert len(cfg.buckets) == 3
        out = srv.infer({"x": np.zeros((1, 4), np.float32)},
                        timeout=10.0)
        assert out[0].shape == (1, 1)
    finally:
        srv.stop()
    # default stays off without the compile-cache env
    assert serving.ServingConfig().prewarm in (False,)


# ---------------------------------------------------------------------------
# flash attention on token-major operands (ISSUE 31): [B, T, H*d] rides
# P(dp, None, tp), the sharding a column-parallel projection leaves
# ---------------------------------------------------------------------------

def _rank3_attention_net(h, d, b=4, t=32):
    """One rank-3 flash_attention op with its backward; returns
    (program, feed)."""
    from paddle_tpu.backward import append_backward

    _fresh()
    rng = np.random.RandomState(0)
    feed = {n: rng.randn(b, t, h * d).astype(np.float32) for n in "qkv"}
    q, k, v = (layers.data(n, shape=[t, h * d], dtype="float32")
               for n in "qkv")
    for var in (q, k, v):
        var.stop_gradient = False
    out = layers.flash_attention(q, k, v, causal=True, n_head=h,
                                 block_q=16, block_k=16)
    append_backward(layers.mean(layers.square(out)))
    return framework.default_main_program(), feed


def _shard_map_specs(fn, *args):
    """in_specs / out_specs of every shard_map in the jaxpr of fn."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "shard_map":
                found.append((eqn.params["in_specs"],
                              eqn.params["out_specs"]))
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple))
                            else [val]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_rank3_flash_runs_under_shard_map_and_matches_one_device(
        monkeypatch):
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.core.compiler import _TraceEnv, _run_block_symbolic
    from paddle_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_auto_impl", lambda: "interpret")
    grads = ["q@GRAD", "k@GRAD", "v@GRAD"]
    prog, feed = _rank3_attention_net(h=4, d=64)
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        want = exe.run(fluid.CompiledProgram(prog), feed=feed,
                       fetch_list=grads)
    set_flags({"gspmd": True})
    compiled = shard_program(fluid.CompiledProgram(prog),
                             MeshPlan(dp=2, tp=2),
                             devices=jax.devices()[:4])
    ops = [op for op in prog.global_block().ops
           if op.type.startswith("flash_attention")]
    assert [(op.attrs.get("gspmd_batch_axis"),
             op.attrs.get("gspmd_head_axis"), op.attrs["heads"])
            for op in ops] == [("dp", "tp", 4)] * 2

    def counts():
        c = pk._M_KERNEL_IMPL
        return {k: c.value(kernel=k[0], impl=k[1]) for k in (
            ("flash_attention_gspmd", "shard_map"),
            ("flash_attention_gspmd", "plain"),
            ("flash_attention_layout", "token_major"),
            ("flash_attention_layout", "head_major"))}

    before = counts()
    with scope_guard(Scope()):
        got = exe.run(compiled, feed=feed, fetch_list=grads)
    # both ops under shard_map; a shard holds 2 of the 4 heads, one
    # 128-lane block: token-major inside it too
    assert {k: v - before[k] for k, v in counts().items()} == {
        ("flash_attention_gspmd", "shard_map"): 2,
        ("flash_attention_gspmd", "plain"): 0,
        ("flash_attention_layout", "token_major"): 2,
        ("flash_attention_layout", "head_major"): 0}
    for name, a, w in zip(grads, got, want):
        np.testing.assert_allclose(np.asarray(a), w,
                                   atol=2e-6 * np.abs(w).max(),
                                   err_msg=name)
        assert np.abs(w).max() > 0

    def step(feeds):
        env = _TraceEnv()
        env.update(feeds)
        _run_block_symbolic(prog, 0, env)
        return [env[g] for g in grads]

    x, lse = P("dp", None, "tp"), P("dp", "tp", None)
    assert _shard_map_specs(step, feed) == [
        ((x, x, x), (x, lse)), ((x, x, x, x, lse, x), (x, x, x))]


def test_shape_check_reads_heads_from_the_rank3_op():
    from paddle_tpu.analysis import check_sharding
    from paddle_tpu.analysis.shape_check import ShardingCheckError
    from paddle_tpu.parallel.gspmd import tag_attention_ops

    def diags(h, d, plan):
        prog, _ = _rank3_attention_net(h=h, d=d)
        assert tag_attention_ops(prog, plan) == 2
        return check_sharding(prog, plan, raise_=False)

    assert diags(4, 64, MeshPlan(dp=2, tp=2)) == []
    # 6 heads over tp=4: the shard_map gate would fall back silently
    bad = diags(6, 64, MeshPlan(dp=1, tp=4))
    assert {(d.rule, d.severity) for d in bad} == {
        ("sharding-indivisible", "error")}
    assert all("head extent 6" in d.message for d in bad)
    prog, _ = _rank3_attention_net(h=6, d=64)
    tag_attention_ops(prog, MeshPlan(dp=1, tp=4))
    with pytest.raises(ShardingCheckError):
        check_sharding(prog, MeshPlan(dp=1, tp=4))
    # 6 heads of 64 lanes over tp=2: 3 a shard do not fill lane
    # blocks; right numbers, the copies back (a warning, both ops)
    odd = diags(6, 64, MeshPlan(dp=2, tp=2))
    assert [(d.rule, d.severity, d.op_type) for d in odd] == [
        ("attention-head-layout", "warning", "flash_attention"),
        ("attention-head-layout", "warning", "flash_attention_grad")]
    # at 128 lanes a head every count fills its blocks
    assert diags(6, 128, MeshPlan(dp=2, tp=2)) == []
