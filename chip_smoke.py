"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the framework's two main paths once, through the entry points a
user calls, at Transformer-base width, in ONE process (it never starts a
child that needs the chip):

  device   jax.devices()[0].platform == "tpu", or fail at once.
  train    Transformer-base (d512, 6 layers, 8 heads, ffn 2048; seq 512,
           batch 32, AMP bf16) built with layers.* /
           models/transformer.py, Adam.minimize, Executor(TPUPlace()),
           startup program, then 5 x exe.run(CompiledProgram(main)) on
           one fixed batch.  Then ResNet-50 (batch 128, 224^2, NHWC, AMP
           bf16, Momentum, default flags) the same way, 3 steps.
  serve    DecodeServer over TinyDecodeLM(vocab 32000, d_model 1024,
           8 heads x 128) with page_size 128: 8 ragged prompts, 32 new
           tokens each, every token checked against the dense
           full-prefix oracle, zero pages leaked — in f32 and in bf16.
  kernels  flash_attention fwd+bwd, flash_decode, conv2d_epilogue,
           conv2d_bn_act, fc_epilogue against their XLA references,
           each with a stated tolerance.

`--chips 4` runs instead, and only: the Transformer-base step as one
shard_program program over MeshPlan dp2 x tp2, against the same program
unsharded on chip 0.

Every phase prints one JSON line.  The LAST line of stdout is exactly
{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}.
A phase that fails ends the run: exit code 1, {"ok": false, ...} on
stderr, no result line on stdout.  Nothing here catches a failed phase
to keep it alive.

Left out on purpose: the stride-2 and 7x7-stem Pallas convs (about 50 s
each to compile, behind default-off flags).

Rehearsals without the chip (tests/test_chip_smoke.py,
tests/test_chip_compile.py, tools/tpu_lowering_check.py): the phases at
tiny size on the CPU, the four-chip phase on four virtual devices, and
the real-size compiles for a described v5e:2x2.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import time

import numpy as np

SEED = 0

# the real sizes.  tests/test_chip_smoke.py runs the same phases with
# its own tiny sizes; nothing else chooses a size.
REAL = {
    "transformer": dict(batch=32, seq=512, steps=5, n_layer=6,
                        # 6 layers x (1 forward + 1 backward flash
                        # kernel): what the v5e compile of this step
                        # shows (the grad op reads the forward's Out
                        # and LSE, it ran the forward again until
                        # PR 25; one backward sweep since PR 29)
                        custom_calls=12),
    "resnet": dict(batch=128, image=224, steps=3, custom_calls=0),
    "serve": dict(vocab=32000, d_model=1024, num_heads=8, head_dim=128,
                  page_size=128, n_requests=8, prompt_min=32,
                  prompt_max=256, new_tokens=32),
    "kernels": dict(flash=(32, 8, 512, 64),
                    decode=dict(batch=64, heads=8, head_dim=128,
                                page_size=128, max_pages=4),
                    conv3x3=(128, 56, 56, 64, 64),
                    conv1x1=(128, 56, 56, 64, 256),
                    fc=(16384, 512, 2048)),
    "gspmd": dict(batch=32, seq=512, steps=3, n_layer=6, dp=2, tp=2,
                  custom_calls=12),
}

# stated tolerances of the on-chip comparisons (max abs difference,
# relative to the reference's max abs value).  bf16 keeps 8 bits of
# mantissa: two correctly rounded bf16 results of one f32 value differ
# by at most 2^-8 relative, and the kernels and their XLA references
# round at different points.
TOL = {"bf16": 2.0 ** -6, "f32": 2.0 ** -10}
# an argmax of the server and of the oracle may differ only where the
# oracle itself holds the two logits this close (relative to the
# largest |logit| of the row): a tie broken another way, not a wrong
# answer
TIE_TOL = {"float32": 2.0 ** -10, "bfloat16": 2.0 ** -6}


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(phase, **fields):
    print(json.dumps({"phase": phase, **fields}, default=str),
          flush=True)


# ---------------------------------------------------------------------------
# what the run observes about itself
# ---------------------------------------------------------------------------

class CompileWatch:
    """Counts XLA backend compiles and persistent-cache hits/misses
    from jax's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return (self.compiles, self.compile_s, self.cache_hits,
                self.cache_misses)

    def since(self, snap):
        c, s, h, m = self.snapshot()
        return {"compiles": c - snap[0],
                "compile_s": round(s - snap[1], 2),
                "cache_hits": h - snap[2],
                "cache_misses": m - snap[3]}


def kernel_impls():
    """{(kernel, impl): count} from the counter the kernel entries keep
    (ops/pallas_kernels.py paddle_tpu_kernel_impl_total)."""
    from paddle_tpu.observability import metrics

    c = metrics.registry().get("paddle_tpu_kernel_impl_total")
    return {(lbl["kernel"], lbl["impl"]): int(v) for lbl, v in c.items()}


def impls_since(before):
    now = kernel_impls()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v - before.get(k, 0)}


def check_impl(used, kernel, expect):
    """The phase names `expect` for `kernel`: fail when any entry of
    that family resolved to something else, or none ran."""
    got = {impl: n for (k, impl), n in used.items() if k == kernel}
    check(got.get(expect, 0) > 0 and set(got) == {expect},
          "%s resolved to %s, expected only %r"
          % (kernel, got or "nothing", expect))
    return expect


def run_resolved(kernel, expect, thunk):
    """thunk() and the impl `kernel` resolved to while it ran, which
    must be `expect` and nothing else."""
    before = kernel_impls()
    out = thunk()
    return out, check_impl(impls_since(before), kernel, expect)


def fmt_impls(used):
    return {"%s:%s" % k: v for k, v in sorted(used.items())}


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def compiled_text(compiled, feed):
    """Text of the executable CompiledProgram runs for this feed: the
    jitted step it cached, lowered with the avals of the state the
    scope holds now, compiled (a persistent-cache hit when the cache is
    on: the step itself was compiled moments ago)."""
    import jax

    from paddle_tpu.core.scope import global_scope

    fns = [v for v in compiled._cache.values() if callable(v)]
    check(len(fns) == 1, "expected ONE cached jitted step, found %d"
          % len(fns))

    def sds(d):
        return {k: jax.ShapeDtypeStruct(np.shape(v), v.dtype)
                for k, v in d.items()}

    state = {n: global_scope().find_var(n).get()
             for n in compiled._persistable_names}
    # the avals CompiledProgram._run feeds: the declared var dtype,
    # canonicalized (int64 ids are int32 on the device)
    block = compiled._program.global_block()
    feeds = {k: jax.ShapeDtypeStruct(
        np.shape(v), jax.dtypes.canonicalize_dtype(block.var(k).dtype))
        for k, v in feed.items()}
    exe = fns[0].lower(sds(state), feeds).compile()
    return exe.as_text(), exe.memory_analysis()


def count_custom_calls(text):
    return text.count('custom_call_target="tpu_custom_call"')


# ---------------------------------------------------------------------------
# phase a: device
# ---------------------------------------------------------------------------

def phase_device(count):
    import jax

    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    check(dev.platform == "tpu",
          "no accelerator: jax.devices()[0] is %s (%s) — chip_smoke "
          "runs on the chip or not at all" % (dev.platform,
                                              dev.device_kind))
    check(len(devs) == count,
          "this run needs %d chip(s), jax sees %d" % (count, len(devs)))
    say("device", jax=jax.__version__, **info)
    return info


# ---------------------------------------------------------------------------
# phase b: train through Executor.run(CompiledProgram)
# ---------------------------------------------------------------------------

def _train_steps(name, model_loss, feed, steps, watch, platform,
                 expect_custom_calls, flash_impl=None, compiled=None,
                 more=None):
    """startup program, then `steps` x exe.run(CompiledProgram(main)) on
    one fixed batch — README.md's own entry.  Checks: finite losses,
    state on the device after step 1, no compile after step 1, the
    kernels the step should hold are in the compiled module.
    `compiled`: the program when it is not the plain
    CompiledProgram(main); `more(compiled, used_impls, text, mem)`:
    further checks, returning fields for the phase's line."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.core.compiler import _M_COMPILES
    from paddle_tpu.core.scope import global_scope

    impl0, began = kernel_impls(), watch.snapshot()
    exe = fluid.Executor(fluid.TPUPlace())
    t0 = time.perf_counter()
    exe.run(fluid.default_startup_program())
    startup_s = time.perf_counter() - t0
    if compiled is None:
        compiled = fluid.CompiledProgram(fluid.default_main_program())
    losses, step_s, after_first = [], [], None
    for i in range(steps):
        t0 = time.perf_counter()
        lv, = exe.run(compiled, feed=feed, fetch_list=[model_loss])
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
        step_s.append(round(time.perf_counter() - t0, 3))
        if i == 0:
            after_first = (watch.snapshot(), _M_COMPILES.total())
            stray = []
            for n in compiled._persistable_names:
                v = global_scope().find_var(n).get()
                if not isinstance(v, jax.Array) or any(
                        d.platform != platform for d in v.devices()):
                    stray.append(n)
            check(not stray, "%s: persistable state not on the %s "
                  "device after step 1: %s" % (name, platform,
                                               stray[:5]))
    later = watch.since(after_first[0])
    check(all(math.isfinite(v) for v in losses),
          "%s: non-finite loss %s" % (name, losses))
    check(later["compiles"] == 0 and
          _M_COMPILES.total() == after_first[1],
          "%s: steps 2..%d recompiled (%s)" % (name, steps, later))
    text, mem = compiled_text(compiled, feed)
    n_calls = count_custom_calls(text)
    check(n_calls == expect_custom_calls,
          "%s: compiled step holds %d tpu_custom_call, expected %d"
          % (name, n_calls, expect_custom_calls))
    used = impls_since(impl0)
    if flash_impl is not None:
        check_impl(used, "flash_attention", flash_impl)
    extra = more(compiled, used, text, mem) if more else {}
    say(name, losses=losses, startup_s=round(startup_s, 2),
        step_s=step_s, compiles_after_step1=later["compiles"],
        n_persistables=len(compiled._persistable_names),
        state_on=platform, tpu_custom_calls=n_calls,
        kernel_impls=fmt_impls(used), compiled=watch.since(began),
        temp_bytes=getattr(mem, "temp_size_in_bytes", None),
        alias_bytes=getattr(mem, "alias_size_in_bytes", None),
        peak_bytes_in_use=peak_bytes(), **extra)
    return losses


def phase_train_transformer(cfg, watch, platform, flash_impl="pallas"):
    from tools.gate_programs import TRANSFORMER_BASE as c
    from tools.gate_programs import _fresh_programs
    from paddle_tpu import optimizer
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.models.transformer import transformer_encoder_model

    _fresh_programs()
    np.random.seed(SEED)
    model = transformer_encoder_model(
        vocab_size=c["vocab"], max_len=cfg["seq"], d_model=c["d_model"],
        n_head=c["n_head"], d_inner=c["d_inner"],
        n_layer=cfg["n_layer"], dropout_rate=0.0)
    # bf16 has fp32's exponent range: static loss scale 1.0, as in
    # tools/gate_programs._build_transformer_train
    decorate(optimizer.Adam(learning_rate=1e-4), init_loss_scaling=1.0,
             use_dynamic_loss_scaling=False).minimize(model["loss"])
    ids = np.random.RandomState(SEED).randint(
        0, c["vocab"], (cfg["batch"], cfg["seq"], 1)).astype(np.int64)
    losses = _train_steps(
        "train_transformer", model["loss"],
        {"src_ids": ids, "tgt_label": ids}, cfg["steps"], watch,
        platform, cfg["custom_calls"], flash_impl=flash_impl)
    check(all(b < a for a, b in zip(losses, losses[1:])),
          "train_transformer: losses not falling on a fixed batch: %s"
          % losses)


def phase_train_resnet(cfg, watch, platform):
    from tools.gate_programs import _fresh_programs
    from paddle_tpu import framework, optimizer
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.models.resnet import resnet50
    from paddle_tpu.transpiler import nhwc_transpile

    _fresh_programs()
    np.random.seed(SEED)
    model = resnet50(is_test=False,
                     image_shape=(3, cfg["image"], cfg["image"]))
    nhwc_transpile(framework.default_main_program())
    decorate(optimizer.Momentum(learning_rate=0.1, momentum=0.9),
             init_loss_scaling=1.0,
             use_dynamic_loss_scaling=False).minimize(model["loss"])
    rng = np.random.RandomState(SEED)
    feed = {"image": rng.rand(cfg["batch"], 3, cfg["image"],
                              cfg["image"]).astype(np.float32),
            "label": rng.randint(0, 1000, (cfg["batch"], 1))
            .astype(np.int64)}
    _train_steps("train_resnet50", model["loss"], feed, cfg["steps"],
                 watch, platform, cfg["custom_calls"])


# ---------------------------------------------------------------------------
# phase c: serve through DecodeServer
# ---------------------------------------------------------------------------

def _oracle_rows(model, max_hist, n_new):
    """The dense full-prefix oracle the tests use
    (tests/test_paged_decode.py): the model's own projections, plain
    softmax attention over the whole prefix, argmax.  Made
    shape-stable: ONE jitted function over a history padded to
    `max_hist` scores the `n_new` positions that follow the prompt,
    instead of one retrace per history length.  f32 logits
    [n_new, vocab].  The weights are an argument, not constants of the
    executable (serving/decode_engine.py TinyDecodeLM)."""
    import jax
    import jax.numpy as jnp

    def rows(params, hist, first):
        q, k, v = model.qkv_of(params, hist)             # [T, H, d]
        pos = first - 1 + jnp.arange(n_new)              # query rows
        s = jnp.einsum("rhd,thd->rht", q[pos].astype(jnp.float32),
                       k.astype(jnp.float32)) / math.sqrt(model.head_dim)
        seen = jnp.arange(max_hist)[None, None, :] <= pos[:, None, None]
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        o = jnp.einsum("rht,thd->rhd", p, v.astype(jnp.float32))
        return model.logits_of(params, o).astype(jnp.float32)

    return functools.partial(jax.jit(rows), model.params)


def phase_serve(cfg, dtype_name, watch, impl=None, expect="pallas"):
    import jax.numpy as jnp

    from paddle_tpu import serving
    from paddle_tpu.serving.decode_engine import TinyDecodeLM

    dtype = jnp.dtype(dtype_name)
    impl0, began = kernel_impls(), watch.snapshot()
    n_req, n_new = cfg["n_requests"], cfg["new_tokens"]
    pages_per_seq = -(-(cfg["prompt_max"] + n_new) // cfg["page_size"])
    dcfg = serving.DecodeConfig(
        max_batch=n_req, max_new_tokens=n_new,
        page_size=cfg["page_size"],
        num_pages=n_req * pages_per_seq + n_req, n_replicas=1,
        eos_id=1, default_deadline_s=600.0, impl=impl)
    srv = serving.DecodeServer(
        lambda i: TinyDecodeLM(vocab=cfg["vocab"],
                               d_model=cfg["d_model"],
                               num_heads=cfg["num_heads"],
                               head_dim=cfg["head_dim"], seed=SEED,
                               dtype=dtype), dcfg)
    rng = np.random.RandomState(SEED)
    prompts = [rng.randint(2, cfg["vocab"], size=int(n)).astype(np.int32)
               for n in rng.randint(cfg["prompt_min"],
                                    cfg["prompt_max"] + 1, size=n_req)]
    t0 = time.perf_counter()
    srv.start()
    try:
        futures = [srv.submit(p, max_new_tokens=n_new) for p in prompts]
        outs = [[int(t) for t in f.result(timeout=600.0)[0]]
                for f in futures]
        serve_s = time.perf_counter() - t0
        left = srv.drain(timeout=30.0)
        model = srv.replicas[0].model
    finally:
        srv.stop()
    check(left == 0, "serve[%s]: drain left %d requests" % (dtype_name,
                                                            left))
    ok, detail = srv.page_accounting()
    check(ok, "serve[%s]: page accounting: %s" % (dtype_name, detail))
    in_use = sum(r.cache.stats()["in_use_pages"] for r in srv.replicas)
    check(in_use == 0, "serve[%s]: %d pages leaked" % (dtype_name,
                                                       in_use))
    check(srv.stats()["accounted"], "serve[%s]: request accounting"
          % dtype_name)

    # every token against the oracle, on the server's own history: with
    # no difference this IS the free-running greedy comparison (by
    # induction); with one, it names the position and the two logits
    # instead of every token after it
    max_hist = cfg["prompt_max"] + n_new
    oracle = _oracle_rows(model, max_hist, n_new)
    differ = []
    for ri, (p, out) in enumerate(zip(prompts, outs)):
        check(len(out) == n_new or (out and out[-1] == dcfg.eos_id),
              "serve[%s]: request %d answered %d tokens"
              % (dtype_name, ri, len(out)))
        hist = np.zeros((max_hist,), np.int32)
        hist[:len(p)] = p
        hist[len(p):len(p) + len(out)] = out
        logits = np.asarray(oracle(jnp.asarray(hist), len(p)))
        for ti, tok in enumerate(out):
            row = logits[ti]
            want = int(row.argmax())
            if tok != want:
                differ.append({
                    "request": ri, "position": ti, "server": tok,
                    "oracle": want,
                    "oracle_logit_of_server": float(row[tok]),
                    "oracle_logit_of_oracle": float(row[want]),
                    "rel_gap": float((row[want] - row[tok])
                                     / np.abs(row).max())})
    used = impls_since(impl0)
    resolved = check_impl(used, "flash_decode", expect)
    say("serve_" + dtype_name, requests=n_req,
        prompt_lens=[len(p) for p in prompts],
        tokens=sum(len(o) for o in outs), serve_s=round(serve_s, 2),
        equal_to_oracle=not differ, differing=differ[:4],
        n_differing=len(differ), tie_tol=TIE_TOL[dtype_name],
        leaked_pages=in_use, flash_decode_impl=resolved,
        kernel_impls=fmt_impls(used), compiled=watch.since(began),
        first_tokens=outs[0][:8], peak_bytes_in_use=peak_bytes())
    wrong = [d for d in differ if d["rel_gap"] > TIE_TOL[dtype_name]]
    check(not wrong, "serve[%s]: %d tokens differ from the oracle "
          "beyond a tie (rel gap > %g); first: %s"
          % (dtype_name, len(wrong), TIE_TOL[dtype_name], wrong[:1]))


# ---------------------------------------------------------------------------
# phase d: kernels against their references
# ---------------------------------------------------------------------------

def _rel_diff(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape and np.isfinite(got).all(),
          "shape %s vs %s, finite=%s" % (got.shape, ref.shape,
                                         bool(np.isfinite(got).all())))
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def _compare(name, pairs, tol, resolved, **extra):
    """pairs: {label: (got, ref)}.  Reports every measured difference,
    then holds each to the stated tolerance."""
    diffs = {k: _rel_diff(g, r) for k, (g, r) in pairs.items()}
    exact = {k: bool(np.array_equal(np.asarray(g), np.asarray(r)))
             for k, (g, r) in pairs.items()}
    say("kernel_" + name, impl=resolved, rel_max_abs_diff=diffs,
        bit_identical=exact, tol=tol, **extra)
    bad = {k: v for k, v in diffs.items() if v > tol}
    check(not bad, "kernel %s: difference beyond tolerance %g: %s"
          % (name, tol, bad))


def phase_kernels(cfg, watch, impl=None, expect="pallas"):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.ops.epilogue import fc_epilogue
    from paddle_tpu.ops.pallas_conv import conv2d_bn_act, conv2d_epilogue

    key = jax.random.PRNGKey(SEED)
    bf16 = jnp.bfloat16
    began = watch.snapshot()

    def rnd(i, shape, dtype=bf16, scale=1.0):
        return (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale).astype(dtype)

    # -- flash_attention fwd + bwd, bf16 causal ---------------------------
    b, h, t, d = cfg["flash"]
    q, k, v, w = (rnd(i, (b, h, t, d)) for i in range(4))

    def flash_loss(impl_):
        def f(q, k, v):
            o = pk.flash_attention(q, k, v, causal=True, impl=impl_)
            return (o.astype(jnp.float32) * w.astype(jnp.float32)).sum(), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    ((_, o_k), g_k), resolved = run_resolved(
        "flash_attention", expect, lambda: flash_loss(impl)(q, k, v))
    (_, o_r), g_r = flash_loss("xla")(q, k, v)
    _compare("flash_attention", {
        "out": (o_k, o_r), "dq": (g_k[0], g_r[0]),
        "dk": (g_k[1], g_r[1]), "dv": (g_k[2], g_r[2])},
        TOL["bf16"], resolved, shape=[b, h, t, d], dtype="bfloat16",
        causal=True)

    # -- the same heads token-major, [B, T, H*d] as a projection leaves
    # them: the kernels address them in place, and must give what they
    # give head-major (delta is summed in another order: a few ulps)
    def token_major_loss(q, k, v):
        o = pk.flash_attention(q, k, v, causal=True, impl=impl, heads=h)
        return (o.astype(jnp.float32)
                * pk._merge_heads(w).astype(jnp.float32)).sum(), o

    layouts = kernel_impls()
    ((_, o_t), g_t), resolved = run_resolved(
        "flash_attention", expect, lambda: jax.jit(jax.value_and_grad(
            token_major_loss, argnums=(0, 1, 2), has_aux=True))(
                *(pk._merge_heads(x) for x in (q, k, v))))
    layouts = impls_since(layouts)
    check(layouts.get(("flash_attention_layout", "token_major"))
          and not layouts.get(("flash_attention_layout", "head_major")),
          "flash_attention on [B, T, H*d] did not address the heads in "
          "place: %s" % fmt_impls(layouts))
    _compare("flash_attention_token_major", {
        "out": (o_t, pk._merge_heads(o_k)),
        **{n: (g, pk._merge_heads(r))
           for n, g, r in zip(("dq", "dk", "dv"), g_t, g_k)}},
        TOL["bf16"], resolved, shape=[b, t, h * d], dtype="bfloat16",
        causal=True)

    # -- flash_decode against flash_decode_reference ----------------------
    dc = cfg["decode"]
    nb, nh, hd, ps, mp = (dc["batch"], dc["heads"], dc["head_dim"],
                          dc["page_size"], dc["max_pages"])
    rng = np.random.RandomState(SEED)
    lens = rng.randint(1, mp * ps + 1, size=nb).astype(np.int32)
    tables = rng.permutation(nb * mp).reshape(nb, mp).astype(np.int32)
    for dt_name, dt in (("float32", jnp.float32), ("bfloat16", bf16)):
        qd = rnd(10, (nb, nh, hd), dt)
        kp = rnd(11, (nb * mp + 1, nh, ps, hd), dt)
        vp = rnd(12, (nb * mp + 1, nh, ps, hd), dt)
        got, resolved = run_resolved(
            "flash_decode", expect, lambda: pk.flash_decode(
                qd, kp, vp, jnp.asarray(tables), jnp.asarray(lens),
                impl=impl))
        ref = pk.flash_decode_reference(qd, kp, vp, jnp.asarray(tables),
                                        jnp.asarray(lens))
        _compare("flash_decode_" + dt_name, {"out": (got, ref)},
                 TOL["bf16" if dt == bf16 else "f32"], resolved,
                 batch=nb, heads=nh, head_dim=hd, page_size=ps,
                 max_pages=mp)

    # -- conv2d_epilogue / conv2d_bn_act, NHWC bf16, mb128 ----------------
    for tag, (n, hh, ww, cin, cout), ksz, pad in (
            ("3x3", cfg["conv3x3"], 3, 1), ("1x1", cfg["conv1x1"], 1, 0)):
        x = rnd(20, (n, hh, ww, cin))
        wt = rnd(21, (cout, cin, ksz, ksz), scale=1.0 / math.sqrt(
            cin * ksz * ksz))
        bias = rnd(22, (cout,), jnp.float32, 0.1)
        res = rnd(23, (n, hh, ww, cout))
        gamma = 1.0 + rnd(24, (cout,), jnp.float32, 0.1)
        beta = rnd(25, (cout,), jnp.float32, 0.1)

        def ep(impl_):
            return jax.jit(lambda x, wt, bias, res: conv2d_epilogue(
                x, wt, bias, res, strides=(1, 1), paddings=(pad, pad),
                act="relu", impl=impl_))

        got, resolved = run_resolved(
            "conv2d_epilogue", expect,
            lambda: ep(impl)(x, wt, bias, res))
        _compare("conv2d_epilogue_" + tag,
                 {"out": (got, ep("xla")(x, wt, bias, res))},
                 TOL["bf16"], resolved, x=[n, hh, ww, cin], cout=cout)

        def bn(impl_):
            return jax.jit(lambda x, wt, g, bt, res: conv2d_bn_act(
                x, wt, g, bt, None, res, strides=(1, 1),
                paddings=(pad, pad), act="relu", impl=impl_))

        got, resolved = run_resolved(
            "conv2d_bn_stats", expect,
            lambda: bn(impl)(x, wt, gamma, beta, res))
        ref = bn("xla")(x, wt, gamma, beta, res)
        _compare("conv2d_bn_act_" + tag,
                 {"out": (got[0], ref[0]), "mean": (got[1], ref[1]),
                  "var": (got[2], ref[2])},
                 TOL["bf16"], resolved, x=[n, hh, ww, cin], cout=cout)

    # -- fc_epilogue: matmul + bias + relu --------------------------------
    m, kk, nn = cfg["fc"]
    x2 = rnd(30, (m, kk))
    w2 = rnd(31, (kk, nn), scale=1.0 / math.sqrt(kk))
    b2 = rnd(32, (nn,), jnp.float32, 0.1)

    def fc(impl_):
        return jax.jit(lambda x2, w2, b2: fc_epilogue(
            x2, w2, b2, act="relu", impl=impl_))

    got, resolved = run_resolved("fc_epilogue", expect,
                                 lambda: fc(impl)(x2, w2, b2))
    _compare("fc_epilogue", {"out": (got, fc("xla")(x2, w2, b2))},
             TOL["bf16"], resolved, m=m, k=kk, n=nn)
    say("kernels", compiled=watch.since(began))


# ---------------------------------------------------------------------------
# --chips 4: one shard_program step over dp2 x tp2
# ---------------------------------------------------------------------------

def build_gspmd_transformer(cfg, sharded, devices=None):
    """The Transformer-base train program, and — `sharded` — the SAME
    program as ONE pjit step over MeshPlan(dp, tp) on `devices`
    (default: all jax sees).  Returns (compiled, model, feed)."""
    import paddle_tpu as fluid
    from tools.gate_programs import TRANSFORMER_BASE as c
    from tools.gate_programs import _fresh_programs
    from paddle_tpu import optimizer
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.flags import set_flags
    from paddle_tpu.models.transformer import transformer_encoder_model

    _fresh_programs()
    set_flags({"gspmd": bool(sharded)})
    np.random.seed(SEED)
    model = transformer_encoder_model(
        vocab_size=c["vocab"], max_len=cfg["seq"], d_model=c["d_model"],
        n_head=c["n_head"], d_inner=c["d_inner"],
        n_layer=cfg["n_layer"], dropout_rate=0.0,
        # the tp name grammar needs deterministic parameter names; the
        # unsharded side takes the same names so both start from the
        # same seeded weights
        param_prefix="tfm")
    decorate(optimizer.Adam(learning_rate=1e-4), init_loss_scaling=1.0,
             use_dynamic_loss_scaling=False).minimize(model["loss"])
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    if sharded:
        from paddle_tpu.parallel.gspmd import MeshPlan
        from paddle_tpu.transpiler import shard_program

        compiled = shard_program(
            compiled, MeshPlan(dp=cfg["dp"], tp=cfg["tp"]),
            loss_name=model["loss"].name, devices=devices)
    ids = np.random.RandomState(SEED).randint(
        0, c["vocab"], (cfg["batch"], cfg["seq"], 1)).astype(np.int64)
    return compiled, model, {"src_ids": ids, "tgt_label": ids}


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "all-to-all", "collective-permute")


def count_collectives(text):
    import re

    return {c: len(re.findall(r"= [^=\n]*\b%s(?:-start)?\(" % c, text))
            for c in _COLLECTIVES}


def phase_gspmd(cfg, watch, platform, flash_impl="pallas"):
    import jax

    from tools.gate_programs import TRANSFORMER_BASE as c
    from paddle_tpu.core.scope import global_scope

    dp, tp = cfg["dp"], cfg["tp"]
    q_name = "tfm_l0_self_q.w"
    # the (dp, tp) shard shapes tests/test_gspmd.py asserts
    want = {q_name: (c["d_model"] // dp, c["d_model"] // tp),
            q_name + "_moment1": (c["d_model"] // dp, c["d_model"] // tp),
            "tfm_emb.w": (c["vocab"] // dp, c["d_model"])}

    def spread(compiled, used, text, mem):
        # flash really ran under shard_map (a failed gate falls back to
        # the plain call without a word)
        check(used.get(("flash_attention_gspmd", "shard_map"), 0) > 0
              and not used.get(("flash_attention_gspmd", "plain")),
              "gspmd: flash_attention did not run under shard_map: %s"
              % fmt_impls(used))
        # parameters and Adam moments really spread: four distinct
        # devices, each holding its (dp, tp) shard
        shards = {}
        for prefix, shape in want.items():
            gb = compiled._program.global_block()
            n = prefix if prefix in gb.vars else next(
                v for v in gb.vars if v.startswith(prefix))
            arr = global_scope().find_var(n).get()
            devs = {sh.device for sh in arr.addressable_shards}
            shapes = {tuple(sh.data.shape)
                      for sh in arr.addressable_shards}
            check(len(devs) == dp * tp,
                  "gspmd: %s lives on %d device(s): %s"
                  % (n, len(devs), sorted(map(str, devs))))
            check(shapes == {shape}, "gspmd: %s shard shapes %s, "
                  "expected %s" % (n, shapes, shape))
            shards[n] = {"global": list(arr.shape), "shard": list(shape),
                         "devices": len(devs)}
        return {"shards": shards,
                "collectives": count_collectives(text),
                "per_device_argument_bytes": getattr(
                    mem, "argument_size_in_bytes", None)}

    def on_chip_0(compiled, used, text, mem):
        arr = global_scope().find_var(q_name).get()
        devs = {sh.device for sh in arr.addressable_shards}
        check(devs == {jax.devices()[0]}, "gspmd: the unsharded side "
              "is not on chip 0: %s" % sorted(map(str, devs)))
        return {}

    results = {}
    for side, more in (("sharded", spread), ("unsharded", on_chip_0)):
        compiled, model, feed = build_gspmd_transformer(
            cfg, side == "sharded")
        results[side] = _train_steps(
            "gspmd_" + side, model["loss"], feed, cfg["steps"], watch,
            platform, cfg["custom_calls"], flash_impl=flash_impl,
            compiled=compiled, more=more)
    # bf16 activations, f32 master weights; the sharded side sums
    # partial products in another order (row-parallel psum, dp mean)
    rtol = 2e-2
    np.testing.assert_allclose(results["sharded"], results["unsharded"],
                               rtol=rtol)
    say("gspmd_compare", sharded=results["sharded"],
        unsharded=results["unsharded"], rtol=rtol, allclose=True)


# ---------------------------------------------------------------------------

def run(sizes, chips, watch, platform, kernel_impl=None,
        expect_impl="pallas", flash_impl="pallas"):
    """Every phase after the device check.  `kernel_impl` /
    `expect_impl` / `flash_impl` are what the CPU rehearsal steers
    (interpret mode, XLA attention); the chip run leaves them alone."""
    if chips == 4:
        phase_gspmd(sizes["gspmd"], watch, platform,
                    flash_impl=flash_impl)
        return
    phase_train_transformer(sizes["transformer"], watch, platform,
                            flash_impl=flash_impl)
    phase_train_resnet(sizes["resnet"], watch, platform)
    for dtype_name in ("float32", "bfloat16"):
        phase_serve(sizes["serve"], dtype_name, watch, impl=kernel_impl,
                    expect=expect_impl)
    phase_kernels(sizes["kernels"], watch, impl=kernel_impl,
                  expect=expect_impl)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the dp2 x tp2 shard_program step and "
                         "the unsharded step it is compared with")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    info = None
    try:
        import paddle_tpu as fluid

        # nothing goes to stdout before the device check has passed
        info = phase_device(args.chips)
        watch = CompileWatch()
        cache_dir = fluid.enable_compile_cache()
        say("start", cache_dir=cache_dir, native=fluid.native.NATIVE,
            left_out="stride-2 and 7x7-stem Pallas convs (~50 s each "
                     "to compile, default-off flags)")
        run(REAL, args.chips, watch, info["platform"])
        total = watch.since((0, 0.0, 0, 0))
        say("done", wall_s=round(time.perf_counter() - t0, 1),
            compiled=total, claim=None)
    except BaseException as e:
        print(json.dumps({"ok": False, "device": info,
                          "error": "%s: %s" % (type(e).__name__, e)}),
              file=sys.stderr, flush=True)
        raise
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
