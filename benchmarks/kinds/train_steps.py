"""Loop kind `train_steps`: what a Fluid trainer writes, and nothing
shorter.

    loader = DataLoader.from_generator(feed_list=[...], capacity=8)
    loader.decorate_batch_generator(pool_cycle)
    for feed in loader:
        loss, = exe.run(compiled, feed=feed, fetch_list=[loss])

A closed loop of one client: the next step is issued when the last loss
has arrived.  The clock is the host's, each step ends in the fetched
loss, and the window's rate is steps x items per step over (end of the
last step - start of the first).  The pool of `pool` distinct batches
is drawn from the seed during set-up and yielded in turn: the
generator's cost is the host-to-device copy a real input pipeline also
pays, not random-number generation.

Job parameters (the cell's traffic file): batch, rate_metric (the
end-to-end name the rate is reported under), plus what the
configuration's builder reads (seq_len, mesh).
"""

from __future__ import annotations

import math
import time

import numpy as np

POOL = 8                   # distinct batches, yielded in turn
CAPACITY = 8               # the loader's queue of host batches
FIXED_BATCH_STEPS = 3      # pool batch 0, fed this often first
WARMUP_STEPS = 2           # further steps before the window
TRACE_STEPS = 20           # the traced stretch: this many steps
TRACE_SECONDS = 5.0        # or this long, whichever is shorter
LOSS_STEPS_KEPT = 8        # window losses written to the record


def _fresh_programs():
    """New default programs, scope and names: one process may run
    several cells (the rehearsal test does)."""
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core.program import Program
    from paddle_tpu.parallel import env as penv

    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    scope_mod._global_scope = scope_mod.Scope()
    penv.reset()


def _scope_get(name):
    from paddle_tpu.core.scope import global_scope

    var = global_scope().find_var(name)
    if var is None or var.get() is None:
        raise KeyError(name)
    return var.get()


def _off_sharding(compiled):
    """Persistables that do not live on the sharding the program
    declares for them (a program without a mesh declares none)."""
    if getattr(compiled, "_mesh", None) is None:
        return []
    bad = []
    for n in compiled._persistable_names:
        v = _scope_get(n)
        want = compiled._state_named_sharding(n, np.shape(v))
        if not (hasattr(v, "sharding")
                and want.is_equivalent_to(v.sharding, v.ndim)):
            bad.append(n)
    return bad


def run(ctx):
    """ctx: config, job, seed, seconds, trace, clock_start, devices,
    load(kind, name), flops, observe, trace_reduce, scratch_dir,
    say(**fields).  Returns the measurement the harness turns into
    metrics."""
    import paddle_tpu as fluid
    from paddle_tpu.reader import DataLoader

    config, job, obs = ctx["config"], ctx["job"], ctx["observe"]
    say, now = ctx["say"], time.perf_counter
    watch = obs.CompileWatch()
    impls0 = obs.kernel_impls()
    # imports, reaching the chip, reading the cell's files
    before_build_s = now() - ctx["clock_start"]

    # -- build: layers -> minimize -> transpile -> CompiledProgram --------
    _fresh_programs()
    np.random.seed(ctx["seed"])      # initializers draw from np.random
    t = now()
    built = ctx["load"]("builders", config["builder"]).build(
        config, job, ctx["flops"])
    build_s = now() - t
    compiled, loss_var = built["compiled"], built["loss"]
    exe = fluid.Executor(fluid.TPUPlace())
    t = now()
    exe.run(fluid.default_startup_program())
    startup_s = now() - t

    # -- inputs from the seed ------------------------------------------
    t = now()
    rng = np.random.default_rng(ctx["seed"])
    pool = [built["make_batch"](rng) for _ in range(POOL)]
    pool_s = now() - t
    names = [v.name for v in built["feed_list"]]

    # -- the plain reference on pool batch 0, before step 1 donates and
    # changes the weights -------------------------------------------------
    t = now()
    ref = ctx["load"]("reference", config["reference"])
    ref_loss = ref.loss(ref.read_params(config, _scope_get), pool[0],
                        config)
    reference_s = now() - t

    def batches():
        for _ in range(FIXED_BATCH_STEPS):
            yield pool[0]
        i = 1
        while True:
            yield pool[i % len(pool)]
            i += 1

    loader = DataLoader.from_generator(feed_list=built["feed_list"],
                                       capacity=CAPACITY)
    loader.decorate_batch_generator(batches)
    feeds = iter(loader)

    def step():
        t0 = now()
        feed = next(feeds)
        t1 = now()
        loss, = exe.run(compiled, feed=feed, fetch_list=[loss_var])
        return t0, t1, now(), float(np.asarray(loss).reshape(-1)[0])

    try:
        # -- warm-up: compiles (or loads) the one shape of this cell --
        t = now()
        warm = [step() for _ in range(FIXED_BATCH_STEPS)]
        first_step_s = warm[0][2] - warm[0][0]
        off_sharding = _off_sharding(compiled)
        warm += [step() for _ in range(WARMUP_STEPS)]
        warmup_s = now() - t
        warm_losses = [w[3] for w in warm]
        setup = watch.snapshot()
        compiles0 = obs.counter_total("paddle_tpu_executor_compiles_total")
        enq0 = obs.histogram_count_sum("paddle_tpu_executor_step_seconds")

        # -- the measured window, profiler off --------------------------
        steps, failed = [], 0
        start = now()
        setup_s = start - ctx["clock_start"]
        while True:
            try:
                rec = step()
            except Exception as e:          # a failed step is counted
                say(event="step_failed", error=repr(e)[:300])
                rec = (now(), now(), now(), float("nan"))
            steps.append(rec)
            failed += not math.isfinite(rec[3])
            if rec[2] - start >= ctx["seconds"]:
                break
        window_s = steps[-1][2] - steps[0][0]
        enq1 = obs.histogram_count_sum("paddle_tpu_executor_step_seconds")
        in_window = watch.since(setup)
        in_window["executor_compiles"] = obs.counter_total(
            "paddle_tpu_executor_compiles_total") - compiles0

        # -- a short traced stretch, its own steps ----------------------
        traced_xplane, traced_steps, prof = None, 0, None
        if ctx["trace"]:
            from jax.profiler import TraceAnnotation

            prof = obs.Profiler(ctx["scratch_dir"])
            prof.start()
            t = now()
            while traced_steps < TRACE_STEPS and \
                    now() - t < TRACE_SECONDS:
                # exe.run's two halves apart, so that the trace can say
                # which one the device waited for: the enqueue
                # (return_numpy=False returns when the step is issued)
                # and the fetch (np.asarray, what return_numpy=True does)
                with TraceAnnotation("bm:step"):
                    with TraceAnnotation("bm:next"):
                        feed = next(feeds)
                    with TraceAnnotation("bm:enqueue"):
                        out, = exe.run(compiled, feed=feed,
                                       fetch_list=[loss_var],
                                       return_numpy=False)
                    with TraceAnnotation("bm:fetch"):
                        np.asarray(out)
                traced_steps += 1
            traced_xplane = prof.stop()
    finally:
        feeds.stop()

    # -- the compiled step's own report, last ------------------------------
    t, before = now(), watch.snapshot()
    hlo_text, memory = obs.step_program(
        compiled, dict(zip(names, pool[0])))
    memory_analysis_s = now() - t
    # 0 where jit found the executable that ran; a compile here means
    # the text and the bytes are another module's
    memory["recompiled"] = watch.since(before)["compiles"]
    reduced = None
    if traced_xplane is not None:
        reduced = ctx["trace_reduce"].reduce(
            ctx["trace_reduce"].read_xplane(traced_xplane), hlo_text)
        prof.remove()
    if reduced is not None:
        first = reduced["devices"][reduced["first"]]
        say(event="trace", steps=first["steps"],
            category_ms_per_step={k: v / first["steps"] / 1e6
                                  for k, v in first["category_ns"].items()},
            # traced against untraced: the tracing overhead
            traced_step_ms=first["window_ns"] / first["steps"] / 1e6,
            untraced_step_ms=window_s / len(steps) * 1e3,
            host_offset_ns=first["host_offset_ns"],
            host_offset_slack_ns=first["host_offset_slack_ns"])

    # -- correctness --------------------------------------------------------
    used = {k: v - impls0.get(k, 0) for k, v in obs.kernel_impls().items()
            if v - impls0.get(k, 0)}
    want_impls = config.get("kernel_impls", {})
    wrong_impls = {
        kernel: sorted(i for (k, i) in used if k == kernel)
        for kernel, impl in want_impls.items()
        if {i for (k, i) in used if k == kernel} != {impl}}
    tol = config["reference_rtol"]
    fixed = warm_losses[:FIXED_BATCH_STEPS]
    checks = {
        "reference": abs(fixed[0] - ref_loss) <= tol * abs(ref_loss),
        "falling": all(b < a for a, b in zip(fixed, fixed[1:])),
        "finite": failed == 0 and all(map(math.isfinite, warm_losses)),
        "no_compile_in_window": in_window["compiles"] == 0
        and in_window["executor_compiles"] == 0,
        "kernel_impls": not wrong_impls,
        "on_declared_sharding": not off_sharding,
    }
    say(event="correctness", checks=checks, reference_loss=ref_loss,
        first_loss=fixed[0], rel_diff=abs(fixed[0] - ref_loss)
        / abs(ref_loss), rtol=tol, fixed_batch_losses=fixed,
        wrong_impls=wrong_impls, off_sharding=off_sharding[:5],
        kernel_impls={"%s:%s" % k: v for k, v in sorted(used.items())})
    losses = {"warmup": warm_losses,
              "window_first": [s[3] for s in steps[:LOSS_STEPS_KEPT]]}
    say(event="losses", **losses)
    say(event="setup", setup_s=setup_s, before_build_s=before_build_s,
        build_s=build_s,
        startup_s=startup_s, pool_s=pool_s, reference_s=reference_s,
        warmup_s=warmup_s, first_step_s=first_step_s,
        memory_analysis_s=memory_analysis_s, compile=setup)

    step_s = np.array([s[2] - s[0] for s in steps])
    feed_wait_s = np.array([s[1] - s[0] for s in steps])
    rate = len(steps) * built["items_per_step"] / window_s
    return {
        "correct": all(checks.values()), "checks": checks,
        "attempted": len(steps), "failed": int(failed),
        "end_to_end": {job["rate_metric"]: rate, "setup_s": setup_s},
        "clocks": {"build_s": build_s, "step_s": step_s,
                   "feed_wait_s": feed_wait_s, "window_s": window_s,
                   "rate": rate},
        "counters": {"compile_s": setup["compile_s"],
                     "enqueue_count": enq1[0] - enq0[0],
                     "enqueue_sum_s": enq1[1] - enq0[1],
                     "in_window": in_window},
        "work": {"items_per_step": built["items_per_step"],
                 "flops_per_item": built["flops_per_item"],
                 "kernel_work": built["kernel_work"]},
        "memory": memory, "trace": reduced,
        # for benchmarks/out/: what a later PR compares or looks into
        "record": {"losses": losses, "checks": checks, "memory": memory,
                   "steps": {"start_s": [s[0] - start for s in steps],
                             "feed_wait_ms": (feed_wait_s * 1e3).tolist(),
                             "step_ms": (step_s * 1e3).tolist()}},
    }
