"""What a run observes about the program from outside: jax's compile
events, the program's own registry (counts and the enqueue-time
histogram), the bytes a compiled step holds, and the profiler.

CompileWatch and step_program are copies of chip_smoke.py's
CompileWatch and compiled_text (PERF.md section 6 lists the originals
for a later PR to delete).
"""

from __future__ import annotations

import glob
import os
import shutil


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
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    def since(self, snap):
        now = self.snapshot()
        return {k: now[k] - snap[k] for k in now}


def _instrument(name):
    from paddle_tpu.observability import metrics

    return metrics.registry().get(name)


def counter_total(name):
    """Sum over the series of one of the program's counters (0 when
    the program has not made it yet)."""
    c = _instrument(name)
    return 0 if c is None else sum(v for _, v in c.items())


def kernel_impls():
    """{(kernel, impl): count} from paddle_tpu_kernel_impl_total."""
    c = _instrument("paddle_tpu_kernel_impl_total")
    if c is None:
        return {}
    return {(lbl["kernel"], lbl["impl"]): int(v) for lbl, v in c.items()}


def histogram_count_sum(name):
    """(count, sum) over the series of one of the program's
    histograms."""
    h = _instrument(name)
    if h is None:
        return 0, 0.0
    summaries = [s for _, s in h.items()]
    return (sum(s["count"] for s in summaries),
            sum(s["sum"] for s in summaries))


def step_program(compiled, feed):
    """(hlo_text, memory) of the executable CompiledProgram runs for
    this feed: the jitted step it cached, lowered with the avals of the
    state the scope holds now and compiled.  jit finds the executable
    it already has, so nothing compiles (the loop kind counts, and
    tests/test_rehearsal.py checks).  `memory` is what
    memory_analysis() says one device holds for the step, in bytes:
    argument + output + temp - alias (donated state is counted once)."""
    import jax
    import numpy as np

    from paddle_tpu.core.scope import global_scope

    fns = [v for v in compiled._cache.values() if callable(v)]
    if len(fns) != 1:
        raise RuntimeError("expected ONE cached jitted step, found %d"
                           % len(fns))
    state, sharded = {}, getattr(compiled, "_mesh", None) is not None
    for n in compiled._persistable_names:
        v = global_scope().find_var(n).get()
        # a sharded step: with the sharding the array lives on.  An
        # aval carries its mesh (jax 0.9), and without it the step is
        # traced and compiled again, as another module than the one
        # that ran.  A one-device step: without, for the same reason
        # (a named device makes the argument a committed one).
        state[n] = jax.ShapeDtypeStruct(
            np.shape(v), v.dtype,
            sharding=v.sharding if sharded else None)
    block = compiled._program.global_block()
    feeds = {k: jax.ShapeDtypeStruct(
        np.shape(v), jax.dtypes.canonicalize_dtype(block.var(k).dtype))
        for k, v in feed.items()}
    exe = fns[0].lower(state, feeds).compile()
    mem = exe.memory_analysis()
    fields = {k: int(getattr(mem, k + "_size_in_bytes", 0) or 0)
              for k in ("argument", "output", "temp", "alias",
                        "generated_code")}
    fields["step_bytes"] = (fields["argument"] + fields["output"]
                            + fields["temp"] - fields["alias"])
    return exe.as_text(), fields


class Profiler:
    """jax's profiler around a short stretch, host tracer only (the
    Python tracer would slow every call of the loop).  stop() returns
    the path of the .xplane.pb; remove() deletes the directory."""

    def __init__(self, directory):
        self.directory = directory

    def start(self):
        import jax
        from jax.profiler import ProfileOptions

        shutil.rmtree(self.directory, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=opts)

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        if len(found) != 1:
            raise RuntimeError("expected one .xplane.pb under %s, found "
                               "%s" % (self.directory, found))
        return found[0]

    def remove(self):
        shutil.rmtree(self.directory, ignore_errors=True)
