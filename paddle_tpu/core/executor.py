"""Serial op-by-op executor — the debug/eager path.

Reference parity:
  - Executor::Run/Prepare/RunPreparedContext:
    /root/reference/paddle/fluid/framework/executor.cc:150,327,375-438
    (hot loop :416 "for op in ops: op->Run(scope, place)")
  - feed/fetch: framework/feed_fetch_method.cc; python feed injection
    python/paddle/fluid/executor.py:397
  - python Executor.run: python/paddle/fluid/executor.py:566

TPU-first difference: each op's compute is a JAX function dispatched eagerly;
there is no kernel-choice/data-transfer machinery (operator.cc:916-940)
because XLA owns placement.  The performance path is CompiledProgram
(compiler.py), which traces the same IR into one XLA module — this
interpreter exists for debugging, host-only ops, and numeric cross-checks
(the reference's OpTest dual-run pattern).
"""

from __future__ import annotations

import numpy as np

from paddle_tpu.core.program import BlockRef, Program
from paddle_tpu.core.registry import get_op_def
from paddle_tpu.core.scope import Scope, SelectedRows, global_scope
from paddle_tpu.core.types import CPUPlace, Place

# op types executed by a python handler instead of a registry compute
# (control flow, feed/fetch, readers, host IO).
_SPECIAL_OPS: dict = {}


def register_special_op(type: str):
    def deco(fn):
        _SPECIAL_OPS[type] = fn
        return fn

    return deco


class RuntimeCtx:
    """Handed to special-op handlers so control-flow ops can run sub-blocks."""

    def __init__(self, executor, program, scope, place, feed, fetch_results):
        self.executor = executor
        self.program = program
        self.scope = scope
        self.place = place
        self.feed = feed or {}
        self.fetch_results = fetch_results

    def run_block(self, block_idx: int, scope: Scope):
        block = self.program.blocks[block_idx]
        self.executor._run_block(block, scope, self)


class Executor:
    """reference: python/paddle/fluid/executor.py:294

    ``place`` is kept for API parity and decides nothing: XLA's default
    device (jax.devices()[0]) owns placement on both the interpreted and
    the compiled path, whatever Place is passed (core/types.py)."""

    def __init__(self, place: Place = None):
        self.place = place if place is not None else CPUPlace()

    # ------------------------------------------------------------------ public
    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        scope=None,
        return_numpy=True,
    ):
        from paddle_tpu import framework
        from paddle_tpu.core.compiler import CompiledProgram

        if program is None:
            program = framework.default_main_program()
        if scope is None:
            scope = global_scope()
        popt = getattr(program, "_pipeline_opt", None)
        if popt is not None:
            from paddle_tpu.parallel.pipeline import PipelineRunner

            runner = popt.get("_runner")
            if runner is None:
                runner = PipelineRunner(
                    program, popt["sections"], popt["loss_stage"],
                    popt["loss_name"], popt["num_microbatches"], scope,
                    shared=popt.get("shared"),
                    schedule=popt.get("schedule", "gpipe"))
                popt["_runner"] = runner
            elif runner.scope is not scope:
                # keep the jitted per-stage functions; just re-point the
                # scope and force a state re-pull
                runner.scope = scope
                runner._state = None
            return runner.run(feed or {}, fetch_list or [], return_numpy)
        if isinstance(program, CompiledProgram):
            feed = dict(feed or {})
            # program-integrated py_reader: the host-only read op is
            # skipped in the XLA trace; its outputs arrive as ordinary
            # (already device-resident, prefetched) feeds
            from paddle_tpu import reader as reader_mod

            reader_mod.augment_feed_from_readers(program._program, feed)
            return program._run(self, feed, fetch_list or [], scope,
                                return_numpy)
        return self._run_interpreted(
            program, feed or {}, fetch_list or [], scope, return_numpy
        )

    # -------------------------------------------------------------- internals
    def _run_interpreted(self, program: Program, feed, fetch_list, scope,
                         return_numpy):
        self._feed_data(program, feed, scope)
        fetch_results = {}
        ctx = RuntimeCtx(self, program, scope, self.place, feed,
                         fetch_results)
        self._run_block(program.global_block(), scope, ctx)
        out = self._fetch(fetch_list, scope, return_numpy)
        # trainer fleet push (ISSUE 12): an Executor.run IS the
        # trainer's step boundary on the op-at-a-time path (the
        # compiled path hooks inside CompiledProgram.step); cost when
        # off is one None check + one memo check
        from paddle_tpu.observability import collector as _collector

        _collector.maybe_step_push()
        return out

    def _feed_data(self, program, feed, scope):
        import jax
        import jax.numpy as jnp

        block = program.global_block()
        for name, value in feed.items():
            if isinstance(value, jax.Array):
                # device-resident (e.g. DeviceFeeder-prefetched): no host
                # round-trip, just dtype coercion
                if block.has_var(name):
                    v = block.var(name)
                    if v.dtype is not None:
                        target = jax.dtypes.canonicalize_dtype(
                            np.dtype(v.dtype))
                        if value.dtype != target:
                            value = value.astype(target)
            elif hasattr(value, "__array__") or isinstance(
                value, (list, tuple, int, float)
            ):
                arr = np.asarray(value)
                if block.has_var(name):
                    v = block.var(name)
                    if v.dtype is not None and arr.dtype != np.dtype(v.dtype):
                        arr = arr.astype(v.dtype)
                value = jnp.asarray(arr)
            scope.var(name).set(value)

    def _run_block(self, block, scope: Scope, ctx: RuntimeCtx):
        for op in block.ops:
            self._run_op(op, block, scope, ctx)

    def _run_op(self, op, block, scope: Scope, ctx: RuntimeCtx):
        from paddle_tpu import flags

        if flags.get_flag("profile_ops"):
            from paddle_tpu import profiler

            with profiler.RecordEvent(op.type):
                self._run_op_inner(op, block, scope, ctx)
        else:
            self._run_op_inner(op, block, scope, ctx)
        if flags.get_flag("check_nan_inf"):
            self._check_nan_inf(op, scope)

    def _check_nan_inf(self, op, scope):
        """reference FLAGS_check_nan_inf sweep (operator.cc:953-983)."""
        import jax.numpy as jnp

        for names in op.outputs.values():
            for n in names:
                var = scope.find_var(n)
                if var is None:
                    continue
                val = var.get()
                if val is None or not hasattr(val, "dtype"):
                    continue
                if jnp.issubdtype(val.dtype, jnp.floating) and \
                        not bool(jnp.all(jnp.isfinite(val))):
                    raise FloatingPointError(
                        f"NaN/Inf in output '{n}' of op {op.type} "
                        f"({op!r})")

    def _run_op_inner(self, op, block, scope: Scope, ctx: RuntimeCtx):
        special = _SPECIAL_OPS.get(op.type)
        if special is not None:
            special(op, block, scope, ctx)
            return
        op_def = get_op_def(op.type)
        ins = {}
        for slot, names in op.inputs.items():
            vals = []
            for n in names:
                var = scope.find_var(n)
                if var is None or var.get() is None:
                    vals.append(None)
                else:
                    vals.append(var.get())
            if slot in op_def.duplicable:
                if any(v is None for v in vals):
                    if slot in op_def.optional:
                        continue
                    missing = [
                        n for n, v in zip(names, vals) if v is None
                    ]
                    raise RuntimeError(
                        f"op {op.type}: input slot {slot} vars {missing}"
                        " are unset"
                    )
                ins[slot] = vals
            else:
                val = vals[0] if vals else None
                if val is None:
                    if slot in op_def.optional or not names:
                        continue
                    raise RuntimeError(
                        f"op {op.type}: input '{names[0]}' (slot {slot})"
                        " is unset"
                    )
                ins[slot] = val
        try:
            outs = op_def.compute(ins, op.attrs)
        except Exception as e:
            raise RuntimeError(
                f"error running op {op.type} ({op!r}): {e}"
            ) from e
        if outs is None:
            outs = {}
        for slot, names in op.outputs.items():
            if slot not in outs:
                continue
            vals = outs[slot]
            if not isinstance(vals, (list, tuple)):
                vals = [vals]
            for n, v in zip(names, vals):
                scope.var(n).set(v)

    def _fetch(self, fetch_list, scope, return_numpy):
        results = []
        for f in fetch_list:
            name = f if isinstance(f, str) else f.name
            var = scope.find_var(name)
            if var is None:
                raise RuntimeError(f"fetch variable '{name}' not found")
            val = var.get()
            if val is None:
                # e.g. deleted by a delete_var op (release_memory without
                # the fetch target in skip_opt_set) — fail loudly instead
                # of returning a None-valued object array
                raise RuntimeError(
                    f"fetch variable '{name}' has no value (was it "
                    "garbage-collected by release_memory/delete_var? add "
                    "it to skip_opt_set)")
            if return_numpy:
                if isinstance(val, SelectedRows):
                    val = np.asarray(val.to_dense())
                else:
                    val = np.asarray(val)
            results.append(val)
        return results

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Dataset-driven training (reference executor.py:927
        train_from_dataset -> framework/executor.cc:120 RunFromDataset).

        The reference spawns a DeviceWorker thread per core, each
        interpreting the program over its file shard (Hogwild).  Here the
        dataset's reader threads + native parser produce batches, a
        DeviceFeeder double-buffers them onto the device (reference
        buffered_reader.cc), and ONE compiled program consumes them —
        thread-level compute parallelism is replaced by XLA batch/mesh
        parallelism (SURVEY.md §3.4)."""
        from paddle_tpu import framework
        from paddle_tpu.reader import DeviceFeeder
        from paddle_tpu.trainer_desc import TrainerFactory

        if dataset is None:
            raise ValueError("dataset is required")
        if program is None:
            program = framework.default_main_program()
        if scope is None:
            scope = global_scope()
        if thread:
            dataset.set_thread(thread)
        fetch_list = fetch_list or []
        fetch_info = fetch_info or [
            (f if isinstance(f, str) else f.name) for f in fetch_list]
        # build the trainer descriptor from program._fleet_opt exactly like
        # reference executor.py:927 (_prepare_trainer): it selects the
        # trainer/device-worker pair and validates pipeline/PS programs
        trainer = TrainerFactory()._create_trainer(
            getattr(program, "_fleet_opt", None))
        trainer._set_program(program)
        trainer._set_thread(thread or dataset._thread)
        trainer._set_debug(debug)
        trainer._set_fetch_var_and_info(fetch_list, fetch_info, print_period)
        trainer._gen_trainer_desc()
        # Downpour: the async PS worker loop owns pull/compute/push
        # (reference DownpourWorker::TrainFiles, downpour_worker.cc:369)
        opt_info = getattr(program, "_fleet_opt", None) or {}
        runner = opt_info.get("downpour_runner")
        if runner is None and \
                opt_info.get("device_worker") == "DownpourSGD":
            t = opt_info.get("transpiler")
            if t is None:
                # fall back to the fleet role contract (reference: the
                # pslib fleet init is what wires DownpourWorker to its
                # parameter servers)
                from paddle_tpu.fleet import fleet
                from paddle_tpu.transpiler import (
                    DistributeTranspiler, DistributeTranspilerConfig)

                rm = getattr(fleet, "_role_maker", None)
                eps = ",".join(rm.get_pserver_endpoints()) if rm else ""
                if not eps:
                    raise RuntimeError(
                        "DownpourSGD device worker needs parameter "
                        "servers: fleet.init(role_maker) with pserver "
                        "endpoints, or put a configured "
                        "DistributeTranspiler in "
                        "program._fleet_opt['transpiler'] (async "
                        "mode), or a ready DownpourRunner in "
                        "['downpour_runner']")
                cfg = DistributeTranspilerConfig()
                cfg.sync_mode = False
                t = DistributeTranspiler(cfg)
                t.transpile(rm.worker_index(), program=program,
                            pservers=eps, trainers=rm.worker_num(),
                            sync_mode=False)
                opt_info["transpiler"] = t
            from paddle_tpu.distributed.downpour_worker import (
                DownpourRunner)

            runner = DownpourRunner(
                t, program=program, scope=scope, executor=self,
                push_window=int(opt_info.get("push_window", 4)),
                pull_dense_every=int(
                    opt_info.get("pull_dense_every", 1)))
            opt_info["downpour_runner"] = runner
        if runner is not None:
            runner.train_from_dataset(dataset, fetch_list)
            return None
        step = 0
        feeder = DeviceFeeder(dataset._iter_batches(),
                              capacity=max(4, 2 * (thread or 1)))
        try:
            for feed in feeder:
                results = self.run(program, feed=feed,
                                   fetch_list=fetch_list, scope=scope)
                step += 1
                if debug and fetch_list and step % print_period == 0:
                    msg = ", ".join(
                        f"{name}={np.asarray(val).ravel()[:4]}"
                        for name, val in zip(fetch_info, results))
                    print(f"step {step}: {msg}")
        finally:
            feeder.stop()
        return None

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """reference executor.py infer_from_dataset (same loop, test-mode
        program is the caller's responsibility via Program.clone(True))."""
        return self.train_from_dataset(program, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period)

    def close(self):
        pass
