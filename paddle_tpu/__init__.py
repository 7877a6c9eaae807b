"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle Fluid's
capabilities, built from scratch on JAX/XLA/Pallas/pjit idioms.

Capability map (reference: /root/reference, PaddlePaddle Fluid 1.5):
  - Program/Block/Op/Var serialized IR built by a Python front-end
    (reference: paddle/fluid/framework/framework.proto, python/paddle/fluid/framework.py)
  - Executor with scope/feed/fetch semantics, plus a whole-program compiled path
    (reference: paddle/fluid/framework/executor.cc, parallel_executor.cc)
  - Autodiff and optimizers as IR transformations
    (reference: python/paddle/fluid/backward.py, optimizer.py)
  - Distribution via jax.sharding Mesh + XLA collectives rather than NCCL/gRPC
    (reference: paddle/fluid/operators/distributed*, platform/nccl_helper.h)

The TPU-first design difference: ops are registered as pure JAX compute
functions, so shape inference (jax.eval_shape), autodiff (jax.vjp-derived grad
ops) and whole-program XLA compilation all derive from one definition instead
of the reference's hand-written InferShape/GradOpMaker/CPU/CUDA kernels.
"""

from paddle_tpu.core.types import VarType, CPUPlace, TPUPlace, CUDAPlace
from paddle_tpu.core.program import (Program, Block, OpDesc, VarDesc,
                                     pipeline_stage)
from paddle_tpu.core.scope import Scope, Variable, global_scope
from paddle_tpu.core.executor import Executor
from paddle_tpu.core.compiler import CompiledProgram
from paddle_tpu.framework import (
    default_main_program,
    default_startup_program,
    program_guard,
    name_scope,
    switch_main_program,
    in_dygraph_mode,
)
from paddle_tpu import ops  # registers all ops
from paddle_tpu import layers
from paddle_tpu import initializer
from paddle_tpu import optimizer
from paddle_tpu import regularizer
from paddle_tpu import clip
from paddle_tpu import backward
from paddle_tpu import io
from paddle_tpu import reader
from paddle_tpu import metrics
from paddle_tpu import nets
from paddle_tpu import unique_name
from paddle_tpu import parallel
from paddle_tpu import observability
from paddle_tpu import profiler
from paddle_tpu import dygraph
from paddle_tpu import contrib
from paddle_tpu import dataset
from paddle_tpu import datasets
from paddle_tpu import native
from paddle_tpu.param_attr import ParamAttr, WeightNormParamAttr
from paddle_tpu import transpiler
from paddle_tpu import distributed
from paddle_tpu import decode
from paddle_tpu.dataset import DatasetFactory, InMemoryDataset, QueueDataset
from paddle_tpu import inference
from paddle_tpu import serving
from paddle_tpu import fleet as fleet_pkg
from paddle_tpu import flags as flags_mod
from paddle_tpu import debugger
from paddle_tpu.flags import get_flag, set_flags
from paddle_tpu.data_feeder import DataFeeder


def compile_cache_dir():
    """The one place the persistent compilation cache's directory is
    decided.  ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
    it (jax reads that variable itself, so no code here or anywhere
    else sets a directory then); otherwise ``<checkout>/.jax_cache`` —
    a fixed path, because the path is part of every entry's key and a
    directory that moves never hits.  None when jax's cache is
    switched off (``jax_enable_compilation_cache`` False, as
    tests/conftest.py does)."""
    import os as _os

    import jax as _jax

    if not _jax.config.jax_enable_compilation_cache:
        return None
    return _os.environ.get("JAX_COMPILATION_CACHE_DIR") or _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache")


def enable_compile_cache():
    """Turn on jax's persistent on-disk compilation cache at
    ``compile_cache_dir()``: every XLA/Mosaic compile is keyed on
    (graph, flags, shapes) and reused across processes and restarts, so
    a second run of a program — or a replica fleet warming its bucket
    set — replays compiles from disk.  Called by the entry points that
    compile for the chip (chip_smoke.py, benchmarks/run.py,
    tools/serving_load.py, the servers' ``start()``), not at import.
    A default directory that cannot be made raises.  Returns the
    directory, or None when the cache is switched off."""
    import os as _os

    import jax as _jax

    cache_dir = compile_cache_dir()
    if cache_dir is None:
        return None
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _os.makedirs(cache_dir, exist_ok=True)
        _jax.config.update("jax_compilation_cache_dir", cache_dir)
    # serving buckets and eager startup ops are tiny, fast compiles —
    # cache everything, not just the >1s entries jax defaults to keeping
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


__version__ = "0.1.0"
