"""Python half of the C-ABI predictor (reference
inference/api/paddle_api.h:202 PaddlePredictor + :338
CreatePaddlePredictor, and the C API the reference era shipped demos
against in inference/api/demo_ci/).

native/src/predictor.cc embeds (or joins) the CPython runtime and calls
the module-level functions here with plain buffers — no numpy C API on
the native side, just bytes + shape lists across the boundary.  The
heavy lifting stays in inference.Predictor, so the C surface and the
Python surface cannot diverge.
"""

from __future__ import annotations

import os

import numpy as np

_predictors: dict = {}
_next_handle = [1]


def _apply_platform_override():
    """Standalone C hosts have no conftest to force a platform; honor
    PADDLE_TPU_PLATFORM (else JAX_PLATFORMS) via the config API before
    the first device use."""
    plat = os.environ.get("PADDLE_TPU_PLATFORM") or \
        os.environ.get("JAX_PLATFORMS")
    if plat:
        import jax

        try:
            jax.config.update("jax_platforms", plat.split(",")[0])
        except Exception:
            pass  # already initialized with a real platform


# PtDType codes (include/pt_predictor.h) <-> numpy dtypes.  bfloat16
# payloads cross the boundary as raw 2-byte words via ml_dtypes.
# Built lazily once: ml_dtypes stays a soft dependency of the typed
# path and the hot serving loop doesn't rebuild dicts per request.
_dtype_cache: list = []


def _dtype_map():
    if not _dtype_cache:
        import ml_dtypes

        fwd = {0: np.float32, 1: np.int64, 2: np.int32, 3: np.float64,
               4: ml_dtypes.bfloat16}
        _dtype_cache.append(fwd)
        _dtype_cache.append({np.dtype(dt): code
                             for code, dt in fwd.items()})
    return _dtype_cache[0]


def _dtype_code(np_dtype):
    _dtype_map()
    return _dtype_cache[1].get(np.dtype(np_dtype))


def load_cfg(model_dir, prog_file=None, params_file=None,
             enable_bf16=0, disable_ir_optim=0):
    """Create a Predictor from the PtConfig fields (reference
    AnalysisConfig paddle_analysis_config.h:40); returns an int handle
    for the C side."""
    _apply_platform_override()
    from paddle_tpu.inference import Config, create_predictor

    cfg = Config(model_dir)
    # non-default file names inside the dir (reference AnalysisConfig
    # SetModel(prog_file, params_file)); _model_dir stays set so
    # Predictor resolves both
    if prog_file is not None:
        cfg._prog_file = os.path.join(model_dir, prog_file)
    if params_file is not None:
        cfg._params_file = os.path.join(model_dir, params_file)
    if enable_bf16:
        cfg.enable_mkldnn_bfloat16()
    if disable_ir_optim:
        cfg.switch_ir_optim(False)
    pred = create_predictor(cfg)
    h = _next_handle[0]
    _next_handle[0] += 1
    _predictors[h] = pred
    return h


def load(model_dir, prog_file=None, params_file=None):
    return load_cfg(model_dir, prog_file, params_file)


def input_names(handle):
    return list(_predictors[handle].get_input_names())


def output_names(handle):
    return list(_predictors[handle].get_output_names())


def run_typed(handle, feeds):
    """feeds: list of (name, bytes, shape_list, dtype_code).  Returns
    list of (bytes, shape_list, dtype_code) in get_output_names()
    order; each output keeps its natural dtype."""
    pred = _predictors[handle]
    dmap = _dtype_map()
    by_name = {}
    for name, buf, shape, code in feeds:
        if code not in dmap:
            raise ValueError(f"unknown dtype code {code} for '{name}'")
        by_name[name] = np.frombuffer(
            buf, dtype=dmap[code]).reshape([int(d) for d in shape])
    # every declared input must be fed, by name — a silent positional
    # rebind of a partial feed would produce wrong numbers, not errors
    missing = [n for n in pred.get_input_names() if n not in by_name]
    if missing:
        raise KeyError(f"missing feeds for inputs {missing}")
    inputs = [by_name[n] for n in pred.get_input_names()]
    outs = pred.run(inputs)
    result = []
    for o in outs:
        arr = np.ascontiguousarray(np.asarray(o))
        code = _dtype_code(arr.dtype)
        if code is None:
            # dtype with no C-side code (e.g. bool): negotiate down
            # to float32 rather than hand over uninterpretable bytes
            arr = np.ascontiguousarray(arr, dtype=np.float32)
            code = 0
        result.append((arr.tobytes(), [int(d) for d in arr.shape],
                       code))
    return result


def run_raw(handle, feeds):
    """Pre-typed-API compat (the load -> load_cfg aliasing pattern):
    float32 feeds in, float32 outputs back, dtype codes hidden."""
    typed = [(name, buf, shape, 0) for name, buf, shape in feeds]
    dmap = _dtype_map()
    out = []
    for buf, shape, code in run_typed(handle, typed):
        if code != 0:
            arr = np.frombuffer(buf, dtype=dmap[code]).astype(
                np.float32)
            buf = arr.tobytes()
        out.append((buf, shape))
    return out


def free(handle):
    _predictors.pop(handle, None)
    return 0
