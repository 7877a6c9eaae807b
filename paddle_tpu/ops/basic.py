"""Elementwise / math / tensor-manipulation ops.

Reference parity (op names and attr semantics follow the reference):
  - elementwise family: /root/reference/paddle/fluid/operators/elementwise/
    (axis-broadcast semantics per elementwise_op_function.h)
  - reduce family: /root/reference/paddle/fluid/operators/reduce_ops/
  - activations: /root/reference/paddle/fluid/operators/activation_op.cc
  - tensor manipulation: reshape_op.cc, transpose_op.cc, concat_op.cc,
    split_op.cc, gather_op.cc, scatter_op.cc, slice_op.cc, stack_op.cc...
  - fill/init ops: fill_constant_op.cc, gaussian_random_op.cc,
    uniform_random_op.cc (startup-program initializers)
  - matmul_op.cc, mul_op.cc, softmax_op.cc, cross_entropy_op.cc,
    softmax_with_cross_entropy_op.cc, lookup_table_op.cc, top_k_op.cc
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import REQUIRED, register_op


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _bcast_y(x, y, axis):
    """Fluid elementwise broadcast: Y's dims align to X's dims starting at
    `axis` (default -1 = trailing-aligned).  Reference:
    operators/elementwise/elementwise_op_function.h."""
    if y.ndim == x.ndim or y.ndim == 0:
        return y
    if y.ndim > x.ndim:
        return y  # let jnp broadcasting handle / raise
    a = x.ndim - y.ndim if axis == -1 else axis
    trailing = x.ndim - a - y.ndim
    if trailing > 0:
        y = y.reshape(y.shape + (1,) * trailing)
    return y


def _reduce_dims(attrs, ndim):
    if attrs.get("reduce_all") or not attrs.get("dim"):
        return tuple(range(ndim))
    return tuple(d % ndim for d in attrs["dim"])


def _np_rng(seed):
    if seed:
        return np.random.RandomState(seed)
    return np.random


# ---------------------------------------------------------------------------
# fill / random init ops (run in the startup program; host RNG is fine there,
# reference initializers are ops too: python/paddle/fluid/initializer.py:76)
# ---------------------------------------------------------------------------

@register_op("fill_constant", inputs=(), outputs=("Out",),
             attrs={"shape": REQUIRED, "dtype": "float32", "value": 0.0},
             differentiable=False)
def fill_constant(ins, attrs):
    return {"Out": jnp.full(tuple(attrs["shape"]), attrs["value"],
                            dtype=attrs["dtype"])}


@register_op("gaussian_random", inputs=(), outputs=("Out",),
             attrs={"shape": REQUIRED, "mean": 0.0, "std": 1.0, "seed": 0,
                    "dtype": "float32", "fast": False},
             differentiable=False)
def gaussian_random(ins, attrs):
    """fast: float32 samples from a numpy Generator whose seed is ONE
    draw from the stream `seed` names, instead of float64 samples from
    that stream itself: 4-5x quicker, which counts at 10^8 parameters
    and up.  Another sequence of numbers, so off unless asked for."""
    rng = _np_rng(attrs["seed"])
    shape = tuple(attrs["shape"])
    if attrs["fast"]:
        gen = np.random.default_rng(int(rng.randint(0, 2 ** 31 - 1)))
        x = gen.standard_normal(shape, dtype=np.float32)
        x *= np.float32(attrs["std"])
        x += np.float32(attrs["mean"])
    else:
        x = rng.normal(attrs["mean"], attrs["std"], size=shape)
    return {"Out": jnp.asarray(x.astype(attrs["dtype"]))}


@register_op("truncated_gaussian_random", inputs=(), outputs=("Out",),
             attrs={"shape": REQUIRED, "mean": 0.0, "std": 1.0, "seed": 0,
                    "dtype": "float32"},
             differentiable=False)
def truncated_gaussian_random(ins, attrs):
    rng = _np_rng(attrs["seed"])
    shape = tuple(attrs["shape"])
    x = rng.normal(attrs["mean"], attrs["std"], size=shape)
    lo, hi = attrs["mean"] - 2 * attrs["std"], attrs["mean"] + 2 * attrs["std"]
    bad = (x < lo) | (x > hi)
    while bad.any():
        x[bad] = rng.normal(attrs["mean"], attrs["std"], size=int(bad.sum()))
        bad = (x < lo) | (x > hi)
    return {"Out": jnp.asarray(x.astype(attrs["dtype"]))}


@register_op("uniform_random", inputs=(), outputs=("Out",),
             attrs={"shape": REQUIRED, "min": -1.0, "max": 1.0, "seed": 0,
                    "dtype": "float32"},
             differentiable=False)
def uniform_random(ins, attrs):
    rng = _np_rng(attrs["seed"])
    x = rng.uniform(attrs["min"], attrs["max"], size=tuple(attrs["shape"]))
    return {"Out": jnp.asarray(x.astype(attrs["dtype"]))}


@register_op("assign_value", inputs=(), outputs=("Out",),
             attrs={"values": REQUIRED, "dtype": None},
             differentiable=False)
def assign_value(ins, attrs):
    arr = np.asarray(attrs["values"])
    if attrs["dtype"]:
        arr = arr.astype(attrs["dtype"])
    return {"Out": jnp.asarray(arr)}


@register_op("assign", inputs=("X",), outputs=("Out",))
def assign(ins, attrs):
    return {"Out": ins["X"]}


@register_op("shape", inputs=("Input",), outputs=("Out",),
             differentiable=False)
def shape_op(ins, attrs):
    return {"Out": jnp.asarray(np.asarray(ins["Input"].shape, np.int64))}


@register_op("fill_constant_batch_size_like", inputs=("Input",),
             outputs=("Out",),
             attrs={"shape": REQUIRED, "dtype": "float32", "value": 0.0,
                    "input_dim_idx": 0, "output_dim_idx": 0},
             differentiable=False)
def fill_constant_batch_size_like(ins, attrs):
    shape = list(attrs["shape"])
    shape[attrs["output_dim_idx"]] = ins["Input"].shape[
        attrs["input_dim_idx"]
    ]
    return {"Out": jnp.full(tuple(shape), attrs["value"],
                            dtype=attrs["dtype"])}


@register_op("fill_zeros_like", inputs=("X",), outputs=("Out",),
             differentiable=False)
def fill_zeros_like(ins, attrs):
    return {"Out": jnp.zeros_like(ins["X"])}


@register_op("cast", inputs=("X",), outputs=("Out",),
             attrs={"out_dtype": REQUIRED})
def cast(ins, attrs):
    return {"Out": ins["X"].astype(attrs["out_dtype"])}


@register_op("scale", inputs=("X",), outputs=("Out",),
             attrs={"scale": 1.0, "bias": 0.0, "bias_after_scale": True})
def scale(ins, attrs):
    x = ins["X"]
    if attrs["bias_after_scale"]:
        return {"Out": x * attrs["scale"] + attrs["bias"]}
    return {"Out": (x + attrs["bias"]) * attrs["scale"]}


@register_op("increment", inputs=("X",), outputs=("Out",),
             attrs={"step": 1.0}, differentiable=False,
             in_place={"Out": "X"})
def increment(ins, attrs):
    x = ins["X"]
    return {"Out": x + jnp.asarray(attrs["step"], x.dtype)}


# ---------------------------------------------------------------------------
# elementwise binary family (reference operators/elementwise/)
# ---------------------------------------------------------------------------

def _register_elementwise(name, fn, differentiable=True):
    @register_op(name, inputs=("X", "Y"), outputs=("Out",),
                 attrs={"axis": -1}, differentiable=differentiable)
    def _op(ins, attrs, fn=fn):
        x, y = ins["X"], ins["Y"]
        return {"Out": fn(x, _bcast_y(x, y, attrs["axis"]))}
    return _op


_register_elementwise("elementwise_add", lambda x, y: x + y)
_register_elementwise("elementwise_sub", lambda x, y: x - y)
_register_elementwise("elementwise_mul", lambda x, y: x * y)
_register_elementwise("elementwise_div", lambda x, y: x / y)
_register_elementwise("elementwise_min", jnp.minimum)
_register_elementwise("elementwise_max", jnp.maximum)
_register_elementwise("elementwise_pow", jnp.power)
_register_elementwise("elementwise_mod", jnp.mod, differentiable=False)
_register_elementwise("elementwise_floordiv", jnp.floor_divide,
                      differentiable=False)


@register_op("sum", inputs=("X",), outputs=("Out",), duplicable=("X",))
def sum_op(ins, attrs):
    """Var-arity add; used for gradient accumulation (reference sum_op.cc,
    backward.py _addup_repetitive_outputs_)."""
    xs = ins["X"]
    from paddle_tpu.core.scope import SelectedRows

    if any(isinstance(x, SelectedRows) for x in xs):
        dense = [x.to_dense() if isinstance(x, SelectedRows) else x
                 for x in xs]
        xs = dense
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": out}


@register_op("mean", inputs=("X",), outputs=("Out",))
def mean(ins, attrs):
    return {"Out": jnp.mean(ins["X"])}


# ---------------------------------------------------------------------------
# matmul / mul
# ---------------------------------------------------------------------------

@register_op("matmul", inputs=("X", "Y"), outputs=("Out",),
             attrs={"transpose_X": False, "transpose_Y": False,
                    "alpha": 1.0})
def matmul(ins, attrs):
    x, y = ins["X"], ins["Y"]
    if attrs["transpose_X"]:
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if attrs["transpose_Y"]:
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    out = jnp.matmul(x, y)
    if attrs["alpha"] != 1.0:
        out = out * attrs["alpha"]
    return {"Out": out}


@register_op("mul", inputs=("X", "Y"), outputs=("Out",),
             attrs={"x_num_col_dims": 1, "y_num_col_dims": 1})
def mul(ins, attrs):
    """reference mul_op.cc: flattens X to 2-D at x_num_col_dims, Y at
    y_num_col_dims, then matmul; output keeps the unflattened dims."""
    x, y = ins["X"], ins["Y"]
    xnc, ync = attrs["x_num_col_dims"], attrs["y_num_col_dims"]
    x2 = x.reshape((int(np.prod(x.shape[:xnc])), -1))
    y2 = y.reshape((int(np.prod(y.shape[:ync])), -1))
    out = x2 @ y2
    return {"Out": out.reshape(x.shape[:xnc] + y.shape[ync:])}


# ---------------------------------------------------------------------------
# reductions (reference operators/reduce_ops/)
# ---------------------------------------------------------------------------

def _register_reduce(name, fn, differentiable=True):
    @register_op(name, inputs=("X",), outputs=("Out",),
                 attrs={"dim": [0], "keep_dim": False, "reduce_all": False},
                 differentiable=differentiable)
    def _op(ins, attrs, fn=fn):
        x = ins["X"]
        dims = _reduce_dims(attrs, x.ndim)
        return {"Out": fn(x, axis=dims, keepdims=attrs["keep_dim"])}
    return _op


_register_reduce("reduce_sum", jnp.sum)
_register_reduce("reduce_mean", jnp.mean)
_register_reduce("reduce_max", jnp.max)
_register_reduce("reduce_min", jnp.min)
_register_reduce("reduce_prod", jnp.prod)
_register_reduce("reduce_all", jnp.all, differentiable=False)
_register_reduce("reduce_any", jnp.any, differentiable=False)


# ---------------------------------------------------------------------------
# activations (reference activation_op.cc)
# ---------------------------------------------------------------------------

def _register_act(name, fn, differentiable=True, extra_attrs=None):
    @register_op(name, inputs=("X",), outputs=("Out",),
                 attrs=dict(extra_attrs or {}),
                 differentiable=differentiable)
    def _op(ins, attrs, fn=fn):
        return {"Out": fn(ins["X"], attrs)}
    return _op


_register_act("relu", lambda x, a: jax.nn.relu(x))
_register_act("relu6", lambda x, a: jnp.clip(x, 0.0, a["threshold"]),
              extra_attrs={"threshold": 6.0})
_register_act("leaky_relu", lambda x, a: jax.nn.leaky_relu(x, a["alpha"]),
              extra_attrs={"alpha": 0.02})
_register_act("sigmoid", lambda x, a: jax.nn.sigmoid(x))
_register_act("logsigmoid", lambda x, a: jax.nn.log_sigmoid(x))
_register_act("tanh", lambda x, a: jnp.tanh(x))
_register_act("exp", lambda x, a: jnp.exp(x))
_register_act("log", lambda x, a: jnp.log(x))
_register_act("sqrt", lambda x, a: jnp.sqrt(x))
_register_act("rsqrt", lambda x, a: jax.lax.rsqrt(x))
_register_act("abs", lambda x, a: jnp.abs(x))
_register_act("square", lambda x, a: jnp.square(x))
_register_act("reciprocal", lambda x, a: 1.0 / x)
_register_act("softplus", lambda x, a: jax.nn.softplus(x))
_register_act("softsign", lambda x, a: jax.nn.soft_sign(x))
_register_act("gelu", lambda x, a: jax.nn.gelu(x, approximate=a["approximate"]),
              extra_attrs={"approximate": False})
_register_act("elu", lambda x, a: jax.nn.elu(x, a["alpha"]),
              extra_attrs={"alpha": 1.0})
_register_act("selu", lambda x, a: jax.nn.selu(x))
_register_act("swish", lambda x, a: x * jax.nn.sigmoid(a["beta"] * x),
              extra_attrs={"beta": 1.0})
_register_act("hard_sigmoid",
              lambda x, a: jnp.clip(a["slope"] * x + a["offset"], 0.0, 1.0),
              extra_attrs={"slope": 0.2, "offset": 0.5})
_register_act("hard_swish",
              lambda x, a: x * jnp.clip(x + a["offset"], 0.0, a["threshold"])
              / a["scale"],
              extra_attrs={"threshold": 6.0, "scale": 6.0, "offset": 3.0})
_register_act("floor", lambda x, a: jnp.floor(x), differentiable=False)
_register_act("ceil", lambda x, a: jnp.ceil(x), differentiable=False)
_register_act("round", lambda x, a: jnp.round(x), differentiable=False)
_register_act("sin", lambda x, a: jnp.sin(x))
_register_act("cos", lambda x, a: jnp.cos(x))
_register_act("erf", lambda x, a: jax.scipy.special.erf(x))
_register_act("tanh_shrink", lambda x, a: x - jnp.tanh(x))
_register_act("softshrink",
              lambda x, a: jnp.where(x > a["lambda"], x - a["lambda"],
                                     jnp.where(x < -a["lambda"],
                                               x + a["lambda"], 0.0)),
              extra_attrs={"lambda": 0.5})
_register_act("hard_shrink",
              lambda x, a: jnp.where(jnp.abs(x) > a["threshold"], x, 0.0),
              extra_attrs={"threshold": 0.5})
_register_act("thresholded_relu",
              lambda x, a: jnp.where(x > a["threshold"], x, 0.0),
              extra_attrs={"threshold": 1.0})
_register_act("stanh",
              lambda x, a: a["scale_b"] * jnp.tanh(a["scale_a"] * x),
              extra_attrs={"scale_a": 0.67, "scale_b": 1.7159})


@register_op("pow", inputs=("X",), outputs=("Out",),
             attrs={"factor": 1.0})
def pow_op(ins, attrs):
    return {"Out": jnp.power(ins["X"], attrs["factor"])}


@register_op("clip", inputs=("X",), outputs=("Out",),
             attrs={"min": REQUIRED, "max": REQUIRED})
def clip_op(ins, attrs):
    return {"Out": jnp.clip(ins["X"], attrs["min"], attrs["max"])}


@register_op("clip_by_norm", inputs=("X",), outputs=("Out",),
             attrs={"max_norm": REQUIRED})
def clip_by_norm(ins, attrs):
    x = ins["X"]
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    scale = jnp.minimum(attrs["max_norm"] / jnp.maximum(norm, 1e-12), 1.0)
    return {"Out": x * scale}


# ---------------------------------------------------------------------------
# softmax / losses
# ---------------------------------------------------------------------------

@register_op("softmax", inputs=("X",), outputs=("Out",),
             attrs={"axis": -1})
def softmax(ins, attrs):
    return {"Out": jax.nn.softmax(ins["X"], axis=attrs["axis"])}


@register_op("log_softmax", inputs=("X",), outputs=("Out",),
             attrs={"axis": -1})
def log_softmax(ins, attrs):
    return {"Out": jax.nn.log_softmax(ins["X"], axis=attrs["axis"])}


@register_op("cross_entropy", inputs=("X", "Label"), outputs=("Y",),
             attrs={"soft_label": False, "ignore_index": -100})
def cross_entropy(ins, attrs):
    """X are probabilities (post-softmax), reference cross_entropy_op.cc."""
    x, label = ins["X"], ins["Label"]
    eps = jnp.asarray(1e-12, x.dtype)
    if attrs["soft_label"]:
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, eps)), axis=-1,
                        keepdims=True)
    else:
        lab = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 \
            else label
        picked = jnp.take_along_axis(
            x, lab[..., None].astype(jnp.int32), axis=-1
        )
        loss = -jnp.log(jnp.maximum(picked, eps))
        if attrs["ignore_index"] >= 0:
            mask = (lab[..., None] != attrs["ignore_index"])
            loss = jnp.where(mask, loss, 0.0)
    return {"Y": loss}


@register_op("softmax_with_cross_entropy", inputs=("Logits", "Label"),
             outputs=("Softmax", "Loss"),
             attrs={"soft_label": False, "ignore_index": -100, "axis": -1,
                    "numeric_stable_mode": True})
def softmax_with_cross_entropy(ins, attrs):
    logits, label = ins["Logits"], ins["Label"]
    axis = attrs["axis"]
    logp = jax.nn.log_softmax(logits, axis=axis)
    if attrs["soft_label"]:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lab = label
        if lab.ndim == logits.ndim and lab.shape[axis] == 1:
            lab = jnp.squeeze(lab, axis=axis)
        picked = jnp.take_along_axis(
            logp, lab[..., None].astype(jnp.int32), axis=axis
        )
        loss = -picked
        if attrs["ignore_index"] >= 0:
            loss = jnp.where(lab[..., None] != attrs["ignore_index"],
                             loss, 0.0)
    return {"Softmax": jnp.exp(logp), "Loss": loss}


@register_op("sigmoid_cross_entropy_with_logits",
             inputs=("X", "Label"), outputs=("Out",),
             attrs={"ignore_index": -100, "normalize": False})
def sigmoid_cross_entropy_with_logits(ins, attrs):
    x, label = ins["X"], ins["Label"]
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    if attrs["ignore_index"] >= 0:
        mask = (label != attrs["ignore_index"]).astype(x.dtype)
        loss = loss * mask
        if attrs["normalize"]:
            loss = loss / jnp.maximum(jnp.sum(mask), 1.0)
    return {"Out": loss}


@register_op("square_error_cost", inputs=("X", "Y"), outputs=("Out",))
def square_error_cost(ins, attrs):
    return {"Out": jnp.square(ins["X"] - ins["Y"])}


@register_op("huber_loss", inputs=("X", "Y"), outputs=("Out", "Residual"),
             attrs={"delta": 1.0})
def huber_loss(ins, attrs):
    d = attrs["delta"]
    r = ins["Y"] - ins["X"]
    ar = jnp.abs(r)
    loss = jnp.where(ar <= d, 0.5 * r * r, d * (ar - 0.5 * d))
    return {"Out": loss, "Residual": r}


@register_op("log_loss", inputs=("Predicted", "Labels"), outputs=("Loss",),
             attrs={"epsilon": 1e-4})
def log_loss(ins, attrs):
    p, y = ins["Predicted"], ins["Labels"]
    eps = attrs["epsilon"]
    return {"Loss": -y * jnp.log(p + eps) - (1 - y) * jnp.log(1 - p + eps)}


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

@register_op("lookup_table", inputs=("W", "Ids"), outputs=("Out",),
             attrs={"padding_idx": -1, "is_sparse": False,
                    "is_distributed": False})
def lookup_table(ins, attrs):
    """reference lookup_table_op.cc.  Ids [..., 1] int64 -> Out [..., D].
    padding_idx rows return zeros.  The sparse-grad (SelectedRows) path is
    realised via a custom grad op in layers/backward when is_sparse."""
    w, ids = ins["W"], ins["Ids"]
    squeeze = ids.ndim >= 2 and ids.shape[-1] == 1
    idx = ids[..., 0] if squeeze else ids
    out = jnp.take(w, idx.astype(jnp.int32), axis=0)
    if attrs["padding_idx"] >= 0:
        mask = (idx != attrs["padding_idx"])[..., None]
        out = jnp.where(mask, out, 0.0)
    return {"Out": out}


# ---------------------------------------------------------------------------
# shape manipulation
# ---------------------------------------------------------------------------

@register_op("reshape2", inputs=("X",), outputs=("Out", "XShape"),
             attrs={"shape": REQUIRED})
def reshape2(ins, attrs):
    x = ins["X"]
    shape = list(attrs["shape"])
    for i, d in enumerate(shape):
        if d == 0:
            shape[i] = x.shape[i]
    return {"Out": x.reshape(shape),
            "XShape": jnp.zeros((0,) + x.shape, x.dtype)}


@register_op("transpose2", inputs=("X",), outputs=("Out", "XShape"),
             attrs={"axis": REQUIRED})
def transpose2(ins, attrs):
    x = ins["X"]
    return {"Out": jnp.transpose(x, attrs["axis"]),
            "XShape": jnp.zeros((0,) + x.shape, x.dtype)}


@register_op("flatten2", inputs=("X",), outputs=("Out", "XShape"),
             attrs={"axis": 1})
def flatten2(ins, attrs):
    x = ins["X"]
    a = attrs["axis"]
    lead = int(np.prod(x.shape[:a])) if a > 0 else 1
    return {"Out": x.reshape((lead, -1)),
            "XShape": jnp.zeros((0,) + x.shape, x.dtype)}


@register_op("squeeze2", inputs=("X",), outputs=("Out", "XShape"),
             attrs={"axes": []})
def squeeze2(ins, attrs):
    x = ins["X"]
    axes = attrs["axes"] or [i for i, d in enumerate(x.shape) if d == 1]
    axes = [a % x.ndim for a in axes if x.shape[a % x.ndim] == 1]
    return {"Out": jnp.squeeze(x, axis=tuple(axes)),
            "XShape": jnp.zeros((0,) + x.shape, x.dtype)}


@register_op("unsqueeze2", inputs=("X",), outputs=("Out", "XShape"),
             attrs={"axes": REQUIRED})
def unsqueeze2(ins, attrs):
    x = ins["X"]
    out = x
    for a in sorted(attrs["axes"]):
        out = jnp.expand_dims(out, a)
    return {"Out": out, "XShape": jnp.zeros((0,) + x.shape, x.dtype)}


@register_op("concat", inputs=("X",), outputs=("Out",), duplicable=("X",),
             attrs={"axis": 0})
def concat(ins, attrs):
    return {"Out": jnp.concatenate(ins["X"], axis=attrs["axis"])}


@register_op("split", inputs=("X",), outputs=("Out",), duplicable=("Out",),
             attrs={"num": 0, "sections": [], "axis": 0})
def split(ins, attrs):
    x = ins["X"]
    axis = attrs["axis"]
    if attrs["sections"]:
        idx = np.cumsum(attrs["sections"])[:-1].tolist()
        return {"Out": jnp.split(x, idx, axis=axis)}
    return {"Out": jnp.split(x, attrs["num"], axis=axis)}


@register_op("stack", inputs=("X",), outputs=("Y",), duplicable=("X",),
             attrs={"axis": 0})
def stack(ins, attrs):
    return {"Y": jnp.stack(ins["X"], axis=attrs["axis"])}


@register_op("unstack", inputs=("X",), outputs=("Y",), duplicable=("Y",),
             attrs={"axis": 0, "num": 0})
def unstack(ins, attrs):
    x = ins["X"]
    parts = jnp.split(x, x.shape[attrs["axis"]], axis=attrs["axis"])
    return {"Y": [jnp.squeeze(p, axis=attrs["axis"]) for p in parts]}


@register_op("slice", inputs=("Input",), outputs=("Out",),
             attrs={"axes": REQUIRED, "starts": REQUIRED, "ends": REQUIRED})
def slice_op(ins, attrs):
    x = ins["Input"]
    idx = [slice(None)] * x.ndim
    for a, s, e in zip(attrs["axes"], attrs["starts"], attrs["ends"]):
        dim = x.shape[a]
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        idx[a] = slice(s, e)
    return {"Out": x[tuple(idx)]}


@register_op("strided_slice", inputs=("Input",), outputs=("Out",),
             attrs={"axes": REQUIRED, "starts": REQUIRED, "ends": REQUIRED,
                    "strides": REQUIRED})
def strided_slice(ins, attrs):
    x = ins["Input"]
    idx = [slice(None)] * x.ndim
    for a, s, e, st in zip(attrs["axes"], attrs["starts"], attrs["ends"],
                           attrs["strides"]):
        idx[a] = slice(s, e, st)
    return {"Out": x[tuple(idx)]}


@register_op("gather", inputs=("X", "Index"), outputs=("Out",))
def gather(ins, attrs):
    return {"Out": jnp.take(ins["X"], ins["Index"].astype(jnp.int32),
                            axis=0)}


@register_op("gather_nd", inputs=("X", "Index"), outputs=("Out",))
def gather_nd(ins, attrs):
    x, index = ins["X"], ins["Index"]
    idx = tuple(jnp.moveaxis(index.astype(jnp.int32), -1, 0))
    return {"Out": x[idx]}


@register_op("scatter", inputs=("X", "Ids", "Updates"), outputs=("Out",),
             attrs={"overwrite": True})
def scatter(ins, attrs):
    x, ids, upd = ins["X"], ins["Ids"].astype(jnp.int32), ins["Updates"]
    if attrs["overwrite"]:
        return {"Out": x.at[ids].set(upd)}
    return {"Out": x.at[ids].add(upd)}


@register_op("scatter_nd_add", inputs=("X", "Index", "Updates"),
             outputs=("Out",))
def scatter_nd_add(ins, attrs):
    x, index, upd = ins["X"], ins["Index"].astype(jnp.int32), ins["Updates"]
    idx = tuple(jnp.moveaxis(index, -1, 0))
    return {"Out": x.at[idx].add(upd)}


@register_op("expand", inputs=("X",), outputs=("Out",),
             attrs={"expand_times": REQUIRED})
def expand(ins, attrs):
    return {"Out": jnp.tile(ins["X"], attrs["expand_times"])}


@register_op("pad", inputs=("X",), outputs=("Out",),
             attrs={"paddings": REQUIRED, "pad_value": 0.0})
def pad(ins, attrs):
    p = attrs["paddings"]
    pads = [(p[2 * i], p[2 * i + 1]) for i in range(len(p) // 2)]
    return {"Out": jnp.pad(ins["X"], pads, constant_values=attrs["pad_value"])}


@register_op("pad2d", inputs=("X",), outputs=("Out",),
             attrs={"paddings": REQUIRED, "mode": "constant",
                    "pad_value": 0.0, "data_format": "NCHW"})
def pad2d(ins, attrs):
    p = attrs["paddings"]  # [top, bottom, left, right]
    if attrs["data_format"] == "NCHW":
        pads = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    else:
        pads = [(0, 0), (p[0], p[1]), (p[2], p[3]), (0, 0)]
    mode = {"constant": "constant", "reflect": "reflect",
            "edge": "edge"}[attrs["mode"]]
    if mode == "constant":
        return {"Out": jnp.pad(ins["X"], pads,
                               constant_values=attrs["pad_value"])}
    return {"Out": jnp.pad(ins["X"], pads, mode=mode)}


@register_op("reverse", inputs=("X",), outputs=("Out",),
             attrs={"axis": REQUIRED})
def reverse(ins, attrs):
    return {"Out": jnp.flip(ins["X"], axis=tuple(attrs["axis"]))}


@register_op("tile", inputs=("X",), outputs=("Out",),
             attrs={"repeat_times": REQUIRED})
def tile(ins, attrs):
    return {"Out": jnp.tile(ins["X"], attrs["repeat_times"])}


@register_op("cumsum", inputs=("X",), outputs=("Out",),
             attrs={"axis": -1, "exclusive": False, "reverse": False})
def cumsum(ins, attrs):
    x = ins["X"]
    axis = attrs["axis"]
    if attrs["reverse"]:
        x = jnp.flip(x, axis)
    out = jnp.cumsum(x, axis=axis)
    if attrs["exclusive"]:
        out = out - x
    if attrs["reverse"]:
        out = jnp.flip(out, axis)
    return {"Out": out}


@register_op("one_hot", inputs=("X",), outputs=("Out",),
             attrs={"depth": REQUIRED, "dtype": "float32"},
             differentiable=False)
def one_hot(ins, attrs):
    x = ins["X"]
    if x.ndim >= 2 and x.shape[-1] == 1:
        x = x[..., 0]
    return {"Out": jax.nn.one_hot(x.astype(jnp.int32), attrs["depth"],
                                  dtype=attrs["dtype"])}


@register_op("range", inputs=(), outputs=("Out",),
             attrs={"start": REQUIRED, "end": REQUIRED, "step": 1,
                    "dtype": "int64"},
             differentiable=False)
def range_op(ins, attrs):
    return {"Out": jnp.arange(attrs["start"], attrs["end"], attrs["step"],
                              dtype=attrs["dtype"])}


@register_op("linspace", inputs=(), outputs=("Out",),
             attrs={"start": REQUIRED, "stop": REQUIRED, "num": REQUIRED,
                    "dtype": "float32"},
             differentiable=False)
def linspace(ins, attrs):
    return {"Out": jnp.linspace(attrs["start"], attrs["stop"], attrs["num"],
                                dtype=attrs["dtype"])}


# ---------------------------------------------------------------------------
# comparison / logical / selection
# ---------------------------------------------------------------------------

def _register_cmp(name, fn):
    @register_op(name, inputs=("X", "Y"), outputs=("Out",),
                 attrs={"axis": -1}, differentiable=False)
    def _op(ins, attrs, fn=fn):
        x, y = ins["X"], ins["Y"]
        return {"Out": fn(x, _bcast_y(x, y, attrs["axis"]))}
    return _op


_register_cmp("equal", jnp.equal)
_register_cmp("not_equal", jnp.not_equal)
_register_cmp("less_than", jnp.less)
_register_cmp("less_equal", jnp.less_equal)
_register_cmp("greater_than", jnp.greater)
_register_cmp("greater_equal", jnp.greater_equal)
_register_cmp("logical_and", jnp.logical_and)
_register_cmp("logical_or", jnp.logical_or)
_register_cmp("logical_xor", jnp.logical_xor)


@register_op("logical_not", inputs=("X",), outputs=("Out",),
             differentiable=False)
def logical_not(ins, attrs):
    return {"Out": jnp.logical_not(ins["X"])}


@register_op("where", inputs=("Condition", "X", "Y"), outputs=("Out",))
def where_op(ins, attrs):
    return {"Out": jnp.where(ins["Condition"], ins["X"], ins["Y"])}


@register_op("isfinite", inputs=("X",), outputs=("Out",),
             differentiable=False)
def isfinite(ins, attrs):
    return {"Out": jnp.all(jnp.isfinite(ins["X"]))}


# ---------------------------------------------------------------------------
# sorting / topk / argmax
# ---------------------------------------------------------------------------

@register_op("top_k", inputs=("X",), outputs=("Out", "Indices"),
             attrs={"k": 1}, differentiable=False)
def top_k(ins, attrs):
    vals, idx = jax.lax.top_k(ins["X"], attrs["k"])
    return {"Out": vals, "Indices": idx.astype(jnp.int64)}


@register_op("arg_max", inputs=("X",), outputs=("Out",),
             attrs={"axis": -1, "keepdims": False, "dtype": "int64"},
             differentiable=False)
def arg_max(ins, attrs):
    out = jnp.argmax(ins["X"], axis=attrs["axis"],
                     keepdims=attrs["keepdims"])
    return {"Out": out.astype(attrs["dtype"])}


@register_op("arg_min", inputs=("X",), outputs=("Out",),
             attrs={"axis": -1, "keepdims": False, "dtype": "int64"},
             differentiable=False)
def arg_min(ins, attrs):
    out = jnp.argmin(ins["X"], axis=attrs["axis"],
                     keepdims=attrs["keepdims"])
    return {"Out": out.astype(attrs["dtype"])}


@register_op("argsort", inputs=("X",), outputs=("Out", "Indices"),
             attrs={"axis": -1, "descending": False}, differentiable=False)
def argsort(ins, attrs):
    x = ins["X"]
    axis = attrs["axis"]
    idx = jnp.argsort(-x if attrs["descending"] else x, axis=axis)
    out = jnp.take_along_axis(x, idx, axis=axis)
    return {"Out": out, "Indices": idx.astype(jnp.int64)}


# ---------------------------------------------------------------------------
# dropout (explicit seed-offset input keeps randomness jit-deterministic
# per step; reference dropout_op.cc uses a per-call host seed)
# ---------------------------------------------------------------------------

@register_op("dropout", inputs=("X", "SeedOffset"),
             outputs=("Out", "Mask"),
             optional=("SeedOffset",),
             attrs={"dropout_prob": 0.5, "is_test": False, "seed": 0,
                    "dropout_implementation": "downgrade_in_infer"})
def dropout(ins, attrs):
    x = ins["X"]
    p = attrs["dropout_prob"]
    upscale = attrs["dropout_implementation"] == "upscale_in_train"
    if attrs["is_test"]:
        out = x if upscale else x * (1.0 - p)
        return {"Out": out, "Mask": jnp.ones_like(x)}
    key = jax.random.key(attrs["seed"] or 42)
    off = ins.get("SeedOffset")
    if off is not None:
        from paddle_tpu.ops.rng import fold_seed_offset

        key = fold_seed_offset(key, off)
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    out = x * mask
    if upscale and p < 1.0:
        out = out / (1.0 - p)
    return {"Out": out, "Mask": mask}


@register_op("label_smooth", inputs=("X",), outputs=("Out",),
             attrs={"epsilon": 0.0})
def label_smooth(ins, attrs):
    x = ins["X"]
    eps = attrs["epsilon"]
    k = x.shape[-1]
    return {"Out": x * (1.0 - eps) + eps / k}


@register_op("l2_normalize", inputs=("X",), outputs=("Out", "Norm"),
             attrs={"axis": -1, "epsilon": 1e-10})
def l2_normalize(ins, attrs):
    x = ins["X"]
    sq = jnp.sum(jnp.square(x), axis=attrs["axis"], keepdims=True)
    norm = jnp.sqrt(jnp.maximum(sq, attrs["epsilon"]))
    return {"Out": x / norm, "Norm": norm}


@register_op("norm", inputs=("X",), outputs=("Out", "Norm"),
             attrs={"axis": -1, "epsilon": 1e-10})
def norm_op(ins, attrs):
    x = ins["X"]
    norm = jnp.sqrt(
        jnp.sum(jnp.square(x), axis=attrs["axis"], keepdims=True)
        + attrs["epsilon"]
    )
    return {"Out": x / norm, "Norm": norm}


@register_op("swapaxes", inputs=("X",), outputs=("Out",),
             attrs={"axis1": 0, "axis2": 1})
def swapaxes(ins, attrs):
    """Rank-agnostic axis swap (time-major <-> batch-major flips in
    DynamicRNN; unlike transpose2 it needs no full permutation, so it
    works when the var's rank isn't statically recorded)."""
    return {"Out": jnp.swapaxes(ins["X"], attrs["axis1"],
                                attrs["axis2"])}


@register_op("flip", inputs=("X",), outputs=("Out",),
             attrs={"axis": [0]})
def flip_op(ins, attrs):
    return {"Out": jnp.flip(ins["X"], axis=tuple(attrs["axis"]))}
