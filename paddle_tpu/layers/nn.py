"""Layer functions building the IR (reference:
/root/reference/python/paddle/fluid/layers/nn.py — fc :215, embedding :355,
conv2d :2008, batch_norm :3061, layer_norm :3384, matmul :5162,
softmax_with_cross_entropy :6337)."""

from __future__ import annotations

import numpy as np

from paddle_tpu.layers.helper import LayerHelper

__all__ = [
    "fc", "embedding", "conv2d", "conv2d_transpose", "pool2d",
    "batch_norm", "layer_norm", "group_norm", "dropout", "relu", "softmax",
    "log_softmax", "sigmoid", "tanh", "gelu", "leaky_relu",
    "cross_entropy", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "square_error_cost", "huber_loss",
    "log_loss", "mean", "reduce_sum", "reduce_mean", "reduce_max",
    "reduce_min", "reduce_prod", "matmul", "mul", "elementwise_op",
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "scale", "cast", "reshape", "transpose", "flatten",
    "squeeze", "unsqueeze", "concat", "split", "stack", "slice", "gather",
    "gather_nd", "scatter", "expand", "pad", "topk", "argmax", "argsort",
    "accuracy", "one_hot", "clip", "clip_by_norm", "l2_normalize",
    "label_smooth", "dropout", "lrn", "cos_sim", "where", "equal",
    "less_than", "greater_than", "not_equal", "logical_and", "logical_or",
    "logical_not", "cumsum", "increment", "shape", "reduce_all",
    "reduce_any", "pow", "sqrt", "square", "abs", "exp", "log",
    "sequence_mask", "swish", "hard_sigmoid", "elu", "relu6", "softplus",
    "softsign", "prelu", "brelu", "flash_attention", "linear_chain_crf",
    "crf_decoding", "nce", "hsigmoid", "sample_logits", "step_stat",
]


def _single_out(op_type, x, attrs=None, out_dtype=None, ins_extra=None,
                in_slot="X", out_slot="Out"):
    helper = LayerHelper(op_type)
    out = helper.create_variable_for_type_inference(
        out_dtype or (x.dtype if hasattr(x, "dtype") else "float32")
    )
    inputs = {in_slot: x}
    if ins_extra:
        inputs.update({k: v for k, v in ins_extra.items() if v is not None})
    helper.append_op(type=op_type, inputs=inputs, outputs={out_slot: out},
                     attrs=attrs or {})
    return out


# ---------------------------------------------------------------------------
# dense / embedding
# ---------------------------------------------------------------------------

def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """reference layers/nn.py:215."""
    helper = LayerHelper("fc", name=name)
    in_dim = int(np.prod(input.shape[num_flatten_dims:]))
    w = helper.create_parameter(param_attr, [in_dim, size], input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="mul", inputs={"X": input, "Y": w}, outputs={"Out": out},
        attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
    )
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [size], input.dtype,
                                    is_bias=True)
        out2 = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op(
            type="elementwise_add", inputs={"X": out, "Y": b},
            outputs={"Out": out2}, attrs={"axis": num_flatten_dims},
        )
        out = out2
    return helper.append_activation(out, act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32",
              name=None):
    """reference layers/nn.py:355.  is_sparse selects the SelectedRows-style
    gradient (sparse rows) rather than a dense grad."""
    helper = LayerHelper("embedding", name=name)
    w = helper.create_parameter(param_attr, list(size), dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="lookup_table", inputs={"W": w, "Ids": input},
        outputs={"Out": out},
        attrs={"padding_idx": -1 if padding_idx is None else padding_idx,
               "is_sparse": is_sparse, "is_distributed": is_distributed},
    )
    return out


# ---------------------------------------------------------------------------
# conv / pool / norm
# ---------------------------------------------------------------------------

def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None, name=None,
           use_cudnn=True, data_format="NCHW"):
    helper = LayerHelper("conv2d", name=name)
    c_in = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    fs = filter_size if isinstance(filter_size, (list, tuple)) else (
        filter_size, filter_size)
    w_shape = [num_filters, c_in // groups, fs[0], fs[1]]
    from paddle_tpu.initializer import MSRA

    w = helper.create_parameter(param_attr, w_shape, input.dtype,
                                default_initializer=MSRA(uniform=True))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="conv2d", inputs={"Input": input, "Filter": w},
        outputs={"Output": out},
        attrs={
            "strides": list(stride) if isinstance(stride, (list, tuple))
            else [stride, stride],
            "paddings": list(padding) if isinstance(padding, (list, tuple))
            else [padding, padding],
            "dilations": list(dilation)
            if isinstance(dilation, (list, tuple)) else [dilation, dilation],
            "groups": groups, "data_format": data_format,
        },
    )
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters], input.dtype,
                                    is_bias=True)
        out2 = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op(
            type="elementwise_add", inputs={"X": out, "Y": b},
            outputs={"Out": out2},
            attrs={"axis": 1 if data_format == "NCHW" else -1},
        )
        out = out2
    return helper.append_activation(out, act)


def conv2d_transpose(input, num_filters, filter_size, stride=1, padding=0,
                     dilation=1, groups=1, param_attr=None, bias_attr=None,
                     act=None, name=None, output_size=None,
                     data_format="NCHW"):
    helper = LayerHelper("conv2d_transpose", name=name)
    c_in = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    fs = filter_size if isinstance(filter_size, (list, tuple)) else (
        filter_size, filter_size)
    w = helper.create_parameter(
        param_attr, [c_in, num_filters // groups, fs[0], fs[1]],
        input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="conv2d_transpose", inputs={"Input": input, "Filter": w},
        outputs={"Output": out},
        attrs={
            "strides": [stride, stride] if np.isscalar(stride)
            else list(stride),
            "paddings": [padding, padding] if np.isscalar(padding)
            else list(padding),
            "dilations": [dilation, dilation] if np.isscalar(dilation)
            else list(dilation),
            "groups": groups, "output_size": output_size or [],
            "data_format": data_format,
        },
    )
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters], input.dtype,
                                    is_bias=True)
        out2 = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op(
            type="elementwise_add", inputs={"X": out, "Y": b},
            outputs={"Out": out2},
            attrs={"axis": 1 if data_format == "NCHW" else -1},
        )
        out = out2
    return helper.append_activation(out, act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, ceil_mode=False,
           exclusive=True, adaptive=False, name=None, data_format="NCHW"):
    attrs = {
        "pooling_type": pool_type,
        "ksize": [pool_size, pool_size] if np.isscalar(pool_size)
        else list(pool_size),
        "global_pooling": global_pooling,
        "strides": [pool_stride, pool_stride] if np.isscalar(pool_stride)
        else list(pool_stride),
        "paddings": [pool_padding, pool_padding]
        if np.isscalar(pool_padding) else list(pool_padding),
        "ceil_mode": ceil_mode, "exclusive": exclusive,
        "adaptive": adaptive, "data_format": data_format,
    }
    return _single_out("pool2d", input, attrs)


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               name=None, moving_mean_name=None, moving_variance_name=None,
               use_global_stats=False):
    """reference layers/nn.py:3061.  Running mean/var are persistable,
    non-trainable params updated in place by wiring MeanOut/VarianceOut back
    onto the same vars."""
    from paddle_tpu.initializer import Constant
    from paddle_tpu.param_attr import ParamAttr

    helper = LayerHelper("batch_norm", name=name)
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    scale = helper.create_parameter(param_attr, [c], input.dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(bias_attr, [c], input.dtype,
                                   is_bias=True)
    mean = helper.create_parameter(
        ParamAttr(name=moving_mean_name, trainable=False,
                  initializer=Constant(0.0)), [c], input.dtype)
    var = helper.create_parameter(
        ParamAttr(name=moving_variance_name, trainable=False,
                  initializer=Constant(1.0)), [c], input.dtype)
    mean.stop_gradient = True
    var.stop_gradient = True
    y = helper.create_variable_for_type_inference(input.dtype)
    saved_mean = helper.create_variable_for_type_inference(
        input.dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(
        input.dtype, stop_gradient=True)
    helper.append_op(
        type="batch_norm",
        inputs={"X": input, "Scale": scale, "Bias": bias, "Mean": mean,
                "Variance": var},
        outputs={"Y": y, "MeanOut": mean, "VarianceOut": var,
                 "SavedMean": saved_mean, "SavedVariance": saved_var},
        attrs={"epsilon": epsilon, "momentum": momentum,
               "is_test": is_test, "data_layout": data_layout,
               "use_global_stats": use_global_stats},
    )
    return helper.append_activation(y, act)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    from paddle_tpu.initializer import Constant

    helper = LayerHelper("layer_norm", name=name)
    norm_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": input}
    if scale:
        inputs["Scale"] = helper.create_parameter(
            param_attr, norm_shape, input.dtype,
            default_initializer=Constant(1.0))
    if shift:
        inputs["Bias"] = helper.create_parameter(
            bias_attr, norm_shape, input.dtype, is_bias=True)
    y = helper.create_variable_for_type_inference(input.dtype)
    m = helper.create_variable_for_type_inference(input.dtype, True)
    v = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op(
        type="layer_norm", inputs=inputs,
        outputs={"Y": y, "Mean": m, "Variance": v},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
    )
    return helper.append_activation(y, act)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, name=None):
    from paddle_tpu.initializer import Constant

    helper = LayerHelper("group_norm", name=name)
    c = input.shape[1]
    inputs = {"X": input}
    if param_attr is not False:
        inputs["Scale"] = helper.create_parameter(
            param_attr, [c], input.dtype,
            default_initializer=Constant(1.0))
    if bias_attr is not False:
        inputs["Bias"] = helper.create_parameter(
            bias_attr, [c], input.dtype, is_bias=True)
    y = helper.create_variable_for_type_inference(input.dtype)
    m = helper.create_variable_for_type_inference(input.dtype, True)
    v = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op(
        type="group_norm", inputs=inputs,
        outputs={"Y": y, "Mean": m, "Variance": v},
        attrs={"epsilon": epsilon, "groups": groups},
    )
    return helper.append_activation(y, act)


_dropout_counter_var = {}


def _step_counter(helper, prefix):
    """Per-program persistable int64 step counter feeding SeedOffset
    inputs, so stochastic ops re-randomize every step under jit (one
    counter per (prefix, program))."""
    from paddle_tpu.initializer import Constant
    from paddle_tpu.param_attr import ParamAttr

    key = (prefix, id(helper.main_program))
    if key not in _dropout_counter_var:
        ctr = helper.create_parameter(
            ParamAttr(name=f"{prefix}_step_{key[1]}", trainable=False,
                      initializer=Constant(0.0)),
            [1], "int64")
        ctr.stop_gradient = True
        _dropout_counter_var[key] = ctr
        helper.block.append_op(
            type="increment", inputs={"X": ctr},
            outputs={"Out": ctr}, attrs={"step": 1.0})
    return _dropout_counter_var[key]


def step_stat(name, x, columns=None):
    """Keeps `x`, a float vector of static width computed inside the
    step, one row a step in the ring `step_stat.<name>` [K, width] of
    the program's own state (observability/step_stats.py: `read()` is
    the reader; K = 4096).  The first stat of a program makes its step
    counter and the one `increment` a step; every stat adds one
    `step_stat` op.  Both ops have the role `stat`: no gradient, no
    replay in a recompute segment, dropped by `clone(for_test=True)`.
    `columns` names x's entries for the reader."""
    from paddle_tpu import unique_name
    from paddle_tpu.core.program import STAT
    from paddle_tpu.layers import tensor
    from paddle_tpu.observability import step_stats

    helper = LayerHelper("step_stat", name=name)
    block = helper.main_program.global_block()
    if helper.block is not block:
        raise ValueError("step_stat %r: inside a sub-block; a stat is "
                         "one row a step of the whole program" % name)
    if x.shape is None or len(x.shape) != 1 or int(x.shape[0]) < 1:
        raise ValueError("step_stat %r: x must be a vector of static "
                         "width, got shape %r" % (name, x.shape))
    width = int(x.shape[0])
    columns = [str(c) for c in columns] if columns is not None \
        else ["%d" % i for i in range(width)]
    if len(columns) != width:
        raise ValueError("step_stat %r: %d columns for a width of %d"
                         % (name, len(columns), width))
    stats = [op for op in block.ops if op.type == "step_stat"]
    if any(op.attrs["name"] == name for op in stats):
        raise ValueError("step_stat %r: the program has one already"
                         % name)
    if stats:
        step = block.var(stats[0].inputs["Step"][0])
    else:
        step = tensor.create_global_var(
            [1], 0, "int32", persistable=True,
            name=unique_name.generate("step_stat_step"))
        step.stop_gradient = True
        block.append_op(type="increment", inputs={"X": step},
                        outputs={"Out": step}, attrs={"step": 1.0},
                        op_role=STAT)
    ring = tensor.create_global_var(
        [step_stats.K, width], 0.0, "float32", persistable=True,
        name="step_stat." + name)
    ring.stop_gradient = True
    block.append_op(type="step_stat",
                    inputs={"X": x, "Ring": ring, "Step": step},
                    outputs={"RingOut": ring},
                    attrs={"name": name, "columns": columns},
                    op_role=STAT)
    return ring


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    """Jit-deterministic dropout: a persistable int64 step counter feeds the
    op's SeedOffset so each executor step re-randomizes under jit."""
    helper = LayerHelper("dropout", name=name)
    if not is_test:
        ctr = _step_counter(helper, "dropout")
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference(x.dtype, True)
    inputs = {"X": x}
    if not is_test:
        inputs["SeedOffset"] = ctr
    helper.append_op(
        type="dropout", inputs=inputs,
        outputs={"Out": out, "Mask": mask},
        attrs={"dropout_prob": dropout_prob, "is_test": is_test,
               "seed": seed or 0,
               "dropout_implementation": dropout_implementation},
    )
    return out


# ---------------------------------------------------------------------------
# activations / simple unary
# ---------------------------------------------------------------------------

def _unary(op_type):
    def f(x, name=None):
        return _single_out(op_type, x)
    f.__name__ = op_type
    return f


relu = _unary("relu")
sigmoid = _unary("sigmoid")
tanh = _unary("tanh")
sqrt = _unary("sqrt")
square = _unary("square")
abs = _unary("abs")
exp = _unary("exp")
log = _unary("log")
softplus = _unary("softplus")
softsign = _unary("softsign")


def relu6(x, threshold=6.0, name=None):
    return _single_out("relu6", x, {"threshold": threshold})


def leaky_relu(x, alpha=0.02, name=None):
    return _single_out("leaky_relu", x, {"alpha": alpha})


def gelu(x, approximate=False, name=None):
    return _single_out("gelu", x, {"approximate": approximate})


def elu(x, alpha=1.0, name=None):
    return _single_out("elu", x, {"alpha": alpha})


def swish(x, beta=1.0, name=None):
    return _single_out("swish", x, {"beta": beta})


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return _single_out("hard_sigmoid", x, {"slope": slope,
                                           "offset": offset})


def prelu(x, mode="all", param_attr=None, name=None):
    from paddle_tpu.initializer import Constant

    helper = LayerHelper("prelu", name=name)
    if mode == "all":
        shape = [1]
    elif mode == "channel":
        shape = [x.shape[1]]
    else:
        shape = [int(np.prod(x.shape[1:]))]
    alpha = helper.create_parameter(param_attr, shape, x.dtype,
                                    default_initializer=Constant(0.25))
    # prelu(x) = relu(x) - alpha * relu(-x)
    pos = relu(x)
    neg = relu(scale(x, scale=-1.0))
    scaled_neg = elementwise_mul(neg, alpha, axis=1 if mode == "channel"
                                 else -1)
    return elementwise_sub(pos, scaled_neg)


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return clip(x, t_min, t_max)


def pow(x, factor=1.0, name=None):
    return _single_out("pow", x, {"factor": factor})


def softmax(input, axis=-1, name=None, use_cudnn=False):
    return _single_out("softmax", input, {"axis": axis})


def log_softmax(input, axis=-1, name=None):
    return _single_out("log_softmax", input, {"axis": axis})


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="cross_entropy", inputs={"X": input, "Label": label},
        outputs={"Y": out},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, return_softmax=False,
                               numeric_stable_mode=True, axis=-1):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax_out = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": logits, "Label": label},
        outputs={"Softmax": softmax_out, "Loss": loss},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index,
               "axis": axis, "numeric_stable_mode": numeric_stable_mode},
    )
    if return_softmax:
        return loss, softmax_out
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100,
                                      normalize=False, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="sigmoid_cross_entropy_with_logits",
        inputs={"X": x, "Label": label}, outputs={"Out": out},
        attrs={"ignore_index": ignore_index, "normalize": normalize},
    )
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="square_error_cost", inputs={"X": input, "Y": label},
        outputs={"Out": out},
    )
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    out = helper.create_variable_for_type_inference(input.dtype)
    res = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op(
        type="huber_loss", inputs={"X": input, "Y": label},
        outputs={"Out": out, "Residual": res}, attrs={"delta": delta},
    )
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="log_loss", inputs={"Predicted": input, "Labels": label},
        outputs={"Loss": out}, attrs={"epsilon": epsilon},
    )
    return out


# ---------------------------------------------------------------------------
# math / matmul / elementwise / reductions
# ---------------------------------------------------------------------------

def mean(x, name=None):
    return _single_out("mean", x)


def _reduce(op_type, input, dim, keep_dim):
    if dim is None:
        attrs = {"dim": [0], "keep_dim": keep_dim, "reduce_all": True}
    else:
        attrs = {"dim": dim if isinstance(dim, (list, tuple)) else [dim],
                 "keep_dim": keep_dim, "reduce_all": False}
    return _single_out(op_type, input, attrs)


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_sum", input, dim, keep_dim)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_mean", input, dim, keep_dim)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_max", input, dim, keep_dim)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_min", input, dim, keep_dim)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_prod", input, dim, keep_dim)


def reduce_all(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_all", input, dim, keep_dim)


def reduce_any(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_any", input, dim, keep_dim)


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0,
           name=None):
    helper = LayerHelper("matmul")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="matmul", inputs={"X": x, "Y": y}, outputs={"Out": out},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y,
               "alpha": float(alpha)},
    )
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="mul", inputs={"X": x, "Y": y}, outputs={"Out": out},
        attrs={"x_num_col_dims": x_num_col_dims,
               "y_num_col_dims": y_num_col_dims},
    )
    return out


def elementwise_op(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type=op_type, inputs={"X": x, "Y": y}, outputs={"Out": out},
        attrs={"axis": axis},
    )
    return helper.append_activation(out, act)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_div", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_max", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_min", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_pow", x, y, axis, act, name)


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None,
          name=None):
    helper = LayerHelper("scale")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="scale", inputs={"X": x}, outputs={"Out": out},
        attrs={"scale": float(scale), "bias": float(bias),
               "bias_after_scale": bias_after_scale},
    )
    return helper.append_activation(out, act)


def cos_sim(X, Y):
    xn = l2_normalize(X, axis=-1)
    yn = l2_normalize(Y, axis=-1)
    return reduce_sum(elementwise_mul(xn, yn), dim=-1, keep_dim=True)


# ---------------------------------------------------------------------------
# shape manipulation
# ---------------------------------------------------------------------------

def cast(x, dtype):
    return _single_out("cast", x, {"out_dtype": str(np.dtype(dtype))},
                       out_dtype=str(np.dtype(dtype)))


def reshape(x, shape, actual_shape=None, act=None, inplace=False,
            name=None):
    helper = LayerHelper("reshape2")
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op(
        type="reshape2", inputs={"X": x},
        outputs={"Out": out, "XShape": xshape},
        attrs={"shape": list(shape)},
    )
    return helper.append_activation(out, act)


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2")
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op(
        type="transpose2", inputs={"X": x},
        outputs={"Out": out, "XShape": xshape},
        attrs={"axis": list(perm)},
    )
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2")
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op(
        type="flatten2", inputs={"X": x},
        outputs={"Out": out, "XShape": xshape}, attrs={"axis": axis},
    )
    return out


def squeeze(input, axes=None, name=None):
    helper = LayerHelper("squeeze2")
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op(
        type="squeeze2", inputs={"X": input},
        outputs={"Out": out, "XShape": xshape},
        attrs={"axes": axes or []},
    )
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2")
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op(
        type="unsqueeze2", inputs={"X": input},
        outputs={"Out": out, "XShape": xshape}, attrs={"axes": axes},
    )
    return out


def concat(input, axis=0, name=None):
    helper = LayerHelper("concat")
    out = helper.create_variable_for_type_inference(input[0].dtype)
    helper.append_op(
        type="concat", inputs={"X": input}, outputs={"Out": out},
        attrs={"axis": axis},
    )
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split")
    dim = dim if dim >= 0 else dim + len(input.shape)
    if isinstance(num_or_sections, int):
        n = num_or_sections
        attrs = {"num": n, "sections": [], "axis": dim}
    else:
        n = len(num_or_sections)
        attrs = {"num": 0, "sections": list(num_or_sections), "axis": dim}
    outs = [helper.create_variable_for_type_inference(input.dtype)
            for _ in range(n)]
    helper.append_op(type="split", inputs={"X": input},
                     outputs={"Out": outs}, attrs=attrs)
    return outs


def stack(x, axis=0):
    helper = LayerHelper("stack")
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op(type="stack", inputs={"X": x}, outputs={"Y": out},
                     attrs={"axis": axis})
    return out


def slice(input, axes, starts, ends):
    return _single_out("slice", input,
                       {"axes": list(axes), "starts": list(starts),
                        "ends": list(ends)}, in_slot="Input")


def gather(input, index):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="gather", inputs={"X": input, "Index": index},
                     outputs={"Out": out})
    return out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="gather_nd", inputs={"X": input, "Index": index},
                     outputs={"Out": out})
    return out


def scatter(input, index, updates, overwrite=True, name=None):
    helper = LayerHelper("scatter")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="scatter",
        inputs={"X": input, "Ids": index, "Updates": updates},
        outputs={"Out": out}, attrs={"overwrite": overwrite})
    return out


def expand(x, expand_times, name=None):
    return _single_out("expand", x, {"expand_times": list(expand_times)})


def pad(x, paddings, pad_value=0.0, name=None):
    return _single_out("pad", x, {"paddings": list(paddings),
                                  "pad_value": pad_value})


def one_hot(input, depth, dtype="float32"):
    return _single_out("one_hot", input, {"depth": depth, "dtype": dtype},
                       out_dtype=dtype)


def cumsum(x, axis=-1, exclusive=False, reverse=False):
    return _single_out("cumsum", x, {"axis": axis, "exclusive": exclusive,
                                     "reverse": reverse})


def increment(x, value=1.0, in_place=True):
    helper = LayerHelper("increment")
    if in_place:
        out = x
    else:
        out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="increment", inputs={"X": x},
                     outputs={"Out": out}, attrs={"step": float(value)})
    return out


def shape(input):
    return _single_out("shape", input, out_dtype="int64", in_slot="Input")


# ---------------------------------------------------------------------------
# comparison / logic / selection
# ---------------------------------------------------------------------------

def _cmp(op_type, x, y, cond=None):
    helper = LayerHelper(op_type)
    out = cond or helper.create_variable_for_type_inference("bool")
    helper.append_op(type=op_type, inputs={"X": x, "Y": y},
                     outputs={"Out": out})
    return out


def equal(x, y, cond=None):
    return _cmp("equal", x, y, cond)


def not_equal(x, y, cond=None):
    return _cmp("not_equal", x, y, cond)


def less_than(x, y, cond=None, force_cpu=None):
    return _cmp("less_than", x, y, cond)


def greater_than(x, y, cond=None):
    return _cmp("greater_than", x, y, cond)


def logical_and(x, y, out=None):
    return _cmp("logical_and", x, y, out)


def logical_or(x, y, out=None):
    return _cmp("logical_or", x, y, out)


def logical_not(x, out=None):
    helper = LayerHelper("logical_not")
    out = out or helper.create_variable_for_type_inference("bool")
    helper.append_op(type="logical_not", inputs={"X": x},
                     outputs={"Out": out})
    return out


def where(condition, x, y):
    helper = LayerHelper("where")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="where", inputs={"Condition": condition, "X": x, "Y": y},
        outputs={"Out": out})
    return out


# ---------------------------------------------------------------------------
# topk / argmax / metrics
# ---------------------------------------------------------------------------

def topk(input, k, name=None):
    helper = LayerHelper("top_k")
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64")
    helper.append_op(type="top_k", inputs={"X": input},
                     outputs={"Out": values, "Indices": indices},
                     attrs={"k": k})
    return values, indices


def argmax(x, axis=0, name=None):
    return _single_out("arg_max", x, {"axis": axis}, out_dtype="int64")


def argsort(input, axis=-1, descending=False, name=None):
    helper = LayerHelper("argsort")
    out = helper.create_variable_for_type_inference(input.dtype)
    idx = helper.create_variable_for_type_inference("int64")
    helper.append_op(type="argsort", inputs={"X": input},
                     outputs={"Out": out, "Indices": idx},
                     attrs={"axis": axis, "descending": descending})
    return out, idx


def accuracy(input, label, k=1, correct=None, total=None):
    """reference layers/metric_op.py accuracy."""
    helper = LayerHelper("accuracy")
    values, indices = topk(input, k)
    acc = helper.create_variable_for_type_inference("float32")
    correct = correct or helper.create_variable_for_type_inference("int64")
    total = total or helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="accuracy",
        inputs={"Out": values, "Indices": indices, "Label": label},
        outputs={"Accuracy": acc, "Correct": correct, "Total": total})
    return acc


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

def clip(x, min, max, name=None):
    return _single_out("clip", x, {"min": float(min), "max": float(max)})


def clip_by_norm(x, max_norm, name=None):
    return _single_out("clip_by_norm", x, {"max_norm": float(max_norm)})


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize")
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op(type="l2_normalize", inputs={"X": x},
                     outputs={"Out": out, "Norm": norm},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    return _single_out("label_smooth", label, {"epsilon": float(epsilon)})


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn")
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op(type="lrn", inputs={"X": input},
                     outputs={"Out": out, "MidOut": mid},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    return _single_out("sequence_mask", x,
                       {"maxlen": maxlen or -1, "out_dtype": dtype},
                       out_dtype=dtype, out_slot="Y")


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, name=None, n_head=None, n_kv_head=None,
                    window=None):
    """Fused blockwise attention (Pallas TPU kernel; ops/pallas_kernels.py).

    q/k/v: [B, H, T, D] post-split-heads, or, with n_head, token-major
    [B, T, H*D] as the q, k and v projections leave them: Out is then
    [B, Tq, H*D], what the output projection takes, and no head split
    or merge is made (where the kernels cannot address the heads in
    place the op transposes inside, to the same answer).  k and v may
    have fewer heads than q, n_kv_head (grouped-query attention: query
    head h reads KV head h // (n_head / n_kv_head)): [B, n_kv_head, T,
    D] or token-major [B, T, n_kv_head*D].  The op reads the count off
    k's shape and the kernels read K and V in place, never repeated to
    n_head heads; n_kv_head here only checks k against it.  scale: the
    factor on q.k before the softmax, default D^-1/2.  Replaces the
    reference's matmul+softmax+matmul composition (nets.py
    scaled_dot_product_attention) with a single kernel that never
    materializes the [Tq, Tk] score matrix.  block_q/block_k override
    the kernel tile sizes (default picked by sequence length: 1024 for
    T >= 1024, else 512 — pinned by the 2026-08-01 v5e sweep; PERF.md
    section 6, PR 21).  window (needs causal): a sliding window, query
    i sees the `window` keys that end at its own, i - window < j <= i;
    the kernels walk the band only and pick their tiles from the
    window (docs/FLASH_ATTENTION.md "The band").

    Returns Out.  The op also writes LSE, the per-row log-sum-exp
    (float32 [B, H, Tq], no gradient): the residual flash_attention_grad
    reads, with Out, instead of running the forward kernel again.
    """
    if n_kv_head is not None:
        heads = int(n_head or q.shape[1])
        have = int(k.shape[1]) if not n_head \
            else int(k.shape[-1]) // (int(q.shape[-1]) // heads)
        if have != n_kv_head or heads % n_kv_head:
            raise ValueError(
                "flash_attention: k has %d heads of q's size, n_kv_head "
                "says %d (of %d query heads)" % (have, n_kv_head, heads))
    if window and not causal:
        raise ValueError("flash_attention: window %r needs causal=True"
                         % (window,))
    helper = LayerHelper("flash_attention")
    out = helper.create_variable_for_type_inference(q.dtype)
    lse = helper.create_variable_for_type_inference("float32", True)
    helper.append_op(
        type="flash_attention", inputs={"Q": q, "K": k, "V": v},
        outputs={"Out": out, "LSE": lse},
        attrs={"causal": causal, "scale": float(scale or 0.0),
               "block_q": int(block_q or 0), "block_k": int(block_k or 0),
               "heads": int(n_head or 0), "window": int(window or 0)})
    return out


def linear_chain_crf(input, label, param_attr=None, length=None,
                     name=None):
    """Linear-chain CRF cost (reference layers/nn.py linear_chain_crf;
    op: ops/loss_ops.py).  input: [B, T, D] emissions (padded), label:
    [B, T] or [B, T, 1], length: [B].  Returns per-sequence cost [B, 1];
    the learned 'transition' param holds [start; end; pairwise]."""
    helper = LayerHelper("linear_chain_crf", name=name)
    d = int(input.shape[-1])
    transition = helper.create_parameter(param_attr, [d + 2, d],
                                         "float32")
    out = helper.create_variable_for_type_inference("float32")
    inputs = {"Emission": input, "Transition": transition,
              "Label": label}
    if length is not None:
        inputs["Length"] = length
    helper.append_op(type="linear_chain_crf", inputs=inputs,
                     outputs={"LogLikelihood": out}, infer_shape=False)
    out.shape = (input.shape[0], 1)
    out.transition = transition
    return out


def crf_decoding(input, param_attr=None, label=None, length=None,
                 transition=None, name=None):
    """Viterbi path (or per-position correctness when label given)."""
    helper = LayerHelper("crf_decoding", name=name)
    if transition is None and param_attr is not None:
        from paddle_tpu.param_attr import ParamAttr

        attr = ParamAttr._to_attr(param_attr)
        gb = helper.main_program.global_block()
        if attr.name and gb.has_var(attr.name):
            transition = gb.var(attr.name)
    if transition is None:
        raise ValueError(
            "crf_decoding needs the transition param: pass transition="
            "crf_cost.transition, or param_attr=ParamAttr(name=...) "
            "naming the shared CRF weight")
    out = helper.create_variable_for_type_inference("int64")
    inputs = {"Emission": input, "Transition": transition}
    if label is not None:
        inputs["Label"] = label
    if length is not None:
        inputs["Length"] = length
    helper.append_op(type="crf_decoding", inputs=inputs,
                     outputs={"ViterbiPath": out}, infer_shape=False)
    out.shape = tuple(input.shape[:2])
    return out


def _sampling_seed_counter(helper):
    """Shared jit-deterministic sampling counter (dropout pattern)."""
    return _step_counter(helper, "sampling")


def nce(input, label, num_total_classes, sample_weight=None,
        param_attr=None, bias_attr=None, num_neg_samples=10, seed=0,
        name=None):
    """NCE loss (reference layers/nn.py nce).  Returns [B, 1] cost."""
    helper = LayerHelper("nce", name=name)
    d = int(input.shape[-1])
    w = helper.create_parameter(param_attr, [num_total_classes, d],
                                "float32")
    b = helper.create_parameter(bias_attr, [num_total_classes],
                                "float32", is_bias=True)
    ctr = _sampling_seed_counter(helper)
    out = helper.create_variable_for_type_inference("float32")
    ins = {"Input": input, "Label": label, "Weight": w, "Bias": b,
           "SeedOffset": ctr}
    if sample_weight is not None:
        ins["SampleWeight"] = sample_weight
    helper.append_op(
        type="nce",
        inputs=ins,
        outputs={"Cost": out},
        attrs={"num_total_classes": num_total_classes,
               "num_neg_samples": num_neg_samples, "seed": seed},
        infer_shape=False)
    out.shape = (input.shape[0], 1)
    return out


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None):
    """Hierarchical sigmoid over a complete binary tree (reference
    layers/nn.py hsigmoid)."""
    helper = LayerHelper("hsigmoid", name=name)
    d = int(input.shape[-1])
    w = helper.create_parameter(param_attr, [num_classes - 1, d],
                                "float32")
    b = helper.create_parameter(bias_attr, [num_classes - 1], "float32",
                                is_bias=True)
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="hierarchical_sigmoid",
        inputs={"X": input, "Label": label, "W": w, "Bias": b},
        outputs={"Out": out}, attrs={"num_classes": num_classes},
        infer_shape=False)
    out.shape = (input.shape[0], 1)
    return out


def sample_logits(logits, label, num_samples, seed=0,
                  remove_accidental_hits=True, name=None):
    """Sampled-softmax helper: returns (sampled_logits [B, NT+S],
    samples [B, NT+S]); train with softmax_with_cross_entropy against
    column-0 labels (reference layers/nn.py sample_logits + tests)."""
    helper = LayerHelper("sample_logits", name=name)
    ctr = _sampling_seed_counter(helper)
    out = helper.create_variable_for_type_inference(logits.dtype)
    samples = helper.create_variable_for_type_inference("int64", True)
    helper.append_op(
        type="sample_logits",
        inputs={"Logits": logits, "Labels": label, "SeedOffset": ctr},
        outputs={"SampledLogits": out, "Samples": samples},
        attrs={"num_samples": num_samples, "seed": seed,
               "remove_accidental_hits": remove_accidental_hits},
        infer_shape=False)
    return out, samples
