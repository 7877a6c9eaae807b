"""Layer functions of a Mamba-2 state-space mixer (ops/ssd_ops.py): the
chunked scan ssd_scan, the depthwise causal convolution over time
causal_conv1d, and the gated RMSNorm gated_rms_norm.
docs/GRANITE4_BLOCK.md has the equations; models/granite_hybrid.py
builds a hybrid stack from them.  gated_short_conv is the same
convolution op with both gates of an LFM2 layer inside
(docs/LFM2_BLOCK.md; models/lfm2.py)."""

from __future__ import annotations

from paddle_tpu.layers.helper import LayerHelper
from paddle_tpu.layers.llm import _named

__all__ = ["ssd_scan", "mamba2_scan", "causal_conv1d", "gated_short_conv",
           "gated_rms_norm"]


def ssd_scan(x, dt, a, b, c, d, chunk_size=256, impl=None, name=None):
    """The Mamba-2 recurrence over time, by chunks: per head, from a
    zero state, S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T and
    y_t = S_t c_t + d x_t.  x [B, T, H*P] token-major, dt [B, T, H]
    positive (after its softplus), a [H] negative, b and c [B, T, N]
    (one group: every head reads them), d [H]; returns y [B, T, H*P].
    T must be a multiple of chunk_size.  The op also writes States, the
    state each chunk starts from (float32 [B, T/chunk, H*P, N], no
    gradient): the residual ssd_scan_grad reads, with y, instead of
    running the forward kernel again.  impl: None (pallas on a TPU, xla
    elsewhere), "pallas", "interpret", "xla"."""
    t = x.shape[1] if x.shape is not None else None
    if t is not None and t > 0 and t % int(chunk_size):
        raise ValueError(
            "ssd_scan: %d tokens are no multiple of the chunk size %d; "
            "nothing is padded" % (t, chunk_size))
    helper = LayerHelper("ssd_scan", name=name)
    y = helper.create_variable_for_type_inference(x.dtype)
    states = helper.create_variable_for_type_inference("float32", True)
    helper.append_op(
        type="ssd_scan",
        inputs={"X": x, "Dt": dt, "A": a, "B": b, "C": c, "D": d},
        outputs={"Y": y, "States": states},
        attrs={"chunk_size": int(chunk_size), "impl": impl or ""})
    return y


# what the Mamba-2 reference code draws a mixer's A and its initial
# step size from
_A_RANGE = (1.0, 16.0)
_DT_RANGE = (1e-3, 1e-1)


def mamba2_scan(x, dt, b, c, chunk_size=256, impl=None, name=None):
    """`ssd_scan` with a Mamba-2 mixer's per-head parameters, H =
    dt's last axis: `<name>_A_log.w`, `<name>_dt_bias.w` and
    `<name>_D.w`, each float32 [H].  dt here is the mixer's raw step
    projection; the scan runs with

        Dt = softplus(dt + dt_bias),  A = -exp(A_log),  D

    all float32 under AMP (exp and softplus are not on its bfloat16
    lists).  Initial values as the Mamba-2 reference code draws them,
    from numpy's global generator when the layer is built: A uniform in
    [1, 16], dt_bias the inverse softplus of a log-uniform draw in
    [1e-3, 1e-1], D ones."""
    import numpy as np

    from paddle_tpu.initializer import Constant, NumpyArrayInitializer
    from paddle_tpu.layers import nn

    helper = LayerHelper("mamba2_scan", name=name)
    h = int(dt.shape[-1])

    def per_head(part, init):
        return helper.create_parameter(_named(None, name, part), [h],
                                       "float32", default_initializer=init)

    a0 = np.random.uniform(*_A_RANGE, h)
    dt0 = np.exp(np.random.uniform(*np.log(_DT_RANGE), h))
    a_log = per_head("A_log", NumpyArrayInitializer(
        np.log(a0).astype(np.float32)))
    # softplus(dt0 + ln(1 - exp(-dt0))) = dt0
    dt_bias = per_head("dt_bias", NumpyArrayInitializer(
        (dt0 + np.log(-np.expm1(-dt0))).astype(np.float32)))
    d = per_head("D", Constant(1.0))
    step = nn.softplus(nn.elementwise_add(dt, dt_bias))
    a = nn.scale(nn.exp(a_log), scale=-1.0)
    return ssd_scan(x, step, a, b, c, d, chunk_size=chunk_size,
                    impl=impl, name=name)


def causal_conv1d(input, width, activation="silu", param_attr=None,
                  bias_attr=None, name=None, gated=False):
    """Depthwise convolution over time that never reads ahead, bias and
    activation fused: input [B, T, C], a filter of `width` taps a
    channel (`<name>.w` [C, width]) and a bias (`<name>_bias.w` [C];
    bias_attr False: none), the input zero before t = 0.  Both start
    uniform in (-width^-1/2, width^-1/2) unless their attr says
    otherwise: what the Mamba-2 reference code's depthwise Conv1d
    starts from (one input channel a filter: a fan-in of `width`).
    activation "silu" or None.  gated: input is [B, T, 3 C], see
    gated_short_conv."""
    from paddle_tpu.initializer import Uniform

    helper = LayerHelper("causal_conv1d", name=name)
    c = int(input.shape[-1]) // (3 if gated else 1)
    bound = float(width) ** -0.5
    inputs = {"X": input,
              "W": helper.create_parameter(
                  _named(param_attr, name, ""), [c, int(width)], "float32",
                  default_initializer=Uniform(-bound, bound))}
    if bias_attr is not False:
        inputs["Bias"] = helper.create_parameter(
            _named(bias_attr, name, "bias"), [c], "float32",
            default_initializer=Uniform(-bound, bound))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="causal_conv1d", inputs=inputs,
                     outputs={"Y": out},
                     attrs={"activation": activation or "",
                            "gated": bool(gated)})
    return out


def gated_short_conv(input, width, param_attr=None, name=None):
    """The doubly gated short convolution of an LFM2 layer, between its
    two projections: input [B, T, 3 C] is ONE projection whose thirds
    along the last axis are [Gb | Gc | x], and

        y[t] = Gc[t] * sum_k w[:, k] (Gb * x)[t - (width-1) + k]

    [B, T, C], zero before t = 0; no bias, no activation; a filter of
    `width` taps a channel (`<name>.w` [C, width], uniform in
    (-width^-1/2, width^-1/2) unless param_attr says otherwise).  One
    causal_conv1d op with `gated` set: its kernels read the thirds in
    place and its grad writes the projection's whole gradient once."""
    if int(input.shape[-1]) % 3:
        raise ValueError("gated_short_conv: %d channels are not three "
                         "thirds" % int(input.shape[-1]))
    return causal_conv1d(input, width, activation=None,
                         param_attr=param_attr, bias_attr=False, name=name,
                         gated=True)


def gated_rms_norm(input, gate, epsilon=1e-6, param_attr=None, name=None):
    """RMSNorm(input * silu(gate)) over the last axis with a learnable
    scale (`<name>.w`, initially 1): the gate before the norm, the
    statistic float32, the output in the input's dtype."""
    from paddle_tpu.initializer import Constant

    helper = LayerHelper("gated_rms_norm", name=name)
    scale = helper.create_parameter(
        _named(param_attr, name, ""), [int(input.shape[-1])], "float32",
        default_initializer=Constant(1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="gated_rms_norm",
        inputs={"X": input, "Gate": gate, "Scale": scale},
        outputs={"Y": out}, attrs={"epsilon": float(epsilon)})
    return out
