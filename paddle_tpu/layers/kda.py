"""Layer functions of a Kimi Delta Attention mixer (ops/kda_ops.py): the
chunked delta-rule scan kda_scan, the log-decay gate kda_gate (the safe
sigmoid form with a bound, or the softplus form without one), the
per-head L2 norm head_l2_norm and the gated RMSNorm a head
head_gated_rms_norm (a gate a head or a channel).  docs/LING3_BLOCK.md
and docs/SOLAR_OPEN2_BLOCK.md have the equations; models/ling3.py and
models/solar_open2.py build hybrid stacks from them (the depthwise
causal convolution is layers.causal_conv1d)."""

from __future__ import annotations

from paddle_tpu.layers.helper import LayerHelper
from paddle_tpu.layers.llm import _named

__all__ = ["kda_scan", "kda_gate", "head_l2_norm", "head_gated_rms_norm"]


def kda_scan(q, k, v, g, beta, chunk_size=64, block_chunks=4, impl=None,
             name=None):
    """The delta-rule recurrence with a decay per key channel, by
    chunks: per head, from a zero state,

        S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
              + beta_t k_t v_t^T,      o_t = S_t^T q_t

    q, k, v [B, T, H*D] token-major, g [B, T, H*D] the log-decay a key
    channel (float32, <= 0: `kda_gate`), beta [B, T, H] in (0, 1), or
    in (0, 2) where the caller doubles it; returns o [B, T, H*D].  T
    must be a multiple of block_chunks x chunk_size.  The op's attr
    `decay` is what the gate that made g promises (`kda_gate` marks
    its output): "bounded" where g >= -5.33 a token (the kernels then
    form a sub-block's decays as one product), "unbounded", exact for
    any g <= 0, for the softplus gate and for a g of any other origin.  The op also writes States, the transposed state each
    block of chunks starts from (float32 [B, T/block, H*D, D]), and
    Inverse, the inverse of each chunk's triangular system (float32
    [B, H, T/block, chunk_size, block]); neither has a gradient: the
    residuals kda_scan_grad reads, with o, instead of running the
    forward kernel and inverting every chunk's system again.  impl:
    None (pallas on a TPU, xla elsewhere), "pallas", "interpret",
    "xla"."""
    t = q.shape[1] if q.shape is not None else None
    block = int(chunk_size) * int(block_chunks)
    if t is not None and t > 0 and t % block:
        raise ValueError(
            "kda_scan: %d tokens are no multiple of %d (%d chunks of %d, "
            "one saved state); nothing is padded"
            % (t, block, block_chunks, chunk_size))
    from paddle_tpu.ops.pallas_kda import BOUNDED_G_MIN

    bound = getattr(g, "kda_decay_bound", None)
    decay = "bounded" if bound is not None and bound >= BOUNDED_G_MIN \
        else "unbounded"
    helper = LayerHelper("kda_scan", name=name)
    o = helper.create_variable_for_type_inference(v.dtype)
    states = helper.create_variable_for_type_inference("float32", True)
    inverse = helper.create_variable_for_type_inference("float32", True)
    helper.append_op(
        type="kda_scan",
        inputs={"Q": q, "K": k, "V": v, "G": g, "Beta": beta},
        outputs={"O": o, "States": states, "Inverse": inverse},
        attrs={"chunk_size": int(chunk_size),
               "block_chunks": int(block_chunks), "impl": impl or "",
               "decay": decay})
    return o


# a head's decay rate exp(A_log) is drawn log-uniform in this range,
# and dt_bias so that g at a zero projection (lower_bound *
# sigmoid(rate * dt_bias), or -rate * softplus(dt_bias)) starts
# log-uniform in _G0_RANGE a channel: some channels forget in tens of
# tokens, some carry state over thousands
_RATE_RANGE = (1.0, 4.0)
_G0_RANGE = (1e-4, 1e-1)


def kda_gate(input, n_head, lower_bound=-5.0, name=None,
             form="sigmoid_bound"):
    """The log-decay gate of a KDA mixer: input [B, T, H*D] the decay
    projection -> g float32, by `form`:

        "sigmoid_bound"  g = lower_bound * sigmoid(exp(A_log_h) * (input + dt_bias))
        "softplus"       g = -exp(A_log_h) * softplus(input + dt_bias)

    the first in (lower_bound, 0) (the safe gate), the second in
    (-inf, 0) (Kimi Linear's; lower_bound is not read).  The output is
    marked with what it promises (`kda_decay_bound`), which
    `kda_scan` reads.  Parameters `<name>_A_log.w` [H] and
    `<name>_dt_bias.w` [H*D], float32.  Initial values, from numpy's
    global generator when the layer is built: exp(A_log) log-uniform in
    [1, 4]; dt_bias such that -g starts log-uniform in [1e-4, 1e-1] a
    channel at input 0."""
    import numpy as np

    from paddle_tpu.initializer import NumpyArrayInitializer

    if form not in ("sigmoid_bound", "softplus"):
        raise ValueError("kda_gate: form %r" % (form,))
    helper = LayerHelper("kda_gate", name=name)
    width = int(input.shape[-1])
    rate = np.exp(np.random.uniform(*np.log(_RATE_RANGE), n_head))
    g0 = np.exp(np.random.uniform(*np.log(_G0_RANGE), width))
    per_channel = np.repeat(rate, width // n_head)
    if form == "softplus":
        # rate * softplus(b) = g0
        bias = np.log(np.expm1(g0 / per_channel))
    else:
        # sigmoid(rate * b) = g0 / -lower_bound
        share = g0 / -float(lower_bound)
        bias = np.log(share / (1.0 - share)) / per_channel
    a_log = helper.create_parameter(
        _named(None, name, "A_log"), [n_head], "float32",
        default_initializer=NumpyArrayInitializer(
            np.log(rate).astype(np.float32)))
    dt_bias = helper.create_parameter(
        _named(None, name, "dt_bias"), [width], "float32",
        default_initializer=NumpyArrayInitializer(bias.astype(np.float32)))
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="kda_gate",
        inputs={"X": input, "ALog": a_log, "DtBias": dt_bias},
        outputs={"G": out},
        attrs={"lower_bound": float(lower_bound), "form": form})
    out.kda_decay_bound = float(lower_bound) \
        if form == "sigmoid_bound" else None
    return out


def head_l2_norm(input, n_head, scale=1.0, epsilon=1e-6, name=None):
    """scale * x_h / sqrt(sum(x_h^2) + epsilon) for each of the n_head
    slices of the last axis; no parameter."""
    helper = LayerHelper("head_l2_norm", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="head_l2_norm", inputs={"X": input}, outputs={"Y": out},
        attrs={"n_head": int(n_head), "scale": float(scale),
               "epsilon": float(epsilon)})
    return out


def head_gated_rms_norm(input, gate, epsilon=1e-6, norm=True,
                        param_attr=None, name=None, n_head=None):
    """sigmoid(gate_h) * RMSNorm(x_h) * scale for each head's slice x_h
    of the last axis: input [.., H*D], gate [.., H] one logit a head,
    the norm a head with ONE learnable scale of D (`<name>.w`,
    initially 1), the gate after the norm.  gate [.., H*D], as wide as
    the input, with n_head = H: one logit a CHANNEL, sigmoid(gate) *
    RMSNorm(x_h) * scale.  norm=False: no norm and no parameter,
    sigmoid(gate_h) * x_h, or with a gate a channel sigmoid(gate) * x
    (n_head is then not needed).  gate None with n_head = H: no gate,
    RMSNorm(x_h) * scale (the norm on q and on k of an attention layer,
    a head at a time)."""
    from paddle_tpu.initializer import Constant

    helper = LayerHelper("head_gated_rms_norm", name=name)
    channel = gate is not None \
        and int(gate.shape[-1]) == int(input.shape[-1]) \
        and (n_head is not None or not norm)
    if (gate is None and (n_head is None or not norm)) \
            or (gate is not None and not channel and n_head is not None):
        raise ValueError("head_gated_rms_norm: one of a gate and n_head "
                         "says how many heads (a gate a channel, as "
                         "wide as the input, takes n_head beside it), "
                         "and without a gate there is the norm")
    heads = int(n_head or (1 if channel else gate.shape[-1]))
    inputs = {"X": input}
    if gate is not None:
        inputs["Gate"] = gate
    if norm:
        inputs["Scale"] = helper.create_parameter(
            _named(param_attr, name, ""),
            [int(input.shape[-1]) // heads], "float32",
            default_initializer=Constant(1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="head_gated_rms_norm", inputs=inputs,
                     outputs={"Y": out},
                     attrs={"epsilon": float(epsilon),
                            "n_head": int(n_head or 0)})
    return out
