"""Layer function of an EVA attention mixer (ops/eva_ops.py):
eva_attention, the chunk summariser and the aggregation over a window's
tokens and the earlier windows' summaries in one softmax.
docs/EVABYTE_BLOCK.md has the equations; models/evabyte.py builds a
byte-level decoder from it."""

from __future__ import annotations

import numpy as np

from paddle_tpu.layers.helper import LayerHelper
from paddle_tpu.layers.llm import _named

__all__ = ["eva_attention"]


def eva_attention(q, k, v, n_head, window, chunk, param_attr=None,
                  impl=None, name=None):
    """EVA attention of token-major q, k, v [B, T, n_head*D] (q and k
    rotated, as the projections and `rotary_embedding(n_head=)` leave
    them); returns [B, T, n_head*D], what the output projection takes.

    Query i, in window w = i // window, sees two kinds of key in ONE
    softmax at the scale D^-1/2: the tokens t of its own window
    with t <= i, and for every chunk j of `chunk` tokens of an EARLIER
    window (chunk j // window < w) the learned summaries

        k~_j = sum_m softmax_m(mu . k_{cj+m}) k_{cj+m}
        v~_j = sum_m softmax_m(phi . k_{cj+m}) v_{cj+m}

    with mu, phi float32 [n_head, D]: `<name>_mu.w` and `<name>_phi.w`,
    initially N(0, 1) clipped to +-1, times D^-1/2 (drawn from
    np.random when the layer is built).  Windows are ALIGNED to
    multiples of `window` (no sliding band); window 0 has no chunk
    keys; T <= window is causal attention.  T must be whole chunks and,
    past one window, whole windows: nothing is padded.  Two ops,
    eva_pool and eva_attention; the second also writes LSE (float32
    [B T/window, n_head, window], no gradient), with Out the residual
    its grad op reads instead of running a forward kernel again.  impl: None
    (pallas on a TPU, xla elsewhere), "pallas", "interpret", "xla"."""
    from paddle_tpu.initializer import NumpyArrayInitializer

    n_head, window, chunk = int(n_head), int(window), int(chunk)
    t, width = q.shape[1], int(q.shape[-1])
    if width % n_head or window % chunk:
        raise ValueError(
            "eva_attention: %d channels in %d heads, chunks of %d in a "
            "window of %d" % (width, n_head, chunk, window))
    if t is not None and t > 0 and (t % chunk
                                    or (t > window and t % window)):
        raise ValueError(
            "eva_attention: %d tokens are not whole chunks of %d and, "
            "past one window, whole windows of %d; nothing is padded"
            % (t, chunk, window))
    d = width // n_head
    helper = LayerHelper("eva_attention", name=name)

    def vector(part):
        draw = np.clip(np.random.standard_normal((n_head, d)), -1.0, 1.0)
        return helper.create_parameter(
            _named(param_attr, name, part), [n_head, d], "float32",
            default_initializer=NumpyArrayInitializer(
                (draw / np.sqrt(d)).astype(np.float32)))

    mu, phi = vector("mu"), vector("phi")
    attrs = {"heads": n_head, "window": window, "chunk": chunk,
             "impl": impl or ""}
    ks = helper.create_variable_for_type_inference(k.dtype)
    vs = helper.create_variable_for_type_inference(v.dtype)
    helper.append_op(type="eva_pool",
                     inputs={"K": k, "V": v, "Mu": mu, "Phi": phi},
                     outputs={"KSum": ks, "VSum": vs}, attrs=attrs)
    out = helper.create_variable_for_type_inference(q.dtype)
    lse = helper.create_variable_for_type_inference("float32", True)
    helper.append_op(
        type="eva_attention",
        inputs={"Q": q, "K": k, "V": v, "KSum": ks, "VSum": vs},
        outputs={"Out": out, "LSE": lse}, attrs=attrs)
    return out
