"""Layer functions of the 2024-26 decoder block (ops/llm_ops.py):
rms_norm, rotary_embedding, swiglu, moe_route, moe_experts, the two
halves of a manifold-constrained hyper-connection, mhc_pre / mhc_post,
and the sequence-wise expert-balance loss, a composition of ops the IR
has.  docs/XING4_BLOCK.md and docs/DSV2_BLOCK.md have the equations;
models/xing4.py and models/deepseek_v2.py build models from them."""

from __future__ import annotations

import numpy as np

from paddle_tpu.layers.helper import LayerHelper

__all__ = ["rms_norm", "rotary_embedding", "swiglu", "moe_route",
           "moe_balance_loss", "moe_experts", "mhc_pre", "mhc_post"]


def _named(attr, name, part):
    """A ParamAttr named `<name>_<part>.w` when the layer is named and
    the caller gave none: deterministic names let a reference or a
    second program find the weights in the scope."""
    import copy

    from paddle_tpu.param_attr import ParamAttr

    attr = copy.copy(ParamAttr._to_attr(attr))   # the caller's is shared
    if attr.name is None and name is not None:
        attr.name = "%s_%s.w" % (name, part) if part else name + ".w"
    return attr


def rms_norm(input, epsilon=1e-6, param_attr=None, name=None,
             unit_offset=False):
    """RMSNorm over the last axis with a learnable scale (initially 1);
    the statistic is float32, the output has the input's dtype.
    unit_offset: y = x / rms(x) * (1 + w), the parameter the scale's
    distance from one (initially 0): what weight decay and a small
    initialisation then pull towards is the identity."""
    from paddle_tpu.initializer import Constant

    helper = LayerHelper("rms_norm", name=name)
    scale = helper.create_parameter(
        _named(param_attr, name, ""), [int(input.shape[-1])], "float32",
        default_initializer=Constant(0.0 if unit_offset else 1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="rms_norm", inputs={"X": input, "Scale": scale},
                     outputs={"Y": out},
                     attrs={"epsilon": float(epsilon),
                            "unit_offset": bool(unit_offset)})
    return out


def rotary_embedding(x, rotary_dim=None, theta=10000.0, factor=1.0,
                     original_max_position=4096, beta_fast=32.0,
                     beta_slow=1.0, mscale=1.0, pairing="interleaved",
                     n_head=0, name=None):
    """Rotary position embedding of x [B, T, H, D] over positions
    0..T-1: the last `rotary_dim` entries of D (default all) rotate,
    as interleaved pairs (x[2i], x[2i+1]) or, with pairing="halves",
    as split halves (x[i], x[i + rotary_dim/2]); YaRN-scaled
    frequencies when factor != 1 (ops/llm_ops.py yarn_inv_freq).
    With `n_head` = H, x is a projection as it comes, [B, T, H D], and
    so is the result: no reshape to heads around the op, which on a TPU
    turns x where it lies (ops/pallas_rotary.py)."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="rotary_embedding", inputs={"X": x}, outputs={"Out": out},
        attrs={"rotary_dim": int(rotary_dim or 0), "theta": float(theta),
               "factor": float(factor),
               "original_max_position": int(original_max_position),
               "beta_fast": float(beta_fast),
               "beta_slow": float(beta_slow), "mscale": float(mscale),
               "pairing": str(pairing), "n_head": int(n_head)})
    return out


def swiglu(gate, up, name=None):
    """silu(gate) * up."""
    helper = LayerHelper("swiglu", name=name)
    out = helper.create_variable_for_type_inference(gate.dtype)
    helper.append_op(type="swiglu", inputs={"Gate": gate, "Up": up},
                     outputs={"Out": out})
    return out


def moe_route(input, n_experts, k, routed_scaling_factor=1.0,
              norm_topk_prob=True, param_attr=None, bias_attr=None,
              name=None, scoring_func="sigmoid", return_scores=False,
              n_group=1, topk_group=1, norm_topk_eps=0.0):
    """Top-k router over ALL n_experts, group-limited where n_group > 1
    (the experts are n_group groups of consecutive ids; the topk_group
    groups with the largest sum of their two best s + bias are kept and
    the k experts are chosen among theirs; DeepSeek-V3 2.1.2): returns
    (topk_idx int32, topk_weight float32), both [.., k], and
    with return_scores the float32 scores [.., n_experts] as a third,
    which carry a gradient to every expert (a balance loss reads
    them).  `scoring_func` chooses the scores and the selection:

    * "sigmoid" (DeepSeek-V3 2.1.2, `noaux_tc`): s = sigmoid(x W); the
      k largest s + bias are selected.  The selection bias
      (`<name>_bias.w`, zeros) is persistable and gets no gradient: it
      selects and does not weigh.
    * "softmax" (DeepSeek-V2 2.2, `greedy`): s = softmax(x W) over the
      experts; the k largest s are selected.  No bias parameter is
      made.

    The gates are routed_scaling_factor * s_e, over the sum of the
    selected s (+ norm_topk_eps) first with norm_topk_prob."""
    from paddle_tpu.initializer import Constant

    helper = LayerHelper("moe_route", name=name)
    c = int(input.shape[-1])
    inputs = {"X": input,
              "W": helper.create_parameter(_named(param_attr, name, ""),
                                           [c, n_experts], "float32")}
    if scoring_func == "sigmoid":
        battr = _named(bias_attr, name, "bias")
        battr.trainable = False
        inputs["Bias"] = helper.create_parameter(
            battr, [n_experts], "float32",
            default_initializer=Constant(0.0))
    idx = helper.create_variable_for_type_inference("int32", True)
    weight = helper.create_variable_for_type_inference("float32")
    scores = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="moe_route", inputs=inputs,
        outputs={"TopkIdx": idx, "TopkWeight": weight, "Scores": scores},
        attrs={"k": int(k),
               "routed_scaling_factor": float(routed_scaling_factor),
               "norm_topk_prob": bool(norm_topk_prob),
               "scoring_func": str(scoring_func),
               "n_group": int(n_group), "topk_group": int(topk_group),
               "norm_topk_eps": float(norm_topk_eps)})
    return (idx, weight, scores) if return_scores else (idx, weight)


def moe_balance_loss(topk_idx, scores, alpha, name=None):
    """Sequence-wise expert-balance loss of a top-k router (DeepSeek-V2,
    arXiv:2405.04434, 2.2.3 eq. 23-25; `seq_aux`): topk_idx [B, T, k],
    scores [B, T, E] float32 (`moe_route(..., return_scores=True)`) ->
    a float32 [1]:

        f[b, i] = E / (k T) * #{(t, j): topk_idx[b, t, j] == i}
        P[b, i] = mean over t of scores[b, t, i]
        loss    = alpha * mean over b of sum over i of f[b, i] P[b, i]

    f is a count and gets no gradient; the gradient reaches the router
    through ALL E scores of every token.  A router that spreads a
    sequence's tokens evenly gives alpha.  Float32 under AMP (the
    scores are, one_hot and the reductions stay so), under
    name_scope("pt_moe_balance")."""
    from paddle_tpu.framework import name_scope
    from paddle_tpu.layers import nn

    t, k = int(topk_idx.shape[-2]), int(topk_idx.shape[-1])
    e = int(scores.shape[-1])
    with name_scope("pt_moe_balance"):
        # [B, T k, 1] -> [B, T k, E]: one_hot drops a last axis of 1
        chosen = nn.one_hot(nn.reshape(topk_idx, [-1, t * k, 1]), e)
        f = nn.scale(nn.reduce_sum(chosen, dim=1), scale=e / (k * t))
        f.stop_gradient = True
        p = nn.reduce_mean(scores, dim=1)
        per_seq = nn.reduce_sum(nn.elementwise_mul(f, p), dim=1)
        return nn.scale(nn.mean(per_seq), scale=float(alpha))


def moe_experts(input, topk_idx, topk_weight, held, width,
                param_attr=None, block_m=None, impl=None, name=None):
    """The part of a sparse SwiGLU feed-forward that the experts in
    `held` (a list of expert ids: what this chip holds of the layer)
    contribute: sum over a token's selected held experts of its gate
    times the expert's SwiGLU, width `width`.  Weights are stacked
    `[len(held), C, width]` (`<name>_gate.w`, `<name>_up.w`) and
    `[len(held), width, C]` (`<name>_down.w`).  No token is dropped; a
    selected expert that is not held adds nothing.

    What each step's routing gave this chip is kept a row a step in the
    stat ring `<name>.load` (layers.step_stat; read it with
    observability.step_stats.read()): columns `expert_<id>` the pairs
    routed to each held expert, `routed` their sum, `live_tiles` the
    row tiles the grouped matmuls' grids ran."""
    from paddle_tpu.layers.nn import step_stat

    helper = LayerHelper("moe_experts", name=name)
    c, g = int(input.shape[-1]), len(held)

    def stack(part, shape):
        return helper.create_parameter(_named(param_attr, name, part),
                                       shape, "float32")

    wg, wu = stack("gate", [g, c, width]), stack("up", [g, c, width])
    wd = stack("down", [g, width, c])
    out = helper.create_variable_for_type_inference(input.dtype)
    load = helper.create_variable_for_type_inference("float32", True)
    helper.append_op(
        type="moe_experts",
        inputs={"X": input, "TopkIdx": topk_idx, "TopkWeight": topk_weight,
                "WGate": wg, "WUp": wu, "WDown": wd},
        outputs={"Out": out, "Load": load},
        attrs={"held": [int(e) for e in held], "block_m": int(block_m or 0),
               "impl": impl or ""})
    step_stat(helper.name + ".load", load,
              ["expert_%d" % e for e in held] + ["routed", "live_tiles"])
    return out


def mhc_pre(x, sinkhorn_iters=20, eps=1e-6, clamp_min=-30.0,
            clamp_max=30.0, param_attr=None, name=None):
    """Read half of a manifold-constrained hyper-connection.  x
    [B, n, T, C]: n residual streams, stream-major.  Returns (u
    [B, T, C], the sublayer's input; h_post [B, n, T]; h_res
    [B, n, n, T]) for `mhc_post`.  Parameters: `<name>_norm.w` [nC]
    ones, `<name>_phi.w` [nC, 2n + n^2], `<name>_alpha.w` [3] = 0.01,
    `<name>_bias.w` [2n + n^2] = zeros then 8 I, so that h_res starts
    at the identity to 1e-3."""
    from paddle_tpu.initializer import Constant, NumpyArrayInitializer

    helper = LayerHelper("mhc_pre", name=name)
    n, c = int(x.shape[1]), int(x.shape[3])
    width = 2 * n + n * n
    norm = helper.create_parameter(
        _named(None, name, "norm"), [n * c], "float32",
        default_initializer=Constant(1.0))
    phi = helper.create_parameter(_named(param_attr, name, "phi"),
                                  [n * c, width], "float32")
    alpha = helper.create_parameter(
        _named(None, name, "alpha"), [3], "float32",
        default_initializer=Constant(0.01))
    bias0 = np.concatenate([np.zeros(2 * n), 8.0 * np.eye(n).reshape(-1)]
                           ).astype(np.float32)
    bias = helper.create_parameter(
        _named(None, name, "bias"), [width], "float32",
        default_initializer=NumpyArrayInitializer(bias0))
    u = helper.create_variable_for_type_inference(x.dtype)
    h_post = helper.create_variable_for_type_inference("float32")
    h_res = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="mhc_pre",
        inputs={"X": x, "NormScale": norm, "Phi": phi, "Alpha": alpha,
                "Bias": bias},
        outputs={"U": u, "HPost": h_post, "HRes": h_res},
        attrs={"sinkhorn_iters": int(sinkhorn_iters), "eps": float(eps),
               "clamp_min": float(clamp_min),
               "clamp_max": float(clamp_max)})
    return u, h_post, h_res


def mhc_post(x, y, h_post, h_res, name=None):
    """Write half: h_res x + outer(h_post, y), the streams after the
    sublayer whose output is y [B, T, C]."""
    helper = LayerHelper("mhc_post", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="mhc_post",
        inputs={"X": x, "Y": y, "HPost": h_post, "HRes": h_res},
        outputs={"Out": out})
    return out
