"""Black/white op lists for automatic mixed precision.

Reference parity:
/root/reference/python/paddle/fluid/contrib/mixed_precision/fp16_lists.py
(white = MXU-heavy ops cast to low precision; black = numerically sensitive
ops kept fp32; gray follows its inputs).

TPU-first difference: the low-precision dtype defaults to bfloat16 — same
exponent range as fp32, so unlike fp16 it rarely *needs* loss scaling, but
the scaling machinery is kept for fp16 parity and guard-rails.
"""

from __future__ import annotations

import copy

# MXU-bound: always worth computing in bf16
white_list = {
    "conv2d", "depthwise_conv2d", "conv2d_transpose", "matmul", "mul",
    # the Pallas kernel takes bf16 q/k/v and accumulates in f32
    # internally (softmax stats included) — leaving it unlisted would
    # cast the attention inputs back to fp32 under AMP
    "flash_attention",
    # fused conv+bias+residual+relu (ops/pallas_conv.py): bf16
    # operands, f32 accumulation in VMEM — same story as the conv it
    # replaces
    "conv2d_epilogue",
    # fused conv+BN(train)+residual+relu (ops/pallas_conv.py): the
    # conv half is MXU-bound like conv2d; the BN statistics/params
    # (Scale/BNBias/Mean/Variance) are pinned fp32 by fp16_utils
    # (_WHITE_KEEP_FP32), matching batch_norm's gray-list treatment
    "conv2d_bn_train",
    # fused mul+bias+residual+act (ops/epilogue.py): bf16 operands,
    # f32 accumulation on the MXU — same story as the mul it replaces
    "fc_epilogue",
    # the experts' grouped matmuls (ops/pallas_gmm.py): bf16 rows and
    # weights, f32 accumulation; the gates stay float32
    # (fp16_utils._WHITE_KEEP_FP32)
    "moe_experts",
    # the two halves of a hyper-connection (ops/llm_ops.py): they read
    # and write the n residual streams, the step's largest activations,
    # in bf16; every coefficient (norm, projections, Sinkhorn) is
    # computed and kept in float32 inside them (fp16_utils)
    "mhc_pre", "mhc_post",
    # the chunked state-space scan (ops/pallas_ssd.py): bf16 X, B and C
    # on the MXU; Dt, A, D, the decays, the running state and the saved
    # chunk states stay float32 (fp16_utils)
    "ssd_scan",
    # the chunked delta-rule scan (ops/pallas_kda.py): bf16 Q, K and V
    # on the MXU; the log-decays G, Beta, the inverse of a chunk's
    # triangular system, the running state and the saved block states
    # stay float32 (fp16_utils)
    "kda_scan",
    # EVA attention (ops/pallas_eva.py): bf16 Q, K, V and chunk
    # summaries on the MXU; the pooling vectors Mu and Phi, the pooling
    # softmaxes, the running softmax statistics and LSE stay float32
    # (fp16_utils)
    "eva_pool", "eva_attention",
}

# numerically sensitive: keep fp32
black_list = {
    "exp", "square", "log", "mean", "sum", "cos_sim",
    "softmax", "softmax_with_cross_entropy", "sigmoid_cross_entropy_with_logits",
    "cross_entropy", "cross_entropy2",
    "reduce_sum", "reduce_mean",
    # router scores and the top-k selection over them: a rounding here
    # sends a token to another expert
    "moe_route",
}

# dtype-agnostic: run in whatever dtype arrives
gray_list = {
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "elementwise_mod", "elementwise_floordiv",
    "batch_norm", "layer_norm", "tanh", "sigmoid", "lookup_table",
    "relu", "relu6", "leaky_relu", "soft_relu", "top_k", "pool2d",
    "dropout", "reshape2", "transpose2", "transpose", "concat", "split",
    "slice", "flatten2", "stack", "unstack", "expand", "scale", "cast",
    "elementwise_op", "squeeze2", "unsqueeze2", "pad", "pad2d", "gather",
    "swapaxes", "flip", "assign", "space_to_depth",
    # float32 inside, written in the dtype that arrives
    "rotary_embedding", "swiglu",
}

# normalization ops whose output dtype follows X (statistics stay fp32
# inside the op compute — see ops/nn.py batch_norm/layer_norm)
follow_x_list = {
    "batch_norm", "sync_batch_norm", "layer_norm", "group_norm",
    "instance_norm", "data_norm", "rms_norm",
    # float32 inside, Y in X's dtype, whatever the filter's, the
    # gate's or the scale's is
    "causal_conv1d", "gated_rms_norm", "head_l2_norm",
    "head_gated_rms_norm",
}


class AutoMixedPrecisionLists:
    """reference fp16_lists.py AutoMixedPrecisionLists: base lists plus
    user-supplied custom white/black adjustments."""

    def __init__(self, custom_white_list=None, custom_black_list=None):
        self.white_list = copy.copy(white_list)
        self.black_list = copy.copy(black_list)
        self.gray_list = copy.copy(gray_list)
        if custom_white_list:
            for op in custom_white_list:
                self.white_list.add(op)
                self.black_list.discard(op)
                self.gray_list.discard(op)
        if custom_black_list:
            for op in custom_black_list:
                self.black_list.add(op)
                self.white_list.discard(op)
                self.gray_list.discard(op)
        overlap = self.white_list & self.black_list
        if overlap:
            raise ValueError(f"ops in both white and black lists: {overlap}")
