"""Operations of the `deepseek_v2` block (latent attention with a
full-rank or a low-rank query, a softmax top-k router over all experts
with the chip's share of them held here, stacked shared experts, one
pre-norm residual stream), from shapes alone.  Loaded by
builders/deepseek_v2.py; checked against hand-worked values in
tests/test_deepseek_v2.py.  The attention kernels' and the grouped
matmuls' operations and bytes are builders/xing4_flops.py's
`flash_step` and `gmm_step`: the same kernels.  Work the program
repeats (recomputed segments, scores the backward kernels form again,
rows padded to a tile) is never counted, nor is the balance loss (a
count and two means over 64 scores a token).
"""

from __future__ import annotations


def matmul_params(config):
    """Parameters that multiply a token's activations in one forward
    pass, by part: {"attention", "dense_ffn", "shared_experts",
    "routed_experts", "router", "head"}, each the total over the layers
    of the configuration as run.  The routed experts count the expected
    share a token meets HERE: num_experts_per_tok x held / published
    experts.  The embedding is a gather."""
    c = config["hidden_size"]
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, kvr = config["v_head_dim"], config["kv_lora_rank"]
    qr = config.get("q_lora_rank")
    layers = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], layers)
    published = config.get("n_routed_experts_published",
                           config["n_routed_experts"])
    query = c * heads * (nope + rope) if not qr \
        else c * qr + qr * heads * (nope + rope)
    attention = (query + c * (kvr + rope) + kvr * heads * (nope + vd)
                 + heads * vd * c)
    expert = 3 * c * config["moe_intermediate_size"]
    met = config["num_experts_per_tok"] * config["n_routed_experts"] \
        / published
    return {
        "attention": layers * attention,
        "dense_ffn": dense * 3 * c * config["intermediate_size"],
        "shared_experts": (layers - dense) * config["n_shared_experts"]
        * expert,
        "routed_experts": (layers - dense) * met * expert,
        "router": (layers - dense) * c * published,
        "head": c * config["vocab_size"],
    }


def forward_flops_per_token(config, seq_len):
    """By part: 2 N over the parameters that multiply, and causal
    latent attention, T H (d_qk + d_v) a layer and token (half the
    square of QK^T at d_qk and of PV at d_v)."""
    parts = {k: 2.0 * v for k, v in matmul_params(config).items()}
    d_qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    parts["flash"] = float(seq_len * config["num_attention_heads"]
                           * (d_qk + config["v_head_dim"])
                           * config["num_hidden_layers"])
    return parts


def train_flops_per_token(config, seq_len):
    """Forward + backward: three times the forward."""
    return 3.0 * sum(forward_flops_per_token(config, seq_len).values())
