"""Operations and bytes of the `ling-3.0-flash-vl` block (Kimi Delta
Attention layers and latent-attention layers over a shared expert plus
the chip's share of the routed experts), from shapes alone.  Loaded by
builders/ling3.py; checked against hand-worked values in
tests/test_ling3.py.  The attention kernels' and the grouped matmuls'
operations and bytes are builders/xing4_flops.py's `flash_step` and
`gmm_step`: the same kernels.  Work the program repeats (recomputed
segments, the chunk's forward that the KDA backward kernel runs again
from a block's saved state, scores the flash backward forms again, rows
padded to a tile, the masked half of a triangular product) is never
counted, nor are the depthwise convolutions, the norms and the gates
(elementwise: 4 taps a channel).
"""

from __future__ import annotations


def layer_kinds(config):
    period = config["layer_group_size"]
    return ["mla" if (i + 1) % period == 0 else "kda"
            for i in range(config["num_hidden_layers"])]


def matmul_params(config):
    """Parameters that multiply a token's activations in one forward
    pass, by part: {"kda_proj", "mla_proj", "dense_ffn", "shared_expert",
    "routed_experts", "router", "head"}, each the total over the layers
    of the configuration as run.  The routed experts count the expected
    share a token meets HERE: num_experts_per_tok x held / published
    experts.  The embedding is a gather."""
    c = config["hidden_size"]
    heads, d = config["num_attention_heads"], config["head_dim"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, kvr = config["v_head_dim"], config["kv_lora_rank"]
    kinds = layer_kinds(config)
    layers = len(kinds)
    dense = min(config["first_k_dense_replace"], layers)
    published = config.get("num_experts_published", config["num_experts"])
    # q, k, v, the decay projection and the output; beta and the gate
    kda = 5 * c * heads * d + 2 * c * heads
    mla = (c * heads * (nope + rope) + c * (kvr + rope)
           + kvr * heads * (nope + vd) + heads * vd * c + c * heads)
    expert = 3 * c * config["moe_intermediate_size"]
    met = config["num_experts_per_tok"] * config["num_experts"] / published
    return {
        "kda_proj": kinds.count("kda") * kda,
        "mla_proj": kinds.count("mla") * mla,
        "dense_ffn": dense * 3 * c * config["intermediate_size"],
        "shared_expert": (layers - dense) * 3 * c
        * config["moe_shared_expert_intermediate_size"],
        "routed_experts": (layers - dense) * met * expert,
        "router": (layers - dense) * c * published,
        "head": c * config["vocab_size"],
    }


def kda_flops_per_token(config, backward=False):
    """Operations a token of ONE KDA layer costs in the chunked WY form
    (ops/pallas_kda.py), all heads; C the chunk, D the head size, a
    triangular product counted as half its square.

    Forward, a token and head: M and P (2 x C D), the triangular
    inverse (2 C^2 / 3), W and U (2 x C D), W Z^T and Qg Z^T (2 x 2
    D^2), P Ut (C D), the state's update (2 D^2):
    5 C D + 6 D^2 + 2 C^2 / 3.

    Backward, its own products: dUt (C D + 2 D^2), dP (C D), dQg and
    dKend (2 x 2 D^2), dZ (2 x 2 D^2), dW (2 D^2), dT (2 x C D), dVb
    and dKb (2 x C D), dN (2 C^2), and the scores' factors dleft and
    dright (4 x C D): 10 C D + 12 D^2 + 2 C^2."""
    c, d = config.get("kda_chunk_size", 64), config["head_dim"]
    per_head = 10.0 * c * d + 12.0 * d * d + 2.0 * c * c if backward \
        else 5.0 * c * d + 6.0 * d * d + 2.0 * c * c / 3.0
    return config["num_attention_heads"] * per_head


def forward_flops_per_token(config, seq_len):
    """By part: 2 N over the parameters that multiply, the KDA layers'
    chunk products, and causal latent attention, T H (d_qk + d_v) a
    layer and token (half the square of QK^T at d_qk and of PV at
    d_v)."""
    kinds = layer_kinds(config)
    parts = {k: 2.0 * v for k, v in matmul_params(config).items()}
    d_qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    parts["flash"] = float(seq_len * config["num_attention_heads"]
                           * (d_qk + config["v_head_dim"])
                           * kinds.count("mla"))
    parts["kda"] = kinds.count("kda") * kda_flops_per_token(config)
    return parts


def train_flops_per_token(config, seq_len):
    """Forward + backward: three times the forward of everything but
    the KDA chunk products, whose backward is counted as its own
    (kda_flops_per_token)."""
    parts = forward_flops_per_token(config, seq_len)
    kda_layers = layer_kinds(config).count("kda")
    return 3.0 * (sum(parts.values()) - parts["kda"]) + parts["kda"] \
        + kda_layers * kda_flops_per_token(config, backward=True)


def kda_step(config, batch, seq_len, bytes_per_el=2):
    """(flops, bytes) of the KDA kernels of one train step, all KDA
    layers.  FLOPs: kda_flops_per_token, forward and backward.  Bytes,
    each operand moved once: forward reads Q, K, V (bytes_per_el) and
    the log-decays G (float32) and beta (float32, a head), writes O and
    the block-start states (float32 D x D a head and block of
    kda_block_chunks chunks); backward reads Q, K, V, G, beta, dO and
    the states and writes dQ, dK, dV, dG (float32) and d beta.  A
    replay by a recompute segment is not counted (the program makes
    none)."""
    layers = layer_kinds(config).count("kda")
    h, d = config["num_attention_heads"], config["head_dim"]
    block = config.get("kda_chunk_size", 64) \
        * config.get("kda_block_chunks", 4)
    tokens = batch * seq_len
    act = tokens * h * d * bytes_per_el            # one of Q, K, V, O, dO
    decay = tokens * h * d * 4.0                   # G or dG
    beta = tokens * h * 4.0
    states = (tokens / block) * h * d * d * 4.0
    fwd = 3 * act + decay + beta + act + states
    bwd = 3 * act + decay + beta + act + states + 3 * act + decay + beta
    flops = tokens * (kda_flops_per_token(config)
                      + kda_flops_per_token(config, backward=True))
    return layers * flops, float(layers * (fwd + bwd))
