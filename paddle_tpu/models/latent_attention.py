"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434, 2.1) as
the models of that family build it from `layers.*`, and the helpers
that read a `config.json`-style dict of theirs: the softmax scale under
YaRN, the rotary embedding's arguments, the experts a chip holds.
models/xing4.py (q through a rank-`q_lora_rank` bottleneck) and
models/deepseek_v2.py (`q_lora_rank` null: one full-rank projection)
call the same function; docs/XING4_BLOCK.md and docs/DSV2_BLOCK.md
write the equations out.
"""

from __future__ import annotations

import math

from paddle_tpu import layers


def yarn_mscale(factor, mscale):
    """YaRN's attention factor 0.1 mscale ln(factor) + 1 (1 without
    scaling), as the deepseek_v2 / deepseek_v3 modelling code has it."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def attention_scale(config):
    """Softmax scale of latent attention: (qk_nope + qk_rope)^-1/2 times
    m^2, m = yarn_mscale(factor, mscale_all_dim)."""
    d = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    rs = config.get("rope_scaling") or {}
    m = yarn_mscale(rs.get("factor", 1), rs.get("mscale_all_dim") or 0)
    return d ** -0.5 * m * m


def held_experts(config):
    """The expert ids this chip holds: `held_experts` if the config
    lists them, else the first `n_routed_experts`."""
    return list(config.get("held_experts")
                or range(config["n_routed_experts"]))


def router_width(config):
    """Experts the router scores: the published count when this chip
    holds only its share of them."""
    return config.get("n_routed_experts_published",
                      config["n_routed_experts"])


def rotary(x, config, seq_len):
    """Interleaved rotary embedding of the last qk_rope_head_dim
    entries of x [B, T, H, D], YaRN frequencies from `rope_scaling`;
    cos and sin are multiplied by yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim): 1 where the two are equal."""
    rs = config.get("rope_scaling") or {}
    factor = rs.get("factor", 1.0)
    return layers.rotary_embedding(
        x, rotary_dim=config["qk_rope_head_dim"],
        theta=config["rope_theta"], factor=factor,
        original_max_position=rs.get(
            "original_max_position_embeddings",
            config.get("max_position_embeddings", seq_len)),
        beta_fast=rs.get("beta_fast", 32),
        beta_slow=rs.get("beta_slow", 1),
        mscale=yarn_mscale(factor, rs.get("mscale") or 0)
        / yarn_mscale(factor, rs.get("mscale_all_dim") or 0))


def latent_attention(u, config, seq_len, fc, prefix, lp):
    """u [B, T, C] -> [B, T, C].  The query is `u W_q` where
    `q_lora_rank` is null, else RMSNorm(u W_q_a) W_q_b; keys and values
    come from one rank-`kv_lora_rank` latent, RMS-normed, plus ONE
    rotary key a token that every head shares; q.k size qk_nope +
    qk_rope, v size v_head_dim; causal `flash_attention` head-major.
    With the config key `attention_output_gate` "head_wise" each head's
    output is multiplied by sigmoid(u w_gate^h), one logit a head
    (`<lp>_gate`), before W_o; without the key no gate op is built.

    fc(x, size, name) is the model's bias-free projection over its own
    parameter names (`<lp>_q` or `<lp>_q_a`/`<lp>_q_b`, `<lp>_kv_a`,
    `<lp>_kv_b`, `<lp>_o`); the norms' scales are
    `<prefix>_<lp>_q_a_norm.w` and `<prefix>_<lp>_kv_a_norm.w`."""
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, kvr = config["v_head_dim"], config["kv_lora_rank"]
    eps = config["rms_norm_eps"]
    if config.get("q_lora_rank"):
        cq = layers.rms_norm(fc(u, config["q_lora_rank"], lp + "_q_a"),
                             eps, name="%s_%s_q_a_norm" % (prefix, lp))
        q = fc(cq, heads * (nope + rope), lp + "_q_b")
    else:
        q = fc(u, heads * (nope + rope), lp + "_q")
    q = layers.reshape(q, [-1, seq_len, heads, nope + rope])
    q = layers.transpose(rotary(q, config, seq_len), [0, 2, 1, 3])
    ckv, k_r = layers.split(fc(u, kvr + rope, lp + "_kv_a"),
                            [kvr, rope], dim=2)
    ckv = layers.rms_norm(ckv, eps,
                          name="%s_%s_kv_a_norm" % (prefix, lp))
    kv = layers.reshape(fc(ckv, heads * (nope + vd), lp + "_kv_b"),
                        [-1, seq_len, heads, nope + vd])
    k_nope, v = layers.split(kv, [nope, vd], dim=3)
    # one rotary key a token, shared by every head
    k_r = rotary(layers.reshape(k_r, [-1, seq_len, 1, rope]), config,
                 seq_len)
    k = layers.concat([k_nope, layers.expand(k_r, [1, 1, heads, 1])],
                      axis=3)
    out = layers.flash_attention(
        q, layers.transpose(k, [0, 2, 1, 3]),
        layers.transpose(v, [0, 2, 1, 3]), causal=True,
        scale=attention_scale(config))
    out = layers.reshape(layers.transpose(out, [0, 2, 1, 3]),
                         [-1, seq_len, heads * vd])
    gate = config.get("attention_output_gate")
    if gate == "head_wise":
        out = layers.head_gated_rms_norm(out, fc(u, heads, lp + "_gate"),
                                         norm=False)
    elif gate:
        raise NotImplementedError(
            "latent_attention: attention_output_gate %r" % (gate,))
    return fc(out, config["hidden_size"], lp + "_o")
