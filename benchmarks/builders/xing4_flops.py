"""Operations and bytes of the `xing4_0` block (a DeepSeek-V3-family
decoder with manifold-constrained hyper-connections), from shapes
alone: what benchmarks/flops.py is to the 2017 Transformer.  Loaded by
builders/xing4.py; every function is checked against a hand-worked
value in tests/test_flops.py.  Work the program repeats (recomputed
segments, scores the backward kernels form again, rows padded to a
tile) is never counted.
"""

from __future__ import annotations


def flash_flops(batch, heads, seq_q, seq_k, d_qk, d_v, causal,
                backward=False):
    """Attention's matmuls at a q.k size d_qk and a v size d_v.
    Forward: QK^T (2 B H Tq Tk d_qk) and PV (2 B H Tq Tk d_v); causal
    needs half the square.  Backward: dV and dP at d_v, dQ and dK at
    d_qk = 2 x forward; the scores the kernels recompute are not
    counted."""
    fwd = 2.0 * batch * heads * seq_q * seq_k * (d_qk + d_v)
    if causal:
        fwd /= 2
    return 2 * fwd if backward else fwd


def flash_bytes(batch, heads, seq_q, seq_k, d_qk, d_v, bytes_per_el,
                backward=False):
    """Least HBM traffic: each operand read or written once.  Forward
    reads q, k (d_qk), v (d_v) and writes o (d_v); backward reads q, k,
    v, o, do and writes dq, dk, dv.  Row statistics left out."""
    q, k = seq_q * d_qk, seq_k * d_qk
    v, o = seq_k * d_v, seq_q * d_v
    els = (2 * q + 2 * k + 2 * v + 2 * o) if backward else (q + k + v + o)
    return float(batch * heads * els * bytes_per_el)


def flash_step(batch, heads, seq_len, d_qk, d_v, n_layer, bytes_per_el=2):
    """(flops, bytes) of all causal self-attention calls of one train
    step: n_layer x (forward + backward)."""
    args = (batch, heads, seq_len, seq_len, d_qk, d_v)
    flops = n_layer * (flash_flops(*args, causal=True)
                       + flash_flops(*args, causal=True, backward=True))
    nbytes = n_layer * (flash_bytes(*args, bytes_per_el)
                        + flash_bytes(*args, bytes_per_el, backward=True))
    return flops, nbytes


def routed_rows(tokens, per_token, held, experts):
    """Token-expert pairs a step routes to the experts held here, in
    expectation under a uniform router: tokens x per_token x held /
    experts."""
    return tokens * per_token * held / experts


def gmm_flops(rows, hidden, width, backward=False):
    """The three grouped products of a SwiGLU expert layer over `rows`
    routed rows: gate, up (2 rows hidden width each) and down.
    Backward: a product for the rows' and one for the weights'
    gradient each = 2 x forward."""
    fwd = 6.0 * rows * hidden * width
    return 2 * fwd if backward else fwd


def gmm_bytes(rows, held, hidden, width, bytes_per_el, backward=False):
    """Least HBM traffic of those products: forward reads the rows and
    the three weight stacks of the held experts and writes the rows'
    outputs; backward reads the rows, their output gradients and the
    weights, and writes the rows' input gradients and three weight
    gradients.  The intermediates (width-wide) are left out: a fused
    kernel would keep them on the chip."""
    weights = 3.0 * held * hidden * width
    io = rows * hidden
    els = (3 * io + 2 * weights) if backward else (2 * io + weights)
    return float(els * bytes_per_el)


def gmm_step(tokens, per_token, held, experts, hidden, width, n_layer,
             bytes_per_el=2):
    """(flops, bytes) of the grouped matmuls of one train step over
    n_layer expert layers, at the expected number of routed rows."""
    rows = routed_rows(tokens, per_token, held, experts)
    flops = n_layer * (gmm_flops(rows, hidden, width)
                       + gmm_flops(rows, hidden, width, backward=True))
    nbytes = n_layer * (
        gmm_bytes(rows, held, hidden, width, bytes_per_el)
        + gmm_bytes(rows, held, hidden, width, bytes_per_el,
                    backward=True))
    return flops, nbytes


def mhc_mix_bytes(tokens, streams, hidden, n_sublayers, bytes_per_el=2):
    """Least HBM traffic of the residual-stream mixes of one train step.
    Forward, a sublayer: the read half reads the n streams and writes
    the sublayer's input, the write half reads the streams and the
    sublayer's output and writes the streams: (3 n + 2) hidden elements
    a token.  Backward: the same arrays and their gradients, twice
    that."""
    per_token = (3 * streams + 2) * hidden
    return float(3 * per_token * tokens * n_sublayers * bytes_per_el)


def matmul_params(config):
    """Parameters that multiply a token's activations in one forward
    pass, by part: {"attention", "dense_ffn", "expert_ffn", "mhc",
    "head"}, each the total over the layers of the configuration as
    run.  An expert layer counts its shared expert, its router and the
    expected share of the routed experts a token meets HERE:
    num_experts_per_tok x held / published experts.  The embedding is
    a gather."""
    c = config["hidden_size"]
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, qr, kvr = (config["v_head_dim"], config["q_lora_rank"],
                   config["kv_lora_rank"])
    n = config["hc_mult"]
    layers = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], layers)
    published = config.get("n_routed_experts_published",
                           config["n_routed_experts"])
    attention = (c * qr + qr * heads * (nope + rope) + c * (kvr + rope)
                 + kvr * heads * (nope + vd) + heads * vd * c)
    expert = 3 * c * config["moe_intermediate_size"]
    met = config["num_experts_per_tok"] * config["n_routed_experts"] \
        / published
    return {
        "attention": layers * attention,
        "dense_ffn": dense * 3 * c * config["intermediate_size"],
        "expert_ffn": (layers - dense) * (
            config["n_shared_experts"] * expert + c * published
            + met * expert),
        "mhc": layers * 2 * (n * c) * (2 * n + n * n),
        "head": c * config["vocab_size"],
    }


def train_flops_per_token(config, seq_len):
    """Forward + backward: 6 N over the parameters that multiply
    (matmul_params) plus causal latent attention, 3 T H (d_qk + d_v) a
    layer and token (half the square, backward twice the forward)."""
    d_qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    attn = 3.0 * seq_len * config["num_attention_heads"] \
        * (d_qk + config["v_head_dim"]) * config["num_hidden_layers"]
    return 6.0 * sum(matmul_params(config).values()) + attn
