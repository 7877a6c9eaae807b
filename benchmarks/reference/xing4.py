"""Plain reference of the `xing4_0` decoder (a DeepSeek-V3-family block
with manifold-constrained hyper-connections): forward pass and loss in
straightforward jax.numpy, float32, matmul precision "highest", no
kernels, no AMP.  Shares no code with paddle_tpu/ops.

Follows docs/XING4_BLOCK.md equation by equation:

* embedding replicated into n = hc_mult residual streams; after the last
  layer the streams are summed, RMS-normed and projected by an untied
  head; mean next-token cross-entropy;
* every sublayer F (attention, feed-forward) wrapped as
      x~ = RMSNorm_hc_eps(vec(X)) * s;  p = x~ Phi
      H_pre = sigmoid(a0 p[:n] + b[:n]);  H_post = 2 sigmoid(a1 p[n:2n] + b[n:2n])
      H_res = SK(clip(a2 mat(p[2n:]) + mat(b[2n:])))
      X' = H_res X + outer(H_post, F(RMSNorm(H_pre X)))
  with SK the Sinkhorn loop: exp, then rows and columns divided by their
  sums + hc_eps, hc_sinkhorn_iters times;
* latent attention (DeepSeek-V3 report 2.1.1), rotary on interleaved
  pairs with YaRN frequencies, a full masked softmax;
* feed-forward: SwiGLU, dense in the first first_k_dense_replace layers,
  then a shared expert plus the routed experts this chip HOLDS: sigmoid
  scores over all experts, top-k of score + bias, gates normalised over
  the selected and scaled; an expert that is not held adds nothing
  (2.1.2, noaux_tc, n_group 1).

Departures, each the configuration's and stated there under `assumed`:
hc_eps is both the stream norm's epsilon and the Sinkhorn guard, the
clamp acts before exp, the selection bias is not updated.

Memory at 4,096 tokens: attention is computed one sequence at a time in
blocks of query rows (a whole 4096^2 score matrix for 32 heads is 2.1 GB
in f32), and the experts as a loop over the held ones with a boolean
mask over all tokens.
"""

from __future__ import annotations

import functools
import math

import numpy as np

QUERY_BLOCK = 512


def held_experts(config):
    return list(config.get("held_experts")
                or range(config["n_routed_experts"]))


def param_names(config):
    p = config.get("param_prefix", "xing")
    names = {"emb": p + "_emb.w", "final_norm": p + "_final_norm.w",
             "head": p + "_head.w", "layers": []}

    def hc(lp, kind):
        b = "%s_%s_%s_hc" % (p, lp, kind)
        return {"norm": b + "_norm.w", "phi": b + "_phi.w",
                "alpha": b + "_alpha.w", "bias": b + "_bias.w"}

    def mlp(b):
        return {"gate": b + "_gate.w", "up": b + "_up.w",
                "down": b + "_down.w"}

    for i in range(config["num_hidden_layers"]):
        lp = "l%d" % i
        b = "%s_%s" % (p, lp)
        layer = {
            "attn_hc": hc(lp, "attn"), "ffn_hc": hc(lp, "ffn"),
            "attn_norm": b + "_attn_norm.w", "ffn_norm": b + "_ffn_norm.w",
            "q_a": b + "_q_a.w", "q_a_norm": b + "_q_a_norm.w",
            "q_b": b + "_q_b.w", "kv_a": b + "_kv_a.w",
            "kv_a_norm": b + "_kv_a_norm.w", "kv_b": b + "_kv_b.w",
            "o": b + "_o.w"}
        if i < config["first_k_dense_replace"]:
            layer["dense"] = mlp(b)
        else:
            layer["router"] = b + "_router.w"
            layer["router_bias"] = b + "_router_bias.w"
            layer["experts"] = mlp(b + "_experts")
            layer["shared"] = mlp(b + "_shared")
        names["layers"].append(layer)
    return names


def read_params(config, get):
    """The program's own weights as float32 arrays.  `get(name)` returns
    the array the scope holds under `name`.  No copy is made of an
    array that is float32 already (758 M parameters at the benchmark's
    size): read them before a step donates them."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda n: jnp.asarray(get(n), dtype=jnp.float32),
        param_names(config))


# -- pieces -----------------------------------------------------------------

def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def yarn_inv_freq(config):
    """Inverse rotary frequencies [qk_rope_head_dim / 2], float64."""
    dim, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    rs = config.get("rope_scaling") or {}
    factor = rs.get("factor", 1)
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor == 1:
        return plain
    orig = rs["original_max_position_embeddings"]

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return plain / factor * ramp + plain * (1 - ramp)


def rotate(x, config):
    """x [T, H, d_rope]: interleaved pairs (x[2i], x[2i+1]) turned by
    position * inv_freq[i]."""
    import jax.numpy as jnp

    t = x.shape[0]
    rs = config.get("rope_scaling") or {}
    mscale = 1.0
    if rs.get("mscale") and rs.get("mscale_all_dim"):
        mscale = rs["mscale"] / rs["mscale_all_dim"]
    ang = np.arange(t, dtype=np.float64)[:, None] * yarn_inv_freq(config)
    cos = jnp.asarray((np.cos(ang) * mscale).astype(np.float32))[:, None]
    sin = jnp.asarray((np.sin(ang) * mscale).astype(np.float32))[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def softmax_scale(config):
    d = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    rs = config.get("rope_scaling") or {}
    m = 1.0
    if rs.get("factor", 1) > 1 and rs.get("mscale_all_dim"):
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return m * m / math.sqrt(d)


def causal_attention(q, k, v, scale, block=QUERY_BLOCK):
    """softmax(scale q k^T + causal mask) v for one sequence, q/k
    [H, T, d], v [H, T, dv]; `block` query rows at a time against all
    keys."""
    import jax
    import jax.numpy as jnp

    h, t, _ = q.shape
    block = min(block, t)
    if t % block:
        raise ValueError("seq %d not a multiple of the query block %d"
                         % (t, block))

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("hqd,hkd->hqk", qb, k) * scale
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(block))[:, None]
        return jnp.einsum(
            "hqk,hkd->hqd",
            jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)

    out = jax.lax.map(rows, jnp.arange(0, t, block))   # [nb, H, block, dv]
    return jnp.moveaxis(out, 0, 1).reshape(h, t, v.shape[-1])


def attention(u, lw, config):
    """Latent attention of one sequence, u [T, C] -> [T, C]."""
    import jax.numpy as jnp

    t = u.shape[0]
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, kvr = config["v_head_dim"], config["kv_lora_rank"]
    eps = config["rms_norm_eps"]
    c_q = rms_norm(u @ lw["q_a"], lw["q_a_norm"], eps)
    q = (c_q @ lw["q_b"]).reshape(t, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], config)], -1)
    kv_a = u @ lw["kv_a"]
    c_kv = rms_norm(kv_a[:, :kvr], lw["kv_a_norm"], eps)
    k_r = rotate(kv_a[:, None, kvr:], config)              # [T, 1, rope]
    kv = (c_kv @ lw["kv_b"]).reshape(t, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (t, heads, rope))], -1)
    out = causal_attention(q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                           kv[..., nope:].transpose(1, 0, 2),
                           softmax_scale(config))
    return out.transpose(1, 0, 2).reshape(t, heads * vd) @ lw["o"]


def swiglu(u, w):
    import jax

    return (jax.nn.silu(u @ w["gate"]) * (u @ w["up"])) @ w["down"]


def route(u, lw, config):
    """(selected [T, E] bool, gate [T, E] float, zero where not
    selected) over ALL experts."""
    import jax
    import jax.numpy as jnp

    k = config["num_experts_per_tok"]
    s = jax.nn.sigmoid(u @ lw["router"])
    ranked = jnp.argsort(-(s + lw["router_bias"]), axis=-1, stable=True)
    selected = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], ranked[:, :k]].set(True)
    picked = jnp.where(selected, s, 0.0)
    if config["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    return selected, picked * config["routed_scaling_factor"]


def expert_ffn(u, lw, config, held=None, shared=True):
    """The shared expert plus the held routed experts' part; `held`
    defaults to the configuration's."""
    import jax.numpy as jnp

    held = held_experts(config) if held is None else held
    selected, gate = route(u, lw, config)
    y = swiglu(u, lw["shared"]) if shared else jnp.zeros_like(u)
    for slot, e in enumerate(held):
        w = {k: v[slot] for k, v in lw["experts"].items()}
        mask = selected[:, e]
        y = y + jnp.where(mask[:, None], gate[:, e, None] * swiglu(u, w),
                          0.0)
    return y


def sinkhorn(a, iters, eps):
    import jax.numpy as jnp

    m = jnp.exp(a)
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def hyper_connection(x, hw, config):
    """x [T, n, C] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    import jax
    import jax.numpy as jnp

    t, n, c = x.shape
    flat = rms_norm(x.reshape(t, n * c), hw["norm"], config["hc_eps"])
    p = flat @ hw["phi"]
    a, b = hw["alpha"], hw["bias"]
    h_pre = jax.nn.sigmoid(a[0] * p[:, :n] + b[:n])
    h_post = 2 * jax.nn.sigmoid(a[1] * p[:, n:2 * n] + b[n:2 * n])
    raw = a[2] * p[:, 2 * n:].reshape(t, n, n) + b[2 * n:].reshape(n, n)
    h_res = sinkhorn(jnp.clip(raw, config["mhc_h_res_clamp_min"],
                              config["mhc_h_res_clamp_max"]),
                     config["hc_sinkhorn_iters"], config["hc_eps"])
    return h_pre, h_post, h_res


def sublayer(x, fn, hw, norm, config):
    import jax.numpy as jnp

    h_pre, h_post, h_res = hyper_connection(x, hw, config)
    u = jnp.einsum("tn,tnc->tc", h_pre, x)
    y = fn(rms_norm(u, norm, config["rms_norm_eps"]))
    return jnp.einsum("tij,tjc->tic", h_res, x) \
        + h_post[:, :, None] * y[:, None, :]


def sequence_logits(params, ids, config):
    """Logits [T, vocab] of ONE sequence, ids [T] int."""
    import jax.numpy as jnp

    n = config["hc_mult"]
    h = params["emb"][ids]
    x = jnp.broadcast_to(h[:, None, :], (h.shape[0], n, h.shape[1]))
    for i, lw in enumerate(params["layers"]):
        x = sublayer(x, lambda u: attention(u, lw, config), lw["attn_hc"],
                     lw["attn_norm"], config)
        if i < config["first_k_dense_replace"]:
            ffn = lambda u: swiglu(u, lw["dense"])           # noqa: E731
        else:
            ffn = lambda u: expert_ffn(u, lw, config)        # noqa: E731
        x = sublayer(x, ffn, lw["ffn_hc"], lw["ffn_norm"], config)
    out = rms_norm(x.sum(1), params["final_norm"], config["rms_norm_eps"])
    return out @ params["head"]


def batch_loss(params, ids, labels, config):
    """Mean next-token cross-entropy, ids and labels [B, T] int; a
    function of jax arrays that jax.grad differentiates (the tests'
    gradients)."""
    import jax
    import jax.numpy as jnp

    def one(xy):
        logp = jax.nn.log_softmax(sequence_logits(params, xy[0], config),
                                  axis=-1)
        return -jnp.take_along_axis(logp, xy[1][:, None], axis=1).sum()

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, (ids, labels)).sum() / ids.size


@functools.lru_cache(maxsize=None)
def _jitted(config_items):
    import json

    import jax

    config = json.loads(config_items)
    return jax.jit(lambda params, ids, labels:
                   batch_loss(params, ids, labels, config))


def _split(batch):
    import jax.numpy as jnp

    return tuple(jnp.asarray(np.asarray(a)[..., 0].astype(np.int32))
                 for a in batch)


def loss(params, batch, config):
    """Mean cross-entropy of the batch (ids, labels), each [B, T, 1]."""
    import json

    ids, labels = _split(batch)
    return float(_jitted(json.dumps(config, sort_keys=True))(
        params, ids, labels))
