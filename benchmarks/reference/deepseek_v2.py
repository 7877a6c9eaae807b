"""Plain reference of the `deepseek_v2` decoder (DeepSeek-V2,
arXiv:2405.04434, section 2, at the sizes a `config.json` of that
model_type gives): forward pass, the cross-entropy and the expert-
balance loss in straightforward jax.numpy, float32, matmul precision
"highest", no kernels, no AMP, no recompute, no sorting of tokens.
Shares no code with paddle_tpu/ops or paddle_tpu/models.

Per token t of a sequence, h the residual stream (ONE stream,
pre-norm), C = hidden_size:

* h = Emb[id]; every layer: h <- h + Attn(RMSNorm(h)), then
  h <- h + FFN(RMSNorm(h)); logits = RMSNorm(h) W_head (untied);
* latent attention (2.1.2; eq. 9-19 with the query NOT compressed,
  `q_lora_rank` null, else through RMSNorm(u W_qa) W_qb):
      q = u W_q -> heads x [q_C (qk_nope_head_dim) ; q_R (qk_rope_head_dim)]
      [c_KV ; k_R] = u W_kva       kv_lora_rank ; qk_rope_head_dim
      [k_C ; v] per head = RMSNorm(c_KV) W_kvb
      q_R, k_R <- RoPE (ONE k_R a token, shared by the heads)
      o = softmax_causal(scale [q_C ; q_R] [k_C ; k_R]^T) v;  y = o W_o
  scale = (qk_nope + qk_rope)^-1/2 m^2, m = 0.1 mscale_all_dim
  ln(factor) + 1 (YaRN, as the `deepseek_v2` modelling code);
* FFN (2.2.1, eq. 20-22): SwiGLU of width intermediate_size in the
  first first_k_dense_replace layers; after them
      s = softmax over ALL experts of u W_r
      g_e = s_e if e is among the k largest of s, else 0 (no renormalising)
      y = sum_j SwiGLU_shared_j(u) + sum_{e held} g_e SwiGLU_e(u)
  the n_shared_experts shared experts read from ONE stacked matrix
  each, column block j of gate and up, row block j of down;
* expert-level balance loss (2.2.3, eq. 23-25; `seq_aux`), for every
  expert layer and every SEQUENCE of T tokens:
      f_e = E / (k T) #{t: e selected for t};  P_e = mean_t s_{e,t}
      L_aux = aux_loss_alpha * mean over sequences of sum_e f_e P_e
  f is a count: no gradient goes through it;
* loss = mean over tokens of CE(logits, next id) + sum over expert
  layers of L_aux.

Departures, each the configuration's and stated there under `assumed`:
the device-level and communication balance losses and the token
dropping of 2.2.3-2.2.4 are not in the config and not here; a selected
expert that this chip does not hold adds nothing (the deployment's
other chips would add it); rotary acts on interleaved pairs.

Every array takes the dtype of the parameters it is given, so that the
same functions computed in bfloat16 say what a lower precision does to
the loss (`batch_loss(..., precision=None)`).

Memory at 4,096 tokens: attention is computed one sequence at a time
in blocks of query rows, the experts as a loop over the held ones with
a mask over all tokens.
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
    p = config.get("param_prefix", "dsv2")
    names = {"emb": p + "_emb.w", "final_norm": p + "_final_norm.w",
             "head": p + "_head.w", "layers": []}

    def mlp(b):
        return {"gate": b + "_gate.w", "up": b + "_up.w",
                "down": b + "_down.w"}

    for i in range(config["num_hidden_layers"]):
        b = "%s_l%d" % (p, i)
        layer = {"attn_norm": b + "_attn_norm.w",
                 "ffn_norm": b + "_ffn_norm.w", "kv_a": b + "_kv_a.w",
                 "kv_a_norm": b + "_kv_a_norm.w", "kv_b": b + "_kv_b.w",
                 "o": b + "_o.w"}
        if config.get("q_lora_rank"):
            layer.update(q_a=b + "_q_a.w", q_a_norm=b + "_q_a_norm.w",
                         q_b=b + "_q_b.w")
        else:
            layer["q"] = b + "_q.w"
        if i < config["first_k_dense_replace"]:
            layer["dense"] = mlp(b)
        else:
            layer["router"] = b + "_router.w"
            layer["experts"] = mlp(b + "_experts")
            layer["shared"] = mlp(b + "_shared")
        names["layers"].append(layer)
    return names


def read_params(config, get):
    """The program's own weights as float32 arrays.  `get(name)` returns
    the array the scope holds under `name`.  No copy is made of an
    array that is float32 already: read them before a step donates
    them."""
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


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(config):
    """Inverse rotary frequencies [qk_rope_head_dim / 2], float64: the
    plain ones where a dimension turns more than beta_fast times over
    the original context, divided by `factor` where it turns fewer
    than beta_slow times, a linear ramp between."""
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
    position * inv_freq[i]; cos and sin times yarn_mscale(mscale) /
    yarn_mscale(mscale_all_dim)."""
    import jax.numpy as jnp

    rs = config.get("rope_scaling") or {}
    factor = rs.get("factor", 1)
    mscale = yarn_mscale(factor, rs.get("mscale", 0)) \
        / yarn_mscale(factor, rs.get("mscale_all_dim", 0))
    ang = np.arange(x.shape[0], dtype=np.float64)[:, None] \
        * yarn_inv_freq(config)
    cos = jnp.asarray(np.cos(ang) * mscale, x.dtype)[:, None]
    sin = jnp.asarray(np.sin(ang) * mscale, x.dtype)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def softmax_scale(config):
    d = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    rs = config.get("rope_scaling") or {}
    m = yarn_mscale(rs.get("factor", 1), rs.get("mscale_all_dim", 0))
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
    if "q" in lw:
        q = u @ lw["q"]
    else:
        q = rms_norm(u @ lw["q_a"], lw["q_a_norm"], eps) @ lw["q_b"]
    q = q.reshape(t, heads, nope + rope)
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


def shared_experts(u, w, n):
    """The n shared experts, each its own SwiGLU, summed: expert j reads
    column block j of the stacked gate and up matrices and row block j
    of the stacked down matrix."""
    width = w["gate"].shape[1] // n
    return sum(swiglu(u, {"gate": w["gate"][:, j * width:(j + 1) * width],
                          "up": w["up"][:, j * width:(j + 1) * width],
                          "down": w["down"][j * width:(j + 1) * width]})
               for j in range(n))


def route(u, lw, config):
    """(selected [T, E] bool, scores [T, E]) over ALL experts:
    softmax scores, the k largest selected."""
    import jax
    import jax.numpy as jnp

    if config.get("scoring_func", "softmax") != "softmax":
        raise NotImplementedError(config["scoring_func"])
    k = config["num_experts_per_tok"]
    s = jax.nn.softmax(u @ lw["router"], axis=-1)
    ranked = jnp.argsort(-s, axis=-1, stable=True)
    selected = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], ranked[:, :k]].set(True)
    return selected, s


def gates(selected, s, config):
    """[T, E], zero where not selected."""
    import jax.numpy as jnp

    g = jnp.where(selected, s, 0.0)
    if config["norm_topk_prob"]:
        g = g / g.sum(-1, keepdims=True)
    return g * config["routed_scaling_factor"]


def balance(selected, s, config):
    """sum_e f_e P_e of ONE sequence (before aux_loss_alpha): f_e =
    E / (k T) x tokens that select e, a count with no gradient; P_e the
    sequence's mean score of e."""
    import jax
    import jax.numpy as jnp

    t, e = s.shape
    f = jax.lax.stop_gradient(
        selected.astype(s.dtype).sum(0)
        * (e / (config["num_experts_per_tok"] * t)))
    return (f * s.mean(0)).sum()


def expert_ffn(u, lw, config, held=None, shared=True):
    """(the shared experts plus the held routed experts' part, the
    sequence's balance term); `held` defaults to the configuration's."""
    import jax.numpy as jnp

    held = held_experts(config) if held is None else held
    selected, s = route(u, lw, config)
    gate = gates(selected, s, config)
    y = shared_experts(u, lw["shared"], config["n_shared_experts"]) \
        if shared else jnp.zeros_like(u)
    for slot, e in enumerate(held):
        w = {k: v[slot] for k, v in lw["experts"].items()}
        y = y + jnp.where(selected[:, e, None],
                          gate[:, e, None] * swiglu(u, w), 0.0)
    return y, balance(selected, s, config)


def sequence_forward(params, ids, config):
    """(logits [T, vocab], sum over the expert layers of the balance
    term) of ONE sequence, ids [T] int."""
    eps = config["rms_norm_eps"]
    h = params["emb"][ids]
    aux = 0.0
    for i, lw in enumerate(params["layers"]):
        h = h + attention(rms_norm(h, lw["attn_norm"], eps), lw, config)
        u = rms_norm(h, lw["ffn_norm"], eps)
        if i < config["first_k_dense_replace"]:
            h = h + swiglu(u, lw["dense"])
        else:
            y, term = expert_ffn(u, lw, config)
            h, aux = h + y, aux + term
    return rms_norm(h, params["final_norm"], eps) @ params["head"], aux


def sequence_logits(params, ids, config):
    return sequence_forward(params, ids, config)[0]


def loss_terms(params, ids, labels, config, precision="highest"):
    """(mean next-token cross-entropy, the balance loss), ids and
    labels [B, T] int; functions of jax arrays that jax.grad
    differentiates (the tests' gradients).  The balance loss is 0
    without `seq_aux`."""
    import jax
    import jax.numpy as jnp

    def one(xy):
        logits, aux = sequence_forward(params, xy[0], config)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, xy[1][:, None], axis=1).sum(), \
            jnp.asarray(aux, logits.dtype)

    with jax.default_matmul_precision(precision or "default"):
        ce, aux = jax.lax.map(one, (ids, labels))
    alpha = config["aux_loss_alpha"] if config.get("seq_aux") else 0.0
    return ce.sum() / ids.size, alpha * aux.mean()


def batch_loss(params, ids, labels, config, precision="highest"):
    """The training loss: cross-entropy plus balance loss."""
    ce, aux = loss_terms(params, ids, labels, config, precision)
    return ce + aux


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
    """The training loss of the batch (ids, labels), each [B, T, 1]."""
    import json

    ids, labels = _split(batch)
    return float(_jitted(json.dumps(config, sort_keys=True))(
        params, ids, labels))
