"""Plain reference of LFM2-24B-A2B (model_type `lfm2_moe`: doubly gated
short-convolution layers and grouped-query attention layers over a
dense SwiGLU, then sparse experts): forward pass, logits and loss in
straightforward jax.numpy, float32, matmul precision "highest", no
kernels, no AMP, a Python loop over layers.  Shares no code with
paddle_tpu/ops or models/lfm2.py: the convolution is K shifted copies
of a zero-padded array, attention repeats K and V to the query heads,
and the router marks its experts over all 64.

Follows docs/LFM2_BLOCK.md equation by equation.  h the residual
stream, C = hidden_size:

* h = Emb[id]; every layer: h <- h + Mixer(RMSNorm(h)), then
  h <- h + FFN(RMSNorm(h)); logits = RMSNorm(h_L) Emb^T (tied);
  loss = mean over tokens of CE(logits, next id).
* The layers built are the published layers `kept_layers` (default
  0 .. num_hidden_layers - 1); the mixer of published layer l is
  `layer_types[l]`; the first `num_dense_layers` of them are dense.
* conv (K = conv_L_cache taps, no bias, no activation), u = RMSNorm(h):
      [B | C | x] = u W_in                 thirds of 3 C, in that order
      z = B * x;   c_t = sum_{k<K} w[:, k] z_{t - (K-1) + k}   z = 0, t < 0
      y = (C * c) W_out
* full_attention (H heads, H_kv KV heads, d = C / H), u = RMSNorm(h):
      q, k, v = u W_q, u W_k, u W_v                         no bias
      q = RMSNorm_d(q^h) g_q,  k = RMSNorm_d(k^h) g_k       a head, eps
          norm_eps, one scale of d each
      q, k turned by the rotary embedding over all of d, split halves
          (x[i], x[i + d/2]) by position * theta^(-2i/d)
      o = softmax(q k^T d^-1/2 + causal mask) v, query head h reading
          KV head h // (H / H_kv);   y = o W_o
* FFN: SwiGLU of width intermediate_size for a dense layer; else
      s = sigmoid(x W_r) over ALL experts;  the num_experts_per_tok
      largest s + b are selected (b selects, it does not weigh)
      gate_e = routed_scaling_factor s_e / (sum of the selected s + 1e-6)
      y = sum_{e selected and held} gate_e SwiGLU_e(x)

Departures, each the configuration's and stated there under `assumed`:
a selected expert that this chip does not hold adds nothing (the
deployment's other chips would add it); the order [B | C | x] of the
projection's thirds; the tied head.

Every array up to the logits takes the dtype of the parameters it is
given, so that the same layers computed in bfloat16 say what a lower
precision does to the loss (`loss(..., dtype="bfloat16")`; the router's
logits, the cross-entropy and its mean stay float32).  `variant`
computes a WRONG model on purpose, for the controls: "no_input_gate"
convolves x in place of B * x, "no_qk_norm" leaves q and k unnormed,
"repeat_first_kv" gives every query head KV head 0.

Memory at 8,192 tokens: attention is computed one sequence at a time in
blocks of query rows, the experts as a loop over the held ones with a
mask over all tokens, and the head in blocks of tokens that give their
cross-entropy and are dropped.
"""

from __future__ import annotations

import functools

import numpy as np

QUERY_BLOCK = 512
TOKEN_BLOCK = 512
ROUTER_NORM_EPS = 1e-6

_CONV = ("conv_in", "conv", "conv_out")
_ATTN = ("q", "k", "v", "o", "q_norm", "k_norm")
_MLP = ("gate", "up", "down")


def layer_kinds(config):
    """"conv" or "full_attention" for each layer built."""
    kept = config.get("kept_layers")
    if kept is None:
        kept = range(config["num_hidden_layers"])
    return [config["layer_types"][i] for i in kept]


def held_experts(config):
    return list(config.get("held_experts") or range(config["num_experts"]))


def param_names(config):
    p = config.get("param_prefix", "lfm2")
    names = {"emb": p + "_emb.w", "final_norm": p + "_final_norm.w",
             "layers": []}
    for i, kind in enumerate(layer_kinds(config)):
        b = "%s_l%d" % (p, i)
        layer = {"operator_norm": b + "_operator_norm.w",
                 "ffn_norm": b + "_ffn_norm.w"}
        layer.update({k: "%s_%s.w" % (b, k)
                      for k in (_CONV if kind == "conv" else _ATTN)})
        if i < config["num_dense_layers"]:
            layer["dense"] = {k: "%s_%s.w" % (b, k) for k in _MLP}
        else:
            layer["router"] = b + "_router.w"
            layer["router_bias"] = b + "_router_bias.w"
            layer["experts"] = {k: "%s_experts_%s.w" % (b, k)
                                for k in _MLP}
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


def causal_conv(x, w):
    """x [T, C], w [C, K]: y_t = sum_k w[:, k] x_{t - (K-1) + k}."""
    import jax.numpy as jnp

    t, k = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(padded[i:i + t] * w[:, i] for i in range(k))


def conv_mixer(u, lw, config, variant=""):
    import jax.numpy as jnp

    gate_in, gate_out, x = jnp.split(u @ lw["conv_in"], 3, axis=-1)
    z = x if variant == "no_input_gate" else gate_in * x
    return (gate_out * causal_conv(z, lw["conv"])) @ lw["conv_out"]


def rotate_halves(x, theta):
    """x [T, H, d]: the pairs (x[i], x[i + d/2]) turned by
    position * theta^(-2i / d)."""
    import jax.numpy as jnp

    dim = x.shape[-1]
    inv = 1.0 / float(theta) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(x.shape[0], dtype=np.float64)[:, None] * inv
    cos = jnp.asarray(np.cos(ang), x.dtype)[:, None]
    sin = jnp.asarray(np.sin(ang), x.dtype)[:, None]
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def causal_attention(q, k, v, scale, block=QUERY_BLOCK):
    """softmax(scale q k^T + causal mask) v for one sequence, q, k, v
    [H, T, d]; `block` query rows at a time against all keys."""
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

    out = jax.lax.map(rows, jnp.arange(0, t, block))   # [nb, H, block, d]
    return jnp.moveaxis(out, 0, 1).reshape(h, t, v.shape[-1])


def attention_mixer(u, lw, config, variant=""):
    """Grouped-query attention of one sequence, u [T, C] -> [T, C]."""
    import jax.numpy as jnp

    t = u.shape[0]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // heads
    eps, theta = config["norm_eps"], config["rope_parameters"]["rope_theta"]
    q = (u @ lw["q"]).reshape(t, heads, d)
    k = (u @ lw["k"]).reshape(t, kv, d)
    v = (u @ lw["v"]).reshape(t, kv, d)
    if variant != "no_qk_norm":
        q, k = rms_norm(q, lw["q_norm"], eps), rms_norm(k, lw["k_norm"], eps)
    q, k = rotate_halves(q, theta), rotate_halves(k, theta)
    if variant == "repeat_first_kv":
        k, v = (jnp.broadcast_to(a[:, :1], (t, heads, d)) for a in (k, v))
    else:       # query head h reads KV head h // (heads / kv)
        k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    out = causal_attention(*(a.transpose(1, 0, 2) for a in (q, k, v)),
                           d ** -0.5)
    return out.transpose(1, 0, 2).reshape(t, heads * d) @ lw["o"]


def swiglu(u, w):
    import jax

    return (jax.nn.silu(u @ w["gate"]) * (u @ w["up"])) @ w["down"]


def route(u, lw, config):
    """(selected [T, E] bool, s [T, E] float32) over ALL experts."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(u.astype(jnp.float32)
                       @ lw["router"].astype(jnp.float32))
    ranked = jnp.argsort(-(s + lw["router_bias"].astype(jnp.float32)),
                         axis=-1, stable=True)
    t, e = s.shape
    selected = jnp.zeros((t, e), bool).at[
        jnp.arange(t)[:, None],
        ranked[:, :config["num_experts_per_tok"]]].set(True)
    return selected, s


def gates(selected, s, config):
    """[T, E], zero where not selected."""
    import jax.numpy as jnp

    g = jnp.where(selected, s, 0.0)
    if config["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + ROUTER_NORM_EPS)
    return g * config["routed_scaling_factor"]


def expert_ffn(u, lw, config, held=None):
    """The held routed experts' part; `held` defaults to the
    configuration's.  `lw["experts"]` stacks the held experts' weights
    in `held`'s order."""
    import jax.numpy as jnp

    held = held_experts(config) if held is None else held
    selected, s = route(u, lw, config)
    gate = gates(selected, s, config).astype(u.dtype)
    y = jnp.zeros_like(u)
    for slot, e in enumerate(held):
        w = {k: v[slot] for k, v in lw["experts"].items()}
        y = y + jnp.where(selected[:, e, None],
                          gate[:, e, None] * swiglu(u, w), 0.0)
    return y


def layer(x, lw, config, kind, variant=""):
    eps = config["norm_eps"]
    u = rms_norm(x, lw["operator_norm"], eps)
    x = x + (conv_mixer(u, lw, config, variant) if kind == "conv"
             else attention_mixer(u, lw, config, variant))
    m = rms_norm(x, lw["ffn_norm"], eps)
    if "dense" in lw:
        return x + swiglu(m, lw["dense"])
    return x + expert_ffn(m, lw, config)


def sequence_state(params, ids, config, layer_fn=layer, variant=""):
    """RMSNorm(h_L) [T, C] of ONE sequence, ids [T] int."""
    x = params["emb"][ids]
    for lw, kind in zip(params["layers"], layer_kinds(config)):
        x = layer_fn(x, lw, config, kind, variant)
    return rms_norm(x, params["final_norm"], config["norm_eps"])


def sequence_logits(params, ids, config):
    return sequence_state(params, ids, config) @ params["emb"].T


def cross_entropy(h, emb, labels):
    """Per-token cross-entropy [T] of logits h emb^T, a block of tokens
    at a time; the softmax and what follows float32 whatever the
    layers' dtype (a bfloat16 loss lies on a grid 0.03-0.06 apart at
    ln V: PR 34's finding)."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    block = min(TOKEN_BLOCK, t)
    out = []
    for start in range(0, t, block):
        logp = jax.nn.log_softmax(
            (h[start:start + block] @ emb.T).astype(jnp.float32), axis=-1)
        out.append(-jnp.take_along_axis(
            logp, labels[start:start + block, None], axis=1)[:, 0])
    return jnp.concatenate(out)


def batch_loss(params, ids, labels, config, layer_fn=layer,
               ce_fn=cross_entropy, variant="", precision="highest"):
    """The loss, ids and labels [B, T] int; a function of jax arrays
    that jax.grad differentiates (the tests' gradients)."""
    import jax

    with jax.default_matmul_precision(precision or "default"):
        return sum(
            ce_fn(sequence_state(params, i, config, layer_fn, variant),
                  params["emb"], y).sum()
            for i, y in zip(ids, labels)) / ids.size


@functools.lru_cache(maxsize=None)
def _jitted_pieces():
    """`layer` and `cross_entropy` jitted each on its own: the layers of
    one kind and feed-forward have the same shapes and compile ONCE."""
    import jax

    return {"layer_fn": jax.jit(layer, static_argnums=(2, 3, 4)),
            "ce_fn": jax.jit(cross_entropy)}


class _Static(dict):
    """A config dict as a static (hashable) jit argument."""

    def __hash__(self):
        import json

        return hash(json.dumps(self, sort_keys=True))


def _split(batch):
    import jax.numpy as jnp

    return tuple(jnp.asarray(np.asarray(a)[..., 0].astype(np.int32))
                 for a in batch)


def loss(params, batch, config, variant="", dtype=None):
    """The loss of the batch (ids, labels), each [B, T, 1]: the same
    Python loop over layers, its pieces jitted.  dtype "bfloat16": every
    parameter and so every array in that dtype, at the default matmul
    precision (the control: what a lower precision does)."""
    import jax
    import jax.numpy as jnp

    ids, labels = _split(batch)
    if dtype:
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.dtype(dtype)), params)
    return float(batch_loss(params, ids, labels, _Static(config),
                            variant=variant,
                            precision=None if dtype else "highest",
                            **_jitted_pieces()))
