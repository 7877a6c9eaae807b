"""Plain reference of the language model of Ling-3.0-flash-VL (a hybrid
of Kimi Delta Attention layers and latent-attention layers over sparse
experts): forward pass, logits and loss in straightforward jax.numpy,
float32, matmul precision "highest", no kernels, no AMP, a Python loop
over layers.  Shares no code with paddle_tpu/ops or models/ling3.py,
and not the algorithm either: the delta-rule recurrence runs TOKEN BY
TOKEN (a lax.scan over t of the two lines below), with no chunks and no
WY form, and the router marks its groups and experts over all 512.

Follows docs/LING3_BLOCK.md equation by equation.  h the residual
stream, C = hidden_size:

* h = Emb[id]; every layer: h <- h + Mixer(RMSNorm(h)), then
  h <- h + FFN(RMSNorm(h)); logits = RMSNorm(h_L) W_head (untied);
  loss = mean over tokens of CE(logits, next id).
* Mixer of published layer l: latent attention where
  (l + 1) % layer_group_size == 0, else KDA.
* KDA (H heads, d = head_dim), x = RMSNorm(h):
      q~, k~, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
          conv depthwise over time, short_conv_kernel_size taps, no
          bias, zeros before t = 0
      q = l2norm_h(q~) d^-1/2,  k = l2norm_h(k~)
      g = kda_lower_bound sigmoid(exp(A_log_h) (x W_a + dt_bias))
      beta = sigmoid(x w_beta)                                per head
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
            + beta_t k_t v_t^T,   S_0 = 0;    o_t = S_t^T q_t
      y = [sigmoid(x w_gate^h) RMSNorm_h(o^h) w_norm]_h W_o
* latent attention: DeepSeek-V2's with a full-rank query (eq. 9-19,
  `q_lora_rank` null), plain rotary frequencies at rope_theta, scale
  (qk_nope + qk_rope)^-1/2, and each head's output times
  sigmoid(x w_gate^h) before W_o.
* FFN: SwiGLU of width intermediate_size for l < first_k_dense_replace;
  after them
      s = sigmoid(x W_r) over ALL experts;  r = s + b
      a group (n_group groups of consecutive ids) scores the sum of
      its two largest r; the topk_group best groups are kept; the
      num_experts_per_tok largest r among their experts are selected
      gate_e = routed_scaling_factor s_e / sum of the selected s
      y = SwiGLU_shared(x) + sum_{e selected and held} gate_e SwiGLU_e(x)

Departures, each the configuration's and stated there under `assumed`:
a selected expert that this chip does not hold adds nothing (the
deployment's other chips would add it); rotary acts on interleaved
pairs; no vision tower, no multi-token-prediction layer.

Every array up to the logits takes the dtype of the parameters it is
given, so that the same layers computed in bfloat16 say what a lower
precision does to the loss (`loss(..., dtype="bfloat16")`; the
cross-entropy and its mean stay float32).  `variant` computes a WRONG
model on purpose, for the controls: "no_erase" drops the delta rule's
erase term (S_t = Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T),
"no_group_limit" selects over all experts.

Memory at 4,096 tokens: attention is computed one sequence at a time in
blocks of query rows, the experts as a loop over the held ones with a
mask over all tokens, and the head in blocks of tokens that give their
cross-entropy and are dropped.
"""

from __future__ import annotations

import functools

import numpy as np

QUERY_BLOCK = 512
TOKEN_BLOCK = 512

_KDA = ("kda_q", "kda_k", "kda_v", "kda_a", "kda_beta", "kda_gate",
        "kda_o", "kda_q_conv", "kda_k_conv", "kda_v_conv",
        "kda_decay_A_log", "kda_decay_dt_bias", "kda_norm")
_MLA = ("q", "kv_a", "kv_a_norm", "kv_b", "gate", "o")
_MLP = ("gate", "up", "down")


def layer_kinds(config):
    """"kda" or "mla" for each layer kept: the published layers 0 ..
    num_hidden_layers - 1."""
    period = config["layer_group_size"]
    return ["mla" if (i + 1) % period == 0 else "kda"
            for i in range(config["num_hidden_layers"])]


def held_experts(config):
    return list(config.get("held_experts") or range(config["num_experts"]))


def param_names(config):
    p = config.get("param_prefix", "ling3")
    names = {"emb": p + "_emb.w", "final_norm": p + "_final_norm.w",
             "head": p + "_head.w", "layers": []}
    for i, kind in enumerate(layer_kinds(config)):
        b = "%s_l%d" % (p, i)
        layer = {"mixer_norm": b + "_mixer_norm.w",
                 "ffn_norm": b + "_ffn_norm.w"}
        own = _KDA if kind == "kda" else tuple("mla_" + k for k in _MLA)
        layer.update({k: "%s_%s.w" % (b, k) for k in own})
        if i < config["first_k_dense_replace"]:
            layer["dense"] = {k: "%s_%s.w" % (b, k) for k in _MLP}
        else:
            layer["router"] = b + "_router.w"
            layer["router_bias"] = b + "_router_bias.w"
            layer["experts"] = {k: "%s_experts_%s.w" % (b, k)
                                for k in _MLP}
            layer["shared"] = {k: "%s_shared_%s.w" % (b, k) for k in _MLP}
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


def l2_norm(x, eps=1e-6):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, w):
    """x [T, C], w [C, K]: y_t = sum_k w[:, k] x_{t - (K-1) + k}."""
    import jax.numpy as jnp

    t, k = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(padded[i:i + t] * w[:, i] for i in range(k))


def delta_recurrence(q, k, v, g, beta, erase=True):
    """Token by token.  q, k, v, g [T, H, d], beta [T, H] -> o
    [T, H, d].  erase False drops the delta rule's erase term (a wrong
    model on purpose)."""
    import jax.numpy as jnp
    from jax import lax

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, :, None] * s                 # Diag(alpha) S
        seen = jnp.einsum("hk,hkv->hv", k_t, s) if erase else 0.0
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s)

    t, h, d = q.shape
    _, o = lax.scan(step, jnp.zeros((h, d, d), q.dtype),
                    (q, k, v, g, beta))
    return o


def kda_mixer(u, lw, config, variant=""):
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    h, d = config["num_attention_heads"], config["head_dim"]

    def branch(name):
        return jax.nn.silu(causal_conv(u @ lw["kda_" + name],
                                       lw["kda_%s_conv" % name])
                           ).reshape(t, h, d)

    q = l2_norm(branch("q")) * d ** -0.5
    k = l2_norm(branch("k"))
    v = branch("v")
    rate = jnp.exp(lw["kda_decay_A_log"])[:, None]
    g = config["kda_lower_bound"] * jax.nn.sigmoid(
        rate * (u @ lw["kda_a"] + lw["kda_decay_dt_bias"]).reshape(t, h, d))
    beta = jax.nn.sigmoid(u @ lw["kda_beta"])
    o = delta_recurrence(q, k, v, g.astype(q.dtype), beta,
                         erase=variant != "no_erase")
    o = rms_norm(o, lw["kda_norm"], config["rms_norm_eps"]) \
        * jax.nn.sigmoid(u @ lw["kda_gate"])[:, :, None]
    return o.reshape(t, h * d) @ lw["kda_o"]


def rotate(x, config):
    """x [T, H, d_rope]: interleaved pairs (x[2i], x[2i+1]) turned by
    position * rope_theta^(-2i / d_rope)."""
    import jax.numpy as jnp

    dim = x.shape[-1]
    inv = 1.0 / float(config["rope_theta"]) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(x.shape[0], dtype=np.float64)[:, None] * inv
    cos = jnp.asarray(np.cos(ang), x.dtype)[:, None]
    sin = jnp.asarray(np.sin(ang), x.dtype)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


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


def mla_mixer(u, lw, config):
    """Latent attention of one sequence, u [T, C] -> [T, C], with the
    head-wise output gate."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, kvr = config["v_head_dim"], config["kv_lora_rank"]
    q = (u @ lw["mla_q"]).reshape(t, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], config)], -1)
    kv_a = u @ lw["mla_kv_a"]
    c_kv = rms_norm(kv_a[:, :kvr], lw["mla_kv_a_norm"],
                    config["rms_norm_eps"])
    k_r = rotate(kv_a[:, None, kvr:], config)              # [T, 1, rope]
    kv = (c_kv @ lw["mla_kv_b"]).reshape(t, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (t, heads, rope))], -1)
    out = causal_attention(q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                           kv[..., nope:].transpose(1, 0, 2),
                           (nope + rope) ** -0.5)
    out = out.transpose(1, 0, 2) \
        * jax.nn.sigmoid(u @ lw["mla_gate"])[:, :, None]
    return out.reshape(t, heads * vd) @ lw["mla_o"]


def swiglu(u, w):
    import jax

    return (jax.nn.silu(u @ w["gate"]) * (u @ w["up"])) @ w["down"]


def route(u, lw, config, variant=""):
    """(selected [T, E] bool, s [T, E]) over ALL experts."""
    import jax
    import jax.numpy as jnp

    k = config["num_experts_per_tok"]
    groups, kept = config["n_group"], config["topk_group"]
    s = jax.nn.sigmoid(u @ lw["router"])
    r = s + lw["router_bias"]
    t, e = s.shape
    if variant != "no_group_limit" and groups > 1:
        per = r.reshape(t, groups, e // groups)
        score = jnp.sort(per, axis=-1)[..., -2:].sum(-1)      # [T, groups]
        order = jnp.argsort(-score, axis=-1, stable=True)
        alive = jnp.zeros((t, groups), bool).at[
            jnp.arange(t)[:, None], order[:, :kept]].set(True)
        r = jnp.where(jnp.repeat(alive, e // groups, axis=1), r, -jnp.inf)
    ranked = jnp.argsort(-r, axis=-1, stable=True)
    selected = jnp.zeros((t, e), bool).at[
        jnp.arange(t)[:, None], ranked[:, :k]].set(True)
    return selected, s


def gates(selected, s, config):
    """[T, E], zero where not selected."""
    import jax.numpy as jnp

    g = jnp.where(selected, s, 0.0)
    if config["norm_topk_prob"]:
        g = g / g.sum(-1, keepdims=True)
    return g * config["routed_scaling_factor"]


def expert_ffn(u, lw, config, held=None, shared=True, variant=""):
    """The shared expert plus the held routed experts' part; `held`
    defaults to the configuration's."""
    import jax.numpy as jnp

    held = held_experts(config) if held is None else held
    selected, s = route(u, lw, config, variant)
    gate = gates(selected, s, config)
    y = swiglu(u, lw["shared"]) if shared else jnp.zeros_like(u)
    for slot, e in enumerate(held):
        w = {k: v[slot] for k, v in lw["experts"].items()}
        y = y + jnp.where(selected[:, e, None],
                          gate[:, e, None] * swiglu(u, w), 0.0)
    return y


def layer(x, lw, config, kind, variant=""):
    eps = config["rms_norm_eps"]
    u = rms_norm(x, lw["mixer_norm"], eps)
    x = x + (kda_mixer(u, lw, config, variant) if kind == "kda"
             else mla_mixer(u, lw, config))
    m = rms_norm(x, lw["ffn_norm"], eps)
    if "dense" in lw:
        return x + swiglu(m, lw["dense"])
    return x + expert_ffn(m, lw, config, variant=variant)


def sequence_state(params, ids, config, layer_fn=layer, variant=""):
    """RMSNorm(h_L) [T, C] of ONE sequence, ids [T] int."""
    x = params["emb"][ids]
    for lw, kind in zip(params["layers"], layer_kinds(config)):
        x = layer_fn(x, lw, config, kind, variant)
    return rms_norm(x, params["final_norm"], config["rms_norm_eps"])


def sequence_logits(params, ids, config):
    return sequence_state(params, ids, config) @ params["head"]


def cross_entropy(h, head, labels):
    """Per-token cross-entropy [T] of logits h head, a block of tokens
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
            (h[start:start + block] @ head).astype(jnp.float32), axis=-1)
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
                  params["head"], y).sum()
            for i, y in zip(ids, labels)) / ids.size


@functools.lru_cache(maxsize=None)
def _jitted_pieces():
    """`layer` and `cross_entropy` jitted each on its own: the layers of
    one kind have the same shapes and compile ONCE."""
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
