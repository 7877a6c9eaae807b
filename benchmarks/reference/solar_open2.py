"""Plain reference of Solar-Open2-250B as one tensor- and expert-parallel
rank holds it (a hybrid of gated grouped-KV attention layers without
positions and Kimi Delta Attention layers with an unbounded channel
decay, over a shared expert and sparse experts): forward pass, logits
and loss in straightforward jax.numpy, float32, matmul precision
"highest", no kernels, no AMP, a Python loop over layers.  Shares no
code with paddle_tpu/ops or models/solar_open2.py, and not the
algorithm either: the delta-rule recurrence runs TOKEN BY TOKEN (a
lax.scan over t of the two lines below: e^g of a token multiplies the
state, whatever g is), with no chunks, no WY form and no running sums
of g; attention is the full masked softmax in blocks of query rows with
K and V repeated to the query heads; the router marks its experts over
all 320.

Follows docs/SOLAR_OPEN2_BLOCK.md equation by equation.  h the residual
stream, C = hidden_size, d = head_dim:

* h = Emb[id]; every layer: h <- h + Mixer(RMSNorm(h)), then
  h <- h + FFN(RMSNorm(h)); logits = RMSNorm(h_L) W_head (untied);
  loss = mean over tokens of CE(logits, next id).
* Mixer of published layer l: attention where l is in gqa_layers, else
  KDA.
* attention, x = RMSNorm(h) (no positions, no norm on q or k):
      q, k, v = x W_q, x W_k, x W_v
      o_h = causal softmax(q_h k_{h // group}^T d^-1/2) v_{h // group}
      y = [sigmoid(x W_g) * o] W_o             a gate a CHANNEL
* KDA:
      q~, k~, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
          conv depthwise over time, short_conv_kernel_size taps, no
          bias, zeros before t = 0
      q = l2norm_h(q~) d^-1/2,  k = l2norm_h(k~)
      g = -exp(A_log_h) softplus(x W_fa W_fb + dt_bias)     a channel
      beta = 2 sigmoid(x w_beta)                             a head
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
            + beta_t k_t v_t^T,   S_0 = 0;    o_t = S_t^T q_t
      y = [RMSNorm_h(o^h) w_norm * sigmoid(x W_ga W_gb)] W_o
* FFN:
      s = sigmoid(x W_r) over ALL experts;  r = s + b
      the num_experts_per_tok largest r are selected
      gate_e = routed_scaling_factor s_e / sum of the selected s
      y = SwiGLU_shared(x) + sum_{e selected and held} gate_e SwiGLU_e(x)

The share.  The numbers of heads are read off the weights: W_q's
columns over d say how many query (or KDA) heads a layer's weights
hold, W_k's how many KV heads.  Given a rank's columns of W_q, W_k,
W_v, W_g (W_fb, W_gb, w_beta, the filters, A_log, dt_bias) and its
ROWS of W_o, `gqa_mixer` and `kda_mixer` give that rank's PART of the
mixer's output; the parts of all ranks add up to the uncut layer
(tests/test_solar_open2_model.py), as the held experts' parts do.
A selected expert that this chip does not hold adds nothing.

Every array up to the logits takes the dtype of the parameters it is
given, so that the same layers computed in bfloat16 say what a lower
precision does to the loss (`loss(..., dtype="bfloat16")`; the
cross-entropy and its mean stay float32).  `variant` computes a WRONG
model on purpose, for the controls: "beta_not_doubled" (beta =
sigmoid), "g_clamped" (g no lower than -5 a token), "no_gqa_gate" (the
attention layer's gate dropped), "kda_gate_a_head" (the KDA output gate
one logit a head: the mean of the head's channel logits).

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

_GQA = ("gqa_q", "gqa_k", "gqa_v", "gqa_gate", "gqa_o")
_KDA = ("kda_q", "kda_k", "kda_v", "kda_f_a", "kda_f_b", "kda_g_a",
        "kda_g_b", "kda_beta", "kda_o", "kda_q_conv", "kda_k_conv",
        "kda_v_conv", "kda_decay_A_log", "kda_decay_dt_bias", "kda_norm")
_MLP = ("gate", "up", "down")


def layer_kinds(config):
    """"gqa" or "kda" for each layer kept: the published layers 0 ..
    num_hidden_layers - 1."""
    gqa = set(config["gqa_layers"])
    return ["gqa" if i in gqa else "kda"
            for i in range(config["num_hidden_layers"])]


def held_experts(config):
    return list(config.get("held_experts")
                or range(config["n_routed_experts"]))


def param_names(config):
    p = config.get("param_prefix", "solar")
    names = {"emb": p + "_emb.w", "final_norm": p + "_final_norm.w",
             "head": p + "_head.w", "layers": []}
    for i, kind in enumerate(layer_kinds(config)):
        b = "%s_l%d" % (p, i)
        layer = {"mixer_norm": b + "_mixer_norm.w",
                 "ffn_norm": b + "_ffn_norm.w",
                 "router": b + "_router.w",
                 "router_bias": b + "_router_bias.w",
                 "experts": {k: "%s_experts_%s.w" % (b, k) for k in _MLP},
                 "shared": {k: "%s_shared_%s.w" % (b, k) for k in _MLP}}
        layer.update({k: "%s_%s.w" % (b, k)
                      for k in (_GQA if kind == "gqa" else _KDA)})
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


def delta_recurrence(q, k, v, g, beta):
    """Token by token.  q, k, v, g [T, H, d], beta [T, H] -> o
    [T, H, d]."""
    import jax.numpy as jnp
    from jax import lax

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, :, None] * s                 # Diag(alpha) S
        seen = jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s)

    t, h, d = q.shape
    _, o = lax.scan(step, jnp.zeros((h, d, d), q.dtype),
                    (q, k, v, g, beta))
    return o


def kda_mixer(u, lw, config, variant=""):
    """One sequence, u [T, C] -> the PART of the mixer's output that
    the heads in `lw` give, [T, C]."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    d = config["linear_attn_config"]["head_dim"]
    h = lw["kda_q"].shape[1] // d

    def branch(name):
        return jax.nn.silu(causal_conv(u @ lw["kda_" + name],
                                       lw["kda_%s_conv" % name])
                           ).reshape(t, h, d)

    q = l2_norm(branch("q")) * d ** -0.5
    k = l2_norm(branch("k"))
    v = branch("v")
    rate = jnp.exp(lw["kda_decay_A_log"])[:, None]
    g = -rate * jax.nn.softplus(
        (u @ lw["kda_f_a"] @ lw["kda_f_b"]
         + lw["kda_decay_dt_bias"]).reshape(t, h, d))
    if variant == "g_clamped":
        g = jnp.maximum(g, -5.0)
    beta = jax.nn.sigmoid(u @ lw["kda_beta"])
    if variant != "beta_not_doubled":
        beta = 2.0 * beta
    o = delta_recurrence(q, k, v, g.astype(q.dtype), beta)
    gate = (u @ lw["kda_g_a"] @ lw["kda_g_b"]).reshape(t, h, d)
    if variant == "kda_gate_a_head":
        gate = jnp.mean(gate, axis=-1, keepdims=True)
    o = rms_norm(o, lw["kda_norm"], config["rms_norm_eps"]) \
        * jax.nn.sigmoid(gate)
    return o.reshape(t, h * d) @ lw["kda_o"]


def causal_attention(q, k, v, scale, block=QUERY_BLOCK):
    """softmax(scale q k^T + causal mask) v for one sequence, q/k/v
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


def gqa_mixer(u, lw, config, variant=""):
    """One sequence, u [T, C] -> the PART of the attention layer's
    output that the query heads in `lw` (with their KV heads) give."""
    import jax
    import jax.numpy as jnp

    t, d = u.shape[0], config["head_dim"]
    heads, kv_heads = lw["gqa_q"].shape[1] // d, lw["gqa_k"].shape[1] // d

    def split(x, n):
        return x.reshape(t, n, d).transpose(1, 0, 2)

    def repeated(x):        # query head h reads KV head h // group
        return jnp.repeat(split(x, kv_heads), heads // kv_heads, axis=0)

    out = causal_attention(split(u @ lw["gqa_q"], heads),
                           repeated(u @ lw["gqa_k"]),
                           repeated(u @ lw["gqa_v"]), d ** -0.5)
    out = out.transpose(1, 0, 2).reshape(t, heads * d)
    if variant != "no_gqa_gate":
        out = out * jax.nn.sigmoid(u @ lw["gqa_gate"])
    return out @ lw["gqa_o"]


def swiglu(u, w):
    import jax

    return (jax.nn.silu(u @ w["gate"]) * (u @ w["up"])) @ w["down"]


def route(u, lw, config):
    """(selected [T, E] bool, s [T, E]) over ALL experts."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(u @ lw["router"])
    ranked = jnp.argsort(-(s + lw["router_bias"]), axis=-1, stable=True)
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
        g = g / g.sum(-1, keepdims=True)
    return g * config["routed_scaling_factor"]


def expert_ffn(u, lw, config, held=None, shared=True):
    """The shared expert plus the held routed experts' part; `held`
    defaults to the configuration's."""
    import jax.numpy as jnp

    held = held_experts(config) if held is None else held
    selected, s = route(u, lw, config)
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
             else gqa_mixer(u, lw, config, variant))
    return x + expert_ffn(rms_norm(x, lw["ffn_norm"], eps), lw, config)


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


def _in_dtype(params, dtype):
    import jax
    import jax.numpy as jnp

    if not dtype:
        return params
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.dtype(dtype)), params)


def loss(params, batch, config, variant="", dtype=None):
    """The loss of the batch (ids, labels), each [B, T, 1]: the same
    Python loop over layers, its pieces jitted.  dtype "bfloat16": every
    parameter and so every array in that dtype, at the default matmul
    precision (the control: what a lower precision does)."""
    ids, labels = _split(batch)
    return float(batch_loss(_in_dtype(params, dtype), ids, labels,
                            _Static(config), variant=variant,
                            precision=None if dtype else "highest",
                            **_jitted_pieces()))


def logits(params, batch, config, variant="", dtype=None, every=1):
    """The logits of every `every`-th token of the batch, float32
    [B, T / every, V], by the same pieces as `loss` and under the same
    `variant` and `dtype`: what the loss's mean over tokens that all
    sit near ln V hides (tools/reference_controls.py --logits)."""
    import jax
    import jax.numpy as jnp

    ids, _ = _split(batch)
    params = _in_dtype(params, dtype)
    layer_fn = _jitted_pieces()["layer_fn"]
    with jax.default_matmul_precision("default" if dtype else "highest"):
        return jnp.stack([
            (sequence_state(params, i, _Static(config), layer_fn,
                            variant)[::every]
             @ params["head"]).astype(jnp.float32) for i in ids])
