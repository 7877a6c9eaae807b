"""Plain reference of a Granite 4.0-H decoder (model_type
`granitemoehybrid`): forward pass, logits and loss in straightforward
jax.numpy, float32, matmul precision "highest", no kernels, no AMP, a
Python loop over layers.  Shares no code with paddle_tpu/ops or
models/granite_hybrid.py, and not the algorithm either: the
state-space recurrence runs TOKEN BY TOKEN (a lax.scan over t of the
two lines below), not by chunks.

Follows docs/GRANITE4_BLOCK.md equation by equation:

* h = embedding_multiplier E[ids]; every layer
      h <- h + residual_multiplier Mixer(RMSNorm(h))
      h <- h + residual_multiplier (silu(m Wgate) * (m Wup)) Wdown,
           m = RMSNorm(h)
* Mixer, `layer_types[i]` "mamba" (H heads of P, state N, one group):
      z, xBC, dt = u Wz, u Wxbc, u Wdt
      xBC <- silu(conv(xBC) + b), conv depthwise over time with K taps,
             zeros before t = 0;  x | B | C = xBC  (H P | N | N)
      Delta_t = softplus(dt_t + dt_bias), A = -exp(A_log)   per head
      S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T,  S_{-1} = 0
      y_t = S_t C_t + D x_t
      out = (RMSNorm(y silu(z)) w) Wout
* Mixer, "attention": q, k, v = u Wq, u Wk, u Wv at 32 / 8 / 8 heads,
      no position term; o = softmax_causal(q k^T attention_multiplier)
      v with K and V REPEATED to the query heads' count; out = o Wo
* logits = RMSNorm(h_L) E^T / logits_scaling; loss = mean over tokens
  of CE(logits, next id).

Memory at 8,192 tokens: attention is computed one sequence at a time
in blocks of query rows, and the head in blocks of tokens that give
their cross-entropy and are dropped.
"""

from __future__ import annotations

import functools

import numpy as np

QUERY_BLOCK = 512
TOKEN_BLOCK = 512

_MAMBA = ("in_z", "in_xbc", "in_dt", "out", "conv", "conv_bias",
          "ssm_A_log", "ssm_dt_bias", "ssm_D", "mixer_norm")
_ATTENTION = ("q", "k", "v", "o")
_EVERY = ("norm1", "norm2", "gate", "up", "down")


def layer_kinds(config):
    return list(config["layer_types"][:config["num_hidden_layers"]])


def param_names(config):
    p = config.get("param_prefix", "granite")
    names = {"emb": p + "_emb.w", "final_norm": p + "_final_norm.w",
             "layers": []}
    for i, kind in enumerate(layer_kinds(config)):
        own = _MAMBA if kind == "mamba" else _ATTENTION
        if kind == "mamba" and not config["mamba_conv_bias"]:
            own = tuple(k for k in own if k != "conv_bias")
        names["layers"].append({k: "%s_l%d_%s.w" % (p, i, k)
                                for k in own + _EVERY})
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


def causal_conv(x, w, bias):
    """x [T, C], w [C, K]: y_t = sum_k w[:, k] x_{t - (K-1) + k}."""
    import jax.numpy as jnp

    t, k = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    y = sum(padded[i:i + t] * w[:, i] for i in range(k))
    return y if bias is None else y + bias


def recurrence(x, delta, a, b, c, d, state_reset_every=0):
    """Token by token.  x [T, H, P], delta [T, H], a [H], b and c
    [T, N], d [H] -> y [T, H, P].  state_reset_every: 0, or a number of
    tokens after which the state is zeroed (a wrong model on purpose:
    what a scan that loses the state between chunks computes)."""
    import jax.numpy as jnp
    from jax import lax

    def step(s, inp):
        i, x_t, dt_t, b_t, c_t = inp
        if state_reset_every:
            s = jnp.where(i % state_reset_every == 0, 0.0, s)
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return s, jnp.einsum("hpn,n->hp", s, c_t) + d[:, None] * x_t

    t, h, p = x.shape
    _, y = lax.scan(step, jnp.zeros((h, p, b.shape[1]), x.dtype),
                    (jnp.arange(t), x, delta, b, c))
    return y


def mamba_mixer(u, lw, config, state_reset_every=0):
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    h, p, n = (config["mamba_n_heads"], config["mamba_d_head"],
               config["mamba_d_state"])
    z, dt = u @ lw["in_z"], u @ lw["in_dt"]
    xbc = jax.nn.silu(causal_conv(u @ lw["in_xbc"], lw["conv"],
                                  lw.get("conv_bias")))
    x, b, c = xbc[:, :h * p], xbc[:, h * p:h * p + n], xbc[:, h * p + n:]
    y = recurrence(x.reshape(t, h, p),
                   jax.nn.softplus(dt + lw["ssm_dt_bias"]),
                   -jnp.exp(lw["ssm_A_log"]), b, c, lw["ssm_D"],
                   state_reset_every)
    y = rms_norm(y.reshape(t, h * p) * jax.nn.silu(z), lw["mixer_norm"],
                 config["rms_norm_eps"])
    return y @ lw["out"]


def attention(u, lw, config):
    """Causal self-attention of one sequence, u [T, C] -> [T, C]: K and
    V repeated to the query heads' count, the scores a block of query
    rows at a time."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    d = config["hidden_size"] // heads
    q = (u @ lw["q"]).reshape(t, heads, d)
    k = jnp.repeat((u @ lw["k"]).reshape(t, kv_heads, d),
                   heads // kv_heads, axis=1)
    v = jnp.repeat((u @ lw["v"]).reshape(t, kv_heads, d),
                   heads // kv_heads, axis=1)
    block = min(QUERY_BLOCK, t)
    outs = []
    for start in range(0, t, block):
        s = jnp.einsum("qhd,khd->hqk", q[start:start + block], k) \
            * config["attention_multiplier"]
        rows = jnp.arange(start, min(start + block, t))[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(outs, axis=0).reshape(t, heads * d) @ lw["o"]


def layer(x, lw, config, kind, state_reset_every=0):
    import jax

    eps, res = config["rms_norm_eps"], config["residual_multiplier"]
    u = rms_norm(x, lw["norm1"], eps)
    x = x + res * (mamba_mixer(u, lw, config, state_reset_every)
                   if kind == "mamba" else attention(u, lw, config))
    m = rms_norm(x, lw["norm2"], eps)
    return x + res * ((jax.nn.silu(m @ lw["gate"]) * (m @ lw["up"]))
                      @ lw["down"])


def sequence_state(params, ids, config, layer_fn=layer,
                   state_reset_every=0):
    """RMSNorm(h_L) [T, C] of ONE sequence, ids [T] int."""
    x = config["embedding_multiplier"] * params["emb"][ids]
    for lw, kind in zip(params["layers"], layer_kinds(config)):
        x = layer_fn(x, lw, config, kind, state_reset_every)
    return rms_norm(x, params["final_norm"], config["rms_norm_eps"])


def sequence_logits(params, ids, config):
    return sequence_state(params, ids, config) @ params["emb"].T \
        / config["logits_scaling"]


def cross_entropy(h, emb, labels, scaling):
    """Per-token cross-entropy [T] of logits h emb^T / scaling, a block
    of tokens at a time."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    block = min(TOKEN_BLOCK, t)
    out = []
    for start in range(0, t, block):
        logp = jax.nn.log_softmax(
            h[start:start + block] @ emb.T / scaling, axis=-1)
        out.append(-jnp.take_along_axis(
            logp, labels[start:start + block, None], axis=1)[:, 0])
    return jnp.concatenate(out)


def batch_loss(params, ids, labels, config, layer_fn=layer,
               ce_fn=cross_entropy, state_reset_every=0):
    """The loss, ids and labels [B, T] int; a function of jax arrays
    that jax.grad differentiates (the tests' gradients)."""
    import jax

    with jax.default_matmul_precision("highest"):
        return sum(
            ce_fn(sequence_state(params, i, config, layer_fn,
                                 state_reset_every),
                  params["emb"], y, config["logits_scaling"]).sum()
            for i, y in zip(ids, labels)) / ids.size


@functools.lru_cache(maxsize=None)
def _jitted_pieces():
    """`layer` and `cross_entropy` jitted each on its own: the nine
    state-space layers have the same shapes and compile ONCE."""
    import jax

    return {"layer_fn": jax.jit(layer, static_argnums=(2, 3, 4)),
            "ce_fn": jax.jit(cross_entropy, static_argnums=3)}


class _Static(dict):
    """A config dict as a static (hashable) jit argument."""

    def __hash__(self):
        import json

        return hash(json.dumps(self, sort_keys=True))


def _split(batch):
    import jax.numpy as jnp

    return tuple(jnp.asarray(np.asarray(a)[..., 0].astype(np.int32))
                 for a in batch)


def loss(params, batch, config, state_reset_every=0):
    """The loss of the batch (ids, labels), each [B, T, 1]: the same
    Python loop over layers, its pieces jitted."""
    ids, labels = _split(batch)
    return float(batch_loss(params, ids, labels, _Static(config),
                            state_reset_every=state_reset_every,
                            **_jitted_pieces()))
