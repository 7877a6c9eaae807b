"""Mixture-of-Experts with expert parallelism over a mesh axis.

Capability anchor (SURVEY.md §2.4 "What's absent... expert parallelism"):
Switch-Transformer-style top-1 routing.  Routing (gating, capacity,
dispatch/combine one-hots) is computed replicated — it is O(N·E) cheap —
while the expert FFNs (the FLOPs) run sharded over the 'ep' axis via
shard_map, so each device holds and computes only E/n experts.  With the
batch also sharded on 'dp', XLA partitions the dispatch einsums into the
all-to-all exchange pattern of DeepSpeed-MoE/GShard.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def switch_gating(x2d, gate_w, capacity):
    """Top-1 gating with capacity dropping.

    x2d: [N, d]; gate_w: [d, E].
    Returns (dispatch [N, E, C] 0/1, combine [N, E, C] gate-weighted,
    aux_loss scalar).
    """
    n, _ = x2d.shape
    e = gate_w.shape[1]
    logits = x2d @ gate_w                          # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)            # [N]
    gate = jnp.max(probs, axis=-1)                 # [N]
    onehot = jax.nn.one_hot(expert, e, dtype=x2d.dtype)   # [N, E]

    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot    # [N, E], 0-based
    keep = (pos < capacity) * onehot                       # [N, E]
    pos_cap = jax.nn.one_hot(pos.sum(-1).astype(jnp.int32), capacity,
                             dtype=x2d.dtype)              # [N, C]
    dispatch = keep[:, :, None] * pos_cap[:, None, :]      # [N, E, C]
    combine = dispatch * gate[:, None, None]

    # Switch load-balancing loss: E * sum_e fraction_tokens_e * mean_prob_e
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux


def moe_ffn(x, gate_w, w1, b1, w2, b2, mesh=None, axis="ep",
            capacity_factor=1.25, activation=jax.nn.gelu):
    """Switch MoE feed-forward.

    x: [..., d]; gate_w: [d, E]; w1: [E, d, dff]; b1: [E, dff];
    w2: [E, dff, d]; b2: [E, d].  Expert dim sharded over ``axis`` when a
    mesh is active.  Returns (out [..., d], aux_loss scalar).
    """
    from paddle_tpu.parallel import env as penv

    if mesh is None:
        mesh = penv.get_mesh()
    orig_shape = x.shape
    d = orig_shape[-1]
    x2d = x.reshape(-1, d)
    n = x2d.shape[0]
    e = gate_w.shape[1]
    # n and e are static shapes under jit tracing
    capacity = int(max(1, np.ceil(n / e * capacity_factor)))
    dispatch, combine, aux = switch_gating(x2d, gate_w, capacity)

    # expert inputs: [E, C, d]
    xe = jnp.einsum("nec,nd->ecd", dispatch, x2d)

    def experts(xe_l, w1_l, b1_l, w2_l, b2_l):
        h = activation(jnp.einsum("ecd,edf->ecf", xe_l, w1_l)
                       + b1_l[:, None, :])
        return jnp.einsum("ecf,efd->ecd", h, w2_l) + b2_l[:, None, :]

    if mesh is not None and axis in mesh.axis_names \
            and mesh.shape[axis] > 1 and e % mesh.shape[axis] == 0:
        from jax.sharding import PartitionSpec as P

        es = P(axis)
        ye = jax.shard_map(experts, mesh=mesh,
                           in_specs=(es, es, es, es, es), out_specs=es,
                           check_vma=False)(xe, w1, b1, w2, b2)
    else:
        ye = experts(xe, w1, b1, w2, b2)

    out = jnp.einsum("nec,ecd->nd", combine, ye)
    return out.reshape(orig_shape), aux
