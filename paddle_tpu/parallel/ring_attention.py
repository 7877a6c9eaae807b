"""Ring attention: sequence-parallel exact attention over the ICI ring.

Capability anchor (SURVEY.md §5 "Long-context / sequence parallelism"): the
reference's LoD machinery handled variable-length sequences but had no way
to scale sequence *length* across devices; ring attention is the TPU-native
answer (Liu et al. 2023 pattern): Q stays sharded on the sequence axis while
K/V blocks rotate around the mesh axis via collective-permute, with
flash-style online-softmax accumulation so the full [S, S] score matrix is
never materialized.

Works under jit (CompiledProgram traces it like any op) via shard_map over
the current device mesh; with no mesh or a singleton axis it degrades to
plain attention.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def _attention_block(q, k, v, bias, scale):
    """One [Sq, Sk] score block -> (unnormalized out, running max, denom).
    q: [B, H, Sq, D], k/v: [B, H, Sk, D]."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)                       # [B, H, Sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                       # [B, H, Sq]
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o, m, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Combine two online-softmax partial results."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    o = o1 * a1[..., None] + o2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return o, m, l


def _local_causal_bias(q_pos, k_pos):
    """bias[i, j] = 0 where k_pos[j] <= q_pos[i], else -inf."""
    mask = k_pos[None, :] <= q_pos[:, None]
    return jnp.where(mask, 0.0, _NEG_INF)


def ring_attention(q, k, v, mesh=None, axis="sp", causal=False,
                   scale=None, impl=None, block_q=None, block_k=None):
    """Exact attention with sequence sharded over ``axis``.

    q/k/v: [B, S, H, D] global arrays (S = full sequence).  Inside jit the
    shard_map sees per-device [B, S/n, H, D] blocks; K/V rotate n-1 times
    via lax.ppermute so every Q block attends to every K/V block while only
    ever holding one remote block — O(S/n) memory per chip, comm riding the
    ICI ring.

    impl: None (auto: 'flash' on TPU, 'xla' elsewhere), 'xla' (einsum
    per chunk — materializes the per-chunk [blk, blk] scores),
    'flash' / 'flash_interpret' (each chunk through the Pallas kernel
    via its (out, lse) mergeable summary — scores stay in VMEM even
    within a chunk, forward and backward).

    block_q/block_k: kernel tile override for the per-chunk flash
    calls — the chunk length is S/n, not S, so the kernel's
    seq-length-keyed default can land differently than a whole-seq
    call's; pin them when sweeping.
    """
    from paddle_tpu.parallel import env as penv

    if mesh is None:
        mesh = penv.get_mesh()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if mesh is None or axis not in mesh.axis_names \
            or mesh.shape[axis] == 1:
        return _plain_attention(q, k, v, causal, scale)
    if impl is None:
        from paddle_tpu.ops.pallas_kernels import _on_tpu

        impl = "flash" if _on_tpu() else "xla"

    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    seq = q.shape[1]
    assert seq % n == 0, f"seq {seq} not divisible by {axis}={n}"
    blk = seq // n
    spec = P(None, axis, None, None)
    use_flash = impl in ("flash", "flash_interpret")
    flash_impl = "interpret" if impl == "flash_interpret" else "pallas"

    def _flash_chunk(qt, kc, vc, chunk_causal):
        """One chunk through the Pallas kernel; returns the same
        unnormalized-summary triple _merge consumes: with
        (o_norm, lse) the triple (o_norm, m=lse, l=1) merges exactly
        (merge then scales o by exp(lse-m) and sums the weights)."""
        from paddle_tpu.ops.pallas_kernels import flash_attention_lse

        o, lse = flash_attention_lse(qt, kc, vc, causal=chunk_causal,
                                     scale=scale, impl=flash_impl,
                                     block_q=block_q, block_k=block_k)
        b, h, t, _d = qt.shape
        lse = lse[:, :t].reshape(b, h, t).astype(jnp.float32)
        return o.astype(jnp.float32), lse, jnp.ones_like(lse)

    def local(q_blk, k_blk, v_blk):
        # [B, blk, H, D] -> [B, H, blk, D]
        qt = jnp.swapaxes(q_blk, 1, 2)
        kt = jnp.swapaxes(k_blk, 1, 2)
        vt = jnp.swapaxes(v_blk, 1, 2)
        my = lax.axis_index(axis)
        q_pos = my * blk + jnp.arange(blk)

        perm = [(i, (i + 1) % n) for i in range(n)]

        def block_summary(src, kc, vc):
            if not use_flash:
                if causal:
                    k_pos = src * blk + jnp.arange(blk)
                    bias = _local_causal_bias(q_pos, k_pos)
                else:
                    bias = None
                return _attention_block(
                    qt.astype(jnp.float32), kc.astype(jnp.float32),
                    vc.astype(jnp.float32), bias, scale)
            if not causal:
                return _flash_chunk(qt, kc, vc, False)
            # causal: the diagonal chunk masks within itself, chunks
            # before mine are fully visible, chunks after contribute
            # nothing (empty summary)
            empty = (jnp.zeros(qt.shape, jnp.float32),
                     jnp.full(qt.shape[:-1], _NEG_INF, jnp.float32),
                     jnp.zeros(qt.shape[:-1], jnp.float32))
            return lax.cond(
                src == my,
                lambda _: _flash_chunk(qt, kc, vc, True),
                lambda _: lax.cond(
                    src < my,
                    lambda __: _flash_chunk(qt, kc, vc, False),
                    lambda __: empty, None),
                None)

        def step(carry, i):
            o, m, l, kc, vc = carry
            src = (my - i) % n          # which block kc/vc currently is
            bo, bm, bl = block_summary(src, kc, vc)
            o, m, l = _merge(o, m, l, bo, bm, bl)
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
            return (o, m, l, kc, vc), None

        o0 = jnp.zeros(qt.shape, jnp.float32)
        m0 = jnp.full(qt.shape[:-1], _NEG_INF, jnp.float32)
        l0 = jnp.zeros(qt.shape[:-1], jnp.float32)
        (o, m, l, _, _), _ = lax.scan(
            step, (o0, m0, l0, kt, vt), jnp.arange(n))
        out = (o / jnp.maximum(l[..., None], 1e-30)).astype(q_blk.dtype)
        return jnp.swapaxes(out, 1, 2)          # back to [B, blk, H, D]

    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _plain_attention(q, k, v, causal, scale):
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if causal:
        seq = q.shape[1]
        pos = jnp.arange(seq)
        s = s + _local_causal_bias(pos, pos)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
    return jnp.swapaxes(o, 1, 2)
