"""Ulysses sequence parallelism: all-to-all head<->sequence resharding.

Capability anchor (SURVEY.md §2.4 "What's absent" / §5): DeepSpeed-Ulysses
pattern — activations arrive sharded on the sequence axis; an all-to-all
re-shards them on the *head* axis so each device runs full-sequence
attention for H/n heads, then a second all-to-all restores sequence
sharding.  Comm volume O(S·d/n) per device, riding ICI.

Complementary to ring attention: Ulysses needs H % n == 0 and moves
activations twice; ring keeps heads whole and pipelines K/V instead.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def ulysses_attention(q, k, v, mesh=None, axis="sp", causal=False,
                      scale=None, impl=None, block_q=None,
                      block_k=None):
    """q/k/v: [B, S, H, D] global arrays, S sharded over ``axis``.

    impl: None (auto: 'flash' on TPU, 'xla' elsewhere) — after the
    all-to-all each device holds full-sequence H/n-head blocks, which
    run through the Pallas flash kernel ('flash'/'flash_interpret') or
    the plain einsum path ('xla').

    block_q/block_k pin the kernel tiles."""
    from paddle_tpu.parallel import env as penv
    from paddle_tpu.parallel.ring_attention import _plain_attention

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

    from jax import lax
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    b, s, h, d = q.shape
    assert s % n == 0, f"seq {s} % {axis}={n} != 0"
    assert h % n == 0, f"heads {h} % {axis}={n} != 0 (use ring attention)"
    spec = P(None, axis, None, None)

    def attend(qh, kh, vh):
        if impl in ("flash", "flash_interpret"):
            from paddle_tpu.ops.pallas_kernels import flash_attention

            o = flash_attention(
                jnp.swapaxes(qh, 1, 2), jnp.swapaxes(kh, 1, 2),
                jnp.swapaxes(vh, 1, 2), causal=causal, scale=scale,
                impl="interpret" if impl == "flash_interpret"
                else "pallas", block_q=block_q, block_k=block_k)
            return jnp.swapaxes(o, 1, 2)
        return _plain_attention(qh, kh, vh, causal, scale)

    def local(ql, kl, vl):
        # [B, S/n, H, D] --all_to_all--> [B, S, H/n, D]
        def seq2head(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        def head2seq(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        qh, kh, vh = seq2head(ql), seq2head(kl), seq2head(vl)
        return head2seq(attend(qh, kh, vh))

    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
