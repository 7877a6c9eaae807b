"""The main path's Pallas kernels at real widths, compiled for a
DESCRIBED v5e — rehearsal (iii) of the on-chip-measurement guide, the
cheap part (about two seconds a case), kept as tests so they guard
every later PR at no chip time.  The whole-step compiles (20-40 s each)
live in tools/tpu_lowering_check.py; the stride-2 and stem convs
(~50 s each) are left out.

A compile asks the chip's whole compiler (Mosaic lowering rules, VMEM
limits, HBM fit) and runs nothing: it says nothing about results or
times.  Skipped where the topology cannot be described (no libtpu, or
another process holds its lock).
"""

import functools

import jax
import jax.numpy as jnp
import pytest

BF16 = jnp.bfloat16


def _sds(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _flash(shape, grad, n_bwd=1):
    """n_bwd: the backward's kernels, one sweep where a head's dq fits
    the VMEM it may ask for and two past that."""
    from paddle_tpu.ops.pallas_kernels import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="pallas")

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    return (bwd if grad else fwd), (_sds(shape),) * 3, \
        (n_bwd + 1 if grad else 1)


def _flash_token_major(shape, heads, grad, kv_width=None, window=None):
    """The op's entries on token-major [B, T, H*d] operands, as the
    q, k and v projections leave them: the forward kernel, and the
    one-sweep backward on the saved residuals.  kv_width: K's and V's
    width where they have fewer heads than Q (grouped KV heads).
    window: a sliding window, the kernels on their band grid."""
    from paddle_tpu.ops.pallas_kernels import (_flash_attention_bwd,
                                               _flash_attention_fwd)

    x = _sds(shape)
    kv = _sds(shape[:2] + (kv_width or shape[2],))
    call = dict(causal=True, impl="pallas", heads=heads, window=window)
    if not grad:
        return (lambda q, k, v: _flash_attention_fwd(q, k, v, **call)), \
            (x, kv, kv), 1
    lse = _sds((shape[0], heads, shape[1]), jnp.float32)
    return (lambda q, k, v, o, lse, g: _flash_attention_bwd(
        q, k, v, o, lse, g, **call)), (x, kv, kv, x, lse, x), 1


def _eva(part, shape=(1, 8192, 4096), heads=32, window=2048, chunk=16):
    """EvaByte's cell: 32 heads of 128 over 8,192 bytes, windows of
    2,048 and chunks of 16.  "pool": the summariser, forward or with
    its backward; "attention": the aggregation's forward (four causal
    flash windows, the staircase) or its backward on the saved Out and
    LSE."""
    from paddle_tpu.ops import pallas_eva

    x = _sds(shape)
    pooled = _sds((shape[0], shape[1] // chunk, shape[2]))
    vec = _sds((heads, shape[2] // heads), jnp.float32)
    geometry = (heads, window, chunk, 128 ** -0.5, False)
    if part == "pool_fwd":
        return (lambda k, v, mu, phi: pallas_eva.eva_pool_fwd_pallas(
            k, v, mu, phi, heads=heads, chunk=chunk)), (x, x, vec, vec), 1
    if part == "pool_bwd":
        return (lambda k, v, mu, phi, dks, dvs:
                pallas_eva.eva_pool_bwd_pallas(
                    k, v, mu, phi, dks, dvs, heads=heads, chunk=chunk)), \
            (x, x, vec, vec, pooled, pooled), 1
    if part == "attention_fwd":
        return (lambda *a: pallas_eva._aggregate_fwd(*a, *geometry)), \
            (x, x, x, pooled, pooled), 2
    lse = _sds((shape[0] * shape[1] // window, heads, window), jnp.float32)
    return (lambda *a: pallas_eva._aggregate_bwd(*a, *geometry)), \
        (x, x, x, pooled, pooled, x, lse, x), 2


def _attention_block(batch=64, seq=512, width=512, heads=8):
    """Projection -> flash -> projection, with its gradient: what a
    Transformer layer's attention is once the op takes the
    projections' output as it is."""
    from paddle_tpu.ops.pallas_kernels import flash_attention

    def loss(x, wq, wk, wv, wo):
        q, k, v = (jnp.dot(x, w) for w in (wq, wk, wv))
        out = flash_attention(q, k, v, causal=True, impl="pallas",
                              heads=heads)
        return jnp.dot(out, wo).astype(jnp.float32).sum()

    w = _sds((width, width))
    return jax.grad(loss, argnums=(0, 1, 2, 3, 4)), \
        (_sds((batch, seq, width)), w, w, w, w), 2


def _flash_mla(grad, shape=(1, 32, 4096)):
    """Latent attention's two head sizes: q.k 192 (128 + 64 rotary),
    v 128; the forward and the saved-residual backward kernels."""
    from paddle_tpu.ops.pallas_kernels import (_flash_attention_bwd,
                                               _flash_attention_fwd)

    b, h, t = shape
    q, v = _sds((b, h, t, 192)), _sds((b, h, t, 128))
    call = dict(causal=True, scale=0.1, impl="pallas")
    if not grad:
        return (lambda q, k, v: _flash_attention_fwd(q, k, v, **call)), \
            (q, q, v), 1
    return (lambda q, k, v, o, lse, g: _flash_attention_bwd(
        q, k, v, o, lse, g, **call)), \
        (q, q, v, v, _sds((b, h, t), jnp.float32), v), 1


def _gmm(which, rows=16384, held=8, hidden=3584, width=1024, tm=256):
    """The grouped matmuls of 8 held experts at the worst-case number
    of rows (xing4: 4096 tokens x 4 experts each; dsv2: 8192 x 6 at an
    expert width of 11 x 128, taken whole).  The row-tile axis of each
    grid is the run-time n_active.  All six call forms of a layer, at
    the five cells' shapes: test_tpu_lowering_gate.py."""
    from paddle_tpu.ops.pallas_gmm import gmm_pallas, tgmm_pallas

    m = (rows // tm + held) * tm
    maps = (_sds((m // tm,), jnp.int32), _sds((1,), jnp.int32))
    if which == "fwd":
        return (lambda x, w, tg, na: gmm_pallas(x, w, tg, na, tm)), \
            (_sds((m, hidden)), _sds((held, hidden, width))) + maps, 1
    if which == "dx":
        return (lambda g, w, tg, na: gmm_pallas(
            g, w, tg, na, tm, transpose_rhs=True)), \
            (_sds((m, width)), _sds((held, hidden, width))) + maps, 1
    return (lambda x, g, tg, na: tgmm_pallas(x, g, tg, na, tm, held)), \
        (_sds((m, hidden)), _sds((m, width))) + maps, 1


DSV2_GMM = dict(rows=49152, hidden=2048, width=1408)
# ling3: 32,768 pairs are the layout's 34,816 rows, 136 tiles
LING3_GMM = dict(rows=32768, hidden=2560, width=768)
# lfm2: 8,192 tokens x 4 experts a token over 16 held experts of width
# 1,536 = 12 x 128: the layout's 36,864 rows, 144 tiles
LFM2_GMM = dict(rows=32768, held=16, hidden=2048, width=1536)
# solar-open2: 8,192 tokens x 8 experts a token at hidden 4,096 over 8
# held experts of width 1,280 = 10 x 128: the layout's 67,584 rows
SOLAR_GMM = dict(rows=65536, hidden=4096, width=1280)
# mellum2: 16,384 tokens x 8 experts a token at hidden 2,304 = 18 x 128
# over 16 held experts of width 896 = 7 x 128: the layout's 135,168 rows
MELLUM2_GMM = dict(rows=131072, held=16, hidden=2304, width=896)


def _decode(head_dim, head_pack, batch=64, heads=8, page_size=128,
            max_pages=4):
    from paddle_tpu.ops.pallas_kernels import flash_decode

    def fn(q, kp, vp, tables, lens):
        return flash_decode(q, kp, vp, tables, lens, impl="pallas",
                            head_pack=head_pack)

    pool = _sds((batch * max_pages + 1, heads, page_size, head_dim))
    return fn, (_sds((batch, heads, head_dim)), pool, pool,
                _sds((batch, max_pages), jnp.int32),
                _sds((batch,), jnp.int32)), 1


def _conv(bn, n=128, hw=56, cin=64, cout=64):
    from paddle_tpu.ops.pallas_conv import conv2d_bn_act, conv2d_epilogue

    x, w = _sds((n, hw, hw, cin)), _sds((cout, cin, 3, 3))
    res, vec = _sds((n, hw, hw, cout)), _sds((cout,), jnp.float32)
    if bn:
        return (lambda x, w, g, b, res: conv2d_bn_act(
            x, w, g, b, None, res, paddings=(1, 1), act="relu",
            impl="pallas")), (x, w, vec, vec, res), 2
    return (lambda x, w, b, res: conv2d_epilogue(
        x, w, b, res, paddings=(1, 1), act="relu",
        impl="pallas")), (x, w, vec, res), 1


def _fc(m=16384, k=512, n=2048):
    from paddle_tpu.ops.epilogue import fc_epilogue

    return (lambda x, w, b: fc_epilogue(x, w, b, act="relu",
                                        impl="pallas")), (
        _sds((m, k)), _sds((k, n)), _sds((n,), jnp.float32)), 1


def _ssd(grad, b=1, t=8192, h=64, p=64, n=128, chunk=256):
    """The state-space scan of granite-4.0-h-micro's mixer at the
    cell's size: 64 heads of 64, state 128, 32 chunks of 256."""
    from paddle_tpu.ops.pallas_ssd import ssd_bwd_pallas, ssd_fwd_pallas

    f32 = jnp.float32
    x, bc = _sds((b, t, h * p)), _sds((b, t, n))
    ins = (x, _sds((b, t, h), f32), _sds((h,), f32), bc, bc,
           _sds((h,), f32))
    if not grad:
        return (lambda *a: ssd_fwd_pallas(*a, chunk=chunk)), ins, 1
    return (lambda *a: ssd_bwd_pallas(*a, chunk=chunk)), \
        ins + (_sds((b, t // chunk, h * p, n), f32), x), 1


def _kda(grad, b=1, t=4096, h=32, d=128, chunk=64, block_chunks=4,
         bounded=True):
    """The delta-rule scan of ling-3.0-flash-vl's KDA mixer at the
    cell's size: 32 heads of 128, 16 blocks of 4 chunks of 64; and of
    solar-open2-250b's, 8 held heads over 8,192 tokens on the path
    that is exact for an unbounded decay (the diagonal blocks' pairs
    by levels: four more products a chunk, sublane rolls)."""
    from paddle_tpu.ops.pallas_kda import kda_bwd_pallas, kda_fwd_pallas

    f32 = jnp.float32
    x = _sds((b, t, h * d))
    ins = (x, x, x, _sds((b, t, h * d), f32), _sds((b, t, h), f32))
    sizes = dict(chunk=chunk, block_chunks=block_chunks, bounded=bounded)
    if not grad:
        return (lambda *a: kda_fwd_pallas(*a, **sizes)), ins, 1
    block = chunk * block_chunks
    return (lambda *a: kda_bwd_pallas(*a, **sizes)), ins + (
        _sds((b, t // block, h * d, d), f32),
        _sds((b, h, t // block, chunk, block), f32), x), 1


def _conv1d(grad, t, c, bias):
    """The mixers' short convolution at a cell's size (K 4, SiLU):
    granite-4.0-h-micro 1 x 8,192 x 4,352 with a bias,
    ling-3.0-flash-vl 1 x 4,096 x 4,096 without."""
    from paddle_tpu.ops.pallas_conv1d import (conv1d_bwd_pallas,
                                              conv1d_fwd_pallas)

    f32 = jnp.float32
    x = _sds((1, t, c))
    ins = (x, _sds((c, 4), f32), _sds((c,), f32) if bias else None)
    if not grad:
        return (lambda *a: conv1d_fwd_pallas(*a, act="silu")), ins, 1
    return (lambda x, w, b, g: conv1d_bwd_pallas(x, w, b, g, act="silu")), \
        ins + (x,), 1


def _gated_conv(grad, t=8192, c=2048, taps=3):
    """lfm2-24b-a2b's whole conv mixer between its projections: the
    kernels read the thirds of the 1 x 8,192 x 6,144 projection in
    place (three index maps forward; whole-width row tiles of 512
    backward, which write the projection's gradient as one array)."""
    from paddle_tpu.ops.pallas_conv1d import (conv1d_bwd_pallas,
                                              conv1d_fwd_pallas)

    p, w = _sds((1, t, 3 * c)), _sds((c, taps), jnp.float32)
    if not grad:
        return (lambda p, w: conv1d_fwd_pallas(
            p, w, None, act="", gated=True)), (p, w), 1
    return (lambda p, w, g: conv1d_bwd_pallas(
        p, w, None, g, act="", gated=True)), (p, w, _sds((1, t, c))), 1


def _mhc(kernel, b, t, dtype, n=4, c=3584):
    """A kernel of a hyper-connection (ops/pallas_mhc.py) at
    xing4_29b_train_s4k's streams, 1 x 4 x 4,096 x 3,584, and at twice
    the batch and the length; bfloat16 (the cell, under AMP: the
    product's weight in three bfloat16 parts) and float32 (`highest`
    dots)."""
    from paddle_tpu.ops import pallas_mhc as pm

    f32 = jnp.float32
    assert pm.token_block(kernel, n, t, c, jnp.dtype(dtype).itemsize)
    x, y = _sds((b, n, t, c), dtype), _sds((b, t, c), dtype)
    if kernel.startswith("post"):
        coef = _sds((b, t, n * n + n), f32)
        if kernel == "post_fwd":
            return pm.mhc_post_fwd_pallas, (x, y, coef), 1
        return pm.mhc_post_bwd_pallas, (x, y, coef, x), 1
    width = 2 * n + n * n
    params = (_sds((n * c,), f32), _sds((n * c, width), f32),
              _sds((3,), f32), _sds((width,), f32))
    rounds = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0))
    if kernel == "pre_fwd":
        return functools.partial(pm.mhc_pre_fwd_pallas, **rounds), \
            (x,) + params, 1
    return functools.partial(pm.mhc_pre_bwd_pallas, **rounds), \
        (x,) + params + (y, _sds((b, n, t), f32),
                         _sds((b, n, n, t), f32)), 1


def _rotary(shape, heads, pairing, rotary_dim, grad, dtype=BF16):
    """pt_rotary (ops/pallas_rotary.py) through the op's compute, its
    tables with it, on a projection [B, T, H d] as it comes; `grad`:
    jax.vjp of the op, the same kernel at the negative angle."""
    from paddle_tpu.core.registry import get_op_def

    op = get_op_def("rotary_embedding")
    attrs = dict(op.attrs, pairing=pairing, rotary_dim=rotary_dim,
                 n_head=heads, impl="pallas", factor=4.0, mscale=1.2)

    def fwd(x):
        return op.compute({"X": x}, attrs)["Out"]

    if not grad:
        return fwd, (_sds(shape, dtype),), 1
    return (lambda x, g: jax.vjp(fwd, x)[1](g)[0]), \
        (_sds(shape, dtype),) * 2, 1


def _mellum2_attention_inputs(kernel):
    """A window layer of mellum2_12b_train_s16k from the normed stream
    to the flash call: the three projections, q and k turned, the
    windowed forward kernel.  kernel: the rotary op on the projections
    as they come (pt_rotary); not: the parent's form, a reshape to
    [B, T, H, 128] round the XLA graph."""
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.ops.pallas_kernels import _flash_attention_fwd

    t, c, heads, kv_heads, d = 16384, 2304, 32, 4, 128
    op = get_op_def("rotary_embedding")

    def turned(x, n):
        attrs = op.canonical_attrs(dict(
            pairing="halves", n_head=n if kernel else 0,
            impl="pallas" if kernel else "xla"))
        if not kernel:
            x = x.reshape(1, t, n, d)
        return op.compute({"X": x}, attrs)["Out"].reshape(1, t, n * d)

    def layer(u, wq, wk, wv):
        return _flash_attention_fwd(
            turned(jnp.dot(u, wq), heads), turned(jnp.dot(u, wk), kv_heads),
            jnp.dot(u, wv), causal=True, impl="pallas", heads=heads,
            window=1024)

    return layer, (_sds((1, t, c)), _sds((c, heads * d)),
                   _sds((c, kv_heads * d)), _sds((c, kv_heads * d))), \
        3 if kernel else 1


def _moe_combine(n, k, c, rows, f32_rows):
    """A layer's combine by token at a cell's size, 8 experts held: the
    forward's (bf16 rows, gated) or d x's (float32 rows), the plan made
    in the same program."""
    from paddle_tpu.ops import pallas_moe_combine as pc

    pairs = _sds((n, k), jnp.int32)

    def call(a, dest, slot, gate=None):
        plan = pc.combine_plan(dest, slot, 8, c, rows)
        return pc.moe_combine_pallas(a, plan, gate, out_dtype=BF16)

    if f32_rows:
        return call, (_sds((rows, c), jnp.float32), pairs, pairs), 1
    return call, (_sds((rows, c)), pairs, pairs,
                  _sds((n, k), jnp.float32)), 1


# shape, heads, pairing, rotary_dim: q and k of mellum2 (16,384 tokens,
# 32 / 4 heads of 128), ouro (16 of 128), lfm2 (32 of 64: two heads a
# lane tile), xing4 and dsv2 (32 and 2 x 16 heads of 192, the last 64
# interleaved); then what no cell runs: the other pairing at each head
# size, a part of a 128-lane head in halves, float32
ROTARY = [((1, 16384, 4096), 32, "halves", 0),
          ((1, 16384, 512), 4, "halves", 0),
          ((1, 4096, 2048), 16, "halves", 0),
          ((1, 8192, 2048), 32, "halves", 0),
          ((1, 4096, 6144), 32, "interleaved", 64),
          ((2, 4096, 3072), 16, "interleaved", 64),
          ((1, 4096, 6144), 32, "halves", 64),
          ((1, 4096, 2048), 16, "halves", 64),
          ((1, 4096, 2048), 16, "interleaved", 0),
          ((1, 8192, 2048), 32, "interleaved", 0)]

CASES = {
    **{"rotary_%s_%s_%s_h%d_rd%d%s" % (
        ("bwd" if grad else "fwd", "x".join(map(str, shape)), pairing,
         heads, rd, "" if dtype is BF16 else "_float32")):
       (lambda shape=shape, heads=heads, pairing=pairing, rd=rd, grad=grad,
        dtype=dtype: _rotary(shape, heads, pairing, rd, grad, dtype))
       for shape, heads, pairing, rd in ROTARY for grad in (False, True)
       for dtype in ((BF16, jnp.float32) if shape[1:] == (4096, 2048)
                     else (BF16,))},
    # float32 streams leave mhc_pre's backward to XLA at this width (its
    # blocks pass the 40 MiB a kernel allows itself): no kernel to compile
    **{"mhc_%s_%dx4x%dx3584_%s" % (kernel, b, t, jnp.dtype(dtype).name):
       (lambda kernel=kernel, b=b, t=t, dtype=dtype: _mhc(kernel, b, t,
                                                          dtype))
       for kernel in ("post_fwd", "post_bwd", "pre_fwd", "pre_bwd")
       for b, t, dtype in ((1, 4096, BF16), (1, 4096, jnp.float32),
                           (2, 8192, BF16))
       if (kernel, dtype) != ("pre_bwd", jnp.float32)},
    # ling3, xing4, dsv2: tokens, pairs a token, width, the layout's rows
    **{"moe_combine_%s_%dx%dx%d_rows%d" % (
        ("dx" if f32 else "fwd",) + shape):
       (lambda shape=shape, f32=f32: _moe_combine(*shape, f32))
       # and 32 sequences of ling3's: a grid step reads its own block
       # of the plan, so the tokens of a call are not bounded by SMEM
       # solar-open2: 8,192 tokens x 8 pairs at hidden 4,096
       for shape in ((4096, 8, 2560, 34816), (4096, 4, 3584, 18432),
                     (8192, 6, 2048, 51200), (131072, 8, 2560, 1050624),
                     (8192, 8, 4096, 67584))
       for f32 in (False, True)},
    "conv1d_fwd_1x8192x4352_bias": lambda: _conv1d(False, 8192, 4352, True),
    "conv1d_bwd_1x8192x4352_bias": lambda: _conv1d(True, 8192, 4352, True),
    "conv1d_fwd_1x4096x4096": lambda: _conv1d(False, 4096, 4096, False),
    "conv1d_bwd_1x4096x4096": lambda: _conv1d(True, 4096, 4096, False),
    "conv1d_fwd_1x8192x1024": lambda: _conv1d(False, 8192, 1024, False),
    "conv1d_bwd_1x8192x1024": lambda: _conv1d(True, 8192, 1024, False),
    "conv1d_fwd_gated_1x8192x6144_k3": lambda: _gated_conv(False),
    "conv1d_bwd_gated_1x8192x6144_k3": lambda: _gated_conv(True),
    "kda_fwd_1x4096_h32_d128": lambda: _kda(False),
    "kda_bwd_1x4096_h32_d128": lambda: _kda(True),
    "kda_fwd_unbounded_1x8192_h8_d128": lambda: _kda(
        False, t=8192, h=8, bounded=False),
    "kda_bwd_unbounded_1x8192_h8_d128": lambda: _kda(
        True, t=8192, h=8, bounded=False),
    "ssd_fwd_1x8192_h64_p64_n128": lambda: _ssd(False),
    "ssd_bwd_1x8192_h64_p64_n128": lambda: _ssd(True),
    "flash_fwd_32x8x512x64": lambda: _flash((32, 8, 512, 64), False),
    "flash_bwd_32x8x512x64": lambda: _flash((32, 8, 512, 64), True),
    "flash_fwd_1x8x32768x128": lambda: _flash((1, 8, 32768, 128), False),
    "flash_bwd_1x8x32768x128": lambda: _flash((1, 8, 32768, 128), True),
    "flash_bwd_4x8x8192x64": lambda: _flash((4, 8, 8192, 64), True),
    "flash_bwd_two_sweep_1x2x131072x64": lambda: _flash(
        (1, 2, 131072, 64), True, n_bwd=2),
    # token-major, at the three tfm_base_* cells' widths (the last is
    # one chip's shard of _dp2tp2: half the heads of half the batch)
    **{"flash_%s_token_major_%s_h%d" % (
        "bwd" if grad else "fwd", "x".join(map(str, shape)), heads):
       (lambda shape=shape, heads=heads, grad=grad:
        _flash_token_major(shape, heads, grad))
       for shape, heads in (((4, 8192, 512), 8), ((64, 512, 512), 8),
                            ((64, 512, 256), 4))
       for grad in (False, True)},
    "flash_fwd_2x16384x256_token_major_d128": lambda:
        _flash_token_major((2, 16384, 256), 2, False),
    "flash_bwd_2x16384x256_token_major_d128": lambda:
        _flash_token_major((2, 16384, 256), 2, True),
    # the looped decoder's cell: 16 heads of 128, one head a lane block
    "flash_fwd_1x4096x2048_token_major_d128": lambda:
        _flash_token_major((1, 4096, 2048), 16, False),
    "flash_bwd_1x4096x2048_token_major_d128": lambda:
        _flash_token_major((1, 4096, 2048), 16, True),
    # granite-4.0-h-micro's attention layer: 32 query heads of 64 read
    # 8 KV heads in place (scale 1/64 is a static float like any other)
    "flash_fwd_1x8192x2048_token_major_kv8": lambda:
        _flash_token_major((1, 8192, 2048), 32, False, kv_width=512),
    "flash_bwd_1x8192x2048_token_major_kv8": lambda:
        _flash_token_major((1, 8192, 2048), 32, True, kv_width=512),
    # solar-open2-250b's attention layer as one tensor-parallel rank
    # holds it: 8 query heads of 128 read ONE KV head in place
    "flash_fwd_1x8192x1024_token_major_kv1_d128": lambda:
        _flash_token_major((1, 8192, 1024), 8, False, kv_width=128),
    "flash_bwd_1x8192x1024_token_major_kv1_d128": lambda:
        _flash_token_major((1, 8192, 1024), 8, True, kv_width=128),
    # mellum2-12b-a2.5b's layers at 16,384 tokens: 32 query heads of
    # 128 read 4 KV heads in place; a window layer's kernels on their
    # band grid (1,024 keys, the blocks `_default_block` gives them,
    # the whole dq zeroed and written by loops over q blocks), and the
    # full layer's at the length no cell had
    **{"flash_%s_1x16384x4096_token_major_kv4_d128%s" % (
        "bwd" if grad else "fwd", "_w1024" if window else ""):
       (lambda grad=grad, window=window: _flash_token_major(
           (1, 16384, 4096), 32, grad, kv_width=512, window=window))
       for grad in (False, True) for window in (None, 1024)},
    "attention_block_64x512x512_token_major": _attention_block,
    **{"eva_%s_1x8192x4096_w2048_c16" % part:
       (lambda part=part: _eva(part))
       for part in ("pool_fwd", "pool_bwd", "attention_fwd",
                    "attention_bwd")},
    "flash_fwd_1x32x4096_qk192_v128": lambda: _flash_mla(False),
    "flash_bwd_saved_1x32x4096_qk192_v128": lambda: _flash_mla(True),
    "gmm_fwd_8x3584x1024_rows16384": lambda: _gmm("fwd"),
    "gmm_bwd_dx_8x3584x1024_rows16384": lambda: _gmm("dx"),
    "gmm_bwd_dw_8x3584x1024_rows16384": lambda: _gmm("dw"),
    # dsv2: 49,152 pairs are the layout's 51,200 rows, 200 tiles
    "gmm_fwd_8x2048x1408_rows51200": lambda: _gmm("fwd", **DSV2_GMM),
    "gmm_bwd_dx_8x2048x1408_rows51200": lambda: _gmm("dx", **DSV2_GMM),
    "gmm_bwd_dw_8x2048x1408_rows51200": lambda: _gmm("dw", **DSV2_GMM),
    "gmm_fwd_8x2560x768_rows34816": lambda: _gmm("fwd", **LING3_GMM),
    "gmm_bwd_dx_8x2560x768_rows34816": lambda: _gmm("dx", **LING3_GMM),
    "gmm_bwd_dw_8x2560x768_rows34816": lambda: _gmm("dw", **LING3_GMM),
    "gmm_fwd_16x2048x1536_rows36864": lambda: _gmm("fwd", **LFM2_GMM),
    "gmm_bwd_dx_16x2048x1536_rows36864": lambda: _gmm("dx", **LFM2_GMM),
    "gmm_bwd_dw_16x2048x1536_rows36864": lambda: _gmm("dw", **LFM2_GMM),
    "gmm_fwd_8x4096x1280_rows67584": lambda: _gmm("fwd", **SOLAR_GMM),
    "gmm_bwd_dx_8x4096x1280_rows67584": lambda: _gmm("dx", **SOLAR_GMM),
    "gmm_bwd_dw_8x4096x1280_rows67584": lambda: _gmm("dw", **SOLAR_GMM),
    "gmm_fwd_16x2304x896_rows135168": lambda: _gmm("fwd", **MELLUM2_GMM),
    "gmm_bwd_dx_16x2304x896_rows135168": lambda: _gmm("dx",
                                                      **MELLUM2_GMM),
    "gmm_bwd_dw_16x2304x896_rows135168": lambda: _gmm("dw",
                                                      **MELLUM2_GMM),
    "mellum2_attention_inputs_rotary_kernel":
    lambda: _mellum2_attention_inputs(True),
    "mellum2_attention_inputs_rotary_xla":
    lambda: _mellum2_attention_inputs(False),
    "flash_decode_d128_b64": lambda: _decode(128, False),
    "flash_decode_d64_headpacked_b64": lambda: _decode(64, True),
    "conv2d_epilogue_3x3_56x56x64_mb128": lambda: _conv(False),
    "conv2d_bn_act_3x3_56x56x64_mb128": lambda: _conv(True),
    "fc_epilogue_16384x512x2048": lambda: _fc(),
}


# the backward's sum of dk and dv over a KV head's group
# (pallas_kernels._sum_groups) reshapes [B, T, H d] to [.., group, d]; at
# ONE KV head of 128 the compiler makes that a rank-4 copy of each of the
# two float32 arrays (33.5 MB each), which this count sees (at 8 KV heads
# of 64 the same reshape is rank 5, which it does not).  Left as it is:
# PR 49's review took a lane-slice form of the sum out again for want of
# a traced time; the cell's trace with and without it is in PERF.md
# section 6, PR 49
GROUP_SUM_COPIES = {"flash_bwd_1x8192x1024_token_major_kv1_d128": 2,
                    # mellum2's 32 / 4 heads of 128: the same two, of
                    # [1, 16384, 4, 8, 128] in float32 (268 MB each),
                    # window or none
                    "flash_bwd_1x16384x4096_token_major_kv4_d128": 2,
                    "flash_bwd_1x16384x4096_token_major_kv4_d128_w1024": 2}
# relayouts of a [1, 16384, H 128] array between the projection and the
# flash call (gate.token_relayouts): the XLA rotary form's reshape of
# the lanes costs q and k a copy each (and float32 fusions in a layout
# of their own), which the count sees; pt_rotary turns them where they
# lie
ROTARY_RELAYOUTS = {"mellum2_attention_inputs_rotary_kernel": 0,
                    "mellum2_attention_inputs_rotary_xla": 2}
# the names a windowed call's kernels carry in the compiled module
WINDOW_KERNELS = {False: "pt_flash_win_fwd", True: "pt_flash_win_bwd_dkv"}


# the Mosaic calls of the EVA entries, by name
EVA_KERNELS = {
    "eva_pool_fwd_1x8192x4096_w2048_c16": {"pt_eva_pool_fwd": 1},
    "eva_pool_bwd_1x8192x4096_w2048_c16": {"pt_eva_pool_bwd": 1},
    "eva_attention_fwd_1x8192x4096_w2048_c16":
    {"pt_flash_fwd": 1, "pt_eva_chunk_fwd": 1},
    "eva_attention_bwd_1x8192x4096_w2048_c16":
    {"pt_flash_bwd_dkv": 1, "pt_eva_chunk_bwd": 1}}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_described_v5e(chip_gate, case):
    fn, avals, n_kernels = CASES[case]()
    exe = chip_gate.compile_for_chip(fn, avals)
    # the Pallas kernel is IN the module: a geometry or VMEM gate that
    # rerouted to the XLA form would compile too, and prove nothing
    assert exe.as_text().count(
        'custom_call_target="tpu_custom_call"') == n_kernels
    assert exe.memory_analysis().temp_size_in_bytes >= 0
    if "token_major" in case:
        # no head split or merge around the kernels
        assert chip_gate.head_layout_copies(exe.as_text()) \
            == GROUP_SUM_COPIES.get(case, 0)
    if case.startswith("eva_"):
        assert chip_gate.kernel_calls(exe.as_text()) == EVA_KERNELS[case]
        # no [T, W] or [T, T/c] score array a head, in either direction
        assert not chip_gate.arrays_of(exe.as_text(), (8192, 2048),
                                       (8192, 512), (2048, 2048))
    if case in ROTARY_RELAYOUTS:
        assert chip_gate.token_relayouts(exe.as_text(), 16384) \
            == ROTARY_RELAYOUTS[case]
    if case.startswith("flash_") and "_token_major_kv4" in case:
        want = WINDOW_KERNELS["_bwd_" in case] if case.endswith("_w1024") \
            else WINDOW_KERNELS["_bwd_" in case].replace("_win", "")
        assert chip_gate.kernel_calls(exe.as_text()) == {want: 1}
