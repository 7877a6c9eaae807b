"""Transformer (reference model: the fluid transformer NMT config used by
tests/unittests/dist_transformer.py; BASELINE config 3 Transformer-base).

Built entirely from IR layers (matmul/softmax/layer_norm/fc) so the program
compiles to one XLA module; attention is batched [B, H, T, D/H] matmuls that
XLA tiles onto the MXU.  Sharding-friendly: the fc weights carry optional
tensor-parallel annotations set by parallel/strategies.py.
"""

from __future__ import annotations

import numpy as np

from paddle_tpu import layers
from paddle_tpu.param_attr import ParamAttr as _ParamAttr


def _w(pfx, part):
    """Deterministic weight name under a prefix (None -> auto names).
    Explicit names let a separately-built program (e.g. the KV-cache
    decode loop) share this model's trained parameters through the
    scope, the fluid ParamAttr(name=...) sharing idiom."""
    return _ParamAttr(name=f"{pfx}_{part}.w") if pfx else None


def _b(pfx, part):
    return _ParamAttr(name=f"{pfx}_{part}.b") if pfx else None


def _sub(pfx):
    """Sub-prefix builder: _sub(\"tfm_enc0\")(\"self\") -> \"tfm_enc0_self\";
    a None prefix propagates None (auto names)."""
    return (lambda s: f"{pfx}_{s}") if pfx else (lambda s: None)


def _positional_encoding(max_len, d_model, dtype="float32"):
    pos = np.arange(max_len)[:, None]
    i = np.arange(d_model)[None, :]
    angle = pos / np.power(10000.0, (2 * (i // 2)) / d_model)
    enc = np.zeros((max_len, d_model), np.float64)
    enc[:, 0::2] = np.sin(angle[:, 0::2])
    enc[:, 1::2] = np.cos(angle[:, 1::2])
    return enc.astype(dtype)


def multi_head_attention(q_in, kv_in, d_model, n_head, dropout_rate=0.0,
                         causal=False, is_test=False, seq_len_q=None,
                         seq_len_kv=None, name=None, use_flash=True,
                         pfx=None, attn_bias=None):
    """q_in: [B, Tq, D]; kv_in: [B, Tk, D].

    When attention-weight dropout is off the score+softmax+weighted-sum is
    emitted as one fused `flash_attention` op (Pallas kernel on TPU) —
    the [Tq, Tk] matrix never touches HBM.  With weight dropout on, the
    unfused composition is kept so the reference's dropout-on-weights
    semantics hold exactly.

    attn_bias: optional additive score bias broadcastable to
    [B, H, Tq, Tk] (e.g. a [B, 1, 1, Tk] source-padding mask, the
    reference NMT decoders' LoD-derived attention bias); forces the
    unfused composition.
    """
    tq = q_in.shape[1]
    tk = kv_in.shape[1]
    head_dim = d_model // n_head
    q = layers.fc(q_in, d_model, num_flatten_dims=2, bias_attr=False,
                  param_attr=_w(pfx, "q"))
    k = layers.fc(kv_in, d_model, num_flatten_dims=2, bias_attr=False,
                  param_attr=_w(pfx, "k"))
    v = layers.fc(kv_in, d_model, num_flatten_dims=2, bias_attr=False,
                  param_attr=_w(pfx, "v"))

    weight_dropout = bool(dropout_rate) and not is_test
    if use_flash and not weight_dropout and attn_bias is None:
        # the fc outputs as they are, [B, T, H*hd], and Out as the
        # output fc takes it: the kernels address the heads in place
        out = layers.flash_attention(q, k, v, causal=causal,
                                     n_head=n_head)
    else:
        # the unfused composition needs [B, H, Tq, Tk]
        q = _split_heads(q, tq, n_head, head_dim)
        k = _split_heads(k, tk, n_head, head_dim)
        v = _split_heads(v, tk, n_head, head_dim)
        attn = layers.matmul(q, k, transpose_y=True,
                             alpha=float(head_dim) ** -0.5)  # [B,H,Tq,Tk]
        if causal:
            # bottom-right aligned (query i attends keys <= i + Tk - Tq),
            # matching the flash kernel's q_off convention
            mask = np.triu(np.full((tq, tk), -1e9, np.float32),
                           k=1 + tk - tq)
            mask_var = layers.assign(mask.reshape(1, 1, tq, tk))
            attn = layers.elementwise_add(attn, mask_var)
        if attn_bias is not None:
            attn = layers.elementwise_add(attn, attn_bias)
        weights = layers.softmax(attn)
        if weight_dropout:
            weights = layers.dropout(
                weights, dropout_rate,
                dropout_implementation="upscale_in_train")
        out = layers.matmul(weights, v)  # [B,H,Tq,hd]
        out = layers.transpose(out, [0, 2, 1, 3])
        out = layers.reshape(out, [-1, tq, d_model])
    return layers.fc(out, d_model, num_flatten_dims=2, bias_attr=False,
                     param_attr=_w(pfx, "out"))


def _ffn(x, d_model, d_inner, dropout_rate, is_test, pfx=None):
    h = layers.fc(x, d_inner, num_flatten_dims=2, act="relu",
                  param_attr=_w(pfx, "fc1"), bias_attr=_b(pfx, "fc1"))
    if dropout_rate and not is_test:
        h = layers.dropout(h, dropout_rate,
                           dropout_implementation="upscale_in_train")
    return layers.fc(h, d_model, num_flatten_dims=2,
                     param_attr=_w(pfx, "fc2"), bias_attr=_b(pfx, "fc2"))


def _residual_norm(x, sub, dropout_rate, is_test, pfx=None):
    if dropout_rate and not is_test:
        sub = layers.dropout(sub, dropout_rate,
                             dropout_implementation="upscale_in_train")
    return layers.layer_norm(
        layers.elementwise_add(x, sub), begin_norm_axis=2,
        param_attr=(_ParamAttr(name=f"{pfx}.scale") if pfx else None),
        bias_attr=(_ParamAttr(name=f"{pfx}.bias") if pfx else None))


def encoder_layer(x, d_model, n_head, d_inner, dropout_rate=0.1,
                  is_test=False, pfx=None, attn_bias=None):
    sp = _sub(pfx)
    attn = multi_head_attention(x, x, d_model, n_head, dropout_rate,
                                is_test=is_test, pfx=sp("self"),
                                attn_bias=attn_bias)
    x = _residual_norm(x, attn, dropout_rate, is_test, pfx=sp("ln1"))
    ffn = _ffn(x, d_model, d_inner, dropout_rate, is_test,
               pfx=sp("ffn"))
    return _residual_norm(x, ffn, dropout_rate, is_test, pfx=sp("ln2"))


def decoder_layer(x, enc_out, d_model, n_head, d_inner, dropout_rate=0.1,
                  is_test=False, pfx=None, cross_attn_bias=None):
    sp = _sub(pfx)
    self_attn = multi_head_attention(x, x, d_model, n_head, dropout_rate,
                                     causal=True, is_test=is_test,
                                     pfx=sp("self"))
    x = _residual_norm(x, self_attn, dropout_rate, is_test,
                       pfx=sp("ln1"))
    cross = multi_head_attention(x, enc_out, d_model, n_head,
                                 dropout_rate, is_test=is_test,
                                 pfx=sp("cross"),
                                 attn_bias=cross_attn_bias)
    x = _residual_norm(x, cross, dropout_rate, is_test, pfx=sp("ln2"))
    ffn = _ffn(x, d_model, d_inner, dropout_rate, is_test,
               pfx=sp("ffn"))
    return _residual_norm(x, ffn, dropout_rate, is_test, pfx=sp("ln3"))


def _embed(ids, vocab_size, d_model, max_len, dropout_rate, is_test,
           scale_embedding=True, pfx=None):
    emb = layers.embedding(
        ids, size=[vocab_size, d_model],
        param_attr=(_ParamAttr(name=f"{pfx}.w") if pfx else None))
    if scale_embedding:
        emb = layers.scale(emb, scale=float(d_model) ** 0.5)
    pe = layers.assign(
        _positional_encoding(max_len, d_model)[None, :, :])
    emb = layers.elementwise_add(emb, pe)
    if dropout_rate and not is_test:
        emb = layers.dropout(emb, dropout_rate,
                             dropout_implementation="upscale_in_train")
    return emb


def transformer_encoder_model(
    vocab_size=32000, max_len=256, d_model=512, n_head=8, d_inner=2048,
    n_layer=6, dropout_rate=0.1, is_test=False, tie_embeddings=False,
    label_smooth_eps=0.0, param_prefix=None,
):
    """Encoder-only LM-style transformer: next-token prediction over a
    single stream (the flagship shape for bench/graft entry; the NMT
    encoder-decoder variant is `transformer_nmt_model`).  param_prefix:
    deterministic parameter names so `transformer_lm_sample_decode`
    shares the trained weights by name."""
    p = param_prefix
    sp = _sub(p)
    src = layers.data("src_ids", shape=[max_len, 1], dtype="int64")
    label = layers.data("tgt_label", shape=[max_len, 1], dtype="int64")
    x = _embed(src, vocab_size, d_model, max_len, dropout_rate, is_test,
               pfx=sp("emb"))
    # causal self-attention stack
    for li in range(n_layer):
        lp = _sub(sp(f"l{li}"))
        attn = multi_head_attention(x, x, d_model, n_head, dropout_rate,
                                    causal=True, is_test=is_test,
                                    pfx=lp("self"))
        x = _residual_norm(x, attn, dropout_rate, is_test,
                           pfx=lp("ln1"))
        ffn = _ffn(x, d_model, d_inner, dropout_rate, is_test,
                   pfx=lp("ffn"))
        x = _residual_norm(x, ffn, dropout_rate, is_test, pfx=lp("ln2"))
    logits = layers.fc(x, vocab_size, num_flatten_dims=2,
                       bias_attr=False, param_attr=_w(p, "out_fc"))
    if label_smooth_eps:
        one_hot = layers.one_hot(label, vocab_size)
        smoothed = layers.label_smooth(one_hot, epsilon=label_smooth_eps)
        loss = layers.mean(layers.softmax_with_cross_entropy(
            logits, smoothed, soft_label=True))
    else:
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits, label))
    return {"src_ids": src, "tgt_label": label, "logits": logits,
            "loss": loss}


def _src_pad_bias(src, max_len, pad_id):
    """[B, T, 1] int64 ids -> [B, 1, 1, T] additive attention bias:
    -1e9 on padding positions, 0 elsewhere (the reference NMT models'
    LoD-derived src_slf/src_attn bias, e.g.
    tests/unittests/dist_transformer.py pad-mask construction)."""
    ids = layers.reshape(src, [-1, max_len])
    pad = layers.fill_constant([1], "int64", float(pad_id))
    is_pad = layers.cast(layers.equal(ids, pad), "float32")
    return layers.reshape(layers.scale(is_pad, scale=-1e9),
                          [-1, 1, 1, max_len])


def transformer_nmt_model(
    src_vocab_size=32000, tgt_vocab_size=32000, max_len=256, d_model=512,
    n_head=8, d_inner=2048, n_layer=6, dropout_rate=0.1, is_test=False,
    param_prefix=None, use_src_pad_mask=False, pad_id=0,
):
    """Encoder-decoder NMT transformer (Transformer-base when defaults).

    param_prefix: when set, every parameter gets a deterministic name
    under the prefix so a separately-built program — the KV-cache
    `transformer_nmt_greedy_decode` loop — shares the trained weights
    through the scope.

    use_src_pad_mask: mask `pad_id` source positions out of encoder
    self-attention and decoder cross-attention with a -1e9 score bias,
    so variable-length padded batches don't attend padding.  Pass the
    same flag to the decode builders to keep train/decode parity."""
    p = param_prefix
    sp = _sub(p)
    src = layers.data("src_ids", shape=[max_len, 1], dtype="int64")
    tgt = layers.data("tgt_ids", shape=[max_len, 1], dtype="int64")
    label = layers.data("tgt_label", shape=[max_len, 1], dtype="int64")
    src_bias = _src_pad_bias(src, max_len, pad_id) \
        if use_src_pad_mask else None
    enc = _embed(src, src_vocab_size, d_model, max_len, dropout_rate,
                 is_test, pfx=sp("src_emb"))
    for li in range(n_layer):
        enc = encoder_layer(enc, d_model, n_head, d_inner, dropout_rate,
                            is_test, pfx=sp(f"enc{li}"),
                            attn_bias=src_bias)
    dec = _embed(tgt, tgt_vocab_size, d_model, max_len, dropout_rate,
                 is_test, pfx=sp("tgt_emb"))
    for li in range(n_layer):
        dec = decoder_layer(dec, enc, d_model, n_head, d_inner,
                            dropout_rate, is_test, pfx=sp(f"dec{li}"),
                            cross_attn_bias=src_bias)
    logits = layers.fc(dec, tgt_vocab_size, num_flatten_dims=2,
                       bias_attr=False, param_attr=_w(p, "out_fc"))
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
    return {"src_ids": src, "tgt_ids": tgt, "tgt_label": label,
            "logits": logits, "loss": loss}


def _split_heads(x, t, n_head, head_dim):
    x = layers.reshape(x, [-1, t, n_head, head_dim])
    return layers.transpose(x, [0, 2, 1, 3])          # [B, H, T, hd]


def _decode_encoder(p, src_vocab_size, max_len, d_model, n_head,
                    d_inner, n_layer, use_src_pad_mask=False, pad_id=0):
    """Encoder pass for the decode builders + per-layer cross-attention
    K/V, computed ONCE outside the decode loop (the KV-cache trick's
    encoder half) with the weight names the training build gave these
    fc's.  Returns (src data var, [(enc_k, enc_v)] per layer,
    each [B, H, Tsrc, hd], src_bias [B, 1, 1, Tsrc] or None)."""
    hd = d_model // n_head
    src = layers.data("src_ids", shape=[max_len, 1], dtype="int64")
    src_bias = _src_pad_bias(src, max_len, pad_id) \
        if use_src_pad_mask else None
    enc = _embed(src, src_vocab_size, d_model, max_len, 0.0, True,
                 pfx=f"{p}_src_emb")
    for li in range(n_layer):
        enc = encoder_layer(enc, d_model, n_head, d_inner, 0.0, True,
                            pfx=f"{p}_enc{li}", attn_bias=src_bias)
    cross_kv = []
    for li in range(n_layer):
        ck = layers.fc(enc, d_model, num_flatten_dims=2,
                       bias_attr=False,
                       param_attr=_w(f"{p}_dec{li}_cross", "k"))
        cv = layers.fc(enc, d_model, num_flatten_dims=2,
                       bias_attr=False,
                       param_attr=_w(f"{p}_dec{li}_cross", "v"))
        cross_kv.append((_split_heads(ck, max_len, n_head, hd),
                         _split_heads(cv, max_len, n_head, hd)))
    return src, cross_kv, src_bias


def _cache_attention(q, kc, vc, pos, kpos, decode_len, n_head, hd):
    """Single-query attention against a [T, N, D] cache: positions
    beyond the current step hold zeros and are masked off."""
    q_h = _split_heads(q, 1, n_head, hd)                  # [N, H, 1, hd]
    ck = layers.transpose(layers.reshape(
        kc, [decode_len, -1, n_head, hd]), [1, 2, 0, 3])
    cv = layers.transpose(layers.reshape(
        vc, [decode_len, -1, n_head, hd]), [1, 2, 0, 3])
    s = layers.matmul(q_h, ck, transpose_y=True,
                      alpha=float(hd) ** -0.5)            # [N, H, 1, T]
    valid = layers.cast(layers.less_equal(kpos, pos), "float32")
    s = layers.elementwise_add(s, layers.reshape(
        layers.scale(valid, scale=1e9, bias=-1e9),
        [1, 1, 1, decode_len]))
    o = layers.matmul(layers.softmax(s), cv)              # [N, H, 1, hd]
    return layers.reshape(layers.transpose(o, [0, 2, 1, 3]),
                          [-1, 1, hd * n_head])


def _decode_step(cur, pos, caches, cross_kv, p, tgt_vocab_size,
                 decode_len, d_model, n_head, d_inner, n_layer, kpos,
                 pe, src_bias=None):
    """One decoder-stack step on the current token(s): embeds `cur`
    ([N, 1, 1] ids), writes each layer's new K/V into its cache at
    `pos`, attends cache + precomputed cross K/V.  Returns
    ([N, 1, V] logits, [(kc, vc)] updated caches — the caller registers
    them as memory updates, possibly after beam reordering).  N is B
    for greedy decode, B*beam for beam search — every op is row-wise
    in N, so the same step serves both."""
    hd = d_model // n_head
    x = layers.embedding(
        cur, size=[tgt_vocab_size, d_model],
        param_attr=_ParamAttr(name=f"{p}_tgt_emb.w"))     # [N, 1, D]
    x = layers.scale(x, scale=float(d_model) ** 0.5)
    pe_t = layers.gather(pe, pos)                         # [1, D]
    x = layers.elementwise_add(
        x, layers.reshape(pe_t, [1, 1, d_model]))
    new_caches = []
    for li in range(n_layer):
        sp = f"{p}_dec{li}"
        kc_pre, vc_pre = caches[li]
        # self-attention: new token's q against the cache
        q = layers.fc(x, d_model, num_flatten_dims=2, bias_attr=False,
                      param_attr=_w(f"{sp}_self", "q"))
        k = layers.fc(x, d_model, num_flatten_dims=2, bias_attr=False,
                      param_attr=_w(f"{sp}_self", "k"))
        v = layers.fc(x, d_model, num_flatten_dims=2, bias_attr=False,
                      param_attr=_w(f"{sp}_self", "v"))
        kc = layers.scatter(kc_pre, pos,
                            layers.transpose(k, [1, 0, 2]))
        vc = layers.scatter(vc_pre, pos,
                            layers.transpose(v, [1, 0, 2]))
        new_caches.append((kc, vc))
        o = _cache_attention(q, kc, vc, pos, kpos, decode_len, n_head,
                             hd)
        o = layers.fc(o, d_model, num_flatten_dims=2, bias_attr=False,
                      param_attr=_w(f"{sp}_self", "out"))
        x = _residual_norm(x, o, 0.0, True, pfx=f"{sp}_ln1")
        # cross-attention against the precomputed encoder K/V
        q2 = layers.fc(x, d_model, num_flatten_dims=2, bias_attr=False,
                       param_attr=_w(f"{sp}_cross", "q"))
        enc_k, enc_v = cross_kv[li]
        s2 = layers.matmul(_split_heads(q2, 1, n_head, hd), enc_k,
                           transpose_y=True, alpha=float(hd) ** -0.5)
        if src_bias is not None:
            s2 = layers.elementwise_add(s2, src_bias)
        o2 = layers.matmul(layers.softmax(s2), enc_v)
        o2 = layers.reshape(layers.transpose(o2, [0, 2, 1, 3]),
                            [-1, 1, d_model])
        o2 = layers.fc(o2, d_model, num_flatten_dims=2,
                       bias_attr=False,
                       param_attr=_w(f"{sp}_cross", "out"))
        x = _residual_norm(x, o2, 0.0, True, pfx=f"{sp}_ln2")
        ffn = _ffn(x, d_model, d_inner, 0.0, True, pfx=f"{sp}_ffn")
        x = _residual_norm(x, ffn, 0.0, True, pfx=f"{sp}_ln3")
    logits = layers.fc(x, tgt_vocab_size, num_flatten_dims=2,
                       bias_attr=False, param_attr=_w(p, "out_fc"))
    return logits, new_caches


def transformer_nmt_greedy_decode(
    src_vocab_size=32000, tgt_vocab_size=32000, max_len=256, d_model=512,
    n_head=8, d_inner=2048, n_layer=6, param_prefix=None,
    decode_len=32, bos_id=1, use_src_pad_mask=False, pad_id=0,
):
    """Autoregressive greedy decoding with per-layer KV caches — the
    modern TPU-native successor of the reference's RNN-era
    BeamSearchDecoder (contrib/decoder/beam_search_decoder.py:523): one
    `lax.scan` (via StaticRNN) whose carry holds the last token and the
    self-attention K/V caches, all static shapes.  Each step attends
    the single new query against the cache (O(T) per step instead of
    re-running the O(T^2) decoder stack), writes its K/V at the step
    index, and feeds the argmax token back.

    Build this in its OWN program (fresh program_guard) with the same
    `param_prefix` used for `transformer_nmt_model`: the deterministic
    parameter names make the decode program read the trained weights
    from the scope.  Do not run its startup program.

    Returns {"src_ids": data var, "out_ids": [B, decode_len, 1] int64,
    "step_logits": [B, decode_len, vocab]}.
    """
    from paddle_tpu.layers.control_flow import StaticRNN

    if not param_prefix:
        raise ValueError(
            "transformer_nmt_greedy_decode needs the param_prefix the "
            "training model was built with (weight sharing is by name)")
    p = param_prefix
    src, cross_kv, src_bias = _decode_encoder(
        p, src_vocab_size, max_len, d_model, n_head, d_inner, n_layer,
        use_src_pad_mask=use_src_pad_mask, pad_id=pad_id)
    pe = layers.assign(_positional_encoding(decode_len, d_model))
    pos_seq = layers.assign(
        np.arange(decode_len, dtype=np.int64)[:, None])   # [T, 1]
    kpos = layers.assign(np.arange(decode_len, dtype=np.int64))
    # ids stay 3-D [B, 1, 1] like the training feed: lookup_table's
    # 2-D-ids form returns [B, D] (reference semantics), which would
    # broadcast the positional add into the wrong rank
    bos = layers.fill_constant_batch_size_like(
        src, shape=[-1, 1, 1], dtype="int64", value=float(bos_id))
    cache_init = [
        (layers.fill_constant_batch_size_like(
            src, shape=[decode_len, -1, d_model], dtype="float32",
            value=0.0, output_dim_idx=1),
         layers.fill_constant_batch_size_like(
            src, shape=[decode_len, -1, d_model], dtype="float32",
            value=0.0, output_dim_idx=1))
        for _ in range(n_layer)]

    rnn = StaticRNN()
    with rnn.step():
        pos = rnn.step_input(pos_seq)                     # [1] int64
        cur = rnn.memory(init=bos)                        # [B, 1, 1]
        caches = [(rnn.memory(init=k0), rnn.memory(init=v0))
                  for k0, v0 in cache_init]               # [T, B, D]
        logits, new_caches = _decode_step(
            cur, pos, caches, cross_kv, p, tgt_vocab_size, decode_len,
            d_model, n_head, d_inner, n_layer, kpos, pe,
            src_bias=src_bias)
        for (kc_pre, vc_pre), (kc, vc) in zip(caches, new_caches):
            rnn.update_memory(kc_pre, kc)
            rnn.update_memory(vc_pre, vc)
        nxt = layers.argmax(logits, axis=-1)              # [B, 1] int64
        rnn.update_memory(cur, layers.reshape(nxt, [-1, 1, 1]))
        rnn.step_output(nxt)
        rnn.step_output(layers.reshape(logits, [-1, tgt_vocab_size]))
    ids_tm, logits_tm = rnn()            # [T, B, 1], [T, B, V]
    out_ids = layers.transpose(ids_tm, [1, 0, 2])         # [B, T, 1]
    step_logits = layers.transpose(logits_tm, [1, 0, 2])  # [B, T, V]
    return {"src_ids": src, "out_ids": out_ids,
            "step_logits": step_logits}


def transformer_nmt_beam_decode(
    src_vocab_size=32000, tgt_vocab_size=32000, max_len=256, d_model=512,
    n_head=8, d_inner=2048, n_layer=6, param_prefix=None,
    decode_len=32, beam_size=4, bos_id=1, eos_id=None,
    use_src_pad_mask=False, pad_id=0,
):
    """Beam-search decoding on the KV-cache loop (the transformer
    successor of the reference's dense `beam_search` op + RNN-era
    BeamSearchDecoder, contrib/decoder/beam_search_decoder.py:523) —
    still ONE lax.scan with static shapes.  Beams ride the batch axis
    (N = B*beam rows through the shared `_decode_step`); each step
    joint-scores [B, beam*V], takes the top `beam_size`, reorders every
    layer's K/V cache by the surviving parents with a one-hot batched
    matmul (gather-free, MXU-friendly), and `gather_tree` resolves the
    parent pointers into full sequences after the scan.

    EOS handling: once a beam emits `eos_id` its score freezes — the
    only continuation is another EOS at zero log-prob (the reference
    beam_search op's finished-hypothesis rule; no length normalization).

    Build in its own program with the training `param_prefix` (weight
    sharing by name; never run the decode startup program).  Returns
    {"src_ids", "out_ids": [B, beam, decode_len] int64 (best beam
    first), "scores": [B, beam] cumulative log-probs}.
    """
    from paddle_tpu.layers.control_flow import StaticRNN

    if not param_prefix:
        raise ValueError(
            "transformer_nmt_beam_decode needs the param_prefix the "
            "training model was built with (weight sharing is by name)")
    p = param_prefix
    K, V = beam_size, tgt_vocab_size
    src, cross_kv, src_bias = _decode_encoder(
        p, src_vocab_size, max_len, d_model, n_head, d_inner, n_layer,
        use_src_pad_mask=use_src_pad_mask, pad_id=pad_id)
    hd = d_model // n_head
    # replicate each batch row's encoder K/V across its K beams:
    # [B, H, T, hd] -> [B, K, H, T, hd] -> [B*K, H, T, hd]
    def _to_beams(t):
        t = layers.reshape(t, [-1, 1, n_head, max_len, hd])
        t = layers.expand(t, [1, K, 1, 1, 1])
        return layers.reshape(t, [-1, n_head, max_len, hd])

    cross_kv = [(_to_beams(ck), _to_beams(cv)) for ck, cv in cross_kv]
    if src_bias is not None:
        # beam rows share their batch row's mask: [B,1,1,T] -> [BK,1,1,T]
        src_bias = layers.reshape(
            layers.expand(src_bias, [1, K, 1, 1]),
            [-1, 1, 1, max_len])

    pe = layers.assign(_positional_encoding(decode_len, d_model))
    pos_seq = layers.assign(
        np.arange(decode_len, dtype=np.int64)[:, None])   # [T, 1]
    kpos = layers.assign(np.arange(decode_len, dtype=np.int64))
    # a [B*K, 1] reference var so every *K-batch init sizes off B*K
    bk_ref = layers.reshape(layers.expand(
        layers.fill_constant_batch_size_like(
            src, shape=[-1, 1], dtype="float32", value=0.0),
        [1, K]), [-1, 1])
    bos = layers.fill_constant_batch_size_like(
        bk_ref, shape=[-1, 1, 1], dtype="int64", value=float(bos_id))
    # step-0 collapse: only beam 0 live, so the K identical BOS rows
    # don't flood the first top-k with duplicates
    score_init = layers.elementwise_add(
        layers.fill_constant_batch_size_like(
            src, shape=[-1, K], dtype="float32", value=0.0),
        layers.assign(np.array(
            [[0.0] + [-1e9] * (K - 1)], np.float32)))
    cache_init = [
        (layers.fill_constant_batch_size_like(
            bk_ref, shape=[decode_len, -1, d_model], dtype="float32",
            value=0.0, output_dim_idx=1),
         layers.fill_constant_batch_size_like(
            bk_ref, shape=[decode_len, -1, d_model], dtype="float32",
            value=0.0, output_dim_idx=1))
        for _ in range(n_layer)]
    if eos_id is not None:
        # allowed continuation row for a finished beam: EOS at 0 logp
        eos_row = np.full((1, 1, V), -1e9, np.float32)
        eos_row[0, 0, eos_id] = 0.0
        eos_row = layers.assign(eos_row)

    rnn = StaticRNN()
    with rnn.step():
        pos = rnn.step_input(pos_seq)                     # [1] int64
        cur = rnn.memory(init=bos)                        # [BK, 1, 1]
        scores = rnn.memory(init=score_init)              # [B, K]
        caches = [(rnn.memory(init=k0), rnn.memory(init=v0))
                  for k0, v0 in cache_init]               # [T, BK, D]
        logits, new_caches = _decode_step(
            cur, pos, caches, cross_kv, p, tgt_vocab_size, decode_len,
            d_model, n_head, d_inner, n_layer, kpos, pe,
            src_bias=src_bias)
        # log_softmax, not log(softmax): softmax underflow would put
        # -inf in logp, and the done-mask's 0 * -inf would NaN-poison
        # topk for any finished beam
        logp = layers.log_softmax(logits)                 # [BK, 1, V]
        logp = layers.reshape(logp, [-1, K, V])           # [B, K, V]
        if eos_id is not None:
            done = layers.cast(layers.equal(
                layers.reshape(cur, [-1, K]),
                layers.fill_constant([1], "int64", eos_id)), "float32")
            d3 = layers.reshape(done, [-1, K, 1])
            logp = layers.elementwise_add(
                layers.elementwise_mul(logp, layers.scale(
                    d3, scale=-1.0, bias=1.0)),
                layers.elementwise_mul(
                    layers.expand(eos_row, [1, K, 1]), d3))
        total = layers.elementwise_add(
            logp, layers.reshape(scores, [-1, K, 1]))     # [B, K, V]
        val, idx = layers.topk(
            layers.reshape(total, [-1, K * V]), K)        # [B, K] both
        kv_const = layers.fill_constant([1], "int64", V)
        parent = layers.elementwise_floordiv(idx, kv_const)  # [B, K]
        token = layers.elementwise_mod(idx, kv_const)        # [B, K]
        rnn.update_memory(scores, val)
        rnn.update_memory(cur, layers.reshape(token, [-1, 1, 1]))
        # reorder every cache by the surviving parents: a one-hot
        # batched matmul (sel[b,k,j] picks old beam j for new beam k)
        sel = layers.one_hot(layers.reshape(parent, [-1, K, 1]), K)
        for (kc_pre, vc_pre), (kc, vc) in zip(caches, new_caches):
            for pre, upd in ((kc_pre, kc), (vc_pre, vc)):
                c = layers.reshape(layers.transpose(upd, [1, 0, 2]),
                                   [-1, K, decode_len * d_model])
                c = layers.matmul(sel, c)                 # [B, K, T*D]
                c = layers.transpose(layers.reshape(
                    c, [-1, decode_len, d_model]), [1, 0, 2])
                rnn.update_memory(pre, c)
        rnn.step_output(token)                            # [B, K]
        rnn.step_output(parent)
    tokens_tm, parents_tm = rnn()        # [T, B, K] each
    seqs = layers.gather_tree(tokens_tm, parents_tm)      # [T, B, K]
    out_ids = layers.transpose(seqs, [1, 2, 0])           # [B, K, T]
    return {"src_ids": src, "out_ids": out_ids,
            "scores": rnn.final(scores)}


def transformer_lm_sample_decode(
    vocab_size=32000, prompt_len=64, d_model=512, n_head=8,
    d_inner=2048, n_layer=6, param_prefix=None, gen_len=32,
    temperature=1.0, top_k=0, seed=0,
):
    """GPT-style generation for `transformer_encoder_model`: PREFILL
    the prompt through the causal stack once (full parallel attention,
    seeding every layer's K/V cache with the prompt rows), then one
    `lax.scan` samples `gen_len` tokens incrementally against the
    cache.  temperature=0 is greedy argmax; top_k>0 keeps only the k
    most likely tokens before sampling.  Each step's categorical draw
    folds the step position into the RNG key (`sampling_id` SeedOffset)
    so draws vary across scan iterations.

    Build in its own program with the `param_prefix` the training model
    used (weight sharing by name; never run the decode startup
    program).  Returns {"prompt_ids": data var [B, prompt_len, 1],
    "out_ids": [B, gen_len] int64 sampled continuation}.
    """
    from paddle_tpu.layers.control_flow import StaticRNN

    if not param_prefix:
        raise ValueError(
            "transformer_lm_sample_decode needs the param_prefix the "
            "training model was built with (weight sharing is by name)")
    p = param_prefix
    hd = d_model // n_head
    T = prompt_len + gen_len
    prompt = layers.data("prompt_ids", shape=[prompt_len, 1],
                         dtype="int64")

    def _lm_fcs(x, lp):
        q = layers.fc(x, d_model, num_flatten_dims=2, bias_attr=False,
                      param_attr=_w(f"{lp}_self", "q"))
        k = layers.fc(x, d_model, num_flatten_dims=2, bias_attr=False,
                      param_attr=_w(f"{lp}_self", "k"))
        v = layers.fc(x, d_model, num_flatten_dims=2, bias_attr=False,
                      param_attr=_w(f"{lp}_self", "v"))
        return q, k, v

    def _lm_tail(x, attn_out, lp):
        o = layers.fc(attn_out, d_model, num_flatten_dims=2,
                      bias_attr=False,
                      param_attr=_w(f"{lp}_self", "out"))
        x = _residual_norm(x, o, 0.0, True, pfx=f"{lp}_ln1")
        ffn = _ffn(x, d_model, d_inner, 0.0, True, pfx=f"{lp}_ffn")
        return _residual_norm(x, ffn, 0.0, True, pfx=f"{lp}_ln2")

    # ---- prefill: full causal pass over the prompt, capturing K/V ----
    x = _embed(prompt, vocab_size, d_model, prompt_len, 0.0, True,
               pfx=f"{p}_emb")
    cache_init = []
    for li in range(n_layer):
        lp = f"{p}_l{li}"
        q, k, v = _lm_fcs(x, lp)
        # seed the cache: prompt rows first, zeros for the gen rows
        zeros = layers.fill_constant_batch_size_like(
            prompt, shape=[gen_len, -1, d_model], dtype="float32",
            value=0.0, output_dim_idx=1)
        cache_init.append(
            (layers.concat([layers.transpose(k, [1, 0, 2]), zeros],
                           axis=0),
             layers.concat([layers.transpose(v, [1, 0, 2]), zeros],
                           axis=0)))                      # [T, B, D]
        attn = layers.flash_attention(
            _split_heads(q, prompt_len, n_head, hd),
            _split_heads(k, prompt_len, n_head, hd),
            _split_heads(v, prompt_len, n_head, hd), causal=True)
        attn = layers.reshape(layers.transpose(attn, [0, 2, 1, 3]),
                              [-1, prompt_len, d_model])
        x = _lm_tail(x, attn, lp)
    # only the last prompt position seeds generation: slice BEFORE the
    # [D, vocab] projection so prefill doesn't pay prompt_len times the
    # logits matmul and a [B, P, vocab] intermediate
    x_last = layers.slice(x, axes=[1], starts=[prompt_len - 1],
                          ends=[prompt_len])              # [B, 1, D]
    last = layers.fc(x_last, vocab_size, num_flatten_dims=2,
                     bias_attr=False, param_attr=_w(p, "out_fc"))

    def _pick(logits3, off):
        """[N, 1, V] logits -> [N, 1] sampled/argmax ids."""
        if temperature == 0.0:
            return layers.argmax(logits3, axis=-1)
        lg = layers.scale(logits3, scale=1.0 / float(temperature))
        if top_k:
            vals, _ = layers.topk(lg, top_k)              # [N, 1, k]
            kth = layers.slice(vals, axes=[2], starts=[top_k - 1],
                               ends=[top_k])              # [N, 1, 1]
            keep = layers.cast(layers.less_equal(kth, lg), "float32")
            lg = layers.elementwise_add(lg, layers.scale(
                keep, scale=1e9, bias=-1e9))
        probs = layers.reshape(layers.softmax(lg), [-1, vocab_size])
        out = layers.sampling_id(probs, seedoffset=off, seed=int(seed))
        return layers.reshape(out, [-1, 1])

    pe = layers.assign(_positional_encoding(T, d_model))
    pos_seq = layers.assign(
        np.arange(prompt_len, T, dtype=np.int64)[:, None])  # [G, 1]
    kpos = layers.assign(np.arange(T, dtype=np.int64))
    first = layers.reshape(_pick(last, layers.assign(
        np.array([prompt_len - 1], np.int64))), [-1, 1, 1])

    rnn = StaticRNN()
    with rnn.step():
        pos = rnn.step_input(pos_seq)                     # [1] int64
        cur = rnn.memory(init=first)                      # [B, 1, 1]
        caches = [(rnn.memory(init=k0), rnn.memory(init=v0))
                  for k0, v0 in cache_init]
        x = layers.embedding(
            cur, size=[vocab_size, d_model],
            param_attr=_ParamAttr(name=f"{p}_emb.w"))     # [B, 1, D]
        x = layers.scale(x, scale=float(d_model) ** 0.5)
        x = layers.elementwise_add(
            x, layers.reshape(layers.gather(pe, pos), [1, 1, d_model]))
        for li in range(n_layer):
            lp = f"{p}_l{li}"
            kc_pre, vc_pre = caches[li]
            q, k, v = _lm_fcs(x, lp)
            kc = layers.scatter(kc_pre, pos,
                                layers.transpose(k, [1, 0, 2]))
            vc = layers.scatter(vc_pre, pos,
                                layers.transpose(v, [1, 0, 2]))
            rnn.update_memory(kc_pre, kc)
            rnn.update_memory(vc_pre, vc)
            o = _cache_attention(q, kc, vc, pos, kpos, T, n_head, hd)
            x = _lm_tail(x, o, lp)
        logits = layers.fc(x, vocab_size, num_flatten_dims=2,
                           bias_attr=False, param_attr=_w(p, "out_fc"))
        rnn.step_output(layers.reshape(cur, [-1, 1]))     # emit, then
        nxt = _pick(logits, pos)                          # pick next
        rnn.update_memory(cur, layers.reshape(nxt, [-1, 1, 1]))
    ids_tm = rnn()                                        # [G, B, 1]
    out_ids = layers.reshape(layers.transpose(ids_tm, [1, 0, 2]),
                             [-1, gen_len])               # [B, G]
    return {"prompt_ids": prompt, "out_ids": out_ids}
