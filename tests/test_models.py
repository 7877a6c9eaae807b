"""Model-zoo smoke + convergence tests (reference model: tests/book/ —
train until loss drops; tiny configs keep CPU CI fast)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu.models import (
    bert_model,
    deepfm_model,
    mnist_mlp,
    resnet,
    transformer_encoder_model,
)
from paddle_tpu.models.bert import bert_inputs_synthetic
from paddle_tpu.models.deepfm import deepfm_inputs_synthetic


def _train(loss, feeds_fn, steps=10, lr=0.01, opt=None):
    (opt or optimizer.Adam(lr)).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    losses = []
    for i in range(steps):
        (lv,) = exe.run(compiled, feed=feeds_fn(i), fetch_list=[loss])
        assert np.isfinite(lv), f"loss diverged at step {i}"
        losses.append(float(lv))
    return losses


def test_resnet_tiny_cifar_trains():
    model = resnet(depth=18, num_classes=10, image_shape=(3, 32, 32))
    rng = np.random.RandomState(0)
    img = rng.rand(8, 3, 32, 32).astype(np.float32)
    lab = rng.randint(0, 10, (8, 1)).astype(np.int64)
    losses = _train(model["loss"],
                    lambda i: {"image": img, "label": lab},
                    steps=12, lr=1e-3)
    assert losses[-1] < losses[0], losses


def test_resnet_cifar10_trains_and_benches():
    """resnet_cifar10 (reference tests/book/test_image_classification
    .py:28, the ResNet32 row of float16_benchmark.md:72-74): trains,
    and the two cifar bf16+NHWC inference programs of the lowering
    gate build and run on CPU."""
    from paddle_tpu.models.resnet import resnet_cifar10

    with pytest.raises(ValueError):
        resnet_cifar10(depth=33)
    model = resnet_cifar10(depth=8)  # 6n+2, n=1: one block per stage
    rng = np.random.RandomState(0)
    img = rng.rand(8, 3, 32, 32).astype(np.float32)
    lab = rng.randint(0, 10, (8, 1)).astype(np.int64)
    losses = _train(model["loss"],
                    lambda i: {"image": img, "label": lab},
                    steps=12, lr=1e-3)
    assert losses[-1] < losses[0], losses

    from tools import gate_programs, tpu_lowering_check

    for which in ("vgg_cifar", "rn32_cifar"):
        fn, state, feeds = tpu_lowering_check._infer(
            gate_programs, which, 16)
        _, (logits,) = fn(state, feeds)
        assert logits.shape == (16, 10)
        assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_transformer_tiny_trains():
    model = transformer_encoder_model(
        vocab_size=128, max_len=16, d_model=32, n_head=4, d_inner=64,
        n_layer=2, dropout_rate=0.0)
    rng = np.random.RandomState(0)
    src = rng.randint(0, 128, (4, 16, 1)).astype(np.int64)
    losses = _train(model["loss"],
                    lambda i: {"src_ids": src, "tgt_label": src},
                    steps=15, lr=3e-3)
    assert losses[-1] < losses[0] * 0.8, losses


def test_transformer_kv_cache_greedy_decode():
    """KV-cache autoregressive decode (one lax.scan via StaticRNN)
    equals the teacher-forced decoder run exactly, and solves the copy
    task greedily after training.  The strong check: feeding the
    decoded sequence back as teacher input must reproduce the decode
    loop's per-step logits — cache attention == full causal attention."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models.transformer import (
        transformer_nmt_greedy_decode, transformer_nmt_model)

    np.random.seed(0)
    vocab, t_len = 32, 8
    cfg = dict(d_model=32, n_head=4, d_inner=64, n_layer=2)
    m = transformer_nmt_model(
        src_vocab_size=vocab, tgt_vocab_size=vocab, max_len=t_len,
        dropout_rate=0.0, param_prefix="tfm", **cfg)
    eval_prog = fluid.default_main_program().clone(for_test=True)
    rng = np.random.RandomState(0)
    fixed = []
    for _ in range(3):
        sq = rng.randint(2, vocab, (8, t_len, 1)).astype(np.int64)
        tin = np.concatenate(
            [np.ones((8, 1, 1), np.int64), sq[:, :-1]], axis=1)
        fixed.append({"src_ids": sq, "tgt_ids": tin, "tgt_label": sq})
    losses = _train(m["loss"], lambda i: fixed[i % 3], steps=150,
                    lr=5e-3)
    assert losses[-1] < losses[0] * 0.35, (losses[0], losses[-1])

    exe = fluid.Executor(fluid.CPUPlace())
    decode_prog, decode_startup = Program(), Program()
    with program_guard(decode_prog, decode_startup):
        d = transformer_nmt_greedy_decode(
            src_vocab_size=vocab, tgt_vocab_size=vocab, max_len=t_len,
            param_prefix="tfm", decode_len=t_len, bos_id=1, **cfg)
    # decode_startup is never run: the deterministic param names make
    # the decode program read the TRAINED weights from the scope
    src = fixed[0]["src_ids"]
    out_ids, step_logits = exe.run(
        decode_prog, feed={"src_ids": src},
        fetch_list=[d["out_ids"], d["step_logits"]])
    # greedy decode solves the trained copy task
    assert (out_ids[:, :, 0] == src[:, :, 0]).mean() > 0.6

    # exactness: teacher-force the DECODED sequence through the full
    # causal decoder; per-step logits must match the cache loop's
    tin = np.concatenate(
        [np.ones((8, 1, 1), np.int64), out_ids[:, :-1]], axis=1)
    (tf_logits,) = exe.run(
        eval_prog,
        feed={"src_ids": src, "tgt_ids": tin,
              "tgt_label": np.zeros_like(src)},
        fetch_list=[m["logits"]])
    np.testing.assert_allclose(step_logits, tf_logits, atol=2e-4,
                               rtol=2e-3)


def test_transformer_src_pad_mask_truncation_equivalence():
    """use_src_pad_mask semantics: with the mask on, a source padded
    from length L to max_len produces — at the first L target
    positions (causal tgt self-attention sees only <= own position) —
    EXACTLY the logits of the same weights built at max_len=L on the
    unpadded source; without the mask the padded run differs.  The
    KV-cache greedy decode threads the same bias, so its step logits
    match the short-program decode too (advisor r4: reference NMT
    decoders mask padding via the LoD-derived attention bias)."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models.transformer import (
        transformer_nmt_greedy_decode, transformer_nmt_model)

    np.random.seed(5)
    vocab, T, L = 32, 8, 5
    cfg = dict(src_vocab_size=vocab, tgt_vocab_size=vocab,
               d_model=32, n_head=4, d_inner=64, n_layer=2,
               dropout_rate=0.0, is_test=True, param_prefix="tfpm")
    exe = fluid.Executor(fluid.CPUPlace())

    progs = {}
    for key, max_len, masked in (("pad", T, True), ("ref", L, True),
                                 ("nomask", T, False)):
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            np.random.seed(5)  # identical param init draws
            m = transformer_nmt_model(max_len=max_len,
                                      use_src_pad_mask=masked, **cfg)
        progs[key] = (prog, startup, m)
    # one scope, one startup run: deterministic param names share the
    # weights across all three programs
    exe.run(progs["pad"][1])

    rng = np.random.RandomState(2)
    srcL = rng.randint(2, vocab, (4, L, 1)).astype(np.int64)
    srcT = np.concatenate(
        [srcL, np.zeros((4, T - L, 1), np.int64)], axis=1)  # 0 = pad
    tgtT = rng.randint(2, vocab, (4, T, 1)).astype(np.int64)
    tgtT[:, 0] = 1

    def logits(key, src, tgt):
        prog, _, m = progs[key]
        (lg,) = exe.run(prog, feed={"src_ids": src, "tgt_ids": tgt,
                                    "tgt_label": np.zeros_like(tgt)},
                        fetch_list=[m["logits"]])
        return lg

    lg_pad = logits("pad", srcT, tgtT)
    lg_ref = logits("ref", srcL, tgtT[:, :L])
    np.testing.assert_allclose(lg_pad[:, :L], lg_ref, atol=2e-5,
                               rtol=1e-4)
    lg_nomask = logits("nomask", srcT, tgtT)
    assert np.abs(lg_nomask[:, :L] - lg_ref).max() > 1e-3, \
        "unmasked padded run should differ — mask is a no-op?"

    # greedy decode threads the same bias: padded decode == short decode
    dec = {}
    for key, max_len in (("pad", T), ("ref", L)):
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            d = transformer_nmt_greedy_decode(
                src_vocab_size=vocab, tgt_vocab_size=vocab,
                max_len=max_len, d_model=32, n_head=4, d_inner=64,
                n_layer=2, param_prefix="tfpm", decode_len=6, bos_id=1,
                use_src_pad_mask=True)
        dec[key] = (prog, d)
    out_p, lg_p = exe.run(dec["pad"][0], feed={"src_ids": srcT},
                          fetch_list=[dec["pad"][1]["out_ids"],
                                      dec["pad"][1]["step_logits"]])
    out_r, lg_r = exe.run(dec["ref"][0], feed={"src_ids": srcL},
                          fetch_list=[dec["ref"][1]["out_ids"],
                                      dec["ref"][1]["step_logits"]])
    np.testing.assert_allclose(lg_p, lg_r, atol=2e-5, rtol=1e-4)
    assert (out_p == out_r).all()

    # beam decode replicates each row's mask across its beams
    # ([B,1,1,T] -> [B*K,1,1,T]): padded == short, per beam and score
    from paddle_tpu.models.transformer import transformer_nmt_beam_decode

    beams = {}
    for key, max_len in (("pad", T), ("ref", L)):
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            b = transformer_nmt_beam_decode(
                src_vocab_size=vocab, tgt_vocab_size=vocab,
                max_len=max_len, d_model=32, n_head=4, d_inner=64,
                n_layer=2, param_prefix="tfpm", decode_len=6, bos_id=1,
                beam_size=2, use_src_pad_mask=True)
        beams[key] = (prog, b)
    bo_p, sc_p = exe.run(beams["pad"][0], feed={"src_ids": srcT},
                         fetch_list=[beams["pad"][1]["out_ids"],
                                     beams["pad"][1]["scores"]])
    bo_r, sc_r = exe.run(beams["ref"][0], feed={"src_ids": srcL},
                         fetch_list=[beams["ref"][1]["out_ids"],
                                     beams["ref"][1]["scores"]])
    assert (bo_p == bo_r).all()
    np.testing.assert_allclose(sc_p, sc_r, atol=1e-4, rtol=1e-4)


def test_transformer_beam_decode():
    """Beam search on the KV-cache loop: beam=1 reproduces greedy
    exactly; beam=4 solves the trained copy task with descending
    scores; a finished beam (EOS) only continues with EOS."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models.transformer import (
        transformer_nmt_beam_decode, transformer_nmt_greedy_decode,
        transformer_nmt_model)

    np.random.seed(0)
    vocab, t_len = 16, 6
    cfg = dict(d_model=32, n_head=4, d_inner=48, n_layer=1)
    m = transformer_nmt_model(
        src_vocab_size=vocab, tgt_vocab_size=vocab, max_len=t_len,
        dropout_rate=0.0, param_prefix="tfm", **cfg)
    rng = np.random.RandomState(0)
    src = rng.randint(2, vocab, (4, t_len, 1)).astype(np.int64)
    tin = np.concatenate(
        [np.ones((4, 1, 1), np.int64), src[:, :-1]], axis=1)
    _train(m["loss"],
           lambda i: {"src_ids": src, "tgt_ids": tin, "tgt_label": src},
           steps=200, lr=5e-3)
    exe = fluid.Executor(fluid.CPUPlace())

    def build(fn, **kw):
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            d = fn(src_vocab_size=vocab, tgt_vocab_size=vocab,
                   max_len=t_len, param_prefix="tfm",
                   decode_len=t_len, bos_id=1, **cfg, **kw)
        return prog, d

    gp, g = build(transformer_nmt_greedy_decode)
    (greedy_ids,) = exe.run(gp, feed={"src_ids": src},
                            fetch_list=[g["out_ids"]])
    b1p, b1 = build(transformer_nmt_beam_decode, beam_size=1)
    b1_ids, b1_scores = exe.run(
        b1p, feed={"src_ids": src},
        fetch_list=[b1["out_ids"], b1["scores"]])
    assert (b1_ids[:, 0, :] == greedy_ids[:, :, 0]).all()
    assert np.isfinite(b1_scores).all()

    b4p, b4 = build(transformer_nmt_beam_decode, beam_size=4)
    b4_ids, b4_scores = exe.run(
        b4p, feed={"src_ids": src},
        fetch_list=[b4["out_ids"], b4["scores"]])
    # top beam solves the copy task at least as well as greedy
    assert (b4_ids[:, 0, :] == src[:, :, 0]).mean() >= \
        (greedy_ids[:, :, 0] == src[:, :, 0]).mean() - 1e-9
    # topk emits beams best-first
    assert (np.diff(b4_scores, axis=1) <= 1e-6).all()

    # EOS rule: once a beam emits eos, every later token in that beam
    # is eos.  Use a token the model PROVABLY emits — beam 0's step-1
    # token from a no-eos run — so the property check can't be vacuous
    # (before any eos is emitted the runs are identical, so the same
    # token reappears at the same step).
    eos = int(b4_ids[0, 0, 1])
    bep, be = build(transformer_nmt_beam_decode, beam_size=4,
                    eos_id=eos)
    (eos_ids,) = exe.run(bep, feed={"src_ids": src},
                         fetch_list=[be["out_ids"]])
    seen_eos = False
    for b in range(eos_ids.shape[0]):
        for k in range(eos_ids.shape[1]):
            seq = eos_ids[b, k]
            hits = np.where(seq == eos)[0]
            if len(hits):
                seen_eos = True
                assert (seq[hits[0]:] == eos).all(), (b, k, seq)
    assert seen_eos, "eos never emitted; property check was vacuous"


def _tiny_nmt_with_decode_prog(batch, vocab=16, t_len=6, steps=40):
    """Train the tiny copy NMT (param_prefix='tfm') and build its
    greedy-decode program.  Returns (exe, decode_prog, decode_outs,
    src) — shared by the mesh/export decode tests."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models.transformer import (
        transformer_nmt_greedy_decode, transformer_nmt_model)

    np.random.seed(0)
    cfg = dict(d_model=32, n_head=4, d_inner=48, n_layer=1)
    m = transformer_nmt_model(
        src_vocab_size=vocab, tgt_vocab_size=vocab, max_len=t_len,
        dropout_rate=0.0, param_prefix="tfm", **cfg)
    rng = np.random.RandomState(0)
    src = rng.randint(2, vocab, (batch, t_len, 1)).astype(np.int64)
    tin = np.concatenate(
        [np.ones((batch, 1, 1), np.int64), src[:, :-1]], axis=1)
    _train(m["loss"],
           lambda i: {"src_ids": src, "tgt_ids": tin,
                      "tgt_label": src}, steps=steps, lr=5e-3)
    exe = fluid.Executor(fluid.CPUPlace())
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        d = transformer_nmt_greedy_decode(
            src_vocab_size=vocab, tgt_vocab_size=vocab, max_len=t_len,
            param_prefix="tfm", decode_len=t_len, bos_id=1, **cfg)
    return exe, prog, d, src


def test_decode_under_data_parallel_mesh():
    """Generation scales like training: the KV-cache greedy decode
    program runs batch-sharded over the 8-device mesh and matches the
    single-device output token for token (the scan carry — token +
    caches — shards on its batch dims)."""
    exe, prog, d, src = _tiny_nmt_with_decode_prog(batch=8)
    (single,) = exe.run(fluid.CompiledProgram(prog),
                        feed={"src_ids": src},
                        fetch_list=[d["out_ids"]])
    sharded_prog = fluid.CompiledProgram(prog).with_data_parallel()
    (sharded,) = exe.run(sharded_prog, feed={"src_ids": src},
                         fetch_list=[d["out_ids"]])
    np.testing.assert_array_equal(single, sharded)


def test_decode_program_exports_and_serves(tmp_path):
    """The generator is servable: save_inference_model prunes+saves the
    decode program (including its scan sub-block), load_inference_model
    round-trips it in a fresh scope, and the inference Predictor serves
    it — all token-identical to the direct run."""
    from paddle_tpu import inference
    from paddle_tpu.core.scope import Scope, scope_guard

    exe, prog, d, src = _tiny_nmt_with_decode_prog(batch=4)
    (ref,) = exe.run(prog, feed={"src_ids": src},
                     fetch_list=[d["out_ids"]])
    dirn = str(tmp_path)
    fluid.io.save_inference_model(dirn, ["src_ids"], [d["out_ids"]],
                                  exe, main_program=prog)
    with scope_guard(Scope()):
        prog2, feeds, fetches = fluid.io.load_inference_model(dirn, exe)
        (out2,) = exe.run(prog2, feed={"src_ids": src},
                          fetch_list=fetches)
    np.testing.assert_array_equal(out2, ref)
    pred = inference.Predictor(inference.Config(dirn))
    h = pred.get_input_handle(pred.get_input_names()[0])
    h.copy_from_cpu(src)
    pred.run()
    out3 = pred.get_output_handle(
        pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_array_equal(out3, ref)


def test_transformer_lm_sample_decode():
    """GPT-style prefill + sampling loop on the encoder-only LM:
    temperature=0 greedily continues and its step-0 token equals the
    teacher-forced argmax at the prompt's last position; different
    seeds give different samples at temperature>0; top_k=1 collapses
    to greedy regardless of seed."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models.transformer import (
        transformer_encoder_model, transformer_lm_sample_decode)

    np.random.seed(0)
    vocab, t_len = 32, 8
    cfg = dict(d_model=32, n_head=4, d_inner=48, n_layer=2)
    m = transformer_encoder_model(
        vocab_size=vocab, max_len=t_len, dropout_rate=0.0,
        param_prefix="lm", **cfg)
    eval_prog = fluid.default_main_program().clone(for_test=True)
    rng = np.random.RandomState(0)
    seq = rng.randint(2, vocab, (4, t_len, 1)).astype(np.int64)
    _train(m["loss"], lambda i: {"src_ids": seq, "tgt_label": seq},
           steps=60, lr=3e-3)
    exe = fluid.Executor(fluid.CPUPlace())

    def build(**kw):
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            d = transformer_lm_sample_decode(
                vocab_size=vocab, prompt_len=t_len, param_prefix="lm",
                gen_len=4, **cfg, **kw)
        return prog, d

    gp, g = build(temperature=0.0)
    (greedy,) = exe.run(gp, feed={"prompt_ids": seq},
                        fetch_list=[g["out_ids"]])
    # the first generated token is the argmax of the training model's
    # logits at the prompt's last position
    (tf_logits,) = exe.run(eval_prog,
                           feed={"src_ids": seq,
                                 "tgt_label": np.zeros_like(seq)},
                           fetch_list=[m["logits"]])
    np.testing.assert_array_equal(greedy[:, 0],
                                  tf_logits[:, -1].argmax(-1))

    s1p, s1 = build(temperature=1.0, seed=7)
    s2p, s2 = build(temperature=1.0, seed=8)
    (samp1,) = exe.run(s1p, feed={"prompt_ids": seq},
                       fetch_list=[s1["out_ids"]])
    (samp2,) = exe.run(s2p, feed={"prompt_ids": seq},
                       fetch_list=[s2["out_ids"]])
    assert (samp1 != samp2).any(), "seeds 7/8 gave identical samples"

    k1p, k1 = build(temperature=1.0, top_k=1, seed=9)
    (topk1,) = exe.run(k1p, feed={"prompt_ids": seq},
                       fetch_list=[k1["out_ids"]])
    np.testing.assert_array_equal(topk1, greedy)

    # per-step draw variation needs a FLAT distribution (the trained
    # model above is an identity-copier, so constant rows are correct
    # for it): an untrained model's near-uniform logits must yield
    # varying tokens within a row — a traced-once RNG key would repeat
    # every step's draw and make each row constant
    up, us = Program(), Program()
    with program_guard(up, us):
        transformer_encoder_model(
            vocab_size=vocab, max_len=t_len, dropout_rate=0.0,
            param_prefix="lm_untrained", **cfg)
    exe.run(us)
    vp, v = Program(), Program()
    with program_guard(vp, v):
        dv = transformer_lm_sample_decode(
            vocab_size=vocab, prompt_len=t_len,
            param_prefix="lm_untrained", gen_len=8, temperature=3.0,
            seed=11, **cfg)
    (flat,) = exe.run(vp, feed={"prompt_ids": seq},
                      fetch_list=[dv["out_ids"]])
    assert (flat != flat[:, :1]).any(), flat


def test_bert_tiny_trains():
    model = bert_model(vocab_size=128, max_len=16, d_model=32, n_head=4,
                       d_inner=64, n_layer=2, dropout_rate=0.0)
    feeds = bert_inputs_synthetic(4, max_len=16, vocab_size=128)
    losses = _train(model["loss"], lambda i: feeds, steps=12, lr=2e-3)
    assert losses[-1] < losses[0], losses


def test_deepfm_trains():
    model = deepfm_model(num_fields=8, vocab_size=1000, embed_dim=8,
                         dense_dim=4, hidden=(32, 32))
    feeds = deepfm_inputs_synthetic(16, num_fields=8, vocab_size=1000,
                                    dense_dim=4)
    losses = _train(model["loss"], lambda i: feeds, steps=20, lr=5e-3)
    assert losses[-1] < losses[0] * 0.9, losses


def test_mlp_model_builder():
    model = mnist_mlp(hidden=(32,), img_dim=64)
    rng = np.random.RandomState(0)
    img = rng.rand(8, 64).astype(np.float32)
    lab = rng.randint(0, 10, (8, 1)).astype(np.int64)
    losses = _train(model["loss"],
                    lambda i: {"img": img, "label": lab}, steps=20,
                    lr=1e-2)
    assert losses[-1] < losses[0] * 0.7


def test_vgg16_builds_and_trains_small():
    """VGG (float16_benchmark.md headline net) builds + one train step
    decreases loss at CIFAR scale."""
    import numpy as np

    from paddle_tpu import unique_name
    from paddle_tpu.core.executor import Executor
    from paddle_tpu.core.scope import Scope, scope_guard
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models.vgg import vgg
    from paddle_tpu.optimizer import SGD

    with scope_guard(Scope()):
        np.random.seed(0)
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            with unique_name.guard():
                m = vgg(11, class_dim=10, img_shape=(3, 32, 32))
                SGD(learning_rate=0.01).minimize(m["loss"])
        exe = Executor()
        exe.run(sprog)
        feed = {"image": np.random.rand(4, 3, 32, 32).astype(np.float32),
                "label": np.random.randint(0, 10, (4, 1)).astype(np.int64)}
        losses = [float(np.ravel(exe.run(prog, feed=feed,
                                         fetch_list=[m["loss"]])[0])[0])
                  for _ in range(5)]
        assert losses[-1] < losses[0]


def test_se_resnext_builds_and_trains_small():
    """SE-ResNeXt (reference dist_se_resnext.py:49 workload): grouped-conv
    bottleneck + squeeze-excitation; tiny config trains."""
    import numpy as np

    from paddle_tpu import unique_name
    from paddle_tpu.core.executor import Executor
    from paddle_tpu.core.scope import Scope, scope_guard
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models.se_resnext import se_resnext
    from paddle_tpu.optimizer import Momentum

    with scope_guard(Scope()):
        np.random.seed(0)
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            with unique_name.guard():
                m = se_resnext(50, class_dim=10, img_shape=(3, 64, 64),
                               stage_depths=(1, 1, 1, 1))
                Momentum(learning_rate=0.01, momentum=0.9).minimize(
                    m["loss"])
        exe = Executor()
        exe.run(sprog)
        feed = {"image": np.random.rand(2, 3, 64, 64).astype(np.float32),
                "label": np.random.randint(0, 10, (2, 1)).astype(np.int64)}
        losses = [float(np.ravel(exe.run(prog, feed=feed,
                                         fetch_list=[m["loss"]])[0])[0])
                  for _ in range(5)]
        assert losses[-1] < losses[0] * 0.5
    import pytest

    with pytest.raises(ValueError):
        se_resnext(34)


def test_dlpack_interop_with_torch():
    """DLPack exchange (reference framework/dlpack_tensor.cc): torch ->
    scope -> torch round trip, zero copy protocol."""
    import numpy as np
    import torch

    from paddle_tpu.core.dlpack import from_dlpack, to_dlpack
    from paddle_tpu.core.scope import Scope, scope_guard

    with scope_guard(Scope()):
        from paddle_tpu.core.scope import global_scope

        t = torch.arange(12, dtype=torch.float32).reshape(3, 4)
        arr = from_dlpack(t)
        assert arr.shape == (3, 4)
        global_scope().var("w").set(arr)
        t2 = torch.utils.dlpack.from_dlpack(to_dlpack("w"))
        assert torch.equal(t, t2)
        # our own round trip: from_dlpack(to_dlpack(...)) must work
        arr2 = from_dlpack(to_dlpack("w"))
        assert arr2.shape == (3, 4)
        # raw capsules are rejected with a clear error
        import pytest

        with pytest.raises(TypeError, match="protocol"):
            from_dlpack(torch.utils.dlpack.to_dlpack(t))
