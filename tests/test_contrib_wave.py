"""Contrib tail (SURVEY.md §2.7 contrib/ row): analysis tools, AdamW-style
decoupled decay, Trainer/Inferencer, readers, QuantizeTranspiler facade,
basic RNN layers, beam-search decoder.

Reference models: python/paddle/fluid/contrib/{memory_usage_calc,
op_frequence, model_stat, extend_optimizer, trainer, inferencer, reader,
quantize, layers/rnn_impl, decoder/beam_search_decoder}.py
"""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, unique_name
from paddle_tpu.core.executor import Executor
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.optimizer import SGD, Adam


def _mlp_program():
    prog, sprog = Program(), Program()
    with program_guard(prog, sprog):
        x = layers.data(name="x", shape=[8], dtype="float32")
        h = layers.fc(x, size=16, act="relu")
        y = layers.fc(h, size=1)
        label = layers.data(name="label", shape=[1], dtype="float32")
        loss = layers.mean(layers.square_error_cost(y, label))
    return prog, sprog, loss


# -------------------------------------------------------- analysis tools

def test_memory_usage():
    from paddle_tpu.contrib import memory_usage

    prog, _, _ = _mlp_program()
    lo, hi, unit = memory_usage(prog, batch_size=32)
    assert 0 < lo < hi and unit in ("B", "KB", "MB")
    with pytest.raises(ValueError):
        memory_usage(prog, batch_size=0)
    with pytest.raises(TypeError):
        memory_usage("not-a-program", 1)


def test_op_freq_statistic():
    from paddle_tpu.contrib import op_freq_statistic

    prog, _, _ = _mlp_program()
    uni, adj = op_freq_statistic(prog)
    uni_d = dict(uni)
    assert uni_d.get("mul", 0) + uni_d.get("matmul", 0) >= 2
    assert any("->" in k for k, _ in adj)


def test_model_stat_summary(capsys):
    from paddle_tpu.contrib import summary
    from paddle_tpu import framework
    from paddle_tpu.models.resnet import resnet50

    resnet50(is_test=True)
    rows = summary(framework.default_main_program())
    out = capsys.readouterr().out
    assert "Total PARAMs" in out and "Total FLOPs" in out
    conv_rows = [r for r in rows if r["type"] == "conv2d"]
    assert len(conv_rows) == 53
    # resnet50 params ~25.5M; conv+bn+fc params should land in range
    total = sum(r["PARAMs"] for r in rows)
    assert 20e6 < total < 30e6


# ------------------------------------------------- decoupled weight decay

def test_decoupled_weight_decay_exact():
    from paddle_tpu.contrib import extend_with_decoupled_weight_decay

    SGDW = extend_with_decoupled_weight_decay(SGD)
    with scope_guard(Scope()):
        np.random.seed(0)
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            with unique_name.guard():
                x = layers.data(name="x", shape=[4], dtype="float32")
                y = layers.fc(x, size=1, bias_attr=False)
                loss = layers.mean(y)
                SGDW(weight_decay=0.1, learning_rate=0.0).minimize(loss)
        exe = Executor()
        exe.run(sprog)
        feed = {"x": np.ones((2, 4), np.float32)}
        w0 = np.array(exe.run(prog, feed=feed,
                              fetch_list=["fc_0.w_0"])[0])
        w1 = np.array(exe.run(prog, feed=feed,
                              fetch_list=["fc_0.w_0"])[0])
        np.testing.assert_allclose(w1, w0 * 0.9, rtol=1e-5)
    with pytest.raises(TypeError):
        extend_with_decoupled_weight_decay(object)


def test_adamw_trains():
    from paddle_tpu.contrib import extend_with_decoupled_weight_decay

    AdamW = extend_with_decoupled_weight_decay(Adam)
    with scope_guard(Scope()):
        np.random.seed(0)
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            with unique_name.guard():
                x = layers.data(name="x", shape=[4], dtype="float32")
                label = layers.data(name="label", shape=[1],
                                    dtype="float32")
                y = layers.fc(x, size=1)
                loss = layers.mean(layers.square_error_cost(y, label))
                AdamW(weight_decay=0.01, learning_rate=0.1).minimize(loss)
        exe = Executor()
        exe.run(sprog)
        rng = np.random.RandomState(0)
        losses = []
        for _ in range(30):
            bx = rng.rand(8, 4).astype(np.float32)
            lv, = exe.run(prog, feed={"x": bx,
                                      "label": bx.sum(1, keepdims=True)},
                          fetch_list=[loss])
            losses.append(float(np.ravel(lv)[0]))
        assert losses[-1] < losses[0] * 0.5


# ------------------------------------------------- trainer / inferencer

def test_trainer_inferencer_roundtrip(tmp_path):
    from paddle_tpu.contrib import Inferencer, Trainer

    W = np.arange(4, dtype=np.float32).reshape(4, 1)

    def train_func():
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1, name="pred_fc")
        return layers.mean(layers.square_error_cost(pred, y))

    def optimizer_func():
        return SGD(learning_rate=0.05)

    rng = np.random.RandomState(0)

    def reader():
        for _ in range(8):
            xs = rng.rand(16, 4).astype(np.float32)
            yield list(zip(xs, xs @ W))

    events = []
    trainer = Trainer(train_func=train_func,
                      optimizer_func=optimizer_func)
    trainer.train(num_epochs=12, event_handler=lambda e: events.append(e),
                  reader=reader, feed_order=["x", "y"])
    kinds = {type(e).__name__ for e in events}
    assert {"BeginEpochEvent", "EndEpochEvent", "BeginStepEvent",
            "EndStepEvent"} <= kinds
    # loss decreased over training
    from paddle_tpu.contrib.trainer import EndStepEvent

    step_losses = [float(np.ravel(e.metrics[0])[0]) for e in events
                   if isinstance(e, EndStepEvent)]
    assert step_losses[-1] < step_losses[0]
    # test() averages the loss over a reader
    test_loss = trainer.test(reader=reader, feed_order=["x", "y"])
    assert test_loss[0] < step_losses[0]

    param_dir = str(tmp_path / "params")
    trainer.save_params(param_dir)

    def infer_func():
        x = layers.data(name="x", shape=[4], dtype="float32")
        return layers.fc(x, size=1, name="pred_fc")

    inferencer = Inferencer(infer_func=infer_func, param_path=param_dir)
    xs = rng.rand(4, 4).astype(np.float32)
    out, = inferencer.infer({"x": xs})
    # trained weights should be near W (well-conditioned linear fit)
    np.testing.assert_allclose(out, xs @ W, atol=0.5)


def test_trainer_stop_and_checkpoint(tmp_path):
    from paddle_tpu.contrib import CheckpointConfig, Trainer

    def train_func():
        x = layers.data(name="x", shape=[2], dtype="float32")
        return layers.mean(layers.fc(x, size=1))

    ckpt_dir = str(tmp_path / "ckpt")
    trainer = Trainer(
        train_func=train_func, optimizer_func=lambda: SGD(0.1),
        checkpoint_config=CheckpointConfig(checkpoint_dir=ckpt_dir,
                                           step_interval=1))

    def reader():
        for _ in range(4):
            yield [(np.zeros(2, np.float32),)] * 2

    seen = []

    def handler(e):
        seen.append(e)
        if len(seen) > 5:
            trainer.stop()

    trainer.train(num_epochs=10, event_handler=handler, reader=reader,
                  feed_order=["x"])
    assert any(s.isdigit() for s in os.listdir(ckpt_dir))


# -------------------------------------------------------------- readers

def test_distributed_batch_reader(monkeypatch):
    from paddle_tpu.contrib import distributed_batch_reader

    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "1")

    def batch_reader():
        yield from range(10)

    got = list(distributed_batch_reader(batch_reader)())
    assert got == [1, 3, 5, 7, 9]


def test_ctr_reader_csv_and_svm(tmp_path):
    from paddle_tpu.contrib import ctr_reader

    class Var:
        def __init__(self, name):
            self.name = name

    csv = tmp_path / "a.csv"
    csv.write_text("0.5,1 2 3\n0.25,4 5\n1.0,6\n")
    r = ctr_reader([Var("dense"), Var("ids")], "plain", "csv",
                   dense_slot_index=[0], sparse_slot_index=[1],
                   capacity=8, thread_num=1, batch_size=2,
                   file_list=[str(csv)])
    batches = list(r)
    assert sum(b["ids"].shape[0] for b in batches) == 3
    first = batches[0]
    assert first["dense"].dtype == np.float32
    assert first["ids"].dtype == np.int64

    svm = tmp_path / "b.svm"
    svm.write_text("1 3:1 7:1\n0 2:1\n")
    r2 = ctr_reader([Var("ids"), Var("label")], "plain", "svm",
                    dense_slot_index=[], sparse_slot_index=[],
                    capacity=8, thread_num=1, batch_size=2,
                    file_list=[str(svm)])
    b2 = list(r2)
    assert b2[0]["label"].shape == (2, 1)
    assert set(b2[0]["ids"].ravel()) >= {3, 7, 2}


# ---------------------------------------------------- quantize transpiler

def test_quantize_transpiler_qat_roundtrip():
    from paddle_tpu.contrib import QuantizeTranspiler

    with scope_guard(Scope()):
        np.random.seed(0)
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            with unique_name.guard():
                x = layers.data(name="x", shape=[4], dtype="float32")
                y = layers.fc(x, size=3)
                loss = layers.mean(y)
        qt = QuantizeTranspiler()
        qt.training_transpile(prog, sprog)
        types = {op.type for op in prog.global_block().ops}
        assert any("fake_quantize" in t for t in types)
        exe = Executor()
        exe.run(sprog)
        out, = exe.run(prog, feed={"x": np.ones((2, 4), np.float32)},
                       fetch_list=[loss])
        assert np.isfinite(np.ravel(out)).all()
    with pytest.raises(ValueError):
        QuantizeTranspiler(activation_quantize_type="nope")


# --------------------------------------------------------- basic rnn

def test_basic_gru_shapes_and_run():
    from paddle_tpu.contrib.layers import basic_gru

    with scope_guard(Scope()):
        np.random.seed(0)
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            with unique_name.guard():
                x = layers.data(name="x", shape=[5, 6], dtype="float32",
                                append_batch_size=False)
                xb = layers.unsqueeze(x, axes=[0]) if False else x
                inp = layers.data(name="inp", shape=[2, 5, 6],
                                  dtype="float32",
                                  append_batch_size=False)
                out, last_h = basic_gru(inp, None, hidden_size=4,
                                        num_layers=2, bidirectional=True)
        exe = Executor()
        exe.run(sprog)
        o, h = exe.run(prog,
                       feed={"inp": np.random.rand(2, 5, 6)
                             .astype(np.float32)},
                       fetch_list=[out, last_h])
        assert o.shape == (2, 5, 8)      # bidir concat of D=4
        assert h.shape == (4, 2, 4)      # num_layers*2 x B x D


def test_basic_lstm_runs():
    from paddle_tpu.contrib.layers import basic_lstm

    with scope_guard(Scope()):
        np.random.seed(0)
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            with unique_name.guard():
                inp = layers.data(name="inp", shape=[2, 5, 6],
                                  dtype="float32",
                                  append_batch_size=False)
                out, last_h, last_c = basic_lstm(
                    inp, None, None, hidden_size=4, num_layers=1)
        exe = Executor()
        exe.run(sprog)
        o, h, c = exe.run(prog,
                          feed={"inp": np.random.rand(2, 5, 6)
                                .astype(np.float32)},
                          fetch_list=[out, last_h, last_c])
        assert o.shape == (2, 5, 4)
        assert h.shape == (1, 2, 4) and c.shape == (1, 2, 4)


def test_fused_elemwise_activation_layer():
    from paddle_tpu.contrib.layers import fused_elemwise_activation

    with scope_guard(Scope()):
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            x = layers.data(name="x", shape=[4], dtype="float32")
            y = layers.data(name="y", shape=[4], dtype="float32")
            out = fused_elemwise_activation(
                x, y, ["elementwise_add", "relu"])
        exe = Executor()
        exe.run(sprog)
        xv = np.array([[-1, 2, -3, 4]], np.float32)
        yv = np.array([[0.5, -0.5, 0.5, -0.5]], np.float32)
        o, = exe.run(prog, feed={"x": xv, "y": yv}, fetch_list=[out])
        # functor ['elementwise_add','relu'] = add(x, relu(y))
        np.testing.assert_allclose(o, xv + np.maximum(yv, 0), rtol=1e-6)
    with pytest.raises(ValueError):
        fused_elemwise_activation(None, None, ["just_one"])


# ------------------------------------------------------------- decoder

def _build_state_cell(context):
    from paddle_tpu.contrib.decoder import InitState, StateCell

    h = InitState(init=context)
    state_cell = StateCell(inputs={"x": None}, states={"h": h},
                           out_state="h")

    @state_cell.state_updater
    def updater(cell):
        current_word = cell.get_input("x")
        prev_h = cell.get_state("h")
        new_h = layers.fc(layers.concat([prev_h, current_word], axis=-1),
                          size=int(prev_h.shape[-1]), act="tanh",
                          name="dec_fc")
        cell.set_state("h", new_h)

    return state_cell


def test_training_decoder_teacher_forced():
    from paddle_tpu.contrib.decoder import TrainingDecoder

    with scope_guard(Scope()):
        np.random.seed(0)
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            with unique_name.guard():
                ctx = layers.data(name="ctx", shape=[2, 4],
                                  dtype="float32",
                                  append_batch_size=False)
                trg = layers.data(name="trg", shape=[2, 3, 4],
                                  dtype="float32",
                                  append_batch_size=False)
                state_cell = _build_state_cell(ctx)
                decoder = TrainingDecoder(state_cell)
                with decoder.block():
                    word = decoder.step_input(trg)
                    decoder.state_cell.compute_state(inputs={"x": word})
                    score = layers.fc(decoder.state_cell.get_state("h"),
                                      size=7, act="softmax")
                    decoder.state_cell.update_states()
                    decoder.output(score)
                out = decoder()
        exe = Executor()
        exe.run(sprog)
        o, = exe.run(prog, feed={
            "ctx": np.random.rand(2, 4).astype(np.float32),
            "trg": np.random.rand(2, 3, 4).astype(np.float32)},
            fetch_list=[out])
        assert o.shape == (2, 3, 7)
        np.testing.assert_allclose(o.sum(-1), np.ones((2, 3)), rtol=1e-5)


def test_beam_search_decoder_decodes():
    from paddle_tpu.contrib.decoder import BeamSearchDecoder

    V, D, B, K, T = 11, 4, 2, 3, 5
    with scope_guard(Scope()):
        np.random.seed(0)
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            with unique_name.guard():
                ctx = layers.data(name="ctx", shape=[B, D],
                                  dtype="float32",
                                  append_batch_size=False)
                init_ids = layers.data(name="init_ids", shape=[B, 1],
                                       dtype="int64",
                                       append_batch_size=False)
                init_scores = layers.data(
                    name="init_scores", shape=[B, 1], dtype="float32",
                    append_batch_size=False)
                state_cell = _build_state_cell(ctx)
                decoder = BeamSearchDecoder(
                    state_cell=state_cell, init_ids=init_ids,
                    init_scores=init_scores, target_dict_dim=V,
                    word_dim=D, topk_size=V, max_len=T, beam_size=K,
                    end_id=1)
                decoder.decode()
                tr_ids, tr_scores = decoder()
        exe = Executor()
        exe.run(sprog)
        ids, scores = exe.run(prog, feed={
            "ctx": np.random.rand(B, D).astype(np.float32),
            "init_ids": np.zeros((B, 1), np.int64),
            "init_scores": np.zeros((B, 1), np.float32)},
            fetch_list=[tr_ids, tr_scores])
        assert ids.shape == (B, K, T)
        assert scores.shape == (B, K)
        assert ids.min() >= 0 and ids.max() < V
        # beams are sorted best-first per batch element
        assert (np.diff(scores, axis=1) <= 1e-6).all()


# ------------------------------------------------------------- hdfs utils

def test_hdfs_utils_local_helpers(tmp_path):
    from paddle_tpu.contrib.utils import getfilelist

    (tmp_path / "sub").mkdir()
    (tmp_path / "a.txt").write_text("x")
    (tmp_path / "sub" / "b.txt").write_text("y")
    files = sorted(getfilelist(str(tmp_path)))
    assert len(files) == 2 and files[0].endswith("a.txt")


# ------------------------------------------------- new dygraph modules

def test_dygraph_extra_layers():
    """Conv3D/Conv3DTranspose/GroupNorm/BilinearTensorProduct/SequenceConv/
    RowConv/NCE/SpectralNorm/TreeConv (reference dygraph/nn.py:257-2533)."""
    import paddle_tpu.dygraph as dg
    from paddle_tpu.dygraph import guard, to_variable

    rng = np.random.RandomState(0)
    with guard():
        x5 = to_variable(rng.rand(2, 3, 4, 5, 6).astype(np.float32))
        assert list(dg.Conv3D(3, 8, 3, padding=1)(x5).shape) == \
            [2, 8, 4, 5, 6]
        assert list(dg.Conv3DTranspose(3, 8, 3)(x5).shape) == \
            [2, 8, 6, 7, 8]
        x4 = to_variable(rng.rand(2, 8, 5, 5).astype(np.float32))
        gn = dg.GroupNorm(8, groups=4)
        y = gn(x4)
        # per-group normalization: mean ~0 over each (group, spatial)
        yv = np.asarray(y.value).reshape(2, 4, 2 * 5 * 5)
        np.testing.assert_allclose(yv.mean(-1), 0.0, atol=1e-4)
        a = to_variable(rng.rand(2, 4).astype(np.float32))
        b = to_variable(rng.rand(2, 5).astype(np.float32))
        assert list(dg.BilinearTensorProduct(4, 5, 3)(a, b).shape) == \
            [2, 3]
        xs = to_variable(rng.rand(2, 7, 6).astype(np.float32))
        assert list(dg.SequenceConv(6, 8, filter_size=3)(xs).shape) == \
            [2, 7, 8]
        assert list(dg.RowConv(6, 2)(xs).shape) == [2, 7, 6]
        lab = to_variable(rng.randint(0, 20, (2, 1)).astype(np.int64))
        nce = dg.NCE(num_total_classes=20, dim=4, num_neg_samples=5)
        cost = nce(a, lab)
        assert np.isfinite(np.asarray(cost.value)).all()
        nodes = to_variable(rng.rand(2, 7, 6).astype(np.float32))
        edges = to_variable(rng.randint(0, 7, (2, 6, 2)).astype(np.int64))
        assert list(dg.TreeConv(6, 5, num_filters=2)(
            nodes, edges).shape) == [2, 7, 5, 2]


def test_dygraph_spectral_norm_converges():
    import paddle_tpu.dygraph as dg
    from paddle_tpu.dygraph import guard, to_variable

    rng = np.random.RandomState(0)
    with guard():
        sn = dg.SpectralNorm([8, 4])
        w = to_variable(rng.rand(8, 4).astype(np.float32))
        for _ in range(4):
            out = sn(w)  # u/v persist like BatchNorm running stats
        sigma = np.linalg.svd(np.asarray(out.value),
                              compute_uv=False)[0]
        np.testing.assert_allclose(sigma, 1.0, atol=1e-3)


# ------------------------------------------- review-finding regressions

def test_beam_search_decoder_shares_params_across_steps():
    """decode() must reuse ONE embedding table / score fc across all
    unrolled steps (review finding: per-step fresh params)."""
    from paddle_tpu.contrib.decoder import BeamSearchDecoder

    def build(max_len):
        with scope_guard(Scope()):
            prog, sprog = Program(), Program()
            with program_guard(prog, sprog):
                with unique_name.guard():
                    ctx = layers.data(name="ctx", shape=[2, 4],
                                      dtype="float32",
                                      append_batch_size=False)
                    init_ids = layers.data(name="init_ids", shape=[2, 1],
                                           dtype="int64",
                                           append_batch_size=False)
                    init_scores = layers.data(
                        name="init_scores", shape=[2, 1],
                        dtype="float32", append_batch_size=False)
                    sc = _build_state_cell(ctx)
                    dec = BeamSearchDecoder(
                        state_cell=sc, init_ids=init_ids,
                        init_scores=init_scores, target_dict_dim=11,
                        word_dim=4, topk_size=11, max_len=max_len,
                        beam_size=3, end_id=1)
                    dec.decode()
            params = [v.name for v in prog.global_block().vars.values()
                      if getattr(v, "trainable", False)]
            return params

    p3, p6 = build(3), build(6)
    assert sorted(p3) == sorted(p6), "param set scales with max_len"
    emb_params = [p for p in p3 if "embedding" in p]
    assert len(emb_params) == 1


def test_basic_gru_reverse_final_state():
    """Reverse-direction last_hidden must be the whole-sequence state
    (review finding: it was the one-token state at t=T-1)."""
    from paddle_tpu.contrib.layers import basic_gru

    with scope_guard(Scope()):
        np.random.seed(0)
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            with unique_name.guard():
                inp = layers.data(name="inp", shape=[2, 5, 6],
                                  dtype="float32",
                                  append_batch_size=False)
                out, last_h = basic_gru(inp, None, hidden_size=4,
                                        num_layers=1, bidirectional=True)
        exe = Executor()
        exe.run(sprog)
        o, h = exe.run(prog, feed={"inp": np.random.rand(2, 5, 6)
                                   .astype(np.float32)},
                       fetch_list=[out, last_h])
        # fwd last state == out[:, -1, :4]; rev last state == out[:, 0, 4:]
        np.testing.assert_allclose(h[0], o[:, -1, :4], rtol=1e-5)
        np.testing.assert_allclose(h[1], o[:, 0, 4:], rtol=1e-5)


def test_basic_lstm_forget_bias_changes_math():
    from paddle_tpu.contrib.layers import basic_lstm

    def run(forget_bias):
        with scope_guard(Scope()):
            np.random.seed(0)
            prog, sprog = Program(), Program()
            with program_guard(prog, sprog):
                with unique_name.guard():
                    inp = layers.data(name="inp", shape=[2, 5, 6],
                                      dtype="float32",
                                      append_batch_size=False)
                    out, _, _ = basic_lstm(inp, None, None, hidden_size=4,
                                           forget_bias=forget_bias)
            exe = Executor()
            exe.run(sprog)
            o, = exe.run(prog, feed={"inp": np.random.RandomState(7)
                                     .rand(2, 5, 6).astype(np.float32)},
                         fetch_list=[out])
            return o

    assert np.abs(run(0.0) - run(5.0)).max() > 1e-3


def test_nce_sample_weight_scales_cost():
    import paddle_tpu.dygraph as dg
    from paddle_tpu.dygraph import guard, to_variable

    rng = np.random.RandomState(0)
    with guard():
        nce = dg.NCE(num_total_classes=20, dim=4, num_neg_samples=5)
        a = to_variable(rng.rand(2, 4).astype(np.float32))
        lab = to_variable(rng.randint(0, 20, (2, 1)).astype(np.int64))
        base = np.asarray(nce(a, lab).value)
        sw = to_variable(np.array([[2.0], [0.5]], np.float32))
        weighted = np.asarray(nce(a, lab, sample_weight=sw).value)
        np.testing.assert_allclose(weighted.ravel(),
                                   base.ravel() * [2.0, 0.5], rtol=1e-5)


# --------------------------------- old distributed/ + dygraph grad clip

def test_dygraph_grad_clip_classes():
    from paddle_tpu.dygraph_grad_clip import (GradClipByGlobalNorm,
                                              GradClipByNorm,
                                              GradClipByValue)

    g1 = np.array([3.0, -4.0], np.float32)   # norm 5
    g2 = np.array([6.0, 8.0], np.float32)    # norm 10
    pairs = [("p1", g1), ("p2", g2), ("p3", None)]

    out = GradClipByValue(-1.0, 1.0)(pairs)
    np.testing.assert_allclose(out[0][1], [1.0, -1.0])
    assert out[2][1] is None

    out = GradClipByNorm(2.5)(pairs)
    np.testing.assert_allclose(np.linalg.norm(out[0][1]), 2.5, rtol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(out[1][1]), 2.5, rtol=1e-5)

    out = GradClipByGlobalNorm(5.0)(pairs)
    gn = np.sqrt(np.linalg.norm(out[0][1]) ** 2 +
                 np.linalg.norm(out[1][1]) ** 2)
    np.testing.assert_allclose(gn, 5.0, rtol=1e-5)
    # relative magnitudes preserved
    np.testing.assert_allclose(out[1][1] / out[0][1][0] * 3.0,
                               g2 / g1[0] * 3.0, rtol=1e-5)


def test_dygraph_minimize_accepts_gradclip():
    import paddle_tpu.dygraph as dg
    from paddle_tpu.dygraph import guard, to_variable
    from paddle_tpu.dygraph_grad_clip import GradClipByGlobalNorm
    from paddle_tpu.optimizer import SGD

    with guard():
        from paddle_tpu.dygraph.base import _current_tracer
        fc = dg.Linear(4, 2)
        x = to_variable(np.ones((3, 4), np.float32))
        loss = _current_tracer().trace(
            "reduce_mean", {"X": fc(x)}, {"reduce_all": True})["Out"]
        loss.backward()
        SGD(learning_rate=0.1).minimize(
            loss, grad_clip=GradClipByGlobalNorm(0.1))


def test_downpour_sgd_publishes_fleet_opt():
    from paddle_tpu.distributed.downpour import DownpourSGD

    with scope_guard(Scope()):
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            with unique_name.guard():
                ids = layers.data(name="ids", shape=[1], dtype="int64")
                emb = layers.embedding(ids, size=[100, 8],
                                       is_distributed=True)
                dense = layers.fc(emb, size=4)
                loss = layers.mean(dense)
                opt_info, skipped = DownpourSGD(
                    learning_rate=0.1).minimize([loss])
        assert prog._fleet_opt is opt_info
        assert opt_info["sparse_tables"] and "lookup_table" in skipped
        assert any("fc" in n for n in opt_info["dense_tables"])
        with pytest.raises(ValueError):
            DownpourSGD().minimize(loss)  # must be a list


def test_paddle_ps_instance_roles():
    from paddle_tpu.distributed.ps_instance import PaddlePSInstance

    # interleaved mode over 2 nodes x 2 procs: ranks 0,2 servers; 1,3 workers
    roles = [PaddlePSInstance(1, 2, nodes=2, rankid=r) for r in range(4)]
    assert [i.is_server() for i in roles] == [True, False, True, False]
    assert [i.is_worker() for i in roles] == [False, True, False, True]
    assert roles[1].is_first_worker()
    assert roles[3].get_worker_index() == 1
    assert roles[0].get_worker_num() == 2
    # block mode: first block workers, then servers
    blk = [PaddlePSInstance(0, 2, nodes=2, rankid=r) for r in range(4)]
    assert [i.is_worker() for i in blk] == [True, True, False, False]
    assert [i.is_server() for i in blk] == [False, False, True, True]
    blk[0].barrier_all()  # no endpoint: no-op, must not raise


def test_paddle_ps_instance_indices_consistent():
    """Review regressions: block-mode indices follow the block layout;
    interleaved indices are unique for proc_per_node > 2."""
    from paddle_tpu.distributed.ps_instance import PaddlePSInstance

    blk = [PaddlePSInstance(0, 2, nodes=2, rankid=r) for r in range(4)]
    # workers ranks 0,1 -> indices 0,1; servers ranks 2,3 -> indices 0,1
    assert [i.get_worker_index() for i in blk[:2]] == [0, 1]
    assert [i.get_server_index() for i in blk[2:]] == [0, 1]
    assert blk[0].is_first_worker()

    inter = [PaddlePSInstance(1, 4, nodes=2, rankid=r) for r in range(8)]
    workers = [i for i in inter if i.is_worker()]
    servers = [i for i in inter if i.is_server()]
    assert sorted(i.get_worker_index() for i in workers) == [0, 1, 2, 3]
    assert sorted(i.get_server_index() for i in servers) == [0, 1, 2, 3]
    assert sum(i.is_first_worker() for i in inter) == 1

    with pytest.raises(ValueError):
        PaddlePSInstance(1, 3)


def test_beam_search_decoder_shares_trained_weights_by_name():
    """The fluid idiom the reference decode test relies on (reference
    tests/test_beam_search_decoder.py): train with TrainingDecoder,
    build the decode program in the SAME scope with matching creation
    order, and BeamSearchDecoder's steps run on the TRAINED weights
    (natural param names, no decoder prefix)."""
    from paddle_tpu.contrib.decoder import BeamSearchDecoder, TrainingDecoder
    from paddle_tpu.optimizer import Adam

    V, D, B, T = 6, 8, 4, 3
    TARGET = 3

    def build_cell(ctx):
        from paddle_tpu.contrib.decoder import InitState, StateCell

        h = InitState(init=ctx)
        sc = StateCell(inputs={"x": None}, states={"h": h},
                       out_state="h")

        @sc.state_updater
        def up(cell):
            cell.set_state("h", layers.fc(
                layers.concat([cell.get_state("h"),
                               cell.get_input("x")], axis=-1),
                size=D, act="tanh"))

        return sc

    with scope_guard(Scope()):
        np.random.seed(0)
        # ---- training program: teacher-forced, label = TARGET always
        train_prog, sprog = Program(), Program()
        with program_guard(train_prog, sprog):
            with unique_name.guard():
                ctx = layers.data(name="ctx", shape=[B, D],
                                  dtype="float32",
                                  append_batch_size=False)
                trg_ids = layers.data(name="trg_ids", shape=[B, T, 1],
                                      dtype="int64",
                                      append_batch_size=False)
                # embedding FIRST: same creation order as decode()
                emb = layers.embedding(
                    layers.reshape(trg_ids, shape=[-1, 1]),
                    size=[V, D], dtype="float32")
                emb = layers.reshape(emb, shape=[B, T, D])
                sc = build_cell(ctx)
                decoder = TrainingDecoder(sc)
                with decoder.block():
                    word = decoder.step_input(emb)
                    decoder.state_cell.compute_state(inputs={"x": word})
                    score = layers.fc(decoder.state_cell.get_state("h"),
                                      size=V, act="softmax")
                    decoder.state_cell.update_states()
                    decoder.output(score)
                out = decoder()
                label = layers.data(name="label", shape=[B, T, 1],
                                    dtype="int64",
                                    append_batch_size=False)
                loss = layers.mean(layers.cross_entropy(
                    layers.reshape(out, shape=[-1, V]),
                    layers.reshape(label, shape=[-1, 1])))
                Adam(learning_rate=0.1).minimize(loss)
        exe = Executor()
        exe.run(sprog)
        rng = np.random.RandomState(0)
        feed = {"ctx": rng.rand(B, D).astype(np.float32),
                "trg_ids": rng.randint(0, V, (B, T, 1)).astype(np.int64),
                "label": np.full((B, T, 1), TARGET, np.int64)}
        for _ in range(40):
            lv, = exe.run(train_prog, feed=feed, fetch_list=[loss])
        assert float(np.ravel(lv)[0]) < 0.1  # learned "always TARGET"

        # ---- decode program in the SAME scope, matching build order
        infer_prog, isprog = Program(), Program()
        with program_guard(infer_prog, isprog):
            with unique_name.guard():
                ctx2 = layers.data(name="ctx", shape=[B, D],
                                   dtype="float32",
                                   append_batch_size=False)
                ii = layers.data(name="init_ids", shape=[B, 1],
                                 dtype="int64", append_batch_size=False)
                isc = layers.data(name="init_scores", shape=[B, 1],
                                  dtype="float32",
                                  append_batch_size=False)
                sc2 = build_cell(ctx2)
                dec = BeamSearchDecoder(
                    state_cell=sc2, init_ids=ii, init_scores=isc,
                    target_dict_dim=V, word_dim=D, topk_size=V,
                    max_len=T, beam_size=2, end_id=V - 1)
                dec.decode()
                tid, tsc = dec()
        # params must be the TRAINED ones: names match, so skip the
        # decode startup (isprog) entirely — scope already has them
        train_params = {v.name for v in
                        train_prog.global_block().vars.values()
                        if getattr(v, "trainable", False)}
        dec_params = {v.name for v in
                      infer_prog.global_block().vars.values()
                      if getattr(v, "trainable", False)}
        assert dec_params <= train_params, (
            dec_params - train_params)
        ids, _ = exe.run(infer_prog,
                         feed={"ctx": feed["ctx"],
                               "init_ids": np.zeros((B, 1), np.int64),
                               "init_scores": np.zeros((B, 1),
                                                       np.float32)},
                         fetch_list=[tid, tsc])
        # the trained model emits TARGET at (nearly) every step
        frac = float((np.asarray(ids)[:, 0] == TARGET).mean())
        assert frac > 0.9, (frac, ids)


def test_beam_search_decoder_post_decode_layers_do_not_collide():
    """Review regression: layers built AFTER decode() in the same
    program get fresh names — no silent sharing/corruption of the
    decoder's step-internal params."""
    from paddle_tpu.contrib.decoder import BeamSearchDecoder

    with scope_guard(Scope()):
        prog, sprog = Program(), Program()
        with program_guard(prog, sprog):
            with unique_name.guard():
                ctx = layers.data(name="ctx", shape=[2, 4],
                                  dtype="float32",
                                  append_batch_size=False)
                ii = layers.data(name="ii", shape=[2, 1], dtype="int64",
                                 append_batch_size=False)
                isc = layers.data(name="isc", shape=[2, 1],
                                  dtype="float32",
                                  append_batch_size=False)
                sc = _build_state_cell(ctx)
                dec = BeamSearchDecoder(
                    state_cell=sc, init_ids=ii, init_scores=isc,
                    target_dict_dim=11, word_dim=4, topk_size=11,
                    max_len=4, beam_size=2, end_id=1)
                dec.decode()
                params_before = {
                    v.name for v in prog.global_block().vars.values()
                    if getattr(v, "trainable", False)}
                post = layers.fc(ctx, size=4)  # was the crash repro
                params_after = {
                    v.name for v in prog.global_block().vars.values()
                    if getattr(v, "trainable", False)}
        new_params = params_after - params_before
        assert new_params and all(
            p not in params_before for p in new_params)
