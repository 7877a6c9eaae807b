"""Real activation recomputation (VERDICT r2 missing #7).

Reference anchor: incubate RecomputeOptimizer (optimizer.py:732 wrapper
was a pass-through until round 3).  The segmented backward must (a)
produce gradients identical to the plain backward, (b) train
identically, and (c) measurably reduce the compiled step's temp memory
— jax.checkpoint's optimization barrier keeps XLA from CSE-ing the
replay back into the forward pass.
"""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu.backward import append_backward

N_LAYERS = 12
WIDTH = 256


def _deep_mlp():
    x = layers.data("x", shape=[WIDTH], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    h = x
    ckpts = []
    for i in range(N_LAYERS):
        h = layers.fc(h, size=WIDTH, act="tanh", name=f"l{i}")
        if i % 3 == 2:
            ckpts.append(h)
    pred = layers.fc(h, size=1, name="head")
    loss = layers.mean(layers.square_error_cost(pred, y))
    return loss, ckpts


def _batch(bs=64):
    rng = np.random.RandomState(0)
    return (rng.rand(bs, WIDTH).astype(np.float32),
            rng.rand(bs, 1).astype(np.float32))


def test_recompute_grads_match_plain(fresh_programs_factory):
    bx, by = _batch()
    grads = {}
    for use_ckpt in (False, True):
        with fresh_programs_factory():
            np.random.seed(5)
            loss, ckpts = _deep_mlp()
            pg = append_backward(
                loss, checkpoints=ckpts if use_ckpt else None)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            names = [g.name for _, g in pg]
            vals = exe.run(feed={"x": bx, "y": by},
                           fetch_list=[loss] + names)
            grads[use_ckpt] = dict(zip(["loss"] + names, vals))
    assert set(grads[True]) == set(grads[False])
    for k in grads[False]:
        np.testing.assert_allclose(grads[True][k], grads[False][k],
                                   rtol=1e-4, atol=1e-6, err_msg=k)


def test_recompute_optimizer_trains_identically(fresh_programs_factory):
    bx, by = _batch()
    trajs = {}
    for use_ckpt in (False, True):
        with fresh_programs_factory():
            np.random.seed(6)
            loss, ckpts = _deep_mlp()
            opt = optimizer.RecomputeOptimizer(
                optimizer.SGD(learning_rate=0.005))
            if use_ckpt:
                opt._set_checkpoints(ckpts)
            opt.minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            compiled = fluid.CompiledProgram(
                fluid.default_main_program())
            losses = [float(exe.run(compiled,
                                    feed={"x": bx, "y": by},
                                    fetch_list=[loss])[0])
                      for _ in range(5)]
            trajs[use_ckpt] = losses
    np.testing.assert_allclose(trajs[True], trajs[False], rtol=1e-4)
    assert trajs[True][-1] < trajs[True][0]


def test_recompute_backward_live_set_shrinks(fresh_programs_factory):
    """The memory property at the PROGRAM level: with checkpoints, the
    backward consumes ONLY the checkpoint activations (plus params and
    feeds) — every intra-segment activation drops out of the
    forward->backward live set.  With the plain backward, every
    intermediate is consumed by some grad op.

    (This is the level the framework controls.  The on-device arena
    saving follows on TPU, where jax.checkpoint's remat is honored by
    buffer assignment; the CPU test backend ERASES remat during HLO
    simplification — verified with canonical pure-jax jax.checkpoint:
    no barriers survive and temp_size_in_bytes even rises — so no
    XLA-level CPU assertion can be made robustly.)"""
    from paddle_tpu.core.program import BACKWARD

    bx, by = _batch(bs=8)
    live = {}
    for use_ckpt in (False, True):
        with fresh_programs_factory():
            np.random.seed(7)
            loss, ckpts = _deep_mlp()
            ckpt_names = {c.name for c in ckpts}
            pg = append_backward(
                loss, checkpoints=ckpts if use_ckpt else None)
            block = fluid.default_main_program().global_block()
            fwd_act = set()
            for op in block.ops:
                if op.op_role == BACKWARD:
                    continue
                for n in op.output_names():
                    v = block.var(n)
                    if not v.persistable:
                        fwd_act.add(n)
            consumed = set()
            for op in block.ops:
                if op.op_role != BACKWARD:
                    continue
                consumed |= set(op.input_names()) & fwd_act
            live[use_ckpt] = consumed
    # plain backward touches (nearly) every intermediate activation
    assert len(live[False]) > 3 * len(live[True]), (
        len(live[False]), len(live[True]))
    # recompute backward touches only checkpoints (+ the loss-chain tail
    # inside the final segment's boundary)
    with fresh_programs_factory():
        np.random.seed(7)
        loss, ckpts = _deep_mlp()
        ckpt_names = {c.name for c in ckpts}
    non_ckpt = {n for n in live[True]
                if n not in ckpt_names and "tmp" in n}
    # every non-checkpoint var the bwd still reads must be a segment
    # BOUNDARY (a checkpoint) — none of the fc intermediates
    # (l*.tmp_0/tmp_1 pre-activation values) may appear
    assert not any(".tmp_0" in n or ".tmp_1" in n for n in non_ckpt), \
        sorted(non_ckpt)


def test_recompute_backward_live_set_grows_by_the_saved_outputs():
    """What a registered grad op reads of its forward's outputs stays
    live from forward to backward inside a segment too (ISSUE 33): with
    an attention layer a segment, the backward consumes the segments'
    boundary inputs and, beyond them, exactly the flash ops' Out and
    LSE, bound under `Saved`: one [B, H, Tq, D] and one float32
    [B, H, Tq] a layer, the price of not running the forward kernel
    again."""
    from paddle_tpu.core.program import BACKWARD

    q, k, v = (layers.data(n, shape=[2, 32, 8], dtype="float32")
               for n in "qkv")
    q.stop_gradient = False
    h, ckpts = q, []
    for _ in range(3):
        h = layers.tanh(layers.scale(
            layers.flash_attention(h, k, v, causal=True), scale=2.0))
        ckpts.append(h)
    append_backward(layers.mean(layers.square(h)), checkpoints=ckpts)
    block = fluid.default_main_program().global_block()
    fwd = [op for op in block.ops if op.op_role != BACKWARD]
    bwd = [op for op in block.ops if op.op_role == BACKWARD]
    fwd_act = {n for op in fwd for n in op.output_names()}
    segments = [op for op in bwd if op.type == "recompute_segment_grad"]
    boundary = {n for op in segments for n in op.inputs["X"]}
    consumed = {n for op in bwd for n in op.input_names()} & fwd_act
    flash_outs = {n for op in fwd if op.type == "flash_attention"
                  for n in op.outputs["Out"] + op.outputs["LSE"]}
    assert len(flash_outs) == 6
    assert consumed - boundary == flash_outs
    assert {n for op in segments
            for n in op.inputs.get("Saved", ())} == flash_outs
    assert all(op.attrs["saved_names"] == op.inputs["Saved"]
               for op in segments if "Saved" in op.inputs)
    # the pre-activation of every tanh is still its segment's own
    assert not any(n in consumed for op in fwd if op.type == "tanh"
                   for n in op.input_names())
