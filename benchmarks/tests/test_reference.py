"""Each plain reference against the program at tiny size on the CPU.

Tolerances, and why.  In float32 the program and the reference compute
the same mathematics in another order (NHWC rewrite, fused softmax
cross-entropy, one-pass batch-norm moments): they agree to 1e-4
relative.  With AMP the program rounds activations to bf16 (2^-8 =
3.9e-3 per rounding) and the reference does not: 2e-2 at these sizes,
where a loss averages a few dozen items; the cells' own tolerance on
the chip is tighter (configs/*.json), because their losses average
tens of thousands.
"""

import numpy as np
import pytest

from conftest import BENCH

import flops
import harness

F32_RTOL, AMP_RTOL = 1e-4, 2e-2


def _load(kind, name):
    import os

    return harness._load_file(os.path.join(BENCH, kind, name + ".py"))


def _program_and_reference(config, job, feed_names):
    import paddle_tpu as fluid

    kind = _load("kinds", "train_steps")
    kind._fresh_programs()
    np.random.seed(0)
    built = _load("builders", config["builder"]).build(config, job, flops)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = built["make_batch"](np.random.default_rng(0))
    ref = _load("reference", config["reference"])
    want = ref.loss(ref.read_params(config, kind._scope_get), batch,
                    config)
    got, = exe.run(built["compiled"], feed=dict(zip(feed_names, batch)),
                   fetch_list=[built["loss"]])
    return float(np.asarray(got).reshape(-1)[0]), want


@pytest.mark.parametrize("amp,rtol", [(False, F32_RTOL), (True, AMP_RTOL)])
def test_transformer_reference(amp, rtol):
    config = {"builder": "transformer_lm", "reference": "transformer_lm",
              "n_layer": 2, "d_model": 64, "d_inner": 128, "n_head": 2,
              "vocab_size": 128, "dropout_rate": 0.0,
              "label_smooth_eps": 0.0, "amp": amp, "learning_rate": 1e-3,
              "param_prefix": "tfm"}
    got, want = _program_and_reference(
        config, {"batch": 4, "seq_len": 32}, ["src_ids", "tgt_label"])
    assert got == pytest.approx(want, rel=rtol)
    # and it is a loss over 128 classes at random weights
    assert 0.5 * np.log(128) < want < 2 * np.log(128)


@pytest.mark.parametrize("depth,amp,rtol", [(18, False, F32_RTOL),
                                            (50, False, F32_RTOL),
                                            (18, True, AMP_RTOL)])
def test_resnet_reference(depth, amp, rtol):
    config = {"builder": "resnet", "reference": "resnet", "depth": depth,
              "image_size": 64, "num_classes": 10, "momentum": 0.9,
              "weight_decay": 1e-4, "learning_rate": 0.01, "nhwc": True,
              "amp": amp}
    got, want = _program_and_reference(config, {"batch": 16},
                                       ["image", "label"])
    assert got == pytest.approx(want, rel=rtol)


def test_blocked_causal_attention_is_the_unblocked_form():
    import jax

    ref = _load("reference", "transformer_lm")
    rng = np.random.default_rng(1)
    q, k, v = (rng.standard_normal((3, 64, 8)).astype(np.float32)
               for _ in range(3))
    with jax.default_matmul_precision("highest"):
        whole = ref.causal_attention(q, k, v)
        for block in (8, 16, 64):
            blocked = ref.causal_attention_blocked(q, k, v, block=block)
            np.testing.assert_allclose(blocked, whole, rtol=1e-5,
                                       atol=1e-6)
    # causal: the first row attends only to itself
    np.testing.assert_allclose(whole[:, 0], v[:, 0], rtol=1e-6)
    with pytest.raises(ValueError, match="multiple of the query block"):
        ref.causal_attention_blocked(q, k, v, block=48)
