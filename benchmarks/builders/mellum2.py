"""Builder for the `mellum` configurations (models/mellum2.py:
sliding-window and full attention layers by `layer_types`, each kind
with its own rotary parameters, a softmax router over all experts with
the chip's share of them held here in every layer, an untied head), as
a Fluid trainer writes it: layers.* -> [RecomputeOptimizer] -> AMP
decorate -> Adam.minimize -> CompiledProgram.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _beside(name):
    # the operations and bytes, beside this file: this block's own, and
    # the generic grouped-matmul functions of xing4_flops.py
    spec = importlib.util.spec_from_file_location(
        "_bm_" + name, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(config, job, flops):
    import paddle_tpu as fluid
    from paddle_tpu import optimizer
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.flags import set_flags
    from paddle_tpu.models.mellum2 import mellum2_model

    set_flags({"gspmd": False})
    seq, batch = job["seq_len"], job["batch"]
    model = mellum2_model(config, seq_len=seq,
                          param_prefix=config["param_prefix"])
    opt = optimizer.Adam(learning_rate=config["learning_rate"])
    if config["recompute"]:
        # one segment a layer: the residual stream at the layer
        # boundaries stays alive from forward to backward, and what the
        # registered grad ops read (flash's Out and LSE, window layer
        # or full)
        opt = optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints(model["checkpoints"])
    if config["amp"]:
        # bf16 has fp32's exponent range: static loss scale 1.0
        opt = decorate(opt, init_loss_scaling=1.0,
                       use_dynamic_loss_scaling=False)
    opt.minimize(model["loss"])
    compiled = fluid.CompiledProgram(fluid.default_main_program())

    vocab = config["vocab_size"]

    def make_batch(rng):
        # ids uniform over the vocabulary slice held here; the label of
        # position t is the id at t + 1 (the last wraps to the first)
        ids = rng.integers(0, vocab, (batch, seq, 1), dtype=np.int64)
        return ids, np.roll(ids, -1, axis=1)

    work, kernels = _beside("mellum2_flops"), _beside("xing4_flops")
    window = work.window_flash_step(config, batch, seq)
    full = work.gqa_flash_step(config, batch, seq)
    gmm = kernels.gmm_step(
        batch * seq, config["num_experts_per_tok"], config["num_experts"],
        config["num_experts_published"], config["hidden_size"],
        config["moe_intermediate_size"], len(work.layer_kinds(config)))
    return {
        "compiled": compiled,
        "loss": model["loss"],
        "logits": model["logits"],
        "feed_list": [model["src_ids"], model["tgt_label"]],
        "make_batch": make_batch,
        "items_per_step": batch * seq,
        "flops_per_item": work.train_flops_per_token(config, seq),
        "kernel_work": {
            "window_flash": {"flops": window[0], "bytes": window[1]},
            "gqa_flash": {"flops": full[0], "bytes": full[1]},
            "moe_gmm": {"flops": gmm[0], "bytes": gmm[1]}},
    }
