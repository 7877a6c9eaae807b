"""Builder for the `ouro` configurations (models/ouro.py: a stack of
layers run total_ut_steps times over ONE set of weights, an exit gate
and an exit-weighted loss over the passes' heads), as a Fluid trainer
writes it: layers.* -> [RecomputeOptimizer] -> AMP decorate ->
Adam.minimize -> CompiledProgram.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _work():
    # the operations of this model, beside this file
    spec = importlib.util.spec_from_file_location(
        "_bm_ouro_flops", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "ouro_flops.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(config, job, flops):
    import paddle_tpu as fluid
    from paddle_tpu import optimizer
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.flags import set_flags
    from paddle_tpu.models.ouro import ouro_model

    set_flags({"gspmd": False})
    seq, batch = job["seq_len"], job["batch"]
    model = ouro_model(config, seq_len=seq,
                       param_prefix=config["param_prefix"])
    opt = optimizer.Adam(learning_rate=config["learning_rate"])
    if config["recompute"]:
        # a segment an execution of a layer and a head segment a pass:
        # only the state between them, the per-token cross-entropies
        # and the gates stay alive from forward to backward
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
        # ids uniform over the whole vocabulary; the label of position
        # t is the id at t + 1 (the last wraps to the first)
        ids = rng.integers(0, vocab, (batch, seq, 1), dtype=np.int64)
        return ids, np.roll(ids, -1, axis=1)

    # a layer execution is a flash call: R x L of them a step (a
    # forward that a recompute segment runs again is not counted)
    runs = config.get("total_ut_steps", 1) * config["num_hidden_layers"]
    flash = flops.transformer_flash_step(
        batch, config["num_attention_heads"], seq, config["head_dim"],
        runs)
    return {
        "compiled": compiled,
        "loss": model["loss"],
        "feed_list": [model["src_ids"], model["tgt_label"]],
        "make_batch": make_batch,
        "items_per_step": batch * seq,
        "flops_per_item": _work().train_flops_per_token(config, seq),
        "kernel_work": {"flash": {"flops": flash[0], "bytes": flash[1]}},
    }
