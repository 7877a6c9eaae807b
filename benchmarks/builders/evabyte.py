"""Builder for the `evabyte` configurations (models/evabyte.py: EVA
attention in every layer, a dense SwiGLU, norms with a unit offset,
num_pred_heads next-byte heads over one stream position), as a Fluid
trainer writes it: layers.* -> [RecomputeOptimizer] -> AMP decorate ->
Adam.minimize -> CompiledProgram.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _beside(name):
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
    from paddle_tpu.models.evabyte import evabyte_model

    set_flags({"gspmd": False})
    seq, batch = job["seq_len"], job["batch"]
    model = evabyte_model(config, seq_len=seq,
                          param_prefix=config["param_prefix"])
    opt = optimizer.Adam(learning_rate=config["learning_rate"])
    if config["recompute"]:
        # one segment a layer: the residual stream at the layer
        # boundaries stays alive from forward to backward, and what the
        # registered grad ops read (eva_attention's Out and LSE, the
        # summaries)
        opt = optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints(model["checkpoints"])
    if config["amp"]:
        # bf16 has fp32's exponent range: static loss scale 1.0
        opt = decorate(opt, init_loss_scaling=1.0,
                       use_dynamic_loss_scaling=False)
    opt.minimize(model["loss"])
    compiled = fluid.CompiledProgram(fluid.default_main_program())

    vocab, n_pred = config["vocab_size"], config["num_pred_heads"]

    def make_batch(rng):
        # ONE stream of seq + n_pred bytes a sequence: the input is its
        # first seq, and head p's label at position t the byte at
        # t + 1 + p, so every position has its n_pred targets
        stream = rng.integers(0, vocab, (batch, seq + n_pred),
                              dtype=np.int64)
        ahead = np.arange(seq)[:, None] + 1 + np.arange(n_pred)[None, :]
        return stream[:, :seq, None], stream[:, ahead][..., None]

    work = _beside("evabyte_flops")
    eva = work.eva_step(config, batch, seq)
    pool = work.eva_pool_step(config, batch, seq)
    return {
        "compiled": compiled,
        "loss": model["loss"],
        "logits": model["logits"],
        "feed_list": [model["src_ids"], model["tgt_label"]],
        "make_batch": make_batch,
        "items_per_step": batch * seq,
        "flops_per_item": work.train_flops_per_token(config, seq),
        "kernel_work": {
            "eva": {"flops": eva[0], "bytes": eva[1]},
            "eva_pool": {"flops": pool[0], "bytes": pool[1]}},
    }
