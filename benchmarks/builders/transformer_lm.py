"""Builder for the decoder-only causal Transformer LM configurations
(models/transformer.py transformer_encoder_model), as a Fluid trainer
writes it: layers.* -> AMP decorate -> Adam.minimize ->
CompiledProgram, and for a job with a `mesh` one shard_program program
over a MeshPlan.
"""

from __future__ import annotations

import numpy as np


def build(config, job, flops):
    import paddle_tpu as fluid
    from paddle_tpu import optimizer
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.flags import set_flags
    from paddle_tpu.models.transformer import transformer_encoder_model

    mesh = job.get("mesh")
    set_flags({"gspmd": bool(mesh)})
    seq, batch = job["seq_len"], job["batch"]
    model = transformer_encoder_model(
        vocab_size=config["vocab_size"], max_len=seq,
        d_model=config["d_model"], n_head=config["n_head"],
        d_inner=config["d_inner"], n_layer=config["n_layer"],
        dropout_rate=config["dropout_rate"],
        label_smooth_eps=config["label_smooth_eps"],
        param_prefix=config["param_prefix"])
    opt = optimizer.Adam(learning_rate=config["learning_rate"])
    if config["amp"]:
        # bf16 has fp32's exponent range: static loss scale 1.0
        opt = decorate(opt, init_loss_scaling=1.0,
                       use_dynamic_loss_scaling=False)
    opt.minimize(model["loss"])
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    if mesh:
        from paddle_tpu.parallel.gspmd import MeshPlan
        from paddle_tpu.transpiler import shard_program

        compiled = shard_program(compiled, MeshPlan(**mesh),
                                 loss_name=model["loss"].name)

    vocab = config["vocab_size"]

    def make_batch(rng):
        # next-token prediction over one stream: the label of position
        # t is the id at t + 1 (the last label wraps to the first id)
        ids = rng.integers(0, vocab, (batch, seq, 1), dtype=np.int64)
        return ids, np.roll(ids, -1, axis=1)

    head_dim = config["d_model"] // config["n_head"]
    n = flops.transformer_matmul_params(
        config["d_model"], config["n_layer"], config["d_inner"], vocab)
    flash_flops, flash_bytes = flops.transformer_flash_step(
        batch, config["n_head"], seq, head_dim, config["n_layer"])
    return {
        "compiled": compiled,
        "loss": model["loss"],
        "feed_list": [model["src_ids"], model["tgt_label"]],
        "make_batch": make_batch,
        "items_per_step": batch * seq,
        "flops_per_item": flops.transformer_train_flops_per_token(
            n, config["d_model"], config["n_layer"], seq, causal=True),
        "kernel_work": {"flash": {"flops": flash_flops,
                                  "bytes": flash_bytes}},
    }
