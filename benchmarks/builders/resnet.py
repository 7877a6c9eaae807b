"""Builder for the ImageNet ResNet configurations (models/resnet.py),
as a Fluid trainer writes it: layers.* -> nhwc_transpile -> AMP
decorate -> Momentum(L2Decay).minimize -> CompiledProgram.  Images are
float32 NCHW from the host, as Fluid's image readers deliver them.
"""

from __future__ import annotations

import numpy as np


def build(config, job, flops):
    import paddle_tpu as fluid
    from paddle_tpu import framework, optimizer, regularizer
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.flags import set_flags
    from paddle_tpu.models.resnet import resnet
    from paddle_tpu.transpiler import nhwc_transpile

    set_flags({"gspmd": False})
    size, batch = config["image_size"], job["batch"]
    classes = config["num_classes"]
    model = resnet(depth=config["depth"], num_classes=classes,
                   image_shape=(3, size, size), is_test=False)
    if config["nhwc"]:
        nhwc_transpile(framework.default_main_program())
    opt = optimizer.Momentum(
        learning_rate=config["learning_rate"],
        momentum=config["momentum"],
        regularization=regularizer.L2Decay(config["weight_decay"]))
    if config["amp"]:
        opt = decorate(opt, init_loss_scaling=1.0,
                       use_dynamic_loss_scaling=False)
    opt.minimize(model["loss"])
    compiled = fluid.CompiledProgram(fluid.default_main_program())

    def make_batch(rng):
        image = rng.random((batch, 3, size, size), dtype=np.float32)
        label = rng.integers(0, classes, (batch, 1), dtype=np.int64)
        return image, label

    return {
        "compiled": compiled,
        "loss": model["loss"],
        "feed_list": [model["image"], model["label"]],
        "make_batch": make_batch,
        "items_per_step": batch,
        "flops_per_item": flops.resnet_train_flops_per_image(
            config["depth"], size, classes),
        "kernel_work": {},
    }
