"""Plain reference of the ImageNet ResNet configurations: forward pass
and loss in straightforward jax.numpy / lax, float32, precision
"highest", NCHW, no AMP, no layout rewrite.

Follows He et al. 2015 with the stride on the 3x3 convolution of a
bottleneck (v1.5), as models/resnet.py builds it: 7x7/2 stem, 3x3/2
max-pool, four stages, 1x1 projection shortcuts where the shape
changes, global average pool, one fully connected layer, mean softmax
cross-entropy.  Batch normalisation is in training mode: statistics of
the whole batch, biased variance, epsilon 1e-5.

Weights are read by the names the program gives them, in the order the
builder creates them: conv2d_<i>.w_0 and batch_norm_<i>.w_0/.b_0 count
up together, and a block's shortcut comes after its main path.
"""

from __future__ import annotations

import functools

import numpy as np

BN_EPS = 1e-5
_DEPTHS = {18: ("basic", (2, 2, 2, 2)), 34: ("basic", (3, 4, 6, 3)),
           50: ("bottleneck", (3, 4, 6, 3)),
           101: ("bottleneck", (3, 4, 23, 3)),
           152: ("bottleneck", (3, 8, 36, 3))}


def _plan(depth):
    """[(kind, filters, stride)] for every block."""
    kind, counts = _DEPTHS[depth]
    return kind, [(64 * 2 ** stage, 2 if i == 0 and stage > 0 else 1)
                  for stage, count in enumerate(counts)
                  for i in range(count)]


def read_params(config, get):
    """The program's own weights as float32 copies: a list of
    (filter, scale, bias) in creation order, and the classifier."""
    import jax.numpy as jnp

    def f32(name):
        return jnp.array(get(name), dtype=jnp.float32, copy=True)

    convs, i = [], 0
    while True:
        try:
            convs.append((f32("conv2d_%d.w_0" % i),
                          f32("batch_norm_%d.w_0" % i),
                          f32("batch_norm_%d.b_0" % i)))
        except KeyError:
            break
        i += 1
    return {"convs": convs, "fc_w": f32("fc_0.w_0"), "fc_b": f32("fc_0.b_0")}


def _conv_bn(x, w, g, b, stride, relu):
    import jax
    import jax.numpy as jnp

    pad = (w.shape[2] - 1) // 2
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    mu = y.mean((0, 2, 3), keepdims=True)
    var = ((y - mu) ** 2).mean((0, 2, 3), keepdims=True)
    y = (y - mu) / jnp.sqrt(var + BN_EPS) * g[None, :, None, None] \
        + b[None, :, None, None]
    return jax.nn.relu(y) if relu else y


def forward_loss(params, image, label, depth):
    import jax
    import jax.numpy as jnp

    kind, blocks = _plan(depth)
    convs = iter(params["convs"])
    x = _conv_bn(image, *next(convs), 2, True)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        [(0, 0), (0, 0), (1, 1), (1, 1)])
    for filters, stride in blocks:
        cin = x.shape[1]
        if kind == "bottleneck":
            cout = 4 * filters
            y = _conv_bn(x, *next(convs), 1, True)
            y = _conv_bn(y, *next(convs), stride, True)
            y = _conv_bn(y, *next(convs), 1, False)
        else:
            cout = filters
            y = _conv_bn(x, *next(convs), stride, True)
            y = _conv_bn(y, *next(convs), 1, False)
        if cin != cout or stride != 1:
            x = _conv_bn(x, *next(convs), stride, False)
        x = jax.nn.relu(x + y)
    if next(convs, None) is not None:
        raise ValueError("the program holds more convolutions than a "
                         "ResNet-%d" % depth)
    logits = x.mean((2, 3)) @ params["fc_w"] + params["fc_b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, label, axis=1).mean()


@functools.lru_cache(maxsize=None)
def _jitted(depth):
    import jax

    def f(params, image, label):
        with jax.default_matmul_precision("highest"):
            return forward_loss(params, image, label, depth)

    return jax.jit(f)


def loss(params, batch, config):
    """Mean cross-entropy of the batch (image [B,3,H,W] float32, label
    [B,1] int)."""
    import jax.numpy as jnp

    image, label = batch
    return float(_jitted(config["depth"])(
        params, jnp.asarray(image),
        jnp.asarray(np.asarray(label).astype(np.int32))))
