"""Initializers — appended as startup-program ops, like the reference
(python/paddle/fluid/initializer.py:76 Constant..., :451 Xavier)."""

from __future__ import annotations

import numpy as np


class Initializer:
    def __call__(self, var, block):
        raise NotImplementedError


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def __call__(self, var, block):
        block.append_op(
            type="fill_constant",
            outputs={"Out": var},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "value": float(self.value)},
        )


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0, seed=0):
        self.low, self.high, self.seed = low, high, seed

    def __call__(self, var, block):
        block.append_op(
            type="uniform_random",
            outputs={"Out": var},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "min": float(self.low), "max": float(self.high),
                   "seed": self.seed},
        )


class Normal(Initializer):
    """fast: for models of 10^8 parameters and up.  The startup op
    draws float32 samples from a Generator seeded by one draw of the
    seeded stream (ops/basic.py gaussian_random), and is appended
    without append-time shape inference, which would run the op, that
    is draw the whole tensor on the host, once more at build time."""

    def __init__(self, loc=0.0, scale=1.0, seed=0, fast=False):
        self.loc, self.scale, self.seed = loc, scale, seed
        self.fast = fast

    def __call__(self, var, block):
        block.append_op(
            type="gaussian_random",
            outputs={"Out": var},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "mean": float(self.loc), "std": float(self.scale),
                   "seed": self.seed, "fast": self.fast},
            infer_shape=not self.fast,
        )


class TruncatedNormal(Initializer):
    def __init__(self, loc=0.0, scale=1.0, seed=0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, block):
        block.append_op(
            type="truncated_gaussian_random",
            outputs={"Out": var},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "mean": float(self.loc), "std": float(self.scale),
                   "seed": self.seed},
        )


def _fan_in_out(shape):
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    receptive = int(np.prod(shape[2:])) if len(shape) > 2 else 1
    fan_in = shape[1] * receptive if len(shape) > 2 else shape[0]
    fan_out = shape[0] * receptive if len(shape) > 2 else shape[1]
    return fan_in, fan_out


class Xavier(Initializer):
    """reference initializer.py:451 XavierInitializer."""

    def __init__(self, uniform=True, fan_in=None, fan_out=None, seed=0):
        self.uniform = uniform
        self.fan_in, self.fan_out = fan_in, fan_out
        self.seed = seed

    def __call__(self, var, block):
        fi, fo = _fan_in_out(var.shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        if self.uniform:
            limit = float(np.sqrt(6.0 / (fi + fo)))
            Uniform(-limit, limit, self.seed)(var, block)
        else:
            std = float(np.sqrt(2.0 / (fi + fo)))
            Normal(0.0, std, self.seed)(var, block)


class MSRA(Initializer):
    """Kaiming init (reference MSRAInitializer)."""

    def __init__(self, uniform=True, fan_in=None, seed=0):
        self.uniform = uniform
        self.fan_in = fan_in
        self.seed = seed

    def __call__(self, var, block):
        fi, _ = _fan_in_out(var.shape)
        fi = self.fan_in if self.fan_in is not None else fi
        if self.uniform:
            limit = float(np.sqrt(6.0 / fi))
            Uniform(-limit, limit, self.seed)(var, block)
        else:
            std = float(np.sqrt(2.0 / fi))
            Normal(0.0, std, self.seed)(var, block)


class NumpyArrayInitializer(Initializer):
    def __init__(self, value):
        self.value = np.asarray(value)

    def __call__(self, var, block):
        block.append_op(
            type="assign_value",
            outputs={"Out": var},
            attrs={"values": self.value, "dtype": var.dtype},
        )


class Bilinear(Initializer):
    """For conv2d_transpose upsampling weights (reference
    BilinearInitializer)."""

    def __call__(self, var, block):
        shape = var.shape
        if len(shape) != 4:
            raise ValueError("Bilinear initializer needs 4-D weights")
        c_in, c_out, h, w = shape
        f = np.ceil(w / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        weight = np.zeros(shape, dtype=np.float64)
        grid = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
        filt = (1 - np.abs(grid[0] / f - c)) * (1 - np.abs(grid[1] / f - c))
        for i in range(c_in):
            for j in range(c_out):
                weight[i, j] = filt
        NumpyArrayInitializer(weight.astype(var.dtype))(var, block)


# aliases matching the reference public API
ConstantInitializer = Constant
UniformInitializer = Uniform
NormalInitializer = Normal
TruncatedNormalInitializer = TruncatedNormal
XavierInitializer = Xavier
MSRAInitializer = MSRA
BilinearInitializer = Bilinear
