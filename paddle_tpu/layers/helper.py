"""LayerHelper: parameter creation + op appending glue used by every layer.

Reference parity: /root/reference/python/paddle/fluid/layer_helper.py:42
(append_op), layer_helper_base.py:252 (create_parameter with initializer /
regularizer hookup).
"""

from __future__ import annotations

import numpy as np

from paddle_tpu import unique_name
from paddle_tpu.framework import default_main_program, default_startup_program


class LayerHelper:
    def __init__(self, layer_type, **kwargs):
        self.layer_type = layer_type
        self.kwargs = kwargs
        if kwargs.get("name") is None:
            self.name = unique_name.generate(layer_type)
        else:
            self.name = kwargs["name"]

    @property
    def main_program(self):
        return default_main_program()

    @property
    def startup_program(self):
        return default_startup_program()

    @property
    def block(self):
        return self.main_program.current_block()

    def create_variable_for_type_inference(self, dtype, stop_gradient=False):
        return self.block.create_var(
            name=unique_name.generate(self.name + ".tmp"),
            dtype=dtype,
            shape=None,
            stop_gradient=stop_gradient,
        )

    def create_parameter(
        self,
        attr,
        shape,
        dtype,
        is_bias=False,
        default_initializer=None,
    ):
        """attr: ParamAttr or None.  Adds the param var to BOTH main and
        startup global blocks and appends its initializer op to the startup
        program (reference layer_helper_base.py:252)."""
        from paddle_tpu.initializer import Constant, Xavier
        from paddle_tpu.param_attr import ParamAttr

        attr = ParamAttr._to_attr(attr)
        suffix = "b" if is_bias else "w"
        name = attr.name or unique_name.generate(
            f"{self.name}.{suffix}"
        )
        shape = [int(s) for s in shape]
        main_block = self.block.program.global_block()
        startup_block = self.startup_program.global_block()
        shared = name in main_block.vars
        # a name that exists is SHARED (a stack of layers run several
        # times over one set of weights; a test program built beside the
        # train program): ONE VarDesc, whose shape and dtype the second
        # asker has to match and whose attributes the first one set
        main_param = main_block.create_parameter(name, shape, dtype)
        if not shared:
            main_param.stop_gradient = not attr.trainable
            main_param.trainable = attr.trainable
            main_param.regularizer = attr.regularizer
        if name in startup_block.vars:
            # and ONE initializer op: one draw from the seed however
            # many layers read the parameter
            startup_block.create_parameter(name, shape, dtype)
            return main_param
        init = (
            attr.initializer
            or default_initializer
            or (Constant(0.0) if is_bias else Xavier())
        )
        sv = startup_block.create_parameter(name, shape, dtype)
        sv.trainable = attr.trainable
        init(sv, startup_block)
        return main_param

    def append_op(self, **kwargs):
        return self.block.append_op(**kwargs)

    def input(self, name):
        return self.kwargs[name]

    def append_activation(self, out_var, act):
        if act is None:
            return out_var
        act_out = self.create_variable_for_type_inference(out_var.dtype)
        self.block.append_op(
            type=act, inputs={"X": out_var}, outputs={"Out": act_out}
        )
        return act_out
