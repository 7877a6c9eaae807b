"""Program / Block / OpDesc / VarDesc — the serialized program IR.

Reference parity:
  - ProgramDesc/BlockDesc/OpDesc/VarDesc protos:
    /root/reference/paddle/fluid/framework/framework.proto:43,105,165,171,184
  - C++ wrappers: framework/program_desc.h:30, block_desc.h:38, op_desc.h:29
  - Python mirror: /root/reference/python/paddle/fluid/framework.py
    (Program :2775, Block :1436, Operator :985, Variable :376)

The IR is the unit of capture, transformation (autodiff, optimizers,
distribution transpilers) and serialization.  Execution happens by tracing a
Block's ops into a JAX function (compiler.py) or interpreting them
(executor.py).  Nested blocks (while/cond) are stored exactly like the
reference: an op attribute holding a block index.
"""

from __future__ import annotations

import copy
import json
from typing import Optional

import numpy as np

from paddle_tpu.core.types import VarType
from paddle_tpu.core.registry import get_op_def, has_op_def, REQUIRED

# Op role, mirroring reference op_proto_maker.h OpRole: lets transpilers and
# passes tell forward / backward / optimize ops apart.
FORWARD = "forward"
BACKWARD = "backward"
OPTIMIZE = "optimize"
RPC = "rpc"
LRSCHED = "lr_sched"
LOSS = "loss"
# what a training step says of itself (layers.step_stat: the write into
# a stat ring and the increment of its step counter): no gradient, not
# replayed by a recompute segment, dropped by clone(for_test=True)
STAT = "stat"


# active pipeline-stage annotation (reference: fluid.device_guard; ops
# appended inside `with pipeline_stage(i):` carry stage=i for
# PipelineOptimizer's program cut)
_CURRENT_STAGE = [None]

# active name scopes (framework.name_scope; reference: the op_namescope
# attr): ops appended inside carry scope="a/b", and every place that
# runs an op's compute (the compiled trace, a recompute segment's
# replay) does so under jax.named_scope(scope), so the scope is in the
# compiled step's op_name metadata
_NAME_SCOPE: list = []

# global IR mutation counter: bumped by every append_op / OpDesc.set_attr
# so compiled-program fingerprints (compiler._program_fingerprint) can
# memoize cheaply and revalidate on any structured IR edit
_IR_MUTATION = [0]


def ir_mutation_counter() -> int:
    return _IR_MUTATION[0]


def _bump_ir_mutation():
    _IR_MUTATION[0] += 1


def op_scope(op):
    """The context an op's compute runs in, at the two sites that run
    one (core/compiler.py `_run_block_symbolic`, the replay of a
    recompute segment in ops/misc.py): jax.named_scope of its owner,
    `pt_<op_role>.<type>`, then of the name scope it was appended
    under.  The compiled step's instructions carry the path as
    `op_name` metadata (observability/step_owners.py reads it); the
    module itself does not depend on it."""
    import jax

    owner = "pt_%s.%s" % (op.op_role, op.type)
    return jax.named_scope(owner + "/" + op.scope if op.scope else owner)


class pipeline_stage:
    """Context manager annotating appended ops with a pipeline stage."""

    def __init__(self, idx: int):
        self.idx = int(idx)

    def __enter__(self):
        self._prev = _CURRENT_STAGE[0]
        _CURRENT_STAGE[0] = self.idx
        return self

    def __exit__(self, *exc):
        _CURRENT_STAGE[0] = self._prev
        return False


class BlockRef:
    """Attribute value referring to a sub-block (reference: AttrType BLOCK)."""

    __slots__ = ("idx",)

    def __init__(self, idx: int):
        self.idx = idx

    def __repr__(self):
        return f"BlockRef({self.idx})"

    def __eq__(self, other):
        return isinstance(other, BlockRef) and other.idx == self.idx


def _normalize_sharding(spec):
    """Canonical annotation form: a tuple over dims whose entries are
    None, a str axis name, or a tuple of str axis names — so a
    to_dict/from_dict round-trip (JSON turns tuples into lists) and a
    live annotation compare equal."""
    if spec is None:
        return None
    try:
        from jax.sharding import PartitionSpec as _P

        if isinstance(spec, _P):
            spec = tuple(spec)
    except ImportError:
        pass
    out = []
    for entry in spec:
        if entry is None or isinstance(entry, str):
            out.append(entry)
        elif isinstance(entry, (list, tuple)):
            if not all(isinstance(a, str) for a in entry):
                raise ValueError(
                    f"sharding entry {entry!r}: axis names must be str")
            out.append(tuple(entry))
        else:
            raise ValueError(
                f"sharding entry {entry!r}: expected None, an axis "
                "name, or a tuple of axis names")
    return tuple(out)


class VarDesc:
    """A named variable in a block; doubles as the Python front-end handle
    (reference keeps VarDesc and python Variable separate; we fuse them)."""

    def __init__(
        self,
        block: "Block",
        name: str,
        shape=None,
        dtype="float32",
        type: VarType = VarType.DENSE_TENSOR,
        persistable: bool = False,
        stop_gradient: bool = False,
        trainable: bool = False,
        is_data: bool = False,
    ):
        self.block = block
        self.name = name
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = str(np.dtype(dtype)) if dtype is not None else None
        self.type = type
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.trainable = trainable
        self.is_data = is_data
        # optional sharding annotation: PartitionSpec-like tuple, one
        # entry per dim — None (replicated), an axis name, or a tuple
        # of axis names (a dim sharded over several mesh axes, e.g.
        # ZeRO-3 dp on top of a tp row split).  Set via set_sharding so
        # compiled-program fingerprints see the edit; consumed by
        # transpiler.sharding_transpiler (docs/GSPMD.md).
        self.sharding = None
        # error-clip attr: clips this var's upstream error gradient the
        # moment append_backward produces it (reference clip.py:42)
        self.error_clip = None

    def set_sharding(self, spec):
        """Annotate this var with a PartitionSpec-like tuple (one entry
        per dim: None | axis name | tuple of axis names), or None to
        clear.  Goes through the IR mutation counter so an annotation
        edit after a compile invalidates the jit cache the same way an
        op edit does (compiler._program_fingerprint hashes both)."""
        self.sharding = _normalize_sharding(spec)
        _bump_ir_mutation()
        return self

    def _set_error_clip(self, clip):
        """Reference framework.py Variable._set_error_clip."""
        from paddle_tpu.clip import BaseErrorClipAttr

        if not isinstance(clip, BaseErrorClipAttr):
            raise TypeError(
                "error_clip must be an instance of BaseErrorClipAttr")
        self.error_clip = clip

    # -- convenience used by layers ------------------------------------------------
    @property
    def ndim(self):
        return None if self.shape is None else len(self.shape)

    def astype(self, dtype):
        from paddle_tpu import layers

        return layers.cast(self, dtype)

    def __repr__(self):
        return (
            f"Var(name={self.name!r}, shape={self.shape}, dtype={self.dtype},"
            f" type={self.type.name}{', persistable' if self.persistable else ''})"
        )

    # arithmetic sugar (reference: python Variable monkey-patched operators,
    # framework.py monkey_patch_variable)
    def _binary(self, other, op, reverse=False):
        from paddle_tpu import layers

        if not isinstance(other, VarDesc):
            other = layers.fill_constant(
                shape=self.shape if self.shape else [1],
                dtype=self.dtype,
                value=float(other),
            )
        a, b = (other, self) if reverse else (self, other)
        return layers.elementwise_op(op, a, b)

    def __add__(self, o):
        return self._binary(o, "elementwise_add")

    def __radd__(self, o):
        return self._binary(o, "elementwise_add", True)

    def __sub__(self, o):
        return self._binary(o, "elementwise_sub")

    def __rsub__(self, o):
        return self._binary(o, "elementwise_sub", True)

    def __mul__(self, o):
        return self._binary(o, "elementwise_mul")

    def __rmul__(self, o):
        return self._binary(o, "elementwise_mul", True)

    def __truediv__(self, o):
        return self._binary(o, "elementwise_div")

    def __rtruediv__(self, o):
        return self._binary(o, "elementwise_div", True)

    def __neg__(self):
        from paddle_tpu import layers

        return layers.scale(self, scale=-1.0)

    def to_dict(self):
        return {
            "name": self.name,
            "shape": list(self.shape) if self.shape is not None else None,
            "dtype": self.dtype,
            "type": self.type.name,
            "persistable": self.persistable,
            "stop_gradient": self.stop_gradient,
            "trainable": self.trainable,
            "is_data": self.is_data,
            "sharding": [list(e) if isinstance(e, tuple) else e
                         for e in self.sharding]
            if self.sharding else None,
        }

    @staticmethod
    def from_dict(block, d):
        v = VarDesc(
            block,
            d["name"],
            shape=d["shape"],
            dtype=d["dtype"],
            type=VarType[d["type"]],
            persistable=d["persistable"],
            stop_gradient=d["stop_gradient"],
            trainable=d.get("trainable", False),
            is_data=d.get("is_data", False),
        )
        if d.get("sharding"):
            v.sharding = _normalize_sharding(d["sharding"])
        return v


class OpDesc:
    """One operation: type + named input/output var lists + attrs.

    inputs/outputs: {slot: [var_name, ...]} — always lists, like the
    reference proto (framework.proto OpDesc.Var).
    """

    def __init__(self, type: str, inputs=None, outputs=None, attrs=None,
                 op_role: str = FORWARD, stage=None, scope=None):
        self.type = type
        self.inputs = {k: list(v) for k, v in (inputs or {}).items()}
        self.outputs = {k: list(v) for k, v in (outputs or {}).items()}
        self.attrs = dict(attrs or {})
        self.op_role = op_role
        # pipeline stage annotation (reference: the op_device attr set by
        # device_guard that PipelineOptimizer cuts the program at).  None
        # = unannotated; PipelineOptimizer infers by dataflow.
        self.stage = stage
        self.scope = scope      # "a/b" of framework.name_scope, or None

    def set_attr(self, name, value):
        """In-place attr edit visible to compiled-program caching (a raw
        `op.attrs[k] = v` write is NOT — see _program_fingerprint)."""
        self.attrs[name] = value
        _bump_ir_mutation()

    def input_names(self):
        out = []
        for names in self.inputs.values():
            out.extend(names)
        return out

    def output_names(self):
        out = []
        for names in self.outputs.values():
            out.extend(names)
        return out

    def __repr__(self):
        ins = {k: v for k, v in self.inputs.items()}
        outs = {k: v for k, v in self.outputs.items()}
        return f"Op({self.type}, in={ins}, out={outs})"

    def to_dict(self):
        attrs = {}
        for k, v in self.attrs.items():
            if isinstance(v, BlockRef):
                attrs[k] = {"__block__": v.idx}
            elif isinstance(v, np.ndarray):
                attrs[k] = {
                    "__ndarray__": v.tolist(),
                    "dtype": str(v.dtype),
                }
            elif isinstance(v, (np.integer,)):
                attrs[k] = int(v)
            elif isinstance(v, (np.floating,)):
                attrs[k] = float(v)
            else:
                attrs[k] = v
        out = {
            "type": self.type,
            "inputs": self.inputs,
            "outputs": self.outputs,
            "attrs": attrs,
            "op_role": self.op_role,
        }
        if self.stage is not None:
            out["stage"] = self.stage
        if self.scope:
            out["scope"] = self.scope
        return out

    @staticmethod
    def from_dict(d):
        attrs = {}
        for k, v in d["attrs"].items():
            if isinstance(v, dict) and "__block__" in v:
                attrs[k] = BlockRef(v["__block__"])
            elif isinstance(v, dict) and "__ndarray__" in v:
                attrs[k] = np.asarray(v["__ndarray__"], dtype=v["dtype"])
            else:
                attrs[k] = v
        return OpDesc(
            d["type"], d["inputs"], d["outputs"], attrs,
            d.get("op_role", FORWARD), d.get("stage"), d.get("scope"),
        )


class Block:
    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: dict = {}
        self.ops: list = []

    @property
    def parent(self) -> Optional["Block"]:
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    # -- variables ---------------------------------------------------------------
    def create_var(self, name=None, **kwargs) -> VarDesc:
        from paddle_tpu import unique_name

        if name is None:
            name = unique_name.generate("_generated_var")
        if name in self.vars:
            return self.vars[name]
        v = VarDesc(self, name, **kwargs)
        self.vars[name] = v
        return v

    def create_parameter(self, name, shape, dtype, **kwargs) -> VarDesc:
        """A second call under a name that exists is SHARING: it returns
        the one VarDesc, and raises where shape or dtype differ (a layer
        built twice over one ParamAttr name reads one parameter)."""
        have = self.vars.get(name)
        if have is not None:
            if tuple(have.shape or ()) != tuple(shape) or \
                    str(have.dtype) != str(dtype):
                raise ValueError(
                    f"parameter '{name}' is shared by name: it exists as "
                    f"{tuple(have.shape or ())} {have.dtype}, asked for "
                    f"again as {tuple(shape)} {dtype}")
            return have
        v = self.create_var(
            name, shape=shape, dtype=dtype, persistable=True, trainable=True,
            **kwargs,
        )
        v.trainable = True
        v.persistable = True
        return v

    def var(self, name) -> VarDesc:
        """Find in this block or ancestors (reference Block::FindVarRecursive)."""
        b = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent
        raise KeyError(f"variable '{name}' not found in block {self.idx}")

    def has_var(self, name) -> bool:
        try:
            self.var(name)
            return True
        except KeyError:
            return False

    # -- ops ---------------------------------------------------------------------
    def append_op(self, type, inputs=None, outputs=None, attrs=None,
                  op_role=FORWARD, infer_shape=True) -> OpDesc:
        """Validates against the registry and best-effort infers output
        shapes/dtypes (reference: compile-time InferShape)."""
        inputs = {
            k: ([v] if isinstance(v, (VarDesc, str)) else list(v))
            for k, v in (inputs or {}).items()
            if v is not None
        }
        outputs = {
            k: ([v] if isinstance(v, (VarDesc, str)) else list(v))
            for k, v in (outputs or {}).items()
            if v is not None
        }
        in_names = {
            k: [v.name if isinstance(v, VarDesc) else v for v in vs]
            for k, vs in inputs.items()
        }
        out_names = {
            k: [v.name if isinstance(v, VarDesc) else v for v in vs]
            for k, vs in outputs.items()
        }
        op_def = get_op_def(type)
        attrs = op_def.canonical_attrs(attrs or {})
        op = OpDesc(type, in_names, out_names, attrs, op_role,
                    stage=_CURRENT_STAGE[0],
                    scope="/".join(_NAME_SCOPE) or None)
        self.ops.append(op)
        _bump_ir_mutation()
        if infer_shape and not op_def.host_only:
            self._infer_shape(op, op_def)
        return op

    def _infer_shape(self, op: OpDesc, op_def):
        import jax

        from paddle_tpu.core import registry

        ins_specs = {}
        ok = True
        for slot, names in op.inputs.items():
            specs = []
            for n in names:
                try:
                    v = self.var(n)
                except KeyError:
                    ok = False
                    break
                if v.shape is None or v.dtype is None:
                    ok = False
                    break
                specs.append(
                    jax.ShapeDtypeStruct(
                        tuple(v.shape), np.dtype(v.dtype)
                    )
                )
            if not ok:
                break
            if slot in op_def.duplicable:
                ins_specs[slot] = specs
            elif specs:
                ins_specs[slot] = specs[0]
        if not ok:
            return
        out = registry.infer_shapes(op_def, ins_specs, op.attrs,
                                    strict=(self.idx == 0))
        if out is None:
            return
        for slot, names in op.outputs.items():
            if slot not in out:
                continue
            specs = out[slot]
            if not isinstance(specs, list):
                specs = [specs]
            for n, spec in zip(names, specs):
                try:
                    v = self.var(n)
                except KeyError:
                    continue
                if v.shape is None:
                    v.shape = tuple(spec.shape)
                if v.dtype is None:
                    v.dtype = str(np.dtype(spec.dtype))

    def prepend_op(self, *args, **kwargs) -> OpDesc:
        op = self.append_op(*args, **kwargs)
        self.ops.insert(0, self.ops.pop())
        return op

    def to_dict(self):
        return {
            "idx": self.idx,
            "parent_idx": self.parent_idx,
            "vars": [v.to_dict() for v in self.vars.values()],
            "ops": [op.to_dict() for op in self.ops],
        }


class Program:
    """Reference: python/paddle/fluid/framework.py:2775 Program."""

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed = 0
        self._op_role = FORWARD

    # -- blocks ------------------------------------------------------------------
    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def _create_block(self, parent_idx=None) -> Block:
        parent = (
            self.current_block_idx if parent_idx is None else parent_idx
        )
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    # -- introspection ------------------------------------------------------------
    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    def all_parameters(self):
        return [
            v
            for v in self.list_vars()
            if v.trainable and v.persistable
        ]

    def persistables(self):
        return [v for v in self.list_vars() if v.persistable]

    def clone(self, for_test: bool = False) -> "Program":
        """Deep structural copy.  for_test=True drops backward/optimize ops
        and a training step's stat writes (their rings stay, unwritten)
        and switches train-only attrs (reference Program.clone
        framework.py:2950: test mode for dropout/batch_norm)."""
        p = Program()
        p.random_seed = self.random_seed
        p.blocks = []
        for b in self.blocks:
            nb = Block(p, b.idx, b.parent_idx)
            for v in b.vars.values():
                nv = VarDesc.from_dict(nb, v.to_dict())
                # python-side attrs that don't serialize: carried across
                # clone so a pre-transpile clone keeps its semantics
                nv.error_clip = v.error_clip
                nv.sharding = v.sharding
                nb.vars[v.name] = nv
            for op in b.ops:
                if for_test and op.op_role in (BACKWARD, OPTIMIZE, STAT):
                    continue
                nop = OpDesc.from_dict(op.to_dict())
                if for_test and "is_test" in nop.attrs:
                    nop.attrs["is_test"] = True
                nb.ops.append(nop)
            p.blocks.append(nb)
        p.current_block_idx = 0
        return p

    # -- serialization ------------------------------------------------------------
    def to_dict(self):
        return {
            "version": 1,
            "random_seed": self.random_seed,
            "blocks": [b.to_dict() for b in self.blocks],
        }

    def to_bytes(self) -> bytes:
        return json.dumps(self.to_dict()).encode("utf-8")

    @staticmethod
    def from_dict(d) -> "Program":
        p = Program()
        p.random_seed = d.get("random_seed", 0)
        p.blocks = []
        for bd in d["blocks"]:
            b = Block(p, bd["idx"], bd["parent_idx"])
            for vd in bd["vars"]:
                b.vars[vd["name"]] = VarDesc.from_dict(b, vd)
            for od in bd["ops"]:
                b.ops.append(OpDesc.from_dict(od))
            p.blocks.append(b)
        if not p.blocks:
            p.blocks = [Block(p, 0)]
        return p

    @staticmethod
    def parse_from_bytes(data: bytes) -> "Program":
        return Program.from_dict(json.loads(data.decode("utf-8")))

    def __repr__(self):
        lines = []
        for b in self.blocks:
            lines.append(f"-- block {b.idx} (parent {b.parent_idx}) --")
            for v in b.vars.values():
                lines.append(f"  {v!r}")
            for op in b.ops:
                lines.append(f"  {op!r}")
        return "\n".join(lines)
