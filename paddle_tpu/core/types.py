"""Device places and variable types.

Reference parity:
  - Place variant: /root/reference/paddle/fluid/platform/place.h:26-81
    (CPUPlace / CUDAPlace / CUDAPinnedPlace).  Here a Place is kept for
    API parity only: it decides nothing.  XLA's default device
    (jax.devices()[0], chosen by JAX_PLATFORMS / the installed backend)
    owns placement for both executors, so Executor(TPUPlace()) on a
    machine without a chip and Executor(CPUPlace()) on a machine with
    one both run on JAX's default device.  Code that must run on the
    chip asserts the platform and where its state lives itself
    (chip_smoke.py, benchmarks/run.py).  CUDAPlace is accepted as an alias so
    reference user code ports cleanly.
  - VarType enum: /root/reference/paddle/fluid/framework/framework.proto:105-165
"""

from __future__ import annotations

import enum


class VarType(enum.Enum):
    # Tensor variants (reference framework.proto VarType.Type)
    DENSE_TENSOR = "dense_tensor"        # reference LOD_TENSOR; ragged-ness is
                                         # carried by explicit seq_lens tensors
    SELECTED_ROWS = "selected_rows"      # sparse rows {rows, values}
    TENSOR_ARRAY = "tensor_array"        # list of tensors (while-loop carries)
    READER = "reader"                    # data source endpoint
    STEP_SCOPES = "step_scopes"          # control-flow sub-scopes
    RAW = "raw"                          # opaque host object

    # alias used in a few reference-style APIs
    LOD_TENSOR = "dense_tensor"


class Place:
    """A reference-API device label (module docstring): carried by
    Executor for parity, never read for placement — XLA's default
    device owns that."""

    device_id: int = 0

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def __eq__(self, other):
        return (
            type(self) is type(other) and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))


class CPUPlace(Place):
    pass


class TPUPlace(Place):
    pass


class CUDAPlace(TPUPlace):
    """Alias: reference code written against CUDAPlace runs on the TPU."""


class CUDAPinnedPlace(CPUPlace):
    pass
