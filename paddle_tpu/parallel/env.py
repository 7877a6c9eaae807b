"""Parallel environment: the device mesh and ring-id -> mesh-axis mapping.

Reference parity: platform/nccl_helper.h NCCLContextMap (comm per ring_id &
device) and collective_helper.h NCCLCommContext.  On TPU a "ring" is a mesh
axis; collectives compile to XLA ops riding ICI (SURVEY.md §5 "Distributed
communication backend").
"""

from __future__ import annotations

from typing import Optional

import numpy as np

_current_mesh = None
# ring_id -> mesh axis name; ring 0 defaults to the data axis
_rings: dict = {}


def make_mesh(shape=None, axis_names=None, devices=None):
    """Build a jax.sharding.Mesh.  Default: 1-D mesh named 'dp' over all
    devices."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if shape is None:
        shape = (len(devices),)
        axis_names = axis_names or ("dp",)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, axis_names)


def make_hybrid_mesh(dcn_axes, ici_axes, devices=None):
    """Multi-slice mesh: outer axes ride DCN (between slices), inner
    axes ride ICI (within a slice) — the TPU-native replacement for the
    reference's hierarchical allreduce (platform/nccl_helper.h
    h_inter/exter_ctxs_, SURVEY.md §5): put data parallelism on the
    slow DCN axes and model/tensor axes on fast ICI, and XLA's
    collectives decompose along the hierarchy automatically.

    dcn_axes / ici_axes: {name: size} dicts (ordered).  On real
    multi-slice TPU pods the devices' slice topology drives placement
    via mesh_utils.create_hybrid_device_mesh; on a flat topology
    (CPU mesh, single slice) the same mesh is built by reshaping —
    axis semantics and sharding rules stay identical, so programs
    written against the hybrid mesh run anywhere.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    names = tuple(dcn_axes) + tuple(ici_axes)
    return Mesh(_hybrid_device_array(
        tuple(dcn_axes.values()), tuple(ici_axes.values()), devices,
        err_ctx=(dict(dcn_axes), dict(ici_axes))), names)


def _hybrid_device_array(dcn_shape, ici_shape, devices, err_ctx=None):
    """Device ndarray for make_hybrid_mesh, [*dcn, *ici]-shaped with
    each dcn index holding exactly one slice.  Separate from the Mesh
    wrapper so the multi-slice branch is testable with fake devices."""
    err_ctx = err_ctx or (dcn_shape, ici_shape)
    n_needed = int(np.prod(dcn_shape + ici_shape, dtype=np.int64))
    if n_needed != len(devices):
        raise ValueError(
            "hybrid mesh %s x %s needs %d devices, have %d"
            % (err_ctx[0], err_ctx[1], n_needed, len(devices)))
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    n_slices = 1 if None in slice_ids else len(slice_ids)
    if n_slices > 1:
        # real multi-slice topology: placement errors must propagate,
        # not silently degrade to a DCN-oblivious reshape
        if int(np.prod(dcn_shape, dtype=np.int64)) != n_slices:
            raise ValueError(
                "dcn axes %s (product %d) must cover the %d slices"
                % (err_ctx[0],
                   int(np.prod(dcn_shape, dtype=np.int64)), n_slices))
        from jax.experimental import mesh_utils

        # create_hybrid_device_mesh takes SAME-RANK shapes whose
        # elementwise product is the final mesh shape: pad each side
        # with 1s so every axis is purely-DCN or purely-ICI and the
        # result comes out [*dcn, *ici]-ordered directly
        ici_full = (1,) * len(dcn_shape) + ici_shape
        dcn_full = dcn_shape + (1,) * len(ici_shape)
        return mesh_utils.create_hybrid_device_mesh(
            ici_full, dcn_full, devices=devices)
    # flat topology (CPU mesh / single slice): plain reshape keeps the
    # axis semantics; only the physical placement differs
    return np.asarray(devices).reshape(dcn_shape + ici_shape)


def set_mesh(mesh):
    global _current_mesh
    _current_mesh = mesh
    for i, name in enumerate(mesh.axis_names):
        _rings.setdefault(i, name)
    return mesh


def get_mesh():
    return _current_mesh


def register_ring(ring_id: int, axis_name: str):
    _rings[ring_id] = axis_name


def ring_axis(ring_id: int) -> Optional[str]:
    return _rings.get(ring_id)


def reset():
    global _current_mesh
    _current_mesh = None
    _rings.clear()
