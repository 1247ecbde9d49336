"""Device-mesh construction and TPU topology discovery.

This is the TPU-native replacement for the reference's communicator split
(``horovod/common/mpi/mpi_context.cc:147-160`` builds GLOBAL / LOCAL / CROSS
MPI communicators; NCCL forms per-node cliques in
``nccl_operations.cc:59-92``).  On TPU the same three-way split falls out of
the physical fabric:

* ``dp``   — data-parallel axis (the only axis the reference has),
* ``ici``  — devices sharing an ICI slice (reference: LOCAL / intra-node),
* ``dcn``  — slices connected over data-center network (reference: CROSS).

plus model axes (``tp``, ``pp``, ``sp``, ``ep``) the reference never had but
which a complete TPU framework must carry (SURVEY.md §5 long-context note).

Everything here is plain ``jax.sharding`` — collectives are inserted by XLA
from shardings + ``shard_map`` axis names, never hand-scheduled.
"""

from __future__ import annotations

import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# Canonical axis names.  Order matters: leftmost axes change slowest across
# the device list, so putting ``dcn``/``pp`` first keeps their collectives on
# the slow links and lets ``tp``/``sp`` ride adjacent-ICI neighbors.
DATA_AXIS = "dp"
MODEL_AXIS = "tp"
PIPELINE_AXIS = "pp"
SEQUENCE_AXIS = "sp"
EXPERT_AXIS = "ep"
CROSS_AXIS = "dcn"

_ALL_AXES = (CROSS_AXIS, PIPELINE_AXIS, DATA_AXIS, EXPERT_AXIS,
             SEQUENCE_AXIS, MODEL_AXIS)


def num_slices() -> int:
    """Number of ICI slices (DCN-connected groups) visible to this process.

    Reads JAX device attributes when available (``slice_index`` on real TPU
    pods); virtual/CPU devices report one slice.
    """
    import jax

    idx = set()
    for d in jax.devices():
        idx.add(getattr(d, "slice_index", 0))
    return max(1, len(idx))


def _factor_remaining(total: int, sizes: Dict[str, int]) -> Dict[str, int]:
    """Fill in any axis size given as -1 so the product matches ``total``."""
    known = 1
    unknown = None
    for name, s in sizes.items():
        if s == -1:
            if unknown is not None:
                raise ValueError("at most one axis may be -1")
            unknown = name
        else:
            known *= s
    if unknown is not None:
        if total % known != 0:
            raise ValueError(
                f"cannot infer axis {unknown!r}: {total} devices not "
                f"divisible by {known}")
        sizes = dict(sizes)
        sizes[unknown] = total // known
    return sizes


def make_mesh(
    axes: Optional[Dict[str, int]] = None,
    *,
    devices: Optional[Sequence] = None,
    allow_split_physical_axes: bool = True,
):
    """Build a ``jax.sharding.Mesh``.

    ``axes`` maps axis name -> size; one size may be ``-1`` (inferred).  With
    no arguments you get a pure data-parallel mesh over every device — the
    Horovod default (one DP rank per chip).

    On real TPU hardware ``jax.experimental.mesh_utils`` picks a device
    order that keeps each named axis on physically adjacent chips so XLA's
    collectives ride ICI rings; CPU test meshes are a plain reshape.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axes is None:
        axes = {DATA_AXIS: n}
    axes = _factor_remaining(n, dict(axes))
    sizes = list(axes.values())
    names = list(axes.keys())
    if math.prod(sizes) != n:
        raise ValueError(
            f"mesh axes {axes} require {math.prod(sizes)} devices, "
            f"have {n}")

    platform = devices[0].platform if devices else "cpu"
    if platform == "tpu":
        from jax.experimental import mesh_utils

        try:
            dev_array = mesh_utils.create_device_mesh(
                sizes, devices=list(devices),
                allow_split_physical_axes=allow_split_physical_axes)
        except Exception as e:
            # Any topology mesh_utils cannot lay out (odd axis products,
            # a partial slice): the mesh is still valid, its axes just
            # follow enumeration order instead of ICI adjacency.
            from horovod_tpu.utils.logging import get_logger

            get_logger().warning(
                "create_device_mesh(%s) failed (%s: %s); mesh axes follow "
                "device enumeration order, not ICI adjacency",
                sizes, type(e).__name__, e)
            dev_array = np.array(list(devices)).reshape(sizes)
    else:
        dev_array = np.array(list(devices)).reshape(sizes)
    return jax.sharding.Mesh(dev_array, names)


def make_hierarchical_mesh(
    *,
    devices: Optional[Sequence] = None,
    inner_axes: Optional[Dict[str, int]] = None,
):
    """Mesh with an explicit ``dcn`` outer axis over ICI slices.

    TPU analog of the reference's hierarchical allreduce topology
    (``nccl_operations.cc:163-354``: NCCL within a node, MPI across): the
    ``dcn`` axis spans slices, remaining axes span the chips of one slice.
    On a single slice this degenerates to ``dcn=1`` so code written against
    the hierarchical mesh runs unchanged everywhere.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    slices: Dict[int, List] = {}
    for d in devices:
        slices.setdefault(getattr(d, "slice_index", 0), []).append(d)
    n_slices = len(slices)
    per = len(devices) // n_slices
    if inner_axes is None:
        inner_axes = {DATA_AXIS: per}
    inner_axes = _factor_remaining(per, dict(inner_axes))
    ordered = []
    for k in sorted(slices):
        ordered.extend(slices[k])
    sizes = [n_slices] + list(inner_axes.values())
    names = [CROSS_AXIS] + list(inner_axes.keys())
    dev_array = np.array(ordered).reshape(sizes)
    return jax.sharding.Mesh(dev_array, names)


def mesh_axis_size(mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def filter_spec(spec, mesh):
    """Drop PartitionSpec axes that are not in ``mesh`` (→ None).

    Lets models annotate the full axis vocabulary (dp/tp/sp/ep/…) while
    running on meshes that carry any subset.  Handles tuple entries
    (sharding one dim over several axes) by filtering within the tuple.
    """
    from jax.sharding import PartitionSpec as P

    def keep(ax):
        if ax is None:
            return None
        if isinstance(ax, (tuple, list)):
            kept = tuple(a for a in ax if a in mesh.shape)
            return kept if kept else None
        return ax if ax in mesh.shape else None

    return P(*[keep(ax) for ax in spec])


def sharding_for(mesh, spec):
    """``NamedSharding`` for ``spec`` on ``mesh`` with axes the mesh
    doesn't carry dropped (``filter_spec``) — the one-liner every
    consumer of a full-vocabulary spec ends up writing (e.g. the serving
    KV caches, serving/decode.py)."""
    import jax

    return jax.sharding.NamedSharding(mesh, filter_spec(spec, mesh))


def data_parallel_axes(mesh) -> Tuple[str, ...]:
    """Axes that carry gradient reduction: every mesh axis that is a
    replication axis for parameters (dp, dcn and ep-for-non-expert params
    are handled by callers; default is dp + dcn when present)."""
    out = []
    for ax in (CROSS_AXIS, DATA_AXIS):
        if ax in mesh.shape:
            out.append(ax)
    return tuple(out)
