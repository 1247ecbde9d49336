"""In-graph collective ops: the TPU data plane.

These are the XLA-native equivalents of the reference's backend ops
(``horovod/common/ops/nccl_operations.cc``, ``mpi_operations.cc``,
``gloo_operations.cc``).  Instead of launching NCCL/MPI from a background
thread, each op lowers to an XLA HLO collective (all-reduce, all-gather,
all-to-all, collective-permute) over named mesh axes inside ``shard_map`` /
``pjit`` — XLA schedules them onto the ICI rings and overlaps them with
compute, which subsumes the reference's hand-rolled stream management
(``gpu_operations.h:49-63``).

Every function takes ``axis``: one mesh axis name or a tuple of names.  Use
them inside ``shard_map``/``pjit`` bodies; outside a trace use
``horovod_tpu.allreduce`` etc., which dispatch to the process-level runtime.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.types import ReduceOp

AxisSpec = Union[str, Sequence[str]]


def _axes(axis: AxisSpec) -> Tuple[str, ...]:
    if isinstance(axis, str):
        return (axis,)
    return tuple(axis)


def axis_size(axis: AxisSpec) -> int:
    return lax.axis_size(_axes(axis))


def axis_index(axis: AxisSpec):
    """Linearized index of this shard along ``axis`` (row-major over the
    given axis tuple)."""
    axes = _axes(axis)
    idx = lax.axis_index(axes[0])
    for ax in axes[1:]:
        idx = idx * lax.axis_size(ax) + lax.axis_index(ax)
    return idx


def allreduce(
    x,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis: AxisSpec = "dp",
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
):
    """All-reduce over mesh axes.  Parity: ``NCCLAllreduce::Execute``
    (nccl_operations.cc:109-159) — one fused device collective; pre/post
    scaling mirrors the v2 torch binding's prescale/postscale arguments.

    Average divides by the *total* size of the reduction axes, matching the
    reference's ``tensor / horovod_size`` semantics.  Adasum at the pure
    in-graph level needs pairwise recursion — see ``horovod_tpu.ops.adasum``;
    requesting it here raises.
    """
    axes = _axes(axis)
    if op == ReduceOp.ADASUM:
        from horovod_tpu.ops import adasum as _adasum

        return _adasum.adasum_allreduce(x, axis=axes)
    if prescale_factor != 1.0:
        x = x * prescale_factor
    if op in (ReduceOp.AVERAGE, ReduceOp.SUM):
        y = lax.psum(x, axes)
        if op == ReduceOp.AVERAGE:
            y = y / axis_size(axes)
    elif op == ReduceOp.MIN:
        y = lax.pmin(x, axes)
    elif op == ReduceOp.MAX:
        y = lax.pmax(x, axes)
    elif op == ReduceOp.PRODUCT:
        # No hardware product collective: exp/sum-of-logs is lossy, so do an
        # all-gather and reduce locally; product allreduce is rare and small.
        g = lax.all_gather(x, axes[0], axis=0, tiled=False)
        for ax in axes[1:]:
            g = lax.all_gather(g, ax, axis=0, tiled=True)
        y = jnp.prod(g, axis=0)
    else:
        raise ValueError(f"unsupported reduce op {op}")
    if postscale_factor != 1.0:
        y = y * postscale_factor
    return y


def grouped_allreduce(
    tensors,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis: AxisSpec = "dp",
    hierarchical: bool = False,
    outer_axis: str = "dcn",
):
    """Fused allreduce of a pytree: the in-graph analog of the reference's
    tensor fusion (``fusion_buffer_manager.h:28-55`` + ``FuseResponses``,
    controller.cc:638-759).

    Leaves are flattened and concatenated into one contiguous buffer per
    dtype, reduced with a single collective each, then split back.  Fewer,
    larger collectives keep the ICI links saturated exactly like the
    reference's fusion buffer keeps NCCL busy.

    ``hierarchical=True`` reduces each fused buffer with
    :func:`hierarchical_allreduce` — the in-graph twin of
    ``HVD_HIERARCHICAL_ALLREDUCE``.  ``axis`` must then name exactly the
    inner (ICI) and outer (``outer_axis``, DCN) axes, so the reduction
    set is identical to the flat path's.
    """
    inner = None
    if hierarchical:
        names = _axes(axis)
        if len(names) != 2 or outer_axis not in names:
            raise ValueError(
                "hierarchical grouped_allreduce needs axis to name "
                f"exactly the inner and outer axes (got {names}, "
                f"outer_axis={outer_axis!r})")
        inner = names[0] if names[1] == outer_axis else names[1]
    leaves, treedef = jax.tree.flatten(tensors)
    if not leaves:
        return tensors
    by_dtype = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(leaf).dtype, []).append(i)
    out = [None] * len(leaves)
    for dtype, idxs in by_dtype.items():
        flat = jnp.concatenate(
            [jnp.ravel(leaves[i]) for i in idxs], axis=0)
        if hierarchical:
            red = hierarchical_allreduce(
                flat, op=op, inner_axis=inner, outer_axis=outer_axis)
        else:
            red = allreduce(flat, op=op, axis=axis)
        offset = 0
        for i in idxs:
            n = leaves[i].size
            out[i] = jnp.reshape(red[offset:offset + n], leaves[i].shape)
            offset += n
    return jax.tree.unflatten(treedef, out)


def allgather(x, axis: AxisSpec = "dp", tiled: bool = True):
    """Concatenate each shard's tensor along dim 0 across ``axis``.

    Parity: ``MPIAllgather`` / ``NCCLAllgather`` semantics (first-dim
    concatenation, mpi_operations.cc:83-166).  In-graph XLA all-gather
    requires equal shapes on every shard; ragged first dims are only
    supported on the eager path where the controller negotiates sizes.
    """
    axes = _axes(axis)
    g = x
    for ax in reversed(axes):
        g = lax.all_gather(g, ax, axis=0, tiled=tiled)
        tiled = True
    return g


def broadcast(x, root_rank: int = 0, axis: AxisSpec = "dp"):
    """Broadcast the value from linearized index ``root_rank`` of ``axis``.

    Parity: ``NCCLBroadcast`` (nccl_operations.cc:366-396).  Lowered as a
    masked psum, which XLA pattern-matches to a broadcast-like collective;
    correct for every dtype including bool/int.
    """
    idx = axis_index(axis)
    mask = (idx == root_rank)
    if x.dtype == jnp.bool_:
        y = jnp.where(mask, x, False)
        return lax.psum(y.astype(jnp.int32), _axes(axis)).astype(jnp.bool_)
    y = jnp.where(mask, x, jnp.zeros_like(x))
    return lax.psum(y, _axes(axis))


def reduce_scatter(x, op: ReduceOp = ReduceOp.AVERAGE, axis: str = "dp"):
    """Reduce across ``axis`` and scatter equal slices of dim 0.

    The building block of hierarchical allreduce (the reference's
    ``ncclReduceScatter`` leg, nccl_operations.cc:224-342).
    """
    n = lax.axis_size(axis)
    y = lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    if op == ReduceOp.AVERAGE:
        y = y / n
    elif op != ReduceOp.SUM:
        raise ValueError("reduce_scatter supports SUM/AVERAGE")
    return y


def hierarchical_allreduce(
    x,
    op: ReduceOp = ReduceOp.AVERAGE,
    inner_axis: str = "dp",
    outer_axis: str = "dcn",
):
    """reduce-scatter(ICI) → all-reduce(DCN) → all-gather(ICI).

    Direct TPU mapping of ``NCCLHierarchicalAllreduce``
    (nccl_operations.cc:163-363): the bandwidth-heavy phases ride the fast
    inner fabric; only 1/inner_size of the bytes crosses the slow outer
    links.  Requires dim 0 divisible by the inner axis size (the reference
    pads the fused buffer for the same reason).
    """
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("hierarchical_allreduce supports SUM/AVERAGE")
    n_in = lax.axis_size(inner_axis)
    pad = (-x.shape[0]) % n_in
    orig = x.shape[0]
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    piece = lax.psum_scatter(x, inner_axis, scatter_dimension=0, tiled=True)
    piece = lax.psum(piece, outer_axis)
    full = lax.all_gather(piece, inner_axis, axis=0, tiled=True)
    if pad:
        full = full[:orig]
    if op == ReduceOp.AVERAGE:
        full = full / (n_in * lax.axis_size(outer_axis))
    return full


def alltoall(x, splits=None, axis: str = "dp"):
    """Exchange equal (or ``splits``-described) chunks of dim 0 between all
    shards of ``axis``.  Equal-split maps to one XLA all-to-all; ragged
    splits (the torch ``alltoall(splits=...)`` API) are emulated with
    all-gather + gather because XLA all-to-all is static-shape.
    """
    if splits is None:
        return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    # Ragged output sizes are data-dependent, which XLA's static-shape
    # model cannot express without padding every segment to a max size —
    # the eager engine (which negotiates sizes) is the supported path.
    raise NotImplementedError(
        "ragged in-graph alltoall is not supported; use equal splits "
        "in-graph or horovod_tpu.alltoall (eager) for ragged splits")


def barrier(axis: AxisSpec = "dp"):
    """Synchronization barrier: a zero-byte psum every shard must reach."""
    return lax.psum(jnp.zeros((), jnp.int32), _axes(axis))


def ppermute_ring(x, axis: str, shift: int = 1):
    """Send to the neighbor ``shift`` steps around the ``axis`` ring —
    the primitive under ring attention and custom pipeline schedules."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)
