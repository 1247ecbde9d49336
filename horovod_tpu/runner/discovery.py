"""TPU pod topology discovery.

The reference learns world topology from MPI or launcher-injected env
(``HOROVOD_RANK``, gloo_context.cc:44-49).  On TPU pods the runtime itself
knows the topology: each host process belongs to a slice with a bounded
set of chips.  This module turns that metadata into the same
rank/local/cross coordinates the controller uses, with no ssh or env
injection needed.

Sources, in priority order:
1. ``TPU_WORKER_ID`` / ``TPU_WORKER_HOSTNAMES`` (GCE TPU VM metadata, set
   on every TPU VM worker),
2. ``MEGASCALE_SLICE_ID`` / ``MEGASCALE_NUM_SLICES`` for multislice (the
   DCN/cross axis),
3. an initialized ``jax.distributed`` runtime (process_index/count).
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class PodTopology:
    rank: int            # host process index in the whole job
    size: int            # total host processes
    local_rank: int      # index within the slice
    local_size: int      # hosts per slice
    cross_rank: int      # slice index (DCN coordinate)
    cross_size: int      # number of slices


def block_topology_ok(rank: int, size: int, local_rank: int,
                      local_size: int, cross_rank: int,
                      cross_size: int) -> bool:
    """True for a genuine two-level topology in the launcher's homogeneous
    block rank layout (rank = cross_rank*local_size + local_rank) — the
    precondition for the hierarchical data plane (the single shared copy
    of this invariant; the C++ engine mirrors it in
    ``Engine::HierarchicalTopologyOk``)."""
    return (local_size > 1 and cross_size > 1
            and local_size * cross_size == size
            and rank == cross_rank * local_size + local_rank)


def from_tpu_metadata() -> Optional[PodTopology]:
    """Build topology from TPU VM env metadata; None when not on a pod."""
    env = os.environ
    worker_id = env.get("TPU_WORKER_ID")
    hostnames = env.get("TPU_WORKER_HOSTNAMES")
    if worker_id is None or hostnames is None:
        return None
    try:
        local_rank = int(worker_id)
        cross_rank = int(env.get("MEGASCALE_SLICE_ID", "0"))
        cross_size = int(env.get("MEGASCALE_NUM_SLICES", "1"))
    except ValueError:
        # Malformed pod metadata (e.g. a k8s setup exporting a worker
        # *name*): treat as "not on a pod" rather than crashing init().
        return None
    local_size = len([h for h in hostnames.split(",") if h.strip()])
    return PodTopology(
        rank=cross_rank * local_size + local_rank,
        size=cross_size * local_size,
        local_rank=local_rank,
        local_size=local_size,
        cross_rank=cross_rank,
        cross_size=cross_size,
    )


def from_jax_distributed() -> Optional[PodTopology]:
    """Topology of a ``jax.distributed`` job this process already joined;
    None otherwise.  Asking JAX for ``process_count()`` initialises its
    backend (on a TPU host: takes the chips), so a bare ``hvd.init()``
    only asks when ``jax.distributed.initialize`` has already run."""
    jax = sys.modules.get("jax")
    if jax is None or not jax.distributed.is_initialized():
        return None
    n = jax.process_count()
    if n <= 1:
        return None
    r = jax.process_index()
    return PodTopology(rank=r, size=n, local_rank=0, local_size=1,
                       cross_rank=r, cross_size=n)


# MPI-launcher env schemas: (rank, size, local_rank, local_size) names.
# Lets `hvd.init()` work under mpirun / srun / jsrun with no HVD_* env —
# the reference gets this from MPI_Init; we read the launcher's env.
_MPI_SCHEMAS = (
    ("OMPI_COMM_WORLD_RANK", "OMPI_COMM_WORLD_SIZE",
     "OMPI_COMM_WORLD_LOCAL_RANK", "OMPI_COMM_WORLD_LOCAL_SIZE"),
    # IBM JSM (jsrun on LSF/Summit) namespace exports.
    ("JSM_NAMESPACE_RANK", "JSM_NAMESPACE_SIZE",
     "JSM_NAMESPACE_LOCAL_RANK", "JSM_NAMESPACE_LOCAL_SIZE"),
    ("PMIX_RANK", "PMIX_SIZE", "PMIX_LOCAL_RANK", "PMIX_LOCAL_SIZE"),
    ("PMI_RANK", "PMI_SIZE", None, None),
    ("SLURM_PROCID", "SLURM_NTASKS", "SLURM_LOCALID",
     "SLURM_NTASKS_PER_NODE"),
)


def from_mpi_env() -> Optional[PodTopology]:
    """Topology from an MPI-style launcher's environment (Open MPI /
    PMIx / PMI / Slurm).  None when not launched that way."""
    env = os.environ
    for rank_k, size_k, lrank_k, lsize_k in _MPI_SCHEMAS:
        if rank_k not in env or size_k not in env:
            continue
        try:
            rank = int(env[rank_k])
            size = int(env[size_k])
            local_rank = int(env[lrank_k]) if lrank_k and lrank_k in env \
                else 0
            local_size = int(env[lsize_k]) if lsize_k and lsize_k in env \
                else 1
        except ValueError:
            continue
        if size <= 0:
            continue
        cross_rank = rank // local_size if local_size > 0 else 0
        # The hierarchical data plane assumes the block rank layout;
        # launchers mapping by node (mpirun --map-by node) violate it, and
        # ranks must not *disagree* about hierarchy — degrade to a flat
        # local topology unless the layout verifiably holds.
        if (local_size <= 0 or size % local_size
                or rank != cross_rank * local_size + local_rank):
            local_rank, local_size = 0, 1
            cross_rank = rank
        return PodTopology(
            rank=rank, size=size,
            local_rank=local_rank, local_size=local_size,
            cross_rank=cross_rank,
            cross_size=size // local_size,
        )
    return None


def discover() -> Optional[PodTopology]:
    return from_tpu_metadata() or from_mpi_env() or from_jax_distributed()
