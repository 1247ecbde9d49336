"""horovod_tpu: a TPU-native distributed training framework with the
capabilities of Horovod v0.19.1.

Public API parity: ``import horovod_tpu as hvd`` gives the classic surface
(``hvd.init/rank/size/allreduce/allgather/broadcast/join/...``,
``DistributedOptimizer``, ``Compression``) — see
``horovod/common/basics.py`` and per-framework ``mpi_ops.py`` in the
reference.  TPU-native extensions live in ``horovod_tpu.parallel`` (device
meshes, in-graph collectives, hierarchical ICI/DCN reduction, sequence
parallelism) and ``horovod_tpu.ops`` (XLA + Pallas data plane).
"""

from horovod_tpu.version import __version__  # noqa: F401

from horovod_tpu.basics import (  # noqa: F401
    cache_stats,
    cross_rank,
    cross_size,
    cuda_built,
    gloo_built,
    init,
    is_homogeneous,
    is_initialized,
    local_rank,
    local_size,
    metrics_snapshot,
    mpi_built,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
    rank,
    rocm_built,
    shutdown,
    size,
    xla_built,
)
from horovod_tpu.common.types import (  # noqa: F401
    RanksFailedError,
    ReduceOp,
    ReplicaDivergenceError,
)
from horovod_tpu.ops.compression import Compression  # noqa: F401
from horovod_tpu.process_sets import ProcessSet  # noqa: F401
from horovod_tpu.ops.eager import (  # noqa: F401
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    alltoall,
    alltoall_async,
    barrier,
    broadcast,
    broadcast_async,
    broadcast_object,
    broadcast_parameters,
    grouped_allreduce,
    join,
    poll,
    reducescatter,
    reducescatter_async,
    sparse_allreduce,
    synchronize,
)
from horovod_tpu.parallel.optimizer import (  # noqa: F401
    DistributedOptimizer,
    allreduce_gradients,
    distributed_grad,
    distributed_value_and_grad,
)
from horovod_tpu import data  # noqa: F401  (sharded sampling + prefetch)
from horovod_tpu import elastic  # noqa: F401  (commit/rollback + re-form)
from horovod_tpu import integrity  # noqa: F401  (data-plane integrity)
from horovod_tpu import telemetry  # noqa: F401  (metrics registry/export)
from horovod_tpu.parallel.multihost import (  # noqa: F401
    init_jax_distributed,
)

# ReduceOp constants at top level, Horovod-style (basics.py:29-31).
Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Adasum = ReduceOp.ADASUM
Min = ReduceOp.MIN
Max = ReduceOp.MAX
Product = ReduceOp.PRODUCT
