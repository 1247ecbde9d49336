"""Process-group runtime facade: init/rank/size + eager collectives.

Parity: ``horovod/common/basics.py`` (HorovodBasics, the ctypes layer over
``horovod_init/rank/size/...`` in operations.cc:650-788).  Here the native
core is ``horovod_tpu._core`` (C++, see ``csrc/``), loaded via ctypes; when
the extension is unavailable (or size == 1) a pure-Python engine with the
same contract is used so the framework degrades gracefully instead of
failing to import.

Rank discovery, in priority order (TPU-first — no MPI):
1. explicit ``init(rank=..., size=...)`` arguments,
2. ``HVD_RANK/HVD_SIZE/HVD_LOCAL_RANK/...`` env injected by the launcher
   (the reference's ``HOROVOD_RANK`` scheme, gloo_context.cc:44-49),
3. JAX distributed / TPU slice metadata (``jax.process_index()``) when the
   process is already part of a JAX multi-host setup,
4. single-process defaults (rank 0 of 1).
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from horovod_tpu.common.types import ReduceOp, Status

_lock = threading.Lock()
_runtime = None  # the active engine after init()


class _Env:
    RANK = "HVD_RANK"
    SIZE = "HVD_SIZE"
    LOCAL_RANK = "HVD_LOCAL_RANK"
    LOCAL_SIZE = "HVD_LOCAL_SIZE"
    CROSS_RANK = "HVD_CROSS_RANK"
    CROSS_SIZE = "HVD_CROSS_SIZE"
    RENDEZVOUS_ADDR = "HVD_RENDEZVOUS_ADDR"
    RENDEZVOUS_PORT = "HVD_RENDEZVOUS_PORT"


def _discover(rank, size, local_rank, local_size, cross_rank, cross_size):
    env = os.environ
    if size is None and _Env.SIZE in env:
        rank = int(env.get(_Env.RANK, "0"))
        size = int(env[_Env.SIZE])
        local_rank = int(env.get(_Env.LOCAL_RANK, str(rank)))
        local_size = int(env.get(_Env.LOCAL_SIZE, str(size)))
        cross_rank = int(env.get(_Env.CROSS_RANK, "0"))
        cross_size = int(env.get(_Env.CROSS_SIZE, "1"))
    if size is None:
        # TPU pod / JAX multi-host metadata (runner/discovery.py): slice
        # coordinates become the local/cross split the controller uses.
        from horovod_tpu.runner import discovery

        topo = discovery.discover()
        if topo is not None:
            rank, size = topo.rank, topo.size
            local_rank, local_size = topo.local_rank, topo.local_size
            cross_rank, cross_size = topo.cross_rank, topo.cross_size
    if size is None:
        rank, size = 0, 1
    if local_size is None:
        local_rank, local_size = 0, 1
    if cross_rank is None:
        cross_rank, cross_size = rank // max(local_size, 1), (
            size // max(local_size, 1) or 1)
    return rank, size, local_rank, local_size, cross_rank, cross_size


def init(
    rank: Optional[int] = None,
    size: Optional[int] = None,
    local_rank: Optional[int] = None,
    local_size: Optional[int] = None,
    cross_rank: Optional[int] = None,
    cross_size: Optional[int] = None,
) -> None:
    """Initialize the horovod_tpu runtime for this process.

    Idempotent (second call is a no-op), matching ``InitializeHorovodOnce``
    (operations.cc:593-639).
    """
    global _runtime
    with _lock:
        if _runtime is not None:
            return
        r, s, lr, ls, cr, cs = _discover(
            rank, size, local_rank, local_size, cross_rank, cross_size)
        if s == 1:
            from horovod_tpu.runtime_py import SingleProcessEngine

            _runtime = SingleProcessEngine()
        else:
            _runtime = _make_engine(r, s, lr, ls, cr, cs)
        # Telemetry (docs/metrics.md): the Python engines start it in
        # their own __init__ (direct construction in tests included);
        # this idempotent call covers the native engine too, so the
        # eager-layer collective metrics work under either core.
        from horovod_tpu import telemetry

        telemetry.init_from_env(r, lr or 0, size=s)


def _make_engine(r, s, lr, ls, cr, cs):
    addr = os.environ.get(_Env.RENDEZVOUS_ADDR, "127.0.0.1")
    port = int(os.environ.get(_Env.RENDEZVOUS_PORT, "0"))
    try:
        if os.environ.get("HVD_ELASTIC_EPOCH", "") and \
                os.environ.get("HVD_TPU_CORE", "").lower() not in (
                    "py", "python"):
            # The native engine has no in-process reset path (its epoch
            # is pinned to 0 on the wire), so elastic training requires
            # the Python engine.  `hvdrun --min-np/--max-np` sets
            # HVD_TPU_CORE=py automatically; direct users must too.
            raise NotImplementedError(
                "elastic training (HVD_ELASTIC_EPOCH) is not supported "
                "by the native engine; set HVD_TPU_CORE=py")
        from horovod_tpu.runtime_native import NativeEngine
        from horovod_tpu import native

        native.load()  # raises NativeUnavailable before any rendezvous
    except ImportError as e:
        # Only pre-bootstrap failures (no toolchain / forced via
        # HVD_TPU_CORE=py) fall back.  Failures after the mesh is wired
        # must fail fast — peers have already consumed this rank's
        # rendezvous address, so silently re-bootstrapping under a
        # different engine would hang the whole job.
        from horovod_tpu.runtime_py import PyEngine

        if os.environ.get("HVD_TPU_CORE", "").lower() not in (
                "py", "python"):
            # Asked for by name is a choice; anything else is a fallback
            # the user should see.
            from horovod_tpu.utils.logging import get_logger

            get_logger(r).warning(
                "native core unavailable, using the Python engine: %s", e)
        eng = PyEngine(r, s, lr, ls, cr, cs, addr, port)
        eng.native_fallback_reason = str(e)
        return eng
    return NativeEngine(r, s, lr, ls, cr, cs, addr, port)


def _engine():
    if _runtime is None:
        raise ValueError(
            "horovod_tpu has not been initialized; call hvd.init() first.")
    return _runtime


def is_initialized() -> bool:
    return _runtime is not None


def shutdown() -> None:
    global _runtime
    with _lock:
        if _runtime is not None:
            _runtime.shutdown()
            _runtime = None
    # Stop the metrics server/flusher (final flush included).  The
    # registry itself keeps counting: an elastic re-form calls
    # shutdown() + init() in the same process and the counters span it.
    from horovod_tpu import telemetry

    telemetry.stop()


def rank() -> int:
    return _engine().rank


def size() -> int:
    return _engine().size


def local_rank() -> int:
    return _engine().local_rank


def local_size() -> int:
    return _engine().local_size


def cross_rank() -> int:
    return _engine().cross_rank


def cross_size() -> int:
    return _engine().cross_size


def is_homogeneous() -> bool:
    """True when every host runs the same number of processes
    (parity: basics.py is_homogeneous / controller state)."""
    return _engine().is_homogeneous


def nccl_built() -> bool:
    """Reference-API compatibility shims: the TPU build has no NCCL/MPI/
    Gloo/CUDA; code gated on these returns False and takes the generic
    path (parity: basics.py *_built probes)."""
    return False


def mpi_built() -> bool:
    return False


def gloo_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def xla_built() -> bool:
    return True


def mpi_enabled() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False


def cache_stats() -> dict:
    """Response-cache counters (hits/misses/evictions/size/capacity).
    Parity: the reference exposes no such API, but its autotuner and
    timeline read equivalent internals; this is the observable surface
    for tests and tuning."""
    return _engine().cache_stats()


def metrics_snapshot() -> dict:
    """JSON-serializable view of this worker's telemetry registry
    (docs/metrics.md): ``{"counters": ..., "gauges": ...,
    "histograms": ...}`` with Prometheus-style series keys, or ``{}``
    when telemetry is off.  Process-global, not engine-bound — counters
    accumulate across elastic engine resets."""
    from horovod_tpu import telemetry

    return telemetry.snapshot()
