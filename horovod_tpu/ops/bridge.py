"""XLA host-callback bridge: compiled programs ride the negotiated engine.

This is the TPU counterpart of the reference's defining mechanism — the
framework-op-to-coordinator enqueue (``tensorflow/mpi_ops.cc:287-320``
``HorovodAllreduceOp::ComputeAsync`` → ``EnqueueTensorAllreduce``): a
collective called *inside* a jitted JAX program that routes through the
shared background engine, so compiled steps get the controller's full
subsystem stack — tensor **naming**, cross-rank **negotiation**, response
**fusion**, the response **cache**, the **timeline**, **join**/allreduce
interaction, and stall detection — none of which exist on the pure
``lax.psum`` in-graph path (``ops/collective.py``).

Mechanism
---------
Each op lowers to ``jax.experimental.io_callback(ordered=True)``.  At run
time XLA transfers the operand to the host, the callback enqueues it into
the engine (``allreduce_async`` et al.), blocks on ``synchronize``, and
returns the reduced buffer, which XLA transfers back.  The engine's
background thread negotiates with the coordinator exactly as for eager
ops — a bridge tensor and an eager tensor with the same name are
indistinguishable on the wire, and the results are bitwise identical
(same ring walk, same chunk math; asserted by
``tests/eager_worker.py::scenario_bridge_jit``).

Ordering / deadlock-freedom
---------------------------
``ordered=True`` makes XLA execute the callbacks in **program order**.
Every rank compiles the *same* traced program, so the sequence of
(blocking) bridge calls is identical on every rank: when rank 0 sits in
the callback for tensor ``k``, every other rank is in — or headed into —
the callback for the same tensor ``k``.  This is the static-schedule
answer to the async-enqueue problem the reference solves with
``ComputeAsync`` + done-callbacks (SURVEY.md §7 "hard parts"): a dynamic
framework scheduler may issue ops in different orders per rank and needs
the coordinator to re-order; XLA's fixed schedule makes the submission
order itself deterministic.  The coordinator still runs full name-based
negotiation underneath, so even the degenerate interleavings that
host-callback threading could produce (e.g. a second program launched
concurrently) resolve by name, and fusion batches are chosen by the
coordinator (rank 0) in negotiated order — identical on every rank.

For gradient reductions use :func:`grouped_allreduce` (one callback
enqueues *all* tensors asynchronously, then synchronizes them all): the
engine sees the whole group outstanding at once and fuses them into
large wire messages (``runtime_py.py::_fuse_responses``), which is the
compiled-path analog of the reference's fusion-buffer cycle.

Differentiation: ``allreduce``/``grouped_allreduce``/``allgather``/
``broadcast`` carry ``custom_vjp`` rules mirroring the reference's
registered gradients (``tensorflow/__init__.py`` ``_allreduce_grad``:
the gradient of an allreduce is an allreduce of the gradient, name
suffixed ``.grad``).

Shapes are static under jit, so the bridge supports the statically-shaped
subset: equal-shape allgather (ragged first dims negotiate only on the
eager path) and equal-split alltoall.  ``reducescatter`` output shapes are
rank-dependent but *trace-time-constant* (each process traces its own
program), so the NCCL-style near-equal row split works unchanged.

This regime targets the reference's deployment shape: one process per
accelerator (chip), jit placed on that process's device.  Inside a
multi-device ``shard_map``/``pjit`` program, use the mesh-axis collectives
in ``ops/collective.py`` — there XLA *is* the coordinator.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np

from horovod_tpu import basics
from horovod_tpu.common.types import ReduceOp


def _auto_name(kind: str, name: Optional[str]) -> str:
    """Trace-time fallback names (shared counter machinery with the eager
    surface — identical call order across ranks required; pass ``name=``
    in anything beyond a single train step, like the reference's TF graph
    mode derives names from op names)."""
    if name is not None:
        return name
    from horovod_tpu.ops import eager

    return eager._auto_name(f"bridge.{kind}", None)


_MISUSE_MSG = (
    "engine-bridge collectives cannot run inside shard_map/pmap "
    "bodies (named mesh axes are in scope — each shard would "
    "enqueue separately under one tensor name); use the in-graph "
    "mesh-axis collectives in horovod_tpu.ops.collective instead")


def _check_single_device_trace() -> None:
    """The bridge targets the reference's deployment shape: one process
    per chip, jit on that device.  Inside shard_map/pmap bodies (named
    mesh axes in scope) XLA is the coordinator — ordered host callbacks
    there would submit one enqueue per *shard* under the same tensor
    name and hang; refuse at trace time with a pointer to the mesh-axis
    collectives (tests/test_eager_single.py pins the raise)."""
    import jax.core

    if jax.core.nonempty_axis_env_DO_NOT_USE():
        raise TypeError(_MISUSE_MSG)


def _io_callback(fn, result_spec, *args):
    from jax.experimental import io_callback

    return io_callback(fn, result_spec, *args, ordered=True)


def _spec_like(x):
    import jax

    return jax.ShapeDtypeStruct(np.shape(x), x.dtype)


def _group_size(process_set) -> int:
    if process_set is not None:
        process_set.validate(basics.rank(), basics.size())
        return len(process_set.ranks)
    return basics.size()


def _group_index(process_set) -> int:
    if process_set is not None:
        process_set.validate(basics.rank(), basics.size())
        return list(process_set.ranks).index(basics.rank())
    return basics.rank()


# ---------------------------------------------------------------------------
# native XLA custom-call fast path (CPU platform + native engine)
#
# ffi_bridge.cc registers an FFI handler that enqueues straight into the
# C++ engine — no Python in the compiled program's hot loop (the exact
# mechanism of the reference's registered framework op,
# tensorflow/mpi_ops.cc:287-320).  TPU executions keep io_callback (TPU
# has no user custom-call surface; XLA stages the host transfer).

# Only the REGISTRATION is cached; engine/backend checks re-derive per
# trace so a shutdown()/init() cycle (possibly onto the py engine, whose
# process has no live C++ Engine) can never route to a stale handler.
_ffi_state = {"registered": None}

# dtypes the handler's MapDtype accepts (ffi_bridge.cc)
_FFI_DTYPES = ("float32", "float64", "float16", "bfloat16",
               "float8_e4m3fn", "float8_e5m2", "int8", "uint8", "int16",
               "uint16", "int32", "int64", "bool")


def _native_ffi_ready() -> bool:
    import os

    if os.environ.get("HVD_NO_FFI_BRIDGE") == "1":
        return False
    try:
        import jax

        from horovod_tpu.runtime_native import NativeEngine

        if not isinstance(basics._engine(), NativeEngine):
            return False
        if jax.default_backend() != "cpu":
            return False
    except Exception:
        return False
    if _ffi_state["registered"] is None:
        _ffi_state["registered"] = False
        try:
            from horovod_tpu import native

            lib = native.load()
            handler = getattr(lib, "HvdGroupedAllreduce", None)
            if handler is not None:
                jax.ffi.register_ffi_target(
                    "hvd_grouped_allreduce",
                    jax.ffi.pycapsule(handler), platform="cpu")
                _ffi_state["registered"] = True
        except Exception:
            _ffi_state["registered"] = False
    return _ffi_state["registered"]


def _ffi_eligible(leaves, compression) -> bool:
    from horovod_tpu.ops.compression import Compression

    if compression is not None and compression is not Compression.none:
        # wire compression casts host-side — io_callback path
        return False
    if not all(str(l.dtype) in _FFI_DTYPES for l in leaves):
        return False
    return _native_ffi_ready()


def _ffi_grouped_call(leaves, base, op, prescale, postscale, process_set):
    import jax

    ps_id, ps_size = 0, 0
    if process_set is not None:
        ps_id, ps_size = process_set.validate(basics.rank(), basics.size())
    call = jax.ffi.ffi_call(
        "hvd_grouped_allreduce",
        tuple(_spec_like(l) for l in leaves),
        has_side_effect=True)
    # `single=0`: grouped entries wire-name as `{base}.{i}`, identical
    # to the io_callback/eager grouped surface (mixed gangs align).
    return call(*leaves, name=base, op=np.int32(int(op)),
                prescale=np.float64(prescale),
                postscale=np.float64(postscale),
                ps_id=np.int32(ps_id), ps_size=np.int32(ps_size),
                single=np.int32(0))


# ---------------------------------------------------------------------------
# allreduce


def _host_allreduce(name, op, prescale, postscale, compression,
                    process_set, arr):
    from horovod_tpu.ops.eager import _np_compress, _np_decompress

    arr = np.asarray(arr)
    comp, ctx = _np_compress(compression, arr)
    eng = basics._engine()
    h = eng.allreduce_async(name, comp, op=op, prescale=prescale,
                            postscale=postscale, process_set=process_set)
    out = _np_decompress(compression, eng.synchronize(h), ctx)
    return np.ascontiguousarray(out, dtype=arr.dtype)


def allreduce(x, name: Optional[str] = None,
              op: ReduceOp = ReduceOp.AVERAGE,
              prescale_factor: float = 1.0,
              postscale_factor: float = 1.0,
              compression=None, process_set=None):
    """Named, negotiated allreduce usable inside ``jit``.

    Parity: ``HorovodAllreduceOp`` (tensorflow/mpi_ops.cc:287-320) — the
    collective enters the compiled program but executes on the shared
    engine, with negotiation/fusion/cache/timeline on the path.
    Differentiable: the cotangent rides its own allreduce (name
    ``{name}.grad``), matching ``_allreduce_grad``.
    """
    from horovod_tpu.ops.compression import Compression

    _check_single_device_trace()
    _ensure_vjps()
    name = _auto_name("allreduce", name)
    compression = compression or Compression.none
    return _allreduce_vjp(x, name, op, prescale_factor, postscale_factor,
                          compression, process_set)


def _allreduce_call(x, name, op, prescale, postscale, compression,
                    process_set):
    # Single-tensor calls stay on the ORDERED host callback even when
    # the native custom call is available: a program with several
    # independent blocking collectives relies on identical cross-rank
    # submission order, which only the ordered-effects path guarantees
    # (XLA may schedule plain custom calls in any data-flow-consistent
    # order).  The FFI fast path serves grouped_allreduce, where every
    # tensor is enqueued before any wait inside ONE call.
    return _io_callback(
        partial(_host_allreduce, name, op, prescale, postscale,
                compression, process_set),
        _spec_like(x), x)


def _make_allreduce_vjp():
    import jax

    @partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
    def f(x, name, op, prescale, postscale, compression, process_set):
        return _allreduce_call(x, name, op, prescale, postscale,
                               compression, process_set)

    def fwd(x, name, op, prescale, postscale, compression, process_set):
        return _allreduce_call(x, name, op, prescale, postscale,
                               compression, process_set), None

    def bwd(name, op, prescale, postscale, compression, process_set, _, ct):
        # Reference `_allreduce_grad`: grad of an allreduce is an
        # allreduce of the grad with the same op (pre/post scaling swap
        # by linearity; both are scalar multiplies, so reuse as-is).
        g = _allreduce_call(ct, name + ".grad", op, prescale, postscale,
                            compression, process_set)
        return (g,)

    f.defvjp(fwd, bwd)
    return f


_allreduce_vjp = None


def _ensure_vjps():
    global _allreduce_vjp, _grouped_vjp, _allgather_vjp, _broadcast_vjp
    if _allreduce_vjp is None:
        _allreduce_vjp = _make_allreduce_vjp()
        _grouped_vjp = _make_grouped_vjp()
        _allgather_vjp = _make_allgather_vjp()
        _broadcast_vjp = _make_broadcast_vjp()


# ---------------------------------------------------------------------------
# grouped allreduce (fusion on the compiled path)


def _host_grouped_allreduce(base, op, compression, process_set, *arrs):
    """One host call for the whole gradient group: enqueue every tensor
    async, then synchronize — the engine's controller sees them all
    outstanding and fuses compatible responses into large wire messages
    (the compiled-path analog of the fusion-buffer cycle,
    fusion_buffer_manager.h:28-55)."""
    from horovod_tpu.ops.eager import _np_compress, _np_decompress

    eng = basics._engine()
    handles = []
    for i, a in enumerate(arrs):
        a = np.asarray(a)
        comp, ctx = _np_compress(compression, a)
        h = eng.allreduce_async(f"{base}.{i}", comp, op=op,
                                process_set=process_set)
        handles.append((h, ctx, a.dtype))
    outs = []
    for h, ctx, dt in handles:
        out = _np_decompress(compression, eng.synchronize(h), ctx)
        outs.append(np.ascontiguousarray(out, dtype=dt))
    return tuple(outs)


def grouped_allreduce(tensors, name: Optional[str] = None,
                      op: ReduceOp = ReduceOp.AVERAGE,
                      compression=None, process_set=None):
    """Allreduce a pytree through the engine with controller fusion,
    inside ``jit``.  The gradient-reduction primitive for
    ``DistributedOptimizer`` on the compiled path."""
    import jax

    from horovod_tpu.ops.compression import Compression

    _check_single_device_trace()
    _ensure_vjps()
    base = _auto_name("grouped_allreduce", name)
    compression = compression or Compression.none
    leaves, treedef = jax.tree.flatten(tensors)
    if not leaves:
        return tensors
    outs = _grouped_vjp(tuple(leaves), base, op, compression, process_set)
    return jax.tree.unflatten(treedef, list(outs))


def _grouped_call(leaves, base, op, compression, process_set):
    # Native custom call (ffi_bridge.cc): every tensor enqueues before
    # any wait inside one blocking call, so a step's gradient reduction
    # cannot cross-rank deadlock regardless of XLA's schedule.  Several
    # INDEPENDENT grouped calls in one program must be ordered by data
    # flow (true for optimizer steps; HVD_NO_FFI_BRIDGE=1 opts out and
    # the stall inspector names the tensors if a custom program trips
    # this).
    if _ffi_eligible(leaves, compression):
        return tuple(_ffi_grouped_call(
            list(leaves), base, op, 1.0, 1.0, process_set))
    return _io_callback(
        partial(_host_grouped_allreduce, base, op, compression,
                process_set),
        tuple(_spec_like(l) for l in leaves), *leaves)


def _make_grouped_vjp():
    import jax

    @partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
    def f(leaves, base, op, compression, process_set):
        return _grouped_call(leaves, base, op, compression, process_set)

    def fwd(leaves, base, op, compression, process_set):
        return _grouped_call(leaves, base, op, compression, process_set), \
            None

    def bwd(base, op, compression, process_set, _, cts):
        return (_grouped_call(tuple(cts), base + ".grad", op, compression,
                              process_set),)

    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# allgather


def _host_allgather(name, process_set, arr):
    eng = basics._engine()
    h = eng.allgather_async(name, np.asarray(arr), process_set=process_set)
    return np.ascontiguousarray(eng.synchronize(h))


def allgather(x, name: Optional[str] = None, process_set=None):
    """First-dim-concat allgather through the engine, inside ``jit``.
    Static shapes require every rank to contribute the same shape (the
    ragged-first-dim negotiation is eager-only; in-graph XLA has the same
    restriction, ops/collective.py:153)."""
    _check_single_device_trace()
    _ensure_vjps()
    name = _auto_name("allgather", name)
    return _allgather_vjp(x, name, process_set)


def _allgather_call(x, name, process_set):
    import jax

    n = _group_size(process_set)
    shape = (n * x.shape[0],) + tuple(x.shape[1:]) if x.ndim else (n,)
    spec = jax.ShapeDtypeStruct(shape, x.dtype)
    return _io_callback(partial(_host_allgather, name, process_set),
                        spec, x)


def _make_allgather_vjp():
    import jax
    import jax.numpy as jnp

    @partial(jax.custom_vjp, nondiff_argnums=(1, 2))
    def f(x, name, process_set):
        return _allgather_call(x, name, process_set)

    def fwd(x, name, process_set):
        return _allgather_call(x, name, process_set), x.shape

    def bwd(name, process_set, in_shape, ct):
        # Reference `_allgather_grad`: sum-allreduce the cotangent and
        # slice out this rank's segment.
        summed = _allreduce_call(
            ct, name + ".grad", ReduceOp.SUM, 1.0, 1.0,
            _none_compression(), process_set)
        d0 = in_shape[0] if in_shape else 1
        me = _group_index(process_set)
        seg = jax.lax.dynamic_slice_in_dim(summed, me * d0, d0, axis=0)
        return (jnp.reshape(seg, in_shape),)

    f.defvjp(fwd, bwd)
    return f


def _none_compression():
    from horovod_tpu.ops.compression import Compression

    return Compression.none


# ---------------------------------------------------------------------------
# broadcast


def _host_broadcast(name, root_rank, process_set, arr):
    eng = basics._engine()
    h = eng.broadcast_async(name, np.asarray(arr), root_rank=root_rank,
                            process_set=process_set)
    return np.ascontiguousarray(eng.synchronize(h))


def broadcast(x, root_rank: int = 0, name: Optional[str] = None,
              process_set=None):
    """Negotiated broadcast inside ``jit``.  Gradient: sum-allreduce on
    the root, zero elsewhere (reference ``_broadcast_grad``)."""
    _check_single_device_trace()
    _ensure_vjps()
    name = _auto_name("broadcast", name)
    return _broadcast_vjp(x, name, root_rank, process_set)


def _broadcast_call(x, name, root_rank, process_set):
    return _io_callback(
        partial(_host_broadcast, name, root_rank, process_set),
        _spec_like(x), x)


def _make_broadcast_vjp():
    import jax
    import jax.numpy as jnp

    @partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
    def f(x, name, root_rank, process_set):
        return _broadcast_call(x, name, root_rank, process_set)

    def fwd(x, name, root_rank, process_set):
        return _broadcast_call(x, name, root_rank, process_set), None

    def bwd(name, root_rank, process_set, _, ct):
        g = _allreduce_call(ct, name + ".grad", ReduceOp.SUM, 1.0, 1.0,
                            _none_compression(), process_set)
        if basics.rank() != root_rank:
            g = jnp.zeros_like(g)
        return (g,)

    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# reducescatter / alltoall / barrier (non-differentiable bridge ops)


def _host_reducescatter(name, op, process_set, arr):
    eng = basics._engine()
    h = eng.reducescatter_async(name, np.asarray(arr), op=op,
                                process_set=process_set)
    return np.ascontiguousarray(eng.synchronize(h))


def reducescatter(x, name: Optional[str] = None,
                  op: ReduceOp = ReduceOp.AVERAGE, process_set=None):
    """Reduce+scatter over dim 0 inside ``jit``.  The output shape is this
    rank's NCCL-style near-equal row chunk — rank-dependent but constant
    at trace time (each process traces its own program), so it stays
    static under XLA.  Chunk math is the engine's own
    (ops/cpu_backend.py::_chunk_bounds, imported, not copied)."""
    import jax

    from horovod_tpu.ops.cpu_backend import _chunk_bounds

    _check_single_device_trace()
    if op not in (ReduceOp.AVERAGE, ReduceOp.SUM, ReduceOp.MIN,
                  ReduceOp.MAX, ReduceOp.PRODUCT):
        raise ValueError(f"reducescatter does not support op {op}")
    if x.ndim == 0:
        raise ValueError(
            "reducescatter needs at least one dimension to scatter over "
            "(got a scalar)")
    name = _auto_name("reducescatter", name)
    n = _group_size(process_set)
    me = _group_index(process_set)
    bounds = _chunk_bounds(x.shape[0], n)
    shape = (bounds[me + 1] - bounds[me],) + tuple(x.shape[1:])
    spec = jax.ShapeDtypeStruct(shape, x.dtype)
    return _io_callback(partial(_host_reducescatter, name, op, process_set),
                        spec, x)


def _host_alltoall(name, splits, process_set, arr):
    eng = basics._engine()
    h = eng.alltoall_async(name, np.asarray(arr), splits=splits,
                           process_set=process_set)
    out = eng.synchronize(h)
    if isinstance(out, tuple):
        out = out[0]
    return np.ascontiguousarray(out)


def alltoall(x, name: Optional[str] = None, process_set=None):
    """Equal-split alltoall inside ``jit`` (dim 0 divisible by group
    size; ragged ``splits`` need runtime shapes — eager path only, same
    restriction as the in-graph op, ops/collective.py:232)."""
    import jax

    _check_single_device_trace()
    name = _auto_name("alltoall", name)
    n = _group_size(process_set)
    if x.shape[0] % n:
        raise ValueError(
            f"bridge alltoall needs dim 0 ({x.shape[0]}) divisible by "
            f"group size ({n}); ragged splits are eager-only")
    spec = jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
    return _io_callback(partial(_host_alltoall, name, None, process_set),
                        spec, x)


def _host_barrier(process_set, _x):
    basics._engine().barrier(process_set=process_set)
    return np.zeros((), np.int32)


def barrier(process_set=None):
    """Engine barrier inside ``jit``; returns an int32 token (use or
    thread it so XLA cannot dead-code it away)."""
    import jax
    import jax.numpy as jnp

    _check_single_device_trace()
    return _io_callback(partial(_host_barrier, process_set),
                        jax.ShapeDtypeStruct((), np.int32),
                        jnp.zeros((), jnp.int32))
