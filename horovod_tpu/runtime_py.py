"""Pure-Python process-group engine: controller + CPU data plane.

This is a complete, wire-compatible implementation of the coordination
protocol that the native C++ core (``csrc/``) also implements; it serves as
(a) the always-available fallback when the extension is not built, and
(b) the executable specification the native core is tested against.

Behavioral parity map (reference → here):
* ``horovod/common/operations.cc:333-589`` BackgroundThreadLoop /
  RunLoopOnce            → ``PyEngine._background_loop`` / ``_run_loop_once``
* ``horovod/common/controller.cc:62-354`` ComputeResponseList
  (coordinator negotiation, rank-0 message table)
                          → ``_coordinator_cycle`` / ``_MessageTable``
* ``horovod/common/controller.cc:376-609`` ConstructResponse (mismatch
  checking)               → ``_construct_response``
* ``horovod/common/controller.cc:638-759`` FuseResponses
                          → ``_fuse_responses``
* ``horovod/common/tensor_queue.cc``        → ``_pending`` + ``_table``
* ``horovod/torch/handle_manager.h:31-42``  → ``HandleManager``
* ``horovod/common/stall_inspector.cc``     → ``_check_stalls``
* ``horovod/common/ops/gloo_operations.cc`` (CPU data plane)
                          → ``horovod_tpu.ops.cpu_backend`` (ring algorithms)

The controller is a star over TCP (workers → rank 0), like the reference's
coordinator; the data plane is a full mesh running ring collectives.  All
of it is host-network traffic — on TPU the performance path is the in-graph
XLA backend (``horovod_tpu.ops.collective``); this engine exists for
Horovod-style multi-process eager semantics and as the correctness oracle.
"""

from __future__ import annotations

import os
import socket
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from horovod_tpu.common import fault_injection as _fi
from horovod_tpu.common import wire
from horovod_tpu.common import response_cache as rcache
from horovod_tpu.common.types import (
    CollectiveTimeoutError,
    DataType,
    FencedError,
    RanksFailedError,
    ReduceOp,
    Request,
    RequestType,
    Response,
    ResponseType,
    Status,
    StatusType,
    TensorShape,
)
from horovod_tpu.common.types import dtype_from_numpy, dtype_to_numpy_name
from horovod_tpu import telemetry as _telemetry
from horovod_tpu.telemetry import blackbox as blackbox_mod
from horovod_tpu.telemetry import registry as _tmx
from horovod_tpu.telemetry import trace as trace_mod
from horovod_tpu.utils import env as env_util
from horovod_tpu.utils import socketutil as su
from horovod_tpu.utils import timeline as timeline_mod
from horovod_tpu.utils.logging import get_logger

_OP_NAMES = {
    RequestType.ALLREDUCE: "ALLREDUCE",
    RequestType.ALLGATHER: "ALLGATHER",
    RequestType.BROADCAST: "BROADCAST",
    RequestType.ALLTOALL: "ALLTOALL",
    RequestType.JOIN: "JOIN",
    RequestType.BARRIER: "BARRIER",
    RequestType.REDUCESCATTER: "REDUCESCATTER",
}

# -- evict-and-replay retention ----------------------------------------
# When the gang aborts an in-flight fused reduction (CollectiveTimeout-
# Error), the survivors retain copies of the ORIGINAL inputs here —
# pack() copies into the fusion buffer and the ring mutates only that
# buffer, so entry.array is pristine at abort time.  The holder is
# module-level on purpose: the elastic wrapper tears the engine down
# and re-forms a new one, and the replay must survive that.
_replay_lock = threading.Lock()
_replay_batch: Optional[List[dict]] = None


def retain_aborted_batch(batch: List[dict]) -> None:
    global _replay_batch
    with _replay_lock:
        _replay_batch = batch


def take_retained_batch() -> Optional[List[dict]]:
    """Pop the retained aborted batch (None when nothing was aborted).
    Each item: {name, array (copy), op, prescale, postscale}."""
    global _replay_batch
    with _replay_lock:
        batch, _replay_batch = _replay_batch, None
    return batch


class HandleManager:
    """Async handle table; parity: torch/handle_manager.h:31-42."""

    def __init__(self):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._next = 0
        self._status: Dict[int, Optional[Status]] = {}
        self._result: Dict[int, object] = {}

    def allocate(self) -> int:
        with self._lock:
            h = self._next
            self._next += 1
            self._status[h] = None
            return h

    def mark_done(self, handle: int, status: Status, result=None) -> None:
        with self._cv:
            self._status[handle] = status
            self._result[handle] = result
            self._cv.notify_all()

    def poll(self, handle: int) -> bool:
        with self._lock:
            if handle not in self._status:
                raise ValueError(f"unknown handle {handle}")
            return self._status[handle] is not None

    def wait(self, handle: int, timeout: Optional[float] = None):
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while self._status.get(handle) is None:
                remaining = None if deadline is None else max(
                    0.0, deadline - time.monotonic())
                if deadline is not None and remaining == 0.0:
                    raise TimeoutError(f"handle {handle} timed out")
                self._cv.wait(remaining)
            status = self._status.pop(handle)
            result = self._result.pop(handle, None)
        if not status.ok_():
            if status.exc is not None:
                # Typed failure (e.g. CollectiveTimeoutError) — the
                # elastic wrapper dispatches on the exception class.
                raise status.exc
            raise RuntimeError(status.reason or "collective failed")
        return result


@dataclass
class TensorTableEntry:
    """One enqueued tensor awaiting its collective.
    Parity: common.h TensorTableEntry."""

    name: str
    array: np.ndarray
    handle: int
    request: Request
    root_rank: int = -1
    splits: Optional[List[int]] = None
    enqueue_ns: int = field(default_factory=time.monotonic_ns)


class _MessageTable:
    """Coordinator-side ready-count tracking.
    Parity: controller.h:33 MessageTable + IncrementTensorCount
    (controller.cc:787-810)."""

    def __init__(self, size: int):
        self.size = size
        self.entries: Dict[str, List[Request]] = {}
        self.first_seen: Dict[str, float] = {}

    @staticmethod
    def key_of(req: Request) -> str:
        """Table key: process-set requests are scoped by set id, so the
        same tensor name may be in flight in two different sets at once
        (both subgroups allreducing "grad.w" is legitimate traffic)."""
        if req.process_set_id:
            return f"{req.tensor_name}@ps{req.process_set_id}"
        return req.tensor_name

    def increment(self, req: Request, joined_size: int) -> bool:
        """Record a rank's readiness; True when all non-joined ranks are
        in (for a process-set request: when every member is in — join is
        global-set-only, so joined_size does not apply)."""
        key = self.key_of(req)
        lst = self.entries.setdefault(key, [])
        if any(q.request_rank == req.request_rank for q in lst):
            # Duplicate ready tick from the same rank: a child re-sends
            # its in-flight request frames after re-parenting away from
            # a dead sub-coordinator, and the original may have been
            # relayed just before the parent died.  Counting it twice
            # would fire the collective before every rank is in.
            return False
        lst.append(req)
        self.first_seen.setdefault(key, time.monotonic())
        if req.process_set_id:
            return len(lst) == req.process_set_size
        return len(lst) == self.size - joined_size

    def pop(self, name: str) -> List[Request]:
        self.first_seen.pop(name, None)
        return self.entries.pop(name)


def _np_dtype(dt: DataType):
    name = dtype_to_numpy_name(dt)
    if name in ("bfloat16", "float8_e4m3fn", "float8_e5m2"):
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))
    return np.dtype(name)


class _EngineBase:
    """Shared enqueue-side logic and introspection."""

    def __init__(self, rank, size, local_rank, local_size,
                 cross_rank, cross_size):
        self.rank = rank
        self.size = size
        self.local_rank = local_rank
        self.local_size = local_size
        self.cross_rank = cross_rank
        self.cross_size = cross_size
        self.is_homogeneous = True
        self.handles = HandleManager()
        self._pending_names: set = set()
        self._name_lock = threading.Lock()
        self._barrier_counters = {0: 0}  # per process-set id

    # -- duplicate-name guard (parity: tensor_queue.cc:27-35) -------------

    def _claim_name(self, name: str) -> None:
        with self._name_lock:
            if name in self._pending_names:
                raise ValueError(
                    f"Requested a collective on a tensor with the same name "
                    f"as another tensor that is currently being processed: "
                    f"{name}")
            self._pending_names.add(name)

    def _release_name(self, name: str) -> None:
        with self._name_lock:
            self._pending_names.discard(name)

    def poll(self, handle: int) -> bool:
        return self.handles.poll(handle)

    def synchronize(self, handle: int, timeout: Optional[float] = None):
        return self.handles.wait(handle, timeout)

    def cache_stats(self) -> Dict[str, int]:
        return {"hits": 0, "misses": 0, "evictions": 0, "size": 0,
                "capacity": 0}


class SingleProcessEngine(_EngineBase):
    """size == 1: every collective is the identity (modulo scaling), applied
    synchronously.  Keeps the async handle API so user code is unchanged."""

    def __init__(self):
        super().__init__(0, 1, 0, 1, 0, 1)
        self.timeline = timeline_mod.from_env(0)
        _telemetry.init_from_env(0, 0)
        # No collective to trace at size 1; the tracer is there for the
        # serving loop's spans (telemetry/trace.py ``span``).
        self._tracer = trace_mod.from_env(0)
        # Serving surface (serving/loop.py): a broadcast to a gang of
        # one is a local enqueue, so the loop's drive/apply split works
        # unchanged single-process.
        self.epoch = 0
        self._aborted = False
        self._serve_inbox: List[bytes] = []
        self._serve_cv = threading.Condition()
        self._shutdown_requested = threading.Event()
        self._shutdown_flag = threading.Event()

    def shutdown(self):
        self._shutdown_flag.set()
        with self._serve_cv:
            self._serve_cv.notify_all()
        self.timeline.shutdown()
        trace_mod.release(self._tracer)
        self._tracer = None

    def serve_broadcast(self, payload: bytes) -> None:
        with self._serve_cv:
            self._serve_inbox.append(payload)
            self._serve_cv.notify_all()

    def serve_recv(self, timeout: float) -> Optional[bytes]:
        deadline = time.monotonic() + timeout
        with self._serve_cv:
            while True:
                if self._serve_inbox:
                    return self._serve_inbox.pop(0)
                if self._shutdown_flag.is_set() \
                        or self._shutdown_requested.is_set():
                    return None
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._serve_cv.wait(min(0.05, remaining))

    def _finish(self, name, op_name, result):
        self.timeline.negotiate_start(name, op_name)
        self.timeline.negotiate_rank_ready(name, 0)
        self.timeline.negotiate_end(name)
        self.timeline.start(name, op_name)
        self.timeline.end(name)
        h = self.handles.allocate()
        self.handles.mark_done(h, Status.ok(), result)
        return h

    def _check_ps(self, process_set):
        # size 1: the only valid set is {0} (shared validation helper).
        if process_set is not None:
            process_set.validate(0, 1)

    def allreduce_async(self, name, array, op=ReduceOp.SUM,
                        prescale=1.0, postscale=1.0, process_set=None):
        self._check_ps(process_set)
        out = np.asarray(array)
        if prescale != 1.0 or postscale != 1.0:
            out = out * (prescale * postscale)
        else:
            out = out.copy()
        return self._finish(name, "ALLREDUCE", out)

    def allgather_async(self, name, array, process_set=None):
        self._check_ps(process_set)
        return self._finish(name, "ALLGATHER", np.asarray(array).copy())

    def reducescatter_async(self, name, array, op=ReduceOp.SUM,
                            process_set=None):
        # size 1: the reduction of one rank's tensor, scattered to the
        # one rank — the input itself.
        self._check_ps(process_set)
        return self._finish(name, "REDUCESCATTER",
                            np.asarray(array).copy())

    def broadcast_async(self, name, array, root_rank=0, process_set=None):
        self._check_ps(process_set)
        if root_rank != 0:
            raise ValueError(
                f"broadcast root rank {root_rank} out of range for size 1")
        return self._finish(name, "BROADCAST", np.asarray(array).copy())

    def alltoall_async(self, name, array, splits=None, process_set=None):
        # Same splits validation as the multi-process engines, so code
        # written single-process fails the same way it would at scale.
        self._check_ps(process_set)
        arr = np.asarray(array)
        if splits is not None:
            splits = [int(s) for s in splits]
            if len(splits) != 1:
                raise ValueError(
                    "alltoall needs one split per participant (1)")
            if sum(splits) != (arr.shape[0] if arr.ndim else 0):
                raise ValueError("splits must sum to dim 0")
        # (no-splits divisibility: any dim 0 divides a world of 1)
        return self._finish(name, "ALLTOALL", arr.copy())

    def barrier(self, process_set=None):
        self._check_ps(process_set)
        return None

    def join(self) -> int:
        return 0


class PyEngine(_EngineBase):
    """Multi-process engine: background thread, star controller, ring data
    plane.  See module docstring for the parity map."""

    def __init__(self, rank, size, local_rank, local_size,
                 cross_rank, cross_size, rdv_addr, rdv_port):
        super().__init__(rank, size, local_rank, local_size,
                         cross_rank, cross_size)
        self.log = get_logger(rank)
        self.timeline = timeline_mod.from_env(rank)
        self.cycle_time = env_util.cycle_time_ms() / 1e3
        self.fusion_threshold = env_util.fusion_threshold_bytes()
        # Ring-hop receive segmentation (docs/performance.md); autotunable
        # like the fusion threshold, receiver-local so any mix of segment
        # settings (and the native engine) stays wire-compatible.
        self.ring_segment_bytes = env_util.ring_segment_bytes()
        self.stall_warn_s = env_util.get_float(env_util.STALL_CHECK_TIME, 60.0)
        self.stall_shutdown_s = env_util.get_float(
            env_util.STALL_SHUTDOWN_TIME, 0.0)
        self.stall_check_disable = env_util.get_bool(
            env_util.STALL_CHECK_DISABLE, False)
        # Two-level data plane (parity: HOROVOD_HIERARCHICAL_* knobs and
        # NCCLHierarchicalAllreduce / MPIHierarchicalAllgather).  Only
        # effective on a genuinely hierarchical topology — see
        # hierarchical_topology_ok().
        self.hierarchical_allreduce = env_util.get_bool(
            env_util.HIERARCHICAL_ALLREDUCE, False)
        self.hierarchical_allgather = env_util.get_bool(
            env_util.HIERARCHICAL_ALLGATHER, False)
        self.native_fallback_reason = None
        # Elastic membership epoch (horovod_tpu.elastic): stamped on every
        # list frame; frames from another incarnation are dropped (worker)
        # or rejected (coordinator) so a zombie rank from a previous gang
        # cannot corrupt this one's negotiation.
        self.epoch = env_util.get_int(env_util.ELASTIC_EPOCH, 0)

        # Telemetry (horovod_tpu.telemetry; docs/metrics.md).  The
        # registry hooks are zero-cost when off, but call sites whose
        # arguments allocate guard on this flag.  The straggler detector
        # is coordinator-only: it folds the per-rank ready ticks the
        # coordinator already sees into a skew histogram.
        self._metrics_on = _telemetry.init_from_env(rank, local_rank,
                                                    size=size)
        self._straggler = None
        if self._metrics_on:
            _tmx.set_gauge("hvd_elastic_epoch", self.epoch)
            if rank == 0:
                self._straggler = _telemetry.StragglerDetector(
                    env_util.get_float(env_util.STRAGGLER_WARN_MS, 0.0),
                    size)

        # Gang-wide tracing (telemetry/trace.py; docs/timeline.md "Gang-
        # wide tracing").  Unlike the rank-0 timeline, EVERY rank traces;
        # None when HVD_TRACE is unset, and all hot-path hooks are one
        # attribute load + None check.
        self._tracer = trace_mod.from_env(rank)
        self._clock_sync_cycles = env_util.trace_clock_sync_cycles()
        self._clock_ping_countdown = 0  # 0 = ping on the next cycle
        if self._tracer is not None and rank == 0:
            # The coordinator defines the gang clock axis: offset 0.
            self._tracer.clock(0, 0)

        # Always-on flight recorder (telemetry/blackbox.py;
        # docs/fault_tolerance.md "the black box").  Process-global so
        # the ring survives elastic engine teardown; every terminal
        # failure path below calls dump() before raising/propagating.
        self._blackbox = blackbox_mod.from_env(rank, epoch=self.epoch)
        self._blackbox_seq = 0
        if self._blackbox is not None:
            self._blackbox.note("engine.init", 0,
                                {"rank": rank, "size": size,
                                 "epoch": self.epoch})

        # request queue (tensor queue) + tensor table
        self._queue_lock = threading.Lock()
        self._request_queue: List[Request] = []
        self._table: Dict[str, TensorTableEntry] = {}

        # join state
        self._joined = False
        self._join_handle: Optional[int] = None
        self._last_joined_rank = -1

        # shutdown: `_shutdown_requested` asks the loop to negotiate the
        # stop through the controller (shutdown bits on the wire) so all
        # ranks exit in the same cycle; `_shutdown_flag` is the hard
        # local stop; `_loop_exited` lets shutdown() bound its wait.
        self._shutdown_requested = threading.Event()
        self._shutdown_flag = threading.Event()
        self._loop_exited = threading.Event()
        self._closed = False  # shutdown() ran its cleanup (socket close)
        self._aborted = False
        self._abort_reason = None
        self._abort_exc = None  # typed abort (e.g. FencedError)

        # coordinator state
        self._msg_table = _MessageTable(size) if rank == 0 else None
        self._joined_ranks: set = set()
        self._ctrl_inbox: "list" = []
        self._ctrl_lock = threading.Lock()
        self._last_stall_check = time.monotonic()

        # Hierarchical control tree (docs/fault_tolerance.md
        # "Hierarchical control plane, fencing, and quorum").  Planned
        # from the block topology BEFORE bootstrap: on a multi-host gang
        # the lowest local rank of each non-root host becomes a
        # sub-coordinator that folds its children's request/heartbeat
        # frames into one TAG_TREE_UP aggregate, so root-side recv work
        # is O(hosts), not O(ranks).  Single-host gangs plan an empty
        # tree and stay byte-identical to the seed star (pinned by
        # tests/test_ctrl_tree.py).
        self.ctrl_fanout = env_util.ctrl_fanout()
        self._tree_parent, self._tree_children, self._rank_route = \
            self._plan_tree()
        self._tree_parent_sock = None          # child: link to sub-coord
        self._tree_child_socks: Dict[int, socket.socket] = {}  # sub-coord
        self._tree_up_buf: List[tuple] = []    # sub-coord: pending entries
        self._tree_up_lock = threading.Lock()
        self._tree_orphaned = False            # child: sub-coord died
        # Child: request payloads sent up the tree since the last
        # response frame — re-sent after a re-parent because the dead
        # sub-coordinator may not have relayed them (bounded; the
        # coordinator absorbs duplicates idempotently).
        self._tree_unacked: List[bytes] = []
        self._reparented_ranks: set = set()    # root: adopted orphans
        self._fenced: Optional[tuple] = None   # worker: TAG_FENCE payload

        # Liveness (parity-extension): heartbeats piggyback on the ctrl
        # connections; a worker silent past the timeout is evicted via
        # the Join machinery.  Default OFF (timeout 0) — identical wire
        # traffic to the pre-heartbeat protocol, and safe to mix with
        # the native engine, which never sees the new frame tag.
        self.heartbeat_timeout = env_util.get_float(
            env_util.HEARTBEAT_TIMEOUT,
            env_util.get_float("HOROVOD_HEARTBEAT_TIMEOUT", 0.0))
        self.heartbeat_interval = env_util.get_float(
            env_util.HEARTBEAT_INTERVAL,
            max(0.05, self.heartbeat_timeout / 4.0))
        self._evicted_ranks: set = set()      # dead ranks, every rank
        self._ranks_failed: List[int] = []    # raises on next enqueue
        self._conn_lost: set = set()          # recv threads -> coord cycle
        self._ctrl_conn_lost = False          # worker: coordinator EOF
        self._last_seen: Dict[int, float] = {}
        self._last_send = time.monotonic()

        # Collective deadlines (docs/fault_tolerance.md "hung ranks vs
        # dead ranks").  Default OFF (0) — identical hot path to the
        # seed, pinned by tests/test_timeouts.py.  When on, every eager
        # collective carries a deadline; a local hop timeout triggers
        # the gang-wide abort agreement over the still-live control
        # mesh (TAG_ABORT_REPORT / TAG_PROBE / TAG_PROBE_ACK /
        # TAG_ABORT_VERDICT).
        self.collective_timeout = env_util.collective_timeout_s()
        self.collective_probe_timeout = env_util.get_float(
            env_util.COLLECTIVE_PROBE_TIMEOUT,
            max(0.5, self.collective_timeout / 2.0))
        # Ctrl sends can happen off the background thread on both sides:
        # workers send from _worker_cycle AND the recv thread (probe
        # acks); the coordinator sends from the background thread AND
        # the serving loop's thread (TAG_SERVE admission broadcasts).
        # Serialize so frames never interleave.
        self._ctrl_send_lock = threading.Lock()
        # Serving admission broadcast (TAG_SERVE): frames land here on
        # every rank (the coordinator delivers to itself directly) and
        # the serving loop drains them via serve_recv().
        self._serve_inbox: List[bytes] = []
        self._serve_cv = threading.Condition()
        # Coordinator: reports/acks captured by the ctrl recv threads.
        self._abort_inbox: List[tuple] = []
        self._abort_lock = threading.Lock()
        # Worker: verdict handoff from the recv thread to the blocked
        # background thread.
        self._abort_verdict: Optional[tuple] = None
        self._abort_cv = threading.Condition(self._abort_lock)
        # Busy marker for probe acks: monotonic start of the collective
        # currently executing on the background thread (0.0 = idle).
        # Only maintained when the deadline knob is on.
        self._in_collective_since = 0.0
        self._in_collective_name = ""
        # Coordinator: last ruled verdict, re-sent to stragglers whose
        # own hop deadline fires after the broadcast.
        self._last_verdict: Optional[tuple] = None
        # Coordinator: flight-recorder dumps pulled from live workers
        # after an abort verdict (TAG_BLACKBOX_DUMP frames, captured by
        # the ctrl recv threads).
        self._blackbox_inbox: List[tuple] = []
        self._blackbox_lock = threading.Lock()

        # response cache (parity: response_cache.cc; protocol adapted to
        # the star controller — see common/response_cache.py docstring).
        # All cache state is touched only on the background thread.
        self._cache = rcache.ResponseCache(
            env_util.get_int(env_util.CACHE_CAPACITY, 1024))
        self._cache_classify_enabled = True
        self._resend_uncached: set = set()
        self._hit_ranks: Dict[str, set] = {}

        # autotuner (coordinator only; parity: parameter_manager.cc —
        # rank 0 tunes and broadcasts).
        self._pm = None
        if rank == 0:
            from horovod_tpu.autotune import ParameterManager

            self._pm = ParameterManager.from_env(
                self.fusion_threshold, self.cycle_time,
                self.hierarchical_allreduce, self.hierarchical_allgather,
                hierarchical_ok=self.hierarchical_topology_ok(),
                ring_segment_bytes=self.ring_segment_bytes)
        self._pending_params = None

        self._bootstrap(rdv_addr, rdv_port)

        if self.epoch and self.timeline.enabled:
            self.timeline.elastic_event(f"ELASTIC_EPOCH_{self.epoch}",
                                        size=self.size)

        self._bg = threading.Thread(
            target=self._background_loop, name="hvd-background", daemon=True)
        self._bg.start()

    # ------------------------------------------------------------------
    # hierarchical control tree
    # ------------------------------------------------------------------

    def _plan_tree(self):
        """Plan the two-level control tree from the block topology.

        Returns ``(parent, children, route)``:

        * ``parent``: this rank's sub-coordinator (None = talk to the
          root directly — the root itself, sub-coordinators, the root's
          own host, and fan-out overflow),
        * ``children``: ranks this sub-coordinator folds,
        * ``route``: root-only map child rank -> sub-coordinator rank.

        Empty on a single-host gang (``cross_size == 1``) or a
        non-block rank layout, where the flat star is already O(hosts):
        the seed protocol runs byte-identical.
        """
        none = (None, [], {})
        if self.size <= 1 or self.local_size <= 1 or self.cross_size <= 1:
            return none
        if not env_util.ctrl_tree_on():
            return none
        if not self.hierarchical_topology_ok():
            return none
        fanout = self.ctrl_fanout
        parent, children, route = None, [], {}
        ls = self.local_size
        for host in range(1, self.cross_size):
            sub = host * ls
            if sub >= self.size:
                break
            members = range(sub + 1, min((host + 1) * ls, self.size))
            folded = list(members if fanout <= 0 else
                          list(members)[:fanout])
            for c in folded:
                route[c] = sub
                if c == self.rank:
                    parent = sub
            if self.rank == sub:
                children = folded
        return parent, children, route

    # ------------------------------------------------------------------
    # bootstrap: rendezvous + socket meshes
    # ------------------------------------------------------------------

    def _bootstrap(self, rdv_addr: str, rdv_port: int) -> None:
        from horovod_tpu.bootstrap import bootstrap_mesh

        # Recovery-ladder mode (HVD_WIRE_CRC=1, docs/fault_tolerance.md
        # "recovery ladder"): keep the bootstrap listener open so a
        # dropped data socket can be re-dialed mid-gang, and remember
        # every peer's advertised address for the re-dial.
        ladder_on = env_util.wire_crc()
        self._reconnect_listener = None
        tree = {"parent": self._tree_parent, "children": self._tree_children}
        if ladder_on:
            (self._data, self._ctrl_sock, self._ctrl_socks,
             kv, kv_prefix, mesh_peers, mesh_listener) = bootstrap_mesh(
                self.rank, self.size, rdv_addr, rdv_port,
                shm_capable=True, keep_listener=True, tree=tree)
        else:
            (self._data, self._ctrl_sock, self._ctrl_socks,
             kv, kv_prefix) = bootstrap_mesh(
                self.rank, self.size, rdv_addr, rdv_port, shm_capable=True,
                tree=tree)
        self._tree_parent_sock = tree.get("parent_sock")
        self._tree_child_socks = tree.get("child_socks") or {}

        # Data-plane hot-path state (docs/performance.md): one transport
        # per peer, selected at mesh-build time (shm ring for same-host
        # peers unless HVD_SHM_DISABLE, TCP otherwise), each with one
        # persistent sender thread — ring hops enqueue sends instead of
        # spawning a thread per hop — plus the persistent fusion/hop
        # scratch the collectives pack into.  Torn down in shutdown();
        # an elastic re-form goes through shutdown() + a fresh engine
        # under a new rendezvous scope, so re-bootstrap always starts
        # from an empty pool and fresh pairing keys.
        from horovod_tpu.ops.fusion_buffer import FusionBuffer
        from horovod_tpu.utils import transport as tpt

        if ladder_on:
            from horovod_tpu.utils import ladder

            self._transports, self._reconnect_listener = \
                ladder.build_ladder_links(
                    self.rank, self.size, self._data, kv, kv_prefix,
                    mesh_peers, mesh_listener, epoch=self.epoch)
            # Ladder links own their sender threads (no PeerSenders).
            self._senders = {}
        else:
            self._transports = tpt.build_transports(
                self.rank, self.size, self._data, kv, kv_prefix)
            # TCP transports own the engine's PeerSenders; shm peers
            # have no socket sender (their thread lives inside the
            # transport), so the per-peer sender-thread count stays
            # exactly one either way.
            self._senders = {r: t.sender
                             for r, t in self._transports.items()
                             if t.kind == "tcp"}
        self._fusion_buf = FusionBuffer()

        # What the receiver threads fill: made before any of them starts
        # (a frame can arrive before this constructor returns).
        self._response_inbox: List[bytes] = []
        self._response_lock = threading.Lock()
        self._response_cv = threading.Condition(self._response_lock)

        # ctrl receiver threads
        if self.rank == 0:
            now = time.monotonic()
            self._last_seen = {r: now for r in self._ctrl_socks}
            for r, s in self._ctrl_socks.items():
                threading.Thread(target=self._ctrl_recv_loop,
                                 args=(r, s), daemon=True).start()
        else:
            threading.Thread(target=self._worker_recv_loop, daemon=True
                             ).start()
            if self._tree_parent_sock is not None:
                threading.Thread(target=self._tree_parent_recv_loop,
                                 daemon=True).start()
            for r, s in self._tree_child_socks.items():
                threading.Thread(target=self._tree_child_recv_loop,
                                 args=(r, s), daemon=True).start()

    def _ctrl_recv_loop(self, peer_rank: int, sock: socket.socket) -> None:
        try:
            while not self._shutdown_flag.is_set():
                tag, payload = su.recv_frame(sock)
                self._dispatch_ctrl_frame(peer_rank, tag, payload, sock)
        except (ConnectionError, OSError):
            # EOF/reset: fast liveness signal, stronger than a missed
            # heartbeat (only acted on when heartbeats are enabled).
            self._conn_lost.add(peer_rank)

    def _dispatch_ctrl_frame(self, peer_rank: int, tag: int,
                             payload: bytes, sock) -> None:
        """Coordinator-side dispatch of one control frame — from a
        rank's own socket, or replayed from a TAG_TREE_UP aggregate
        (then ``peer_rank`` is the entry's origin, and ``sock`` the
        sub-coordinator's link)."""
        # Any frame is proof of life; TAG_HEARTBEAT carries nothing else.
        self._last_seen[peer_rank] = time.monotonic()
        if tag == su.TAG_REQUEST_LIST:
            with self._ctrl_lock:
                self._ctrl_inbox.append((peer_rank, payload))
        elif tag == su.TAG_TREE_UP:
            # A sub-coordinator's aggregate: dispatch every folded entry
            # as if it had arrived on its origin rank's own socket.
            entries, epoch = wire.decode_tree_up(payload)
            for origin, etag, epayload in entries:
                self._dispatch_ctrl_frame(origin, etag, epayload, sock)
        elif tag == su.TAG_REPARENT:
            rank, old_parent, epoch = wire.decode_reparent(payload)
            self._note_reparent(peer_rank, old_parent, epoch)
        elif tag in (su.TAG_ABORT_REPORT, su.TAG_PROBE_ACK):
            with self._abort_lock:
                self._abort_inbox.append(
                    (peer_rank, tag, payload))
        elif tag == su.TAG_CLOCK_PING:
            # Trace clock sync (telemetry/trace.py): echo the
            # worker's t0 with our monotonic read.  Answered
            # from THIS thread so the estimate never waits on a
            # busy background cycle (cf. TAG_PROBE).
            t0_ns, pepoch = wire.decode_clock_ping(payload)
            pong = wire.encode_clock_pong(
                t0_ns, time.monotonic_ns(), pepoch)
            try:
                with self._ctrl_send_lock:
                    su.send_frame(sock, su.TAG_CLOCK_PONG, pong)
            except (ConnectionError, OSError):
                pass  # liveness machinery owns the eviction
        elif tag == su.TAG_BLACKBOX_DUMP:
            # A worker's flight-recorder ring, answering our
            # post-verdict pull (_pull_blackbox_dumps).
            with self._blackbox_lock:
                self._blackbox_inbox.append((peer_rank, payload))

    def _note_reparent(self, rank: int, old_parent: int,
                       epoch: int) -> None:
        """Root: a child of a dead sub-coordinator adopted itself back
        to the direct star.  Only the dead parent gets evicted — the
        orphan keeps its seat, and its in-flight collectives ride on."""
        self._reparented_ranks.add(rank)
        self._rank_route.pop(rank, None)
        self.log.warning(
            "rank %d re-parented to the root (sub-coordinator %d died)",
            rank, old_parent)
        _tmx.inc_counter("hvd_subcoord_reparents_total")
        blackbox_mod.note("subcoord.reparent", time.monotonic_ns(),
                          rank=rank, old_parent=old_parent, epoch=epoch)
        if self.timeline.enabled:
            self.timeline.instant(timeline_mod.SUBCOORD_REPARENT,
                                  rank=rank, old_parent=old_parent)

    def _worker_recv_loop(self) -> None:
        try:
            while not self._shutdown_flag.is_set():
                tag, payload = su.recv_frame(self._ctrl_sock)
                self._dispatch_worker_frame(tag, payload)
        except (ConnectionError, OSError):
            # Coordinator EOF/reset.  During a negotiated shutdown (or
            # after our own close) this is expected teardown noise;
            # otherwise it is the fastest dead-hub signal the worker
            # has — the next worker cycle drains any already-received
            # shutdown frame and only then declares the hub lost.
            if not (self._shutdown_flag.is_set()
                    or self._shutdown_requested.is_set()
                    or self._closed):
                self._ctrl_conn_lost = True
                # Wake a serving loop parked in serve_recv: the abort
                # it needs fires from the next worker cycle, but the
                # cycle only runs every cycle_time — notify so nothing
                # sleeps a full timeout on a dead hub.
                with self._serve_cv:
                    self._serve_cv.notify_all()

    def _dispatch_worker_frame(self, tag: int, payload: bytes) -> None:
        """Worker-side dispatch of one coordinator frame — from the
        direct control socket, or forwarded down the tree by this
        rank's sub-coordinator.  Replies (probe acks, blackbox dumps)
        always go up the DIRECT socket: it stays live even while the
        sub-coordinator is dying, which is exactly when the coordinator
        needs them."""
        if tag == su.TAG_TREE_DOWN:
            # Sub-coordinator: route a root frame to one child or fan
            # it out to the whole host.
            target, itag, ipayload = wire.decode_tree_down(payload)
            for r, s in list(self._tree_child_socks.items()):
                if target != -1 and r != target:
                    continue
                try:
                    _fi.fire("ctrl.subcoord.send", str(r))
                    with self._ctrl_send_lock:
                        su.send_frame(s, itag, ipayload)
                except (ConnectionError, OSError):
                    pass  # the root's liveness machinery owns eviction
            return
        if tag == su.TAG_FENCE:
            # Typed rejection: the coordinator is at a newer membership
            # epoch and we have no seat in it.  The next worker cycle
            # raises FencedError to the training loop and exits.
            self._fenced = wire.decode_fence(payload)
            with self._serve_cv:
                self._serve_cv.notify_all()
            return
        if tag == su.TAG_RESPONSE_LIST:
            with self._response_cv:
                self._response_inbox.append(payload)
                self._response_cv.notify_all()
        elif tag == su.TAG_PROBE:
            # Answer from THIS thread: the background thread may
            # be the very thing that is wedged in the data plane.
            since = self._in_collective_since
            busy_s = (time.monotonic() - since) if since else 0.0
            ack = wire.encode_probe_ack(
                since > 0.0, busy_s, self.epoch)
            try:
                with self._ctrl_send_lock:
                    su.send_frame(self._ctrl_sock,
                                  su.TAG_PROBE_ACK, ack)
            except (ConnectionError, OSError):
                pass
        elif tag == su.TAG_ABORT_VERDICT:
            vname, vranks, vepoch = wire.decode_abort_verdict(
                payload)
            if vepoch != self.epoch:
                return
            with self._abort_cv:
                self._abort_verdict = (vname, vranks)
                self._abort_cv.notify_all()
        elif tag == su.TAG_SERVE:
            with self._serve_cv:
                self._serve_inbox.append(payload)
                self._serve_cv.notify_all()
        elif tag == su.TAG_CLOCK_PONG:
            # Midpoint method: offset maps this rank's monotonic
            # axis onto rank 0's (add offset to local times).
            t1_ns = time.monotonic_ns()
            t0_ns, tc_ns, pepoch = wire.decode_clock_pong(payload)
            tr = self._tracer
            if tr is not None and pepoch == self.epoch:
                offset_ns = tc_ns - (t0_ns + t1_ns) // 2
                tr.clock(offset_ns, t1_ns - t0_ns)
                # The flight recorder rides the same estimate;
                # its dump ships the freshest value so the
                # postmortem can align rank timelines.
                blackbox_mod.note_clock_offset(offset_ns)
                if self._metrics_on:
                    _tmx.set_gauge("hvd_trace_clock_skew_seconds",
                                   offset_ns / 1e9)
        elif tag == su.TAG_BLACKBOX:
            # Coordinator pulling our flight-recorder ring after
            # an abort verdict.  Answered from THIS thread — the
            # background thread may be the wedged party, and its
            # evidence is exactly what the pull is for.
            bb = blackbox_mod.get()
            if bb is not None:
                blob = bb.dump_bytes("coordinator_pull")
                reply = wire.encode_blackbox_dump(
                    self.rank, self.epoch, blob)
                try:
                    with self._ctrl_send_lock:
                        su.send_frame(self._ctrl_sock,
                                      su.TAG_BLACKBOX_DUMP, reply)
                except (ConnectionError, OSError):
                    pass

    # -- hierarchical control tree (docs/fault_tolerance.md) -------------
    #
    # Children of a per-host sub-coordinator send their request/heartbeat
    # frames over a dedicated chan-2 bootstrap link; the sub-coordinator
    # folds everything it buffered plus its own frame into ONE
    # TAG_TREE_UP on its direct root socket each cycle, so the root's
    # recv work scales with hosts, not ranks.  Responses always ride the
    # direct star — a response lost inside a dying sub-coordinator would
    # desync the gang, so nothing irreplaceable ever transits the tree.

    def _tree_parent_recv_loop(self) -> None:
        """Child: frames forwarded down by our sub-coordinator (routed
        probes).  EOF here is the re-parent trigger: the direct root
        socket is still live, so adopt ourselves back to the star."""
        sock = self._tree_parent_sock
        try:
            while not self._shutdown_flag.is_set():
                tag, payload = su.recv_frame(sock)
                self._dispatch_worker_frame(tag, payload)
        except (ConnectionError, OSError):
            if not (self._shutdown_flag.is_set()
                    or self._shutdown_requested.is_set()
                    or self._closed):
                self._reparent_to_root()

    def _tree_child_recv_loop(self, child: int,
                              sock: socket.socket) -> None:
        """Sub-coordinator: buffer a child's uplink frames; the next
        worker cycle folds them into one TAG_TREE_UP.  EOF means the
        child died — the root's heartbeat timeout owns that eviction, so
        nothing to do here."""
        try:
            while not self._shutdown_flag.is_set():
                tag, payload = su.recv_frame(sock)
                with self._tree_up_lock:
                    self._tree_up_buf.append((child, tag, payload))
        except (ConnectionError, OSError):
            pass

    def _reparent_to_root(self) -> None:
        """Child of a dead sub-coordinator: announce TAG_REPARENT on the
        still-open direct socket and resend the recent request payloads
        that may have died inside the parent (the coordinator's message
        table is idempotent per rank, so duplicates are harmless).  From
        here on this rank speaks the flat star; only the dead parent is
        evicted — no gang-wide abort."""
        if self._tree_orphaned or self._tree_parent is None:
            return
        self._tree_orphaned = True
        old = self._tree_parent
        self.log.warning(
            "sub-coordinator %d unreachable; re-parenting to the root",
            old)
        try:
            _fi.fire("ctrl.reparent", str(self.rank))
            with self._ctrl_send_lock:
                su.send_frame(self._ctrl_sock, su.TAG_REPARENT,
                              wire.encode_reparent(self.rank, old,
                                                   self.epoch))
                for payload in list(self._tree_unacked):
                    su.send_frame(self._ctrl_sock, su.TAG_REQUEST_LIST,
                                  payload)
            self._last_send = time.monotonic()
            _tmx.inc_counter("hvd_subcoord_reparents_total")
            blackbox_mod.note("subcoord.reparent", time.monotonic_ns(),
                              rank=self.rank, old_parent=old,
                              epoch=self.epoch)
        except (ConnectionError, OSError):
            # The direct socket is gone too — that is a dead hub, and
            # the ordinary lost-coordinator abort owns it.
            self._ctrl_conn_lost = True
            with self._serve_cv:
                self._serve_cv.notify_all()

    # -- serving admission broadcast (docs/serving.md) -------------------

    def serve_broadcast(self, payload: bytes) -> None:
        """Coordinator: push one serve-step frame (wire.py ServeDelta) to
        every live worker and to the local inbox.  Called from the
        serving loop's thread, hence the ctrl send lock."""
        if self.rank != 0:
            raise RuntimeError("serve_broadcast is coordinator-only")
        for r, s in self._ctrl_socks.items():
            if r in self._evicted_ranks:
                continue
            try:
                with self._ctrl_send_lock:
                    su.send_frame(s, su.TAG_SERVE, payload)
            except (ConnectionError, OSError):
                pass  # liveness machinery owns the eviction
        with self._serve_cv:
            self._serve_inbox.append(payload)
            self._serve_cv.notify_all()

    def serve_recv(self, timeout: float) -> Optional[bytes]:
        """Block (≤ ``timeout`` s) for the next serve-step frame.  None
        on timeout or local shutdown; raises RanksFailedError once peers
        have been declared failed so the serving loop re-forms through
        the same path as a failed collective."""
        deadline = time.monotonic() + timeout
        with self._serve_cv:
            while True:
                if self._serve_inbox:
                    return self._serve_inbox.pop(0)
                if self._ranks_failed:
                    raise RanksFailedError(self._ranks_failed)
                if self._aborted or self._shutdown_flag.is_set() \
                        or self._shutdown_requested.is_set():
                    return None
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                # Short slices: the cv is only notified on frame arrival,
                # and abort/shutdown must still wake this thread.
                self._serve_cv.wait(min(0.05, remaining))

    # ------------------------------------------------------------------
    # enqueue API (framework-thread side)
    # ------------------------------------------------------------------

    def _enqueue(self, entry: TensorTableEntry) -> int:
        if self._ranks_failed:
            # In-flight ops already completed on the survivors; the next
            # submission is the point where the training loop can react.
            raise RanksFailedError(self._ranks_failed)
        if self._abort_exc is not None:
            # Typed abort (FencedError, ...): the class IS the signal —
            # the elastic wrapper re-forms on RanksFailedError but must
            # let a fenced zombie exit.
            raise self._abort_exc
        if self._aborted or self._shutdown_flag.is_set() \
                or self._shutdown_requested.is_set():
            raise RuntimeError("horovod_tpu runtime has been shut down")
        self._claim_name(entry.name)
        with self._queue_lock:
            self._table[entry.name] = entry
            self._request_queue.append(entry.request)
        return entry.handle

    def _ps_fields(self, process_set):
        """Validate + unpack a ProcessSet into (id, size) request fields."""
        if process_set is None:
            return 0, 0
        return process_set.validate(self.rank, self.size)

    def allreduce_async(self, name, array, op=ReduceOp.SUM,
                        prescale=1.0, postscale=1.0, process_set=None):
        arr = np.ascontiguousarray(array)
        ps_id, ps_size = self._ps_fields(process_set)
        req = Request(
            request_rank=self.rank,
            request_type=RequestType.ALLREDUCE,
            tensor_type=dtype_from_numpy(arr.dtype),
            tensor_name=name,
            device="cpu",
            tensor_shape=TensorShape(arr.shape),
            reduce_op=op,
            prescale_factor=prescale,
            postscale_factor=postscale,
            process_set_id=ps_id,
            process_set_size=ps_size,
        )
        h = self.handles.allocate()
        return self._enqueue(TensorTableEntry(name, arr, h, req))

    def allgather_async(self, name, array, process_set=None):
        arr = np.ascontiguousarray(array)
        ps_id, ps_size = self._ps_fields(process_set)
        req = Request(
            request_rank=self.rank,
            request_type=RequestType.ALLGATHER,
            tensor_type=dtype_from_numpy(arr.dtype),
            tensor_name=name,
            device="cpu",
            tensor_shape=TensorShape(arr.shape),
            process_set_id=ps_id,
            process_set_size=ps_size,
        )
        h = self.handles.allocate()
        return self._enqueue(TensorTableEntry(name, arr, h, req))

    def reducescatter_async(self, name, array, op=ReduceOp.SUM,
                            process_set=None):
        arr = np.ascontiguousarray(array)
        if arr.ndim == 0:
            raise ValueError(
                "reducescatter needs at least one dimension to scatter "
                "over (got a scalar)")
        ps_id, ps_size = self._ps_fields(process_set)
        req = Request(
            request_rank=self.rank,
            request_type=RequestType.REDUCESCATTER,
            tensor_type=dtype_from_numpy(arr.dtype),
            tensor_name=name,
            device="cpu",
            tensor_shape=TensorShape(arr.shape),
            reduce_op=op,
            process_set_id=ps_id,
            process_set_size=ps_size,
        )
        h = self.handles.allocate()
        return self._enqueue(TensorTableEntry(name, arr, h, req))

    def broadcast_async(self, name, array, root_rank=0, process_set=None):
        arr = np.ascontiguousarray(array)
        if not (0 <= root_rank < self.size):
            raise ValueError(
                f"broadcast root rank {root_rank} out of range "
                f"[0, {self.size})")
        ps_id, ps_size = self._ps_fields(process_set)
        if process_set is not None and \
                root_rank not in process_set.ranks:
            raise ValueError(
                f"broadcast root rank {root_rank} (global) is not a "
                f"member of {process_set}")
        req = Request(
            request_rank=self.rank,
            request_type=RequestType.BROADCAST,
            tensor_type=dtype_from_numpy(arr.dtype),
            tensor_name=name,
            device="cpu",
            tensor_shape=TensorShape(arr.shape),
            root_rank=root_rank,
            process_set_id=ps_id,
            process_set_size=ps_size,
        )
        h = self.handles.allocate()
        return self._enqueue(
            TensorTableEntry(name, arr, h, req, root_rank=root_rank))

    def alltoall_async(self, name, array, splits=None, process_set=None):
        arr = np.ascontiguousarray(array)
        ps_id, ps_size = self._ps_fields(process_set)
        n = ps_size or self.size
        if splits is not None:
            splits = [int(s) for s in splits]
            if len(splits) != n:
                raise ValueError(
                    f"alltoall needs one split per participant ({n})")
            if sum(splits) != arr.shape[0]:
                raise ValueError("splits must sum to dim 0")
        elif arr.ndim and arr.shape[0] % n:
            raise ValueError(
                "alltoall without splits requires dim 0 divisible by "
                "the participant count")
        req = Request(
            request_rank=self.rank,
            request_type=RequestType.ALLTOALL,
            tensor_type=dtype_from_numpy(arr.dtype),
            tensor_name=name,
            device="cpu",
            tensor_shape=TensorShape(arr.shape),
            process_set_id=ps_id,
            process_set_size=ps_size,
        )
        h = self.handles.allocate()
        entry = TensorTableEntry(name, arr, h, req, splits=splits)
        return self._enqueue(entry)

    def barrier(self, process_set=None):
        # Dedicated per-engine barrier counters (NOT the handle counter,
        # and one per process set): the name must be identical on every
        # member regardless of how many other ops each rank has issued,
        # and wire-compatible with the native engine's naming
        # (csrc/engine.cc Engine::Barrier).
        ps_id, ps_size = self._ps_fields(process_set)
        with self._queue_lock:
            c = self._barrier_counters.get(ps_id, 0)
            self._barrier_counters[ps_id] = c + 1
        # Distinct name families keep a concurrent global barrier and a
        # set barrier from colliding in the local duplicate-name guard.
        name = f"__barrier.{c}" if not ps_id else \
            f"__barrier.ps{ps_id}.{c}"
        req = Request(request_rank=self.rank,
                      request_type=RequestType.BARRIER,
                      tensor_type=DataType.INT32,
                      tensor_name=name, device="cpu",
                      process_set_id=ps_id, process_set_size=ps_size)
        h = self.handles.allocate()
        self._enqueue(TensorTableEntry(
            name, np.zeros(1, np.int32), h, req))
        return self.handles.wait(h)

    def join(self) -> int:
        """Block until every rank has joined; parity: §3.5 of SURVEY.md."""
        req = Request(request_rank=self.rank, request_type=RequestType.JOIN,
                      tensor_name="__join__", device="cpu")
        h = self.handles.allocate()
        with self._queue_lock:
            self._joined = True
            self._join_handle = h
            self._request_queue.append(req)
        self.handles.wait(h)
        return self._last_joined_rank

    def shutdown(self):
        # Cleanup must run exactly once — but it must run even when the
        # loop was already stopped by a PEER's negotiated shutdown (the
        # normal case on every non-initiating rank), so the guard is a
        # dedicated cleanup flag, not the loop-stop flags.
        if self._closed:
            return
        self._closed = True
        # Negotiated shutdown (parity: controller.cc:116-130): the next
        # worker/coordinator cycle carries the shutdown bit, the
        # coordinator's ResponseList stops every rank in the same cycle,
        # and only then do sockets close — no rank reads a socket its
        # peer already closed.  Bounded in case peers are already gone.
        self._shutdown_requested.set()
        self._loop_exited.wait(timeout=10)
        self._shutdown_flag.set()
        self._bg.join(timeout=10)
        self.timeline.shutdown()
        trace_mod.release(self._tracer)
        self._tracer = None
        # Stop the persistent senders first (drains queued frames while
        # the sockets are still open), then close sockets — which also
        # unblocks any sender stuck mid-write to a dead peer — and join.
        # Shm transports go first: their close drains, breaks any writer
        # spinning on a dead peer's full ring via the stop flag, joins
        # the hvd-send-shm-* thread, and unmaps the segment (the /dev/shm
        # name was already unlinked at pairing time, so nothing can leak
        # even if this process dies before reaching here).
        # Ladder mode: stop accepting reconnect re-dials before links
        # close, so no freshly-routed socket lands on a dying link.
        rl = getattr(self, "_reconnect_listener", None)
        if rl is not None:
            try:
                rl.close()
            except Exception:
                pass
        transports = list(getattr(self, "_transports", {}).values())
        for t in transports:
            if t.kind != "tcp":
                try:
                    t.close(timeout=2.0)
                except Exception:
                    pass
        senders = list(getattr(self, "_senders", {}).values())
        for snd in senders:
            try:
                snd.close(timeout=2.0)
            except Exception:
                pass
        self._senders = {}
        for s in list(self._data.values()) + list(self._ctrl_socks.values()):
            try:
                s.close()
            except OSError:
                pass
        if self._ctrl_sock is not None:
            try:
                self._ctrl_sock.close()
            except OSError:
                pass
        # Tree links (chan-2 bootstrap sockets): closing them is what
        # unblocks the child/parent recv threads; the _closed flag above
        # keeps the EOF from reading as a dead sub-coordinator.
        tree_socks = list(self._tree_child_socks.values())
        if self._tree_parent_sock is not None:
            tree_socks.append(self._tree_parent_sock)
        for s in tree_socks:
            try:
                s.close()
            except OSError:
                pass
        # Closed sockets error out any sender blocked in a write; bound
        # the join so shutdown stays prompt even for a wedged thread.
        for snd in senders:
            snd.thread.join(timeout=2.0)
        for t in transports:
            try:
                t.join(timeout=2.0)
            except Exception:
                pass
        self._transports = {}

    # ------------------------------------------------------------------
    # background loop
    # ------------------------------------------------------------------

    def _background_loop(self):
        try:
            while not self._shutdown_flag.is_set():
                t0 = time.monotonic()
                self.timeline.mark_cycle_start()
                if not self._run_loop_once():
                    break
                dt = time.monotonic() - t0
                _tmx.inc_counter("hvd_cycles_total")
                _tmx.observe("hvd_cycle_duration_seconds", dt)
                if self.rank == 0:
                    # Root coordination cost, keyed by gang size — the
                    # curve ctrl_sim's sweep reports (and the number
                    # the hierarchical tree exists to flatten).
                    _tmx.observe("hvd_ctrl_cycle_seconds", dt,
                                 labels=(str(self.size),))
                if dt < self.cycle_time:
                    time.sleep(self.cycle_time - dt)
        except Exception as e:  # deliver failure to all pending handles
            if not (self._shutdown_requested.is_set()
                    or self._shutdown_flag.is_set()):
                self.log.error("background loop failed: %r", e)
            self._abort(str(e))
        finally:
            self._drain_on_shutdown()
            self._loop_exited.set()

    def _drain_on_shutdown(self):
        # Parity: SHUT_DOWN_ERROR delivered to pending callbacks
        # (operations.cc:515-521).
        with self._queue_lock:
            entries = list(self._table.values())
            self._table.clear()
            self._request_queue.clear()
            jh, self._join_handle = self._join_handle, None
        exc = self._abort_exc
        status = Status(StatusType.ABORTED,
                        self._abort_reason or "Horovod has been shut down.",
                        exc) if exc is not None else \
            Status.aborted("Horovod has been shut down.")
        for e in entries:
            self._release_name(e.name)
            self.handles.mark_done(e.handle, status, None)
        if jh is not None:
            self.handles.mark_done(jh, Status.ok(), None)

    def _run_loop_once(self) -> bool:
        _fi.fire("engine.cycle", str(self.rank))
        with self._queue_lock:
            msgs = self._request_queue
            self._request_queue = []
        _tmx.set_gauge("hvd_queue_depth", len(msgs))
        if self.rank == 0:
            return self._coordinator_cycle(msgs)
        return self._worker_cycle(msgs)

    # -- cache classification (both roles, background thread only) -------

    def _classify(self, msgs: List[Request]):
        """Split popped requests into (uncached requests, hit events).
        Parity: the cache check at the top of ComputeResponseList
        (controller.cc:171-200)."""
        requests: List[Request] = []
        hits: List[tuple] = []
        misses = 0
        for req in msgs:
            if req.tensor_name in self._resend_uncached:
                self._resend_uncached.discard(req.tensor_name)
                requests.append(req)
                continue
            if not self._cache_classify_enabled:
                requests.append(req)
                continue
            state, pos = self._cache.classify(req)
            if state == rcache.HIT:
                hits.append((req.tensor_name, pos))
            else:
                requests.append(req)
                misses += 1
        if hits:
            _tmx.inc_counter("hvd_cache_hits_total", len(hits))
        if misses:
            _tmx.inc_counter("hvd_cache_misses_total", misses)
        return requests, hits

    def _execute_cached_hits(self, hit_positions: List[int]) -> None:
        cached: List[Response] = []
        for p in hit_positions:
            resp = self._cache.get_by_position(p)
            if resp is None:
                # A missing position means this rank's cache diverged from
                # the coordinator's.  Executing the remaining hits would
                # launch a different collective sequence than the other
                # ranks and hang the whole job — fail fast instead.
                self.log.error(
                    "cache coherence violation: position %d missing "
                    "locally, aborting", p)
                self._abort(f"cache coherence violation: position {p}")
                return
            self._cache.touch(p)
            # Copy: _fuse_responses mutates its inputs in place, and the
            # cached Response must stay single-tensor.
            cached.append(Response(
                response_type=resp.response_type,
                tensor_type=resp.tensor_type,
                tensor_names=list(resp.tensor_names),
                devices=list(resp.devices),
                tensor_sizes=list(resp.tensor_sizes),
                reduce_op=resp.reduce_op,
                prescale_factor=resp.prescale_factor,
                postscale_factor=resp.postscale_factor,
                tensor_shapes=list(resp.tensor_shapes),
            ))
        for resp in self._fuse_responses(cached):
            self._perform_operation(resp, from_cache=True)

    def _process_resends(self, resend_names: List[str]) -> None:
        """Coordinator could not resolve our hit event (entry evicted
        there in flight): requeue the original full Request."""
        with self._queue_lock:
            for nm in resend_names:
                ent = self._table.get(nm)
                if ent is not None:
                    self._resend_uncached.add(nm)
                    self._request_queue.append(ent.request)

    # -- worker ---------------------------------------------------------

    def _maybe_clock_ping(self) -> None:
        """Tracing only: piggyback a clock-offset ping on the ctrl
        channel at bootstrap and every HVD_TRACE_CLOCK_SYNC_CYCLES
        worker cycles (docs/timeline.md "Gang-wide tracing")."""
        n = self._clock_ping_countdown
        if n > 0:
            self._clock_ping_countdown = n - 1
            return
        self._clock_ping_countdown = self._clock_sync_cycles
        try:
            ping = wire.encode_clock_ping(time.monotonic_ns(), self.epoch)
            with self._ctrl_send_lock:
                su.send_frame(self._ctrl_sock, su.TAG_CLOCK_PING, ping)
            self._last_send = time.monotonic()
        except (ConnectionError, OSError):
            pass  # a dead hub surfaces through the recv loop

    def _worker_cycle(self, msgs: List[Request]) -> bool:
        if self._fenced is not None:
            # The coordinator told us we have no seat in the re-formed
            # gang (TAG_FENCE): deliver the typed error and stop before
            # another frame of ours can touch the new incarnation.
            stale, current = self._fenced
            exc = FencedError("control", stale, current)
            self._abort(str(exc), exc=exc)
            return False
        if self._tracer is not None:
            self._maybe_clock_ping()
        requests, hit_events = self._classify(msgs)
        want_shutdown = self._shutdown_requested.is_set()
        send_failed = False
        # Sub-coordinator: everything the children uplinked since the
        # last cycle folds into one TAG_TREE_UP alongside our own frame.
        tree_entries: List[tuple] = []
        if self._tree_child_socks:
            with self._tree_up_lock:
                tree_entries = self._tree_up_buf
                self._tree_up_buf = []
        if requests or hit_events or want_shutdown:
            payload = wire.encode_request_list(requests,
                                               shutdown=want_shutdown,
                                               cache_hits=hit_events,
                                               epoch=self.epoch)
            if self._tree_child_socks:
                tree_entries.append(
                    (self.rank, su.TAG_REQUEST_LIST, payload))
            else:
                try:
                    _fi.fire("ctrl.worker.send", str(self.rank))
                    if self._tree_parent is not None \
                            and not self._tree_orphaned \
                            and self._tree_parent_sock is not None:
                        # Uplink via our host's sub-coordinator; keep the
                        # payload so a re-parent can replay the frames a
                        # dying parent may never have forwarded.
                        with self._ctrl_send_lock:
                            su.send_frame(self._tree_parent_sock,
                                          su.TAG_REQUEST_LIST, payload)
                        self._tree_unacked.append(payload)
                        del self._tree_unacked[:-8]
                    else:
                        with self._ctrl_send_lock:
                            su.send_frame(self._ctrl_sock,
                                          su.TAG_REQUEST_LIST, payload)
                    self._last_send = time.monotonic()
                except (ConnectionError, OSError):
                    if self._tree_parent is not None \
                            and not self._tree_orphaned:
                        # Dead sub-coordinator, not a dead hub: adopt
                        # ourselves back to the star (which replays the
                        # unacked frames, this one included).
                        self._tree_unacked.append(payload)
                        del self._tree_unacked[:-8]
                        self._reparent_to_root()
                    else:
                        # The coordinator may have closed right after
                        # broadcasting a shutdown ResponseList; the
                        # receiver thread may already hold it — drain
                        # before concluding the peer was genuinely lost.
                        send_failed = True
        elif self.heartbeat_timeout > 0 and \
                time.monotonic() - self._last_send >= self.heartbeat_interval:
            # Idle past the heartbeat cadence: prove liveness.  A lost
            # coordinator surfaces through the recv loop, not here.
            if self._tree_child_socks:
                tree_entries.append((self.rank, su.TAG_HEARTBEAT, b""))
            else:
                hb_sock = self._ctrl_sock
                if self._tree_parent is not None \
                        and not self._tree_orphaned \
                        and self._tree_parent_sock is not None:
                    hb_sock = self._tree_parent_sock
                try:
                    with self._ctrl_send_lock:
                        su.send_frame(hb_sock, su.TAG_HEARTBEAT, b"")
                except (ConnectionError, OSError):
                    if hb_sock is self._tree_parent_sock:
                        self._reparent_to_root()
            self._last_send = time.monotonic()
        if tree_entries:
            up = wire.encode_tree_up(tree_entries, epoch=self.epoch)
            try:
                _fi.fire("ctrl.subcoord.send", str(self.rank))
                with self._ctrl_send_lock:
                    su.send_frame(self._ctrl_sock, su.TAG_TREE_UP, up)
                self._last_send = time.monotonic()
            except (ConnectionError, OSError):
                send_failed = True
        with self._response_lock:
            inbox = self._response_inbox
            self._response_inbox = []
        for payload in inbox:
            responses, shutdown, hit_positions, resend, params, epoch = \
                wire.decode_response_list(payload)
            if epoch != self.epoch:
                # Stale incarnation (a coordinator we were re-formed away
                # from, or one we have not re-formed to yet): executing
                # its responses would desync this gang.  Drop the frame.
                self.log.warning(
                    "dropping response frame from epoch %d (ours: %d)",
                    epoch, self.epoch)
                continue
            if params is not None:
                # Apply BEFORE executing this frame's hits: the fusion
                # threshold shapes the fused launches, which must be
                # identical on every rank.
                self._apply_params(params)
            self._process_resends(resend)
            self._execute_cached_hits(hit_positions)
            for resp in responses:
                self._perform_operation(resp)
            if shutdown:
                self._shutdown_flag.set()
                return False
        if send_failed or self._ctrl_conn_lost:
            # A send failure or a recv-thread EOF both mean the hub is
            # unreachable — but a shutdown ResponseList may have landed
            # in the inbox between the drain above and now.  Drain once
            # more so clean teardown never masquerades as a dead hub.
            with self._response_lock:
                late = self._response_inbox
                self._response_inbox = []
            for payload in late:
                decoded = wire.decode_response_list(payload)
                if decoded[1] and decoded[5] == self.epoch:  # shutdown
                    self._shutdown_flag.set()
                    return False
            self._abort("lost connection to coordinator")
            return False
        return True

    def _apply_params(self, params) -> None:
        # 5-tuple frames come from older coordinators (and the native
        # engine) that predate the ring-segment knob; keep the local
        # setting in that case.
        fusion, cycle_s, cache_on, hier_ar, hier_ag = params[:5]
        self.fusion_threshold = fusion
        self.cycle_time = cycle_s
        self._cache_classify_enabled = cache_on
        self.hierarchical_allreduce = hier_ar
        self.hierarchical_allgather = hier_ag
        if len(params) > 5:
            self.ring_segment_bytes = params[5]

    def hierarchical_topology_ok(self) -> bool:
        """True when the two-level data plane can run: a real local/cross
        split and the launcher's homogeneous block rank layout."""
        from horovod_tpu.runner.discovery import block_topology_ok

        return block_topology_ok(self.rank, self.size, self.local_rank,
                                 self.local_size, self.cross_rank,
                                 self.cross_size)

    # -- coordinator ----------------------------------------------------

    def _coordinator_cycle(self, msgs: List[Request]) -> bool:
        ready: List[str] = []
        shutdown = self._shutdown_requested.is_set()
        # names this cycle asks specific ranks to resend in full
        resend_by_rank: Dict[int, List[str]] = {}

        def _absorb(req: Request) -> None:
            nonlocal ready, shutdown
            if req.request_type == RequestType.JOIN:
                self._joined_ranks.add(req.request_rank)
                self._last_joined_rank = req.request_rank
                # Tensors waiting only on joined ranks become ready
                # (global-set entries only; join never applies to
                # process-set traffic).
                for nm, lst in list(self._msg_table.entries.items()):
                    if lst[0].process_set_id == 0 and \
                            len(lst) == self.size - len(self._joined_ranks):
                        if nm not in ready:
                            ready.append(nm)
                return
            if self.timeline.enabled:
                # Start on the FIRST request for this key — a process
                # set may not contain rank 0, and an End without a
                # Start corrupts the trace.
                key = _MessageTable.key_of(req)
                if key not in self._msg_table.entries:
                    self.timeline.negotiate_start(
                        req.tensor_name, _OP_NAMES[req.request_type])
                self.timeline.negotiate_rank_ready(
                    req.tensor_name, req.request_rank)
            if self._straggler is not None:
                self._straggler.note_ready(
                    _MessageTable.key_of(req), req.request_rank)
            if self._msg_table.increment(req, len(self._joined_ranks)):
                ready.append(_MessageTable.key_of(req))

        def _absorb_hit(name: str, pos: int, rank: int) -> None:
            # A hit event stands for the full Request; rebuild it from
            # our own cache (coherent with the sender's) and let it ride
            # the ordinary message table.  If our entry was evicted in
            # flight, ask the sender to resend the full request.
            if self._cache.name_at(pos) != name:
                resend_by_rank.setdefault(rank, []).append(name)
                return
            req = self._cache.synthesize_request(pos, rank)
            self._hit_ranks.setdefault(name, set()).add(rank)
            _absorb(req)

        requests, own_hits = self._classify(msgs)
        for req in requests:
            _absorb(req)
        for name, pos in own_hits:
            _absorb_hit(name, pos, 0)
        with self._ctrl_lock:
            inbox = self._ctrl_inbox
            self._ctrl_inbox = []
        for peer, payload in inbox:
            reqs, peer_shutdown, peer_hits, peer_epoch = \
                wire.decode_request_list(payload)
            if peer_epoch != self.epoch:
                # A zombie from a previous incarnation (evicted but not
                # dead, now reconnected through a stale socket): absorbing
                # its requests would hang or corrupt this gang's
                # negotiation — reject the frame before it touches the
                # message table, and tell the sender WHY with a typed
                # TAG_FENCE so it raises FencedError and exits instead
                # of retrying forever against a gang it has no seat in.
                self.log.warning(
                    "rejecting request frame from rank %d at epoch %d "
                    "(ours: %d)", peer, peer_epoch, self.epoch)
                _tmx.inc_counter("hvd_fenced_writes_total")
                blackbox_mod.note("epoch.fence", time.monotonic_ns(),
                                  rank=peer, stale_epoch=peer_epoch,
                                  epoch=self.epoch)
                fsock = self._ctrl_socks.get(peer)
                if fsock is not None:
                    try:
                        with self._ctrl_send_lock:
                            su.send_frame(
                                fsock, su.TAG_FENCE,
                                wire.encode_fence(peer_epoch, self.epoch))
                    except (ConnectionError, OSError):
                        pass
                continue
            shutdown = shutdown or peer_shutdown
            for req in reqs:
                _absorb(req)
            for name, pos in peer_hits:
                _absorb_hit(name, pos, peer)

        # Hang detection: a worker's hop deadline fired while we are
        # demonstrably healthy (running cycles) — rule on the abort now
        # rather than waiting to block in the collective ourselves.
        if self.collective_timeout > 0:
            self._drain_abort_reports()

        # Liveness: evict ranks silent past the heartbeat timeout (or
        # whose ctrl connection dropped), reusing the Join readiness
        # machinery so survivors complete in-flight negotiation.
        dead = self._check_dead_ranks()
        if dead and not shutdown:
            self._evict_ranks(dead, ready)

        responses: List[Response] = []
        hit_positions: List[int] = []
        for key in ready:
            t_first = self._msg_table.first_seen.get(key) \
                if self._metrics_on else None
            reqs = self._msg_table.pop(key)
            name = reqs[0].tensor_name  # key may be set-scoped
            if self.timeline.enabled:
                self.timeline.negotiate_end(name)
            if t_first is not None:
                _tmx.observe("hvd_negotiation_seconds",
                             time.monotonic() - t_first)
            if self._straggler is not None:
                lagger = self._straggler.note_complete(key)
                if lagger is not None:
                    self._emit_straggler(name, *lagger)
            # Hits are global-set-only, where key == name; popping by key
            # keeps a set-scoped completion from stealing a same-named
            # global tensor's hit record.
            hit_ranks = self._hit_ranks.pop(key, set())
            contributors = {r.request_rank for r in reqs}
            ent_pos = -1
            # An eviction cycle must ship full responses: workers apply
            # cached hits BEFORE the response stream, which would run a
            # collective over the old group before seeing the EVICT.
            if not dead and hit_ranks >= contributors:
                # Every contributor hit → all requests were synthesized
                # from the same cache entry → the negotiated response IS
                # the cached one; broadcast just the position.
                ent_pos = self._cache.position_of(name)
            if ent_pos >= 0:
                hit_positions.append(ent_pos)
            else:
                responses.append(self._construct_response(name, reqs))

        if dead and not shutdown:
            # First in the stream: every rank applies the eviction before
            # executing any collective made ready by it.
            responses.insert(0, Response(
                response_type=ResponseType.EVICT,
                tensor_sizes=sorted(dead)))

        if len(self._joined_ranks) == self.size:
            responses.append(Response(
                response_type=ResponseType.JOIN,
                tensor_sizes=[self._last_joined_rank]))
            # Evicted ranks never un-join: re-seed so post-join traffic
            # keeps counting them out of readiness.
            self._joined_ranks = set(self._evicted_ranks)

        if not self.stall_check_disable:
            shutdown = self._check_stalls() or shutdown

        tuned = self._pending_params
        if responses or hit_positions or resend_by_rank or shutdown \
                or tuned is not None:
            fused = self._fuse_responses(responses)
            if self._metrics_on:
                for resp in fused:
                    if resp.tensor_names and resp.tensor_type is not None:
                        _tmx.observe(
                            "hvd_fused_bytes",
                            sum(resp.tensor_sizes)
                            * resp.tensor_type.itemsize)
                        _tmx.observe("hvd_fused_tensors",
                                     len(resp.tensor_names))
            params = None
            if tuned is not None:
                params = (tuned.fusion_threshold, tuned.cycle_time_s,
                          tuned.cache_enabled,
                          tuned.hierarchical_allreduce,
                          tuned.hierarchical_allgather,
                          getattr(tuned, "ring_segment_bytes",
                                  self.ring_segment_bytes))
                self._pending_params = None
            shared = None
            for r, s in self._ctrl_socks.items():
                resend = resend_by_rank.get(r, [])
                if resend:
                    payload = wire.encode_response_list(
                        fused, shutdown=shutdown,
                        hit_positions=hit_positions, resend_names=resend,
                        params=params, epoch=self.epoch)
                else:
                    if shared is None:
                        shared = wire.encode_response_list(
                            fused, shutdown=shutdown,
                            hit_positions=hit_positions, params=params,
                            epoch=self.epoch)
                    payload = shared
                try:
                    _fi.fire("ctrl.coord.send", str(r))
                    with self._ctrl_send_lock:
                        su.send_frame(s, su.TAG_RESPONSE_LIST, payload)
                except (ConnectionError, OSError):
                    pass
            if params is not None:
                # Same ordering contract as the workers: apply before
                # fusing/executing this frame's cached hits.
                self._apply_params(params)
            self._execute_cached_hits(hit_positions)
            for resp in fused:
                self._perform_operation(resp)
            if self._pm is not None and not self._pm.done:
                nbytes = sum(
                    sum(r.tensor_sizes) * r.tensor_type.itemsize
                    for r in fused
                    if r.response_type == ResponseType.ALLREDUCE)
                nbytes += sum(
                    c.tensor_sizes[0] * c.tensor_type.itemsize
                    for c in map(self._cache.get_by_position, hit_positions)
                    if c is not None)
                new = self._pm.record_bytes(nbytes)
                if new is not None:
                    self._pending_params = new
            if shutdown:
                self._shutdown_flag.set()
                return False
        return True

    def _check_dead_ranks(self) -> List[int]:
        """Ranks whose ctrl connection dropped or that have been silent
        past the heartbeat timeout.  Empty unless liveness is enabled
        (HVD_HEARTBEAT_TIMEOUT > 0)."""
        if self.heartbeat_timeout <= 0:
            return []
        now = time.monotonic()
        dead = []
        for r, t in self._last_seen.items():
            if r in self._evicted_ranks:
                continue
            if r in self._conn_lost or now - t > self.heartbeat_timeout:
                dead.append(r)
        # Orphan grace: a dying sub-coordinator takes its children's
        # uplink with it, so their silence is HIS fault, not theirs.
        # Give every rank still routed through a freshly-dead parent a
        # full timeout window to re-parent and heartbeat directly — only
        # the dead parent is evicted this round.
        if dead and self._rank_route:
            dead_set = set(dead)
            for child, parent in list(self._rank_route.items()):
                if parent in dead_set and child in dead_set:
                    dead.remove(child)
                    self._last_seen[child] = now
                    self._conn_lost.discard(child)
        return dead

    def _evict_ranks(self, dead: List[int], ready: List[str]) -> None:
        """Treat ``dead`` as permanently joined: drop their pending
        requests and rescan readiness so survivors complete the in-flight
        negotiation with zero stand-ins (the Join contract)."""
        for r in dead:
            self.log.error(
                "rank %d unresponsive (%s); evicting from the job", r,
                "connection lost" if r in self._conn_lost
                else f"no heartbeat for {self.heartbeat_timeout:.1f}s")
            if r not in self._conn_lost:
                _tmx.inc_counter("hvd_heartbeat_misses_total")
            _tmx.inc_counter("hvd_evictions_total")
            blackbox_mod.note("heartbeat.miss", time.monotonic_ns(),
                              rank=r,
                              conn_lost=bool(r in self._conn_lost))
            self._evicted_ranks.add(r)
            self._joined_ranks.add(r)
        for nm, lst in list(self._msg_table.entries.items()):
            lst[:] = [q for q in lst
                      if q.request_rank not in self._evicted_ranks]
            if not lst:
                # Only dead ranks had announced it; no survivor holds an
                # entry, so nothing to complete.
                self._msg_table.pop(nm)
                self._hit_ranks.pop(nm, None)
                if self._straggler is not None:
                    self._straggler.forget(nm)
                if nm in ready:
                    ready.remove(nm)
            elif lst[0].process_set_id == 0 and \
                    len(lst) == self.size - len(self._joined_ranks) and \
                    nm not in ready:
                ready.append(nm)

    def _emit_straggler(self, name: str, lag_rank: int,
                        skew_s: float) -> None:
        """The straggler detector tripped: one rank has been last to
        negotiate for several consecutive tensors by more than
        HVD_STRAGGLER_WARN_MS.  Record it on the timeline and warn; the
        detector re-arms, so records are naturally throttled."""
        self.log.warning(
            "straggler: rank %d consistently last to negotiate "
            "(skew %.1f ms on %s)", lag_rank, skew_s * 1e3, name)
        if self.timeline.enabled:
            self.timeline.instant(
                timeline_mod.STRAGGLER, rank=lag_rank,
                skew_ms=round(skew_s * 1e3, 3), tensor=name)
        blackbox_mod.note("straggler", 0, rank=lag_rank,
                          skew_ms=round(skew_s * 1e3, 3), name=name)

    # -- collective-abort agreement (docs/fault_tolerance.md) ------------
    #
    # Heartbeats catch DEAD ranks; these four frames catch HUNG ones.
    # A rank whose ring hop blows HVD_COLLECTIVE_TIMEOUT reports the
    # suspect peer to the coordinator over the still-live control
    # channel (TAG_ABORT_REPORT).  The coordinator probes the gang
    # (TAG_PROBE / TAG_PROBE_ACK — answered from the recv thread, which
    # stays responsive even while the background thread is wedged in
    # the data plane), rules on who is actually stuck, and broadcasts
    # TAG_ABORT_VERDICT so every survivor raises the SAME
    # CollectiveTimeoutError for the SAME step.

    def _drain_abort_reports(self) -> None:
        """Coordinator, between cycles (i.e. not itself blocked in a
        collective): act on hop-timeout reports that arrived while we
        were healthy."""
        with self._abort_lock:
            if not self._abort_inbox:
                return
            inbox, self._abort_inbox = self._abort_inbox, []
        reports: Dict[int, int] = {}
        name = ""
        for peer, tag, payload in inbox:
            if tag != su.TAG_ABORT_REPORT:
                continue  # stray ack from an already-finished probe round
            nm, suspect, epoch = wire.decode_abort_report(payload)
            if epoch != self.epoch:
                continue
            if self._last_verdict is not None and \
                    self._last_verdict[0] == nm:
                # Already ruled: this straggler's own hop deadline fired
                # after the broadcast — re-send the verdict.
                self._send_verdict_to(peer)
                continue
            reports[peer] = suspect
            name = nm
        if reports:
            self._coordinate_abort(name, reports)

    def _send_verdict_to(self, rank: int) -> None:
        vname, vranks = self._last_verdict
        sock = self._ctrl_socks.get(rank)
        if sock is None:
            return
        try:
            with self._ctrl_send_lock:
                su.send_frame(
                    sock, su.TAG_ABORT_VERDICT,
                    wire.encode_abort_verdict(vname, vranks, self.epoch))
        except (ConnectionError, OSError):
            pass

    def _coordinate_abort(self, name: str,
                          reports: Dict[int, int]) -> List[int]:
        """Probe the gang, rule on which rank(s) are wedged, broadcast
        and apply the verdict.  Runs on the coordinator's background
        thread — from _drain_abort_reports (coordinator healthy) or
        from its own HopTimeout (coordinator was blocked in the stalled
        collective too).  ``reports`` maps reporter rank -> the peer it
        blamed.  Returns the agreed wedged ranks."""
        t0 = time.monotonic()
        self.log.error(
            "collective %r blew its %gs deadline (reported by rank(s) "
            "%s); probing the gang", name, self.collective_timeout,
            sorted(reports))
        live = [r for r in self._ctrl_socks
                if r not in self._evicted_ranks]
        acks: Dict[int, tuple] = {}

        def _probe() -> None:
            for r in live:
                # Ranks folded under a live sub-coordinator get their
                # probe routed down the tree (one hop, same host); the
                # ack always returns on the rank's DIRECT socket.  A
                # dead or evicted parent falls back to the direct link.
                parent = self._rank_route.get(r)
                if parent is not None and parent in self._ctrl_socks \
                        and parent not in self._evicted_ranks \
                        and parent not in self._conn_lost:
                    down = wire.encode_tree_down(r, su.TAG_PROBE, b"")
                    try:
                        with self._ctrl_send_lock:
                            su.send_frame(self._ctrl_socks[parent],
                                          su.TAG_TREE_DOWN, down)
                        continue
                    except (ConnectionError, OSError):
                        pass
                try:
                    with self._ctrl_send_lock:
                        su.send_frame(self._ctrl_socks[r],
                                      su.TAG_PROBE, b"")
                except (ConnectionError, OSError):
                    pass

        _probe()
        deadline = t0 + max(0.1, self.collective_probe_timeout)
        last_probe = t0
        while time.monotonic() < deadline:
            with self._abort_lock:
                inbox, self._abort_inbox = self._abort_inbox, []
            for peer, tag, payload in inbox:
                if tag == su.TAG_PROBE_ACK:
                    busy, busy_s, ep = wire.decode_probe_ack(payload)
                    if ep == self.epoch:
                        acks[peer] = (busy, busy_s)
                elif tag == su.TAG_ABORT_REPORT:
                    nm, suspect, ep = wire.decode_abort_report(payload)
                    if ep == self.epoch:
                        reports[peer] = suspect
            # Converged: every live worker has either reported a timeout
            # of its own (a victim of the hang, not its cause) or acked
            # idle — nothing left to learn from the rest of the window.
            if all(r in reports or (r in acks and not acks[r][0])
                   for r in live):
                break
            now = time.monotonic()
            if now - last_probe >= 0.25:
                _probe()  # refresh busy durations
                last_probe = now
            time.sleep(0.02)

        # Verdict: a live rank is wedged when it never reported a hop
        # timeout of its own AND its last word was "busy" (or silence).
        # Every healthy participant's own deadline fires within ~one
        # collective timeout of the first, so by the window's end the
        # busy-and-silent ranks are the truly stuck ones.
        wedged = sorted(
            r for r in live
            if r not in reports and (r not in acks or acks[r][0]))
        if not wedged:
            # Nobody provably stuck (hang healed mid-probe, or the
            # victim died and took its socket along): fall back on the
            # most-blamed suspect, preferring non-reporters; ties go to
            # the lowest rank so every coordinator incarnation would
            # rule identically.
            blame: Dict[int, int] = {}
            for suspect in reports.values():
                if suspect >= 0 and suspect not in reports:
                    blame[suspect] = blame.get(suspect, 0) + 1
            if not blame:
                for suspect in reports.values():
                    if suspect >= 0:
                        blame[suspect] = blame.get(suspect, 0) + 1
            if blame:
                top = max(blame.values())
                wedged = [min(r for r, n in blame.items() if n == top)]

        payload = wire.encode_abort_verdict(name, wedged, self.epoch)
        self._last_verdict = (name, wedged)
        for r in live:
            try:
                with self._ctrl_send_lock:
                    su.send_frame(self._ctrl_socks[r],
                                  su.TAG_ABORT_VERDICT, payload)
            except (ConnectionError, OSError):
                pass
        self._apply_abort_verdict(name, wedged, t0)
        # Archive the evidence: pull every live rank's flight-recorder
        # ring (INCLUDING the wedged ones — their ctrl recv thread stays
        # responsive while the background thread hangs in the data
        # plane) so one dump directory survives even when a rank's own
        # disk write never lands.
        self._pull_blackbox_dumps(live)
        return wedged

    def _pull_blackbox_dumps(self, ranks: List[int],
                             wait_s: float = 1.0) -> None:
        """Coordinator: request TAG_BLACKBOX dumps from ``ranks`` and
        write whatever arrives within ``wait_s`` as
        ``blackbox_rank<r>.pulled.json`` in our own HVD_BLACKBOX_DIR.
        Best-effort evidence collection — never raises."""
        bb = blackbox_mod.get()
        if bb is None or not ranks:
            return
        req = wire.encode_blackbox_request(self.epoch)
        asked = []
        for r in ranks:
            sock = self._ctrl_socks.get(r)
            if sock is None:
                continue
            try:
                with self._ctrl_send_lock:
                    su.send_frame(sock, su.TAG_BLACKBOX, req)
                asked.append(r)
            except (ConnectionError, OSError):
                pass
        got: set = set()
        deadline = time.monotonic() + wait_s
        while len(got) < len(asked) and time.monotonic() < deadline:
            with self._blackbox_lock:
                inbox, self._blackbox_inbox = self._blackbox_inbox, []
            for peer, payload in inbox:
                try:
                    drank, depoch, blob = wire.decode_blackbox_dump(
                        payload)
                    os.makedirs(bb.dir, exist_ok=True)
                    path = os.path.join(
                        bb.dir, f"blackbox_rank{drank}.pulled.json")
                    tmp = f"{path}.tmp.{os.getpid()}"
                    with open(tmp, "wb") as fh:
                        fh.write(blob)
                    os.replace(tmp, path)
                    got.add(peer)
                except Exception:
                    got.add(peer)
            if len(got) < len(asked):
                time.sleep(0.02)
        if asked:
            self.log.info(
                "flight-recorder archive: pulled %d/%d worker dumps "
                "into %s", len(got), len(asked), bb.dir)

    def _report_and_await_verdict(self, name: str,
                                  suspect: int) -> Optional[List[int]]:
        """Worker half of the agreement: report the local hop timeout,
        then block (on the background thread — the collective is dead
        anyway) until the verdict lands.  None = no verdict in time,
        i.e. the coordinator itself is wedged or lost."""
        with self._abort_cv:
            if self._abort_verdict is not None:
                # Broadcast already arrived while this rank was still
                # blocked in the data plane.
                ranks = self._abort_verdict[1]
                self._abort_verdict = None
                return ranks
        try:
            with self._ctrl_send_lock:
                su.send_frame(
                    self._ctrl_sock, su.TAG_ABORT_REPORT,
                    wire.encode_abort_report(name, suspect, self.epoch))
        except (ConnectionError, OSError):
            return None
        # Budget: worst case the coordinator only starts probing after
        # its OWN hop deadline (one collective timeout), then runs a
        # full probe window.
        deadline = time.monotonic() + max(
            2.0 * self.collective_timeout,
            self.collective_timeout + 2.0 * self.collective_probe_timeout)
        with self._abort_cv:
            while self._abort_verdict is None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._abort_cv.wait(remaining)
            ranks = self._abort_verdict[1]
            self._abort_verdict = None
        return ranks

    def _apply_abort_verdict(self, name: str, ranks: List[int],
                             t0: float) -> None:
        """Record + apply an agreed abort: timeline record, metrics,
        eviction state (so the next enqueue raises on every survivor
        and the elastic wrapper re-forms without the wedged ranks)."""
        elapsed = time.monotonic() - t0
        _tmx.inc_counter("hvd_collective_timeouts_total")
        _tmx.observe("hvd_collective_abort_seconds", elapsed)
        if self.timeline.enabled:
            self.timeline.instant(
                timeline_mod.COLLECTIVE_ABORT, ranks=list(ranks),
                tensor=name, abort_ms=round(elapsed * 1e3, 3))
        self.log.error(
            "gang verdict: rank(s) %s wedged during %r; aborting the "
            "collective (%.0f ms after the local timeout)", ranks, name,
            elapsed * 1e3)
        # Terminal event: record the verdict and dump the flight
        # recorder (failure path — the clock read here is free).
        blackbox_mod.note("abort.verdict", time.monotonic_ns(),
                          ranks=list(ranks), name=name,
                          abort_ms=round(elapsed * 1e3, 3))
        blackbox_mod.dump("collective_timeout",
                          f"wedged={list(ranks)} name={name}")
        self._evicted_ranks.update(ranks)
        self._ranks_failed = sorted(set(self._ranks_failed) | set(ranks))
        if self.rank == 0 and self._msg_table is not None:
            # Same pruning as a heartbeat eviction, minus the liveness
            # bookkeeping: drop the wedged ranks' pending requests so
            # the post-abort cycles cannot hang on them.
            self._joined_ranks.update(ranks)
            for nm, lst in list(self._msg_table.entries.items()):
                lst[:] = [q for q in lst
                          if q.request_rank not in self._evicted_ranks]
                if not lst:
                    self._msg_table.pop(nm)
                    self._hit_ranks.pop(nm, None)

    def _retain_for_replay(self, resp: Response,
                           entries: List[TensorTableEntry]) -> None:
        """Keep copies of the aborted fused reduction's ORIGINAL inputs
        (pack() copies; the ring never mutates entry.array) so the
        re-formed gang can replay the batch."""
        if resp.response_type != ResponseType.ALLREDUCE:
            return
        batch = [
            {"name": e.name, "array": np.array(e.array, copy=True),
             "op": resp.reduce_op, "prescale": resp.prescale_factor,
             "postscale": resp.postscale_factor}
            for e in entries if e.handle >= 0]
        if batch:
            retain_aborted_batch(batch)

    def _collective_abort(self, resp: Response,
                          entries: List[TensorTableEntry],
                          hop: Exception) -> Status:
        """A local hop deadline fired: run the gang-wide agreement and
        build the typed failure status every survivor shares."""
        name = resp.tensor_names[0]
        suspect = int(getattr(hop, "peer", -1))
        # Blame record: who THIS rank was blocked on when its deadline
        # fired — the postmortem triangulates the first cause from the
        # gang's blame edges (failure path; clock read is free).
        blackbox_mod.note("collective.timeout", time.monotonic_ns(),
                          name=name, peer=suspect,
                          phase=str(getattr(hop, "phase", "recv")))
        if self.rank == 0:
            wedged = self._coordinate_abort(name, {0: suspect})
        else:
            t0 = time.monotonic()
            wedged = self._report_and_await_verdict(name, suspect)
            if wedged is None:
                # The one rank that could rule never did: treat it like
                # a lost coordinator so the elastic wrapper re-forms
                # around rank 0.
                reason = ("collective timed out and no abort verdict "
                          "arrived: coordinator wedged or lost")
                self._abort(reason)
                return Status.aborted(reason)
            if self.rank in wedged:
                # The gang ruled *us* wedged (e.g. our probe acks never
                # made it out): the group has moved on without this
                # rank — stop before desyncing it.
                raise RuntimeError(
                    "evicted by the coordinator (collective timeout)")
            self._apply_abort_verdict(name, wedged, t0)
        self._retain_for_replay(resp, entries)
        err = CollectiveTimeoutError(wedged, name,
                                     self.collective_timeout)
        status = Status.aborted(str(err))
        status.exc = err
        return status

    def _check_stalls(self) -> bool:
        now = time.monotonic()
        if now - self._last_stall_check < self.stall_warn_s / 4:
            return False
        self._last_stall_check = now
        shutdown = False
        for name, t0 in self._msg_table.first_seen.items():
            waited = now - t0
            if waited > self.stall_warn_s:
                have = sorted(r.request_rank
                              for r in self._msg_table.entries[name])
                missing = [r for r in range(self.size)
                           if r not in have and
                           r not in self._joined_ranks]
                self.log.warning(
                    "Stalled tensor %s: ready on ranks %s, waiting on %s "
                    "for %.0fs", name, have, missing, waited)
                _tmx.inc_counter("hvd_stall_warnings_total")
                if self.stall_shutdown_s > 0 and \
                        waited > self.stall_shutdown_s:
                    self.log.error(
                        "Stalled tensor %s exceeded shutdown threshold; "
                        "shutting down", name)
                    shutdown = True
        return shutdown

    # -- response construction (parity: ConstructResponse) --------------

    def _construct_response(self, name: str, reqs: List[Request]) -> Response:
        first = reqs[0]
        err = None
        if any(r.request_type != first.request_type for r in reqs):
            err = (f"Mismatched collective operations for tensor {name}: "
                   + ", ".join(sorted({_OP_NAMES[r.request_type]
                                       for r in reqs})))
        elif any(r.process_set_id != first.process_set_id or
                 r.process_set_size != first.process_set_size
                 for r in reqs):
            err = f"Mismatched process sets for tensor {name}"
        elif first.process_set_id and \
                first.request_type == RequestType.JOIN:
            err = (f"{_OP_NAMES[first.request_type]} does not support "
                   f"process sets (tensor {name})")
        elif any(r.tensor_type != first.tensor_type for r in reqs):
            err = (f"Mismatched data types for tensor {name}: "
                   + ", ".join(sorted({r.tensor_type.name for r in reqs})))
        elif first.request_type == RequestType.ALLREDUCE:
            if any(r.tensor_shape != first.tensor_shape for r in reqs):
                err = (f"Mismatched allreduce tensor shapes for {name}: "
                       + ", ".join(sorted({str(r.tensor_shape)
                                           for r in reqs})))
            elif any(r.reduce_op != first.reduce_op for r in reqs):
                err = f"Mismatched reduce ops for tensor {name}"
            elif first.process_set_id and \
                    first.reduce_op == ReduceOp.ADASUM:
                err = (f"Adasum is not supported with process sets "
                       f"(tensor {name})")
        elif first.request_type == RequestType.BROADCAST:
            if any(r.root_rank != first.root_rank for r in reqs):
                err = (f"Mismatched broadcast root ranks for {name}: "
                       + ", ".join(sorted({str(r.root_rank)
                                           for r in reqs})))
            elif any(r.tensor_shape != first.tensor_shape for r in reqs):
                err = f"Mismatched broadcast tensor shapes for {name}"
            elif first.process_set_id:
                from horovod_tpu import process_sets

                members = process_sets.ranks_of(first.process_set_id)
                if members is not None and \
                        first.root_rank not in members:
                    # Authoritative check (wrappers pre-check too): a
                    # non-member root would skip while members block.
                    err = (f"broadcast root rank {first.root_rank} is "
                           f"not a member of process set "
                           f"{first.process_set_id} (tensor {name})")
        elif first.request_type == RequestType.ALLGATHER:
            for r in reqs:
                if r.tensor_shape.rank != first.tensor_shape.rank or \
                        r.tensor_shape.dims[1:] != first.tensor_shape.dims[1:]:
                    err = (f"Mismatched allgather tensor shapes for {name}: "
                           f"all dimensions except the first must match")
                    break
        elif first.request_type == RequestType.REDUCESCATTER:
            if any(r.tensor_shape != first.tensor_shape for r in reqs):
                err = (f"Mismatched reducescatter tensor shapes for "
                       f"{name}: "
                       + ", ".join(sorted({str(r.tensor_shape)
                                           for r in reqs})))
            elif any(r.reduce_op != first.reduce_op for r in reqs):
                err = f"Mismatched reduce ops for tensor {name}"
            elif first.reduce_op == ReduceOp.ADASUM:
                err = (f"Adasum is not defined for reducescatter "
                       f"(tensor {name})")

        if err is not None:
            return Response(response_type=ResponseType.ERROR,
                            tensor_names=[name], error_message=err)

        resp = Response(
            response_type=ResponseType(int(first.request_type)),
            tensor_names=[name],
            tensor_type=first.tensor_type,
            devices=[first.device],
            process_set_id=first.process_set_id,
        )
        if first.request_type == RequestType.ALLREDUCE:
            resp.tensor_sizes = [first.tensor_shape.num_elements]
            resp.reduce_op = first.reduce_op
            resp.prescale_factor = first.prescale_factor
            resp.postscale_factor = first.postscale_factor
            # Negotiated dims ride the response so cache parameters stay
            # coherent on every rank (incl. joined ranks' stand-ins).
            resp.tensor_shapes = [first.tensor_shape]
        elif first.request_type == RequestType.ALLGATHER:
            # First-dim size per rank, in rank order (0 for joined
            # ranks); for a process set, per member in member order.
            by_rank = {r.request_rank: r for r in reqs}
            if first.process_set_id:
                from horovod_tpu import process_sets

                members = process_sets.ranks_of(first.process_set_id)
                if members is None:
                    return Response(
                        response_type=ResponseType.ERROR,
                        tensor_names=[name],
                        error_message=(
                            f"process set {first.process_set_id} is not "
                            "registered on the coordinator (construct "
                            "the ProcessSet on every rank)"))
                order = members
            else:
                order = range(self.size)
            resp.tensor_sizes = [
                by_rank[r].tensor_shape.dims[0] if r in by_rank else 0
                for r in order]
        elif first.request_type == RequestType.BROADCAST:
            resp.tensor_sizes = [first.root_rank]
        elif first.request_type == RequestType.REDUCESCATTER:
            resp.tensor_sizes = [first.tensor_shape.num_elements]
            resp.reduce_op = first.reduce_op
            resp.tensor_shapes = [first.tensor_shape]
        return resp

    # -- fusion (parity: FuseResponses, controller.cc:638-759) -----------

    def _fuse_responses(self, responses: List[Response]) -> List[Response]:
        out: List[Response] = []
        pending: Optional[Response] = None
        pending_bytes = 0
        for r in responses:
            fusable = (r.response_type == ResponseType.ALLREDUCE
                       and not r.error_message)
            if not fusable:
                if pending is not None:
                    out.append(pending)
                    pending = None
                out.append(r)
                continue
            nbytes = sum(r.tensor_sizes) * r.tensor_type.itemsize
            if pending is not None and \
                    pending.tensor_type == r.tensor_type and \
                    pending.devices == r.devices and \
                    pending.reduce_op == r.reduce_op and \
                    pending.prescale_factor == r.prescale_factor and \
                    pending.postscale_factor == r.postscale_factor and \
                    pending.process_set_id == r.process_set_id and \
                    pending_bytes + nbytes <= self.fusion_threshold:
                pending.tensor_names.extend(r.tensor_names)
                pending.tensor_sizes.extend(r.tensor_sizes)
                pending.tensor_shapes.extend(r.tensor_shapes)
                pending_bytes += nbytes
            else:
                if pending is not None:
                    out.append(pending)
                pending = r
                pending_bytes = nbytes
        if pending is not None:
            out.append(pending)
        return out

    # -- execution -------------------------------------------------------

    def _get_entries(self, resp: Response) -> List[TensorTableEntry]:
        """Fetch (or zero-allocate, when joined) the entries of a response.
        Parity: GetTensorEntriesFromResponse (tensor_queue.cc:72-117)."""
        entries = []
        with self._queue_lock:
            for i, nm in enumerate(resp.tensor_names):
                if nm in self._table:
                    entries.append(self._table.pop(nm))
                else:
                    # This rank joined: allocate a zero stand-in.
                    dt = _np_dtype(resp.tensor_type)
                    if resp.response_type == ResponseType.ALLREDUCE:
                        n = resp.tensor_sizes[i]
                        arr = np.zeros(n, dt)
                    elif resp.response_type == ResponseType.REDUCESCATTER:
                        # Needs the negotiated shape — the scatter splits
                        # over dim 0, so a flat stand-in would desync the
                        # ring chunk boundaries.
                        arr = np.zeros(
                            tuple(resp.tensor_shapes[i].dims), dt)
                    elif resp.response_type == ResponseType.ALLGATHER:
                        arr = np.zeros(0, dt)
                    else:
                        arr = np.zeros(0, dt)
                    req = Request(request_rank=self.rank,
                                  tensor_name=nm,
                                  tensor_type=resp.tensor_type,
                                  tensor_shape=TensorShape(arr.shape))
                    entries.append(
                        TensorTableEntry(nm, arr, -1, req))
        return entries

    def _perform_operation(self, resp: Response,
                           from_cache: bool = False) -> None:
        from horovod_tpu.ops import cpu_backend

        if resp.process_set_id and \
                resp.response_type != ResponseType.ERROR:
            # Process-set responses reach every rank in the response
            # stream; non-members simply skip (members always have the
            # entries — join is global-set-only, so no stand-ins here).
            from horovod_tpu import process_sets

            members = process_sets.ranks_of(resp.process_set_id)
            if members is None or self.rank not in members:
                return

        if resp.response_type == ResponseType.JOIN:
            self._last_joined_rank = int(resp.tensor_sizes[0]) \
                if resp.tensor_sizes else -1
            with self._queue_lock:
                jh, self._join_handle = self._join_handle, None
                self._joined = False
            if jh is not None:
                self.handles.mark_done(jh, Status.ok(), None)
            return

        if resp.response_type == ResponseType.EVICT:
            ranks = [int(x) for x in resp.tensor_sizes]
            blackbox_mod.note("evict", time.monotonic_ns(),
                              ranks=ranks)
            if self.rank in ranks:
                # The coordinator declared *us* dead (e.g. a long GC
                # pause): the group has moved on without this rank, so
                # rejoining is impossible — stop before desyncing it.
                blackbox_mod.dump("evicted",
                                  "declared dead by the coordinator")
                raise RuntimeError(
                    "evicted by the coordinator (missed heartbeats)")
            self._evicted_ranks.update(ranks)
            self._ranks_failed = sorted(
                set(self._ranks_failed) | set(ranks))
            self.log.error(
                "rank(s) %s evicted; completing in-flight collectives "
                "on the survivors", ranks)
            blackbox_mod.dump("ranks_failed", f"evicted={ranks}")
            return

        if resp.response_type == ResponseType.ERROR:
            for nm in resp.tensor_names:
                entries = self._get_entries(
                    Response(response_type=ResponseType.ERROR,
                             tensor_names=[nm]))
                for e in entries:
                    self._release_name(e.name)
                    if e.handle >= 0:
                        self.handles.mark_done(
                            e.handle,
                            Status.precondition_error(resp.error_message),
                            None)
            return

        if not from_cache:
            # Populate the response cache BEFORE execution and regardless
            # of local execution status: the put stores metadata only, and
            # doing it unconditionally in response-stream order is what
            # keeps every rank's cache (positions, LRU, evictions)
            # coherent even if one rank's data plane hiccups.
            self._cache.put(resp)

        entries = self._get_entries(resp)
        op_name = resp.response_type.name
        self.timeline.start(resp.tensor_names[0], op_name)
        tracer = self._tracer
        if tracer is not None:
            # One collective seq per executed response: responses run
            # serially in response-stream order, identically on every
            # rank, so the counter needs no wire traffic to agree.
            seq = tracer.begin_collective()
            t_exec0 = time.monotonic_ns()
            first_enq = min((e.enqueue_ns for e in entries
                             if e.handle >= 0), default=0)
            if first_enq:
                # Negotiation latency: first local enqueue -> execution.
                tracer.span("negotiate", first_enq, t_exec0, seq=seq,
                            name=resp.tensor_names[0], op=op_name,
                            tensors=len(entries))
        deadline_on = self.collective_timeout > 0
        if deadline_on:
            # Busy marker for probe acks: the recv thread reads it to
            # tell the coordinator we are inside a collective (and for
            # how long) even while this thread is blocked in the ring.
            self._in_collective_name = resp.tensor_names[0]
            self._in_collective_since = time.monotonic()
        bb = self._blackbox
        if bb is not None:
            # Flight-recorder begin record: O(1) append, reusing a
            # timestamp an enabled layer already took (tracer read or
            # deadline marker) — never a fresh clock read.
            self._blackbox_seq += 1
            bb_t0 = (t_exec0 if tracer is not None
                     else int(self._in_collective_since * 1e9)
                     if deadline_on else 0)
            peer = (self.rank - 1) % self.size if self.size > 1 else -1
            tp = getattr(self._transports.get(peer), "kind", "")
            bb.collective_begin(
                bb_t0, self._blackbox_seq, resp.tensor_names[0],
                op_name,
                sum(getattr(e.array, "nbytes", 0) or 0
                    for e in entries),
                peer, tp)
        try:
            if resp.response_type == ResponseType.ALLREDUCE:
                results = cpu_backend.allreduce(self, entries, resp)
            elif resp.response_type == ResponseType.ALLGATHER:
                results = cpu_backend.allgather(self, entries, resp)
            elif resp.response_type == ResponseType.BROADCAST:
                results = cpu_backend.broadcast(self, entries, resp)
            elif resp.response_type == ResponseType.ALLTOALL:
                results = cpu_backend.alltoall(self, entries, resp)
            elif resp.response_type == ResponseType.REDUCESCATTER:
                results = cpu_backend.reducescatter(self, entries, resp)
            elif resp.response_type == ResponseType.BARRIER:
                cpu_backend.barrier(self, resp)
                results = [None] * len(entries)
            else:
                raise RuntimeError(f"bad response type {resp.response_type}")
            status = Status.ok()
        except cpu_backend.HopTimeout as e:
            results = [None] * len(entries)
            if deadline_on:
                self._in_collective_since = 0.0
                status = self._collective_abort(resp, entries, e)
            else:
                # The always-on send-wait backstop tripped with the
                # deadline knob off: surface it like any other
                # data-plane failure (no abort agreement to run).
                self.log.error("collective %s failed: %r", op_name, e)
                status = Status.unknown_error(str(e))
        except wire.WireCorruptionError as e:
            # The recovery ladder exhausted every rung on a link
            # (retransmit budget, reconnect window, failover) — the
            # bottom rung is the exact PR-6 gang-wide abort/evict/replay
            # a hop deadline takes (docs/fault_tolerance.md).
            results = [None] * len(entries)
            blackbox_mod.note("wire.corruption", time.monotonic_ns(),
                              peer=int(getattr(e, "peer", -1)),
                              cause=str(getattr(e, "cause", "")))
            if deadline_on:
                self._in_collective_since = 0.0
                status = self._collective_abort(resp, entries, e)
            else:
                self.log.error("collective %s failed: %r", op_name, e)
                status = Status.unknown_error(str(e))
                blackbox_mod.dump("wire_corruption", str(e))
        except Exception as e:
            self.log.error("collective %s failed: %r", op_name, e)
            results = [None] * len(entries)
            status = Status.unknown_error(str(e))
        if deadline_on:
            self._in_collective_since = 0.0
        if bb is not None:
            # End record closes the in-flight marker.  Untimed on the
            # happy path (no extra clock read when nothing fails); a
            # failed collective may read the clock freely.
            bb.collective_end(
                0 if status.ok_() else time.monotonic_ns(),
                self._blackbox_seq, status.ok_())
        self.timeline.end(resp.tensor_names[0])
        if tracer is not None:
            t_cb0 = time.monotonic_ns()
        for e, res in zip(entries, results):
            self._release_name(e.name)
            if e.handle >= 0:
                self.handles.mark_done(e.handle, status, res)
        if tracer is not None:
            t_end = time.monotonic_ns()
            tracer.span("callback", t_cb0, t_end, seq=seq,
                        tensors=len(entries))
            # Envelope span: contains pack/hop/unpack/callback in the
            # merged view; "negotiate" precedes it on the same seq.
            tracer.span("collective", t_exec0, t_end, seq=seq,
                        name=resp.tensor_names[0], op=op_name,
                        ok=status.ok_())

    def cache_stats(self) -> Dict[str, int]:
        return self._cache.stats()

    def _abort(self, reason: str, exc: Optional[BaseException] = None
               ) -> None:
        self._aborted = True
        # Recorded for the elastic wrapper: a lost-coordinator abort on a
        # worker means rank 0 failed, which re-forms instead of exiting.
        self._abort_reason = reason
        # Typed aborts (FencedError, ...) keep their class all the way
        # to the training loop: pending handles and the next submission
        # re-raise THIS object instead of a bare RuntimeError.
        self._abort_exc = exc
        blackbox_mod.dump("engine_abort", reason)
        self._shutdown_flag.set()
