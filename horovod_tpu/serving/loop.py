"""The gang-wide serving loop: lockstep continuous-batching decode.

One :class:`ServingLoop` runs on every rank (``run()`` blocks for the
life of the deployment).  Rank 0 drives: it drains the scheduler's
admission queue into free slots at each token boundary, encodes the
batch delta as one ``TAG_SERVE`` frame (common/wire.py ServeDelta) and
pushes it to every rank over the control channel
(``runtime_py.serve_broadcast``) — including itself, so coordinator and
workers execute the identical ``_apply_frame`` path.  Every rank then
prefills the admitted prompts, steps the shared jit-ed decode function,
and retires finished slots.  Greedy decode is deterministic, so
retirements need no broadcast: every rank computes the same tokens and
drops the same slots.

A turn (``_turn``) takes one of two orders, and at most one step's token
vector is ever unread.  *Settling* a vector is reading it back,
confirming it with the gang and emitting it, in that order.

* No admissions in the frame: dispatch step k, THEN settle step k-1.  The
  readback, the confirm, the emit and the leader's next frame run while
  the chip works on a step that is already queued: the step's inputs
  (token, position, state) are device arrays, and the host reads tokens
  only to confirm them and hand them to clients.
* Admissions in the frame: settle step k-1 first, prefill on an empty
  chip (``serve.prefill`` times the prefill and nothing else), then
  dispatch step k and leave it unread for the next turn.  A request that
  arrives therefore waits for the step in flight AND the one queued
  behind it: up to one device step more until its first token than a
  loop that reads every step at once (docs/serving.md has the number).
* If no slot will outlive the unread vector (every remaining count is at
  its last token), settle and dispatch nothing: no step runs for an
  empty table, and nothing is unread when the loop sleeps or sends its
  stop frame.

Either way a frame's admissions enter ``_slots`` (the shadow a promoted
follower replays from) when the frame is applied, before the turn
settles or prefills: a leader that dies in that turn's confirm takes no
admitted request with it.

Which order a turn takes depends only on the frame and on ``_slots``,
which every rank holds alike: the gang stays in lock step with no new
field on the wire.  A slot that retires at the settle of k-1 has one
more row computed in step k.  Nobody reads it: the slot has left
``_slots``, rows are independent, the position clamp keeps the row in
bounds, and the next tenant's install overwrites the slot's token,
position, lane and recurrent state.  Retirement by count is known before
the dispatch; one by ``eos_id`` is not, and if it empties the table the
step that ran ahead is read and dropped.
``hvd_serve_steps_ahead_total`` counts the steps dispatched ahead.
Every dispatch has a ``serve.dispatch`` span and every read a
``serve.read`` span, each with the ordinal ``step`` of the engine step it
queues or reads: on a turn that ran ahead the read's is the dispatch's
less one.  ``hvd_serve_read_wait_seconds`` takes the reads' lengths:
over the turns' it is ``chip_wait_share`` on ``GET /stats``.

Robustness is composed from the existing machinery, not rebuilt:

* Each step's tokens pass a tiny token-agreement allreduce before they
  are emitted (``__serve.confirm``, MAX over the next-token vector; a
  rank wedged on the device never enters it).  That gives the
  PR-6 collective deadline a data-plane op to bound — a rank wedged in
  the ring trips the hop deadline, the gang-wide abort agreement names
  it, and the survivors raise :class:`CollectiveTimeoutError` out of
  this loop.  It also feeds the per-step straggler detector (a
  chaos-delayed rank is consistently last into the negotiation and gets
  a STRAGGLER timeline record), and doubles as a determinism check:
  if any rank's tokens differ from the gang max, greedy lockstep has
  diverged and the step fails loudly rather than serving garbage.
* The epoch body is wrapped in ``@hvd.elastic.run``: on an abort the
  gang re-forms in process, a fresh :class:`DecodeEngine` is built
  against the new world (the step in flight goes with the old one), and
  rank 0 requeues every in-flight request at the front of the queue
  (``Scheduler.requeue_inflight``) — requests are replayed from their
  prompts, at-least-once, to the bit-identical completion (greedy).  The
  HTTP front door and its parked handler threads belong to the process,
  so clients only observe added latency.

A rank that stalls *outside* the data plane (``serve.step`` chaos site,
kind=stall) is invisible to the collective deadline — it never submits,
so there is no hung collective to abort, only the coordinator's stalled-
tensor warnings (see docs/serving.md for why that distinction matters).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, Optional

import numpy as np

from horovod_tpu.common import fault_injection as _fi
from horovod_tpu.common import wire
from horovod_tpu.common.types import ReduceOp
from horovod_tpu.serving.decode import DecodeEngine
from horovod_tpu.serving.scheduler import Scheduler
from horovod_tpu.serving.server import FrontDoor
from horovod_tpu.telemetry import blackbox as _bb
from horovod_tpu.telemetry import registry as _tmx
from horovod_tpu.telemetry import trace as _trace
from horovod_tpu.utils import env as env_util
from horovod_tpu.utils.logging import get_logger


class ServingLoop:
    """Continuous-batching inference over the live gang.

    ``run()`` initializes (if needed), starts the rank-0 front door, and
    blocks serving until ``stop()`` — surviving rank failures via
    elastic re-forms along the way.  Knobs default from the
    ``HVD_SERVE_*`` environment (utils/env.py; set by ``hvdrun
    --serve-port/--serve-max-batch/--serve-max-queue``).
    """

    def __init__(self, params, cfg, *,
                 max_batch: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 port: Optional[int] = None,
                 host: str = "0.0.0.0",
                 cache_len: Optional[int] = None,
                 mesh=None,
                 eos_id: Optional[int] = None,
                 request_timeout_s: float = 120.0,
                 recv_timeout_s: float = 1.0,
                 idle_poll_s: float = 0.002,
                 on_ready: Optional[Callable[[int], None]] = None):
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch or env_util.serve_max_batch()
        self.max_queue = max_queue or env_util.serve_max_queue()
        self.port = env_util.serve_port() if port is None else port
        self.host = host
        self.cache_len = cache_len or cfg.max_seq_len
        self.mesh = mesh
        self.eos_id = eos_id
        self.request_timeout_s = request_timeout_s
        self.recv_timeout_s = recv_timeout_s
        self.idle_poll_s = idle_poll_s
        self.on_ready = on_ready
        self.scheduler: Optional[Scheduler] = None
        self._door: Optional[FrontDoor] = None
        self._stop = threading.Event()
        # slot -> {"id", "prompt", "max_new", "remaining"}.  Every rank
        # derives the same dict from the same frame stream — this IS the
        # follower's shadow of the leader's in-flight table: a rank
        # promoted to 0 by a re-form seeds its fresh scheduler from it
        # (prompt + max_new are all a replay needs; greedy decode
        # rebuilds the token tail bit-identically).
        self._slots: Dict[int, Dict] = {}
        # Leader front-door address ("host:port") as last seen in a
        # serve-delta frame; authoritative copy lives under the
        # elastic-scoped KV key serve/leader.
        self._known_leader: Optional[str] = None
        self._elastic_ctx = None
        self.log = get_logger(0)

    # -- lifecycle -------------------------------------------------------

    def stop(self) -> None:
        """Ask the loop to drain and exit: rank 0 finishes every queued
        and active request, then broadcasts a stop frame."""
        self._stop.set()

    def run(self) -> None:
        """Serve until ``stop()``.  Blocks; re-forms the gang in process
        on rank failure (``@hvd.elastic.run`` semantics)."""
        from horovod_tpu import basics, elastic

        os.environ.setdefault("HVD_TPU_CORE", "py")
        if not basics.is_initialized():
            basics.init()
        try:
            if basics.size() == 1 and \
                    not os.environ.get("HVD_RENDEZVOUS_ADDR"):
                # Single process, no launcher: there is no gang to
                # re-form (and no KV store for the elastic protocol),
                # so run one incarnation directly.
                import types

                self._epoch_body(types.SimpleNamespace(
                    serve_generation=0))
            else:
                state = elastic.ObjectState(serve_generation=0)
                elastic.run(self._epoch_body)(state)
        finally:
            if self._door is not None:
                self._door.stop()
                self._door = None
            if self.scheduler is not None:
                self.scheduler.fail_all("serving loop exited")

    # -- one gang incarnation -------------------------------------------

    def _epoch_body(self, state) -> None:
        from horovod_tpu import basics

        eng = basics._runtime
        if eng is None or not hasattr(eng, "serve_broadcast"):
            raise RuntimeError(
                "serving requires the Python engine (HVD_TPU_CORE=py)")
        self.log = get_logger(basics.rank())
        self._elastic_ctx = getattr(state, "_elastic_ctx", None)
        engine = DecodeEngine(self.params, self.cfg,
                              max_batch=self.max_batch,
                              cache_len=self.cache_len, mesh=self.mesh)
        # The previous incarnation's in-flight table survives the reset
        # here: on a promoted rank it seeds the fresh scheduler below.
        shadow = sorted(self._slots.items())
        self._slots = {}
        leader = basics.rank() == 0
        promoted = leader and self.scheduler is None
        if promoted:
            # Seed the fresh scheduler from the shadow BEFORE the front
            # door flips to leader role: a client re-POSTing an in-flight
            # id during the window must join the adopted request (and see
            # its attempts>1), not race it as a fresh admission.
            self.scheduler = Scheduler(self.max_batch, self.max_queue,
                                       self.cache_len)
            if shadow:
                adopted = self.scheduler.adopt_shadow(shadow)
                self.log.info(
                    "promoted to serving leader: adopted %d in-flight "
                    "request(s) from the shadow slot table", adopted)
        self._ensure_front_door(leader=leader)
        if leader:
            state.serve_generation += 1
            replayed = self.scheduler.requeue_inflight()
            if replayed:
                self.log.info(
                    "re-formed gang (generation %d): replaying %d "
                    "in-flight request(s) from their prompts",
                    state.serve_generation, replayed)
            self._publish_leader()
            self._drive(eng, engine)
        else:
            self._follow(eng, engine)

    def _ensure_front_door(self, leader: bool = True) -> None:
        """Bind this rank's front door once per process (its port is
        stable across re-elections).  The leader's door admits into the
        local scheduler; a follower's door forwards to the current
        leader.  A follower promoted by a re-form flips its existing
        door to leader role in place — clients keep the same endpoint."""
        if leader and self.scheduler is None:
            self.scheduler = Scheduler(self.max_batch, self.max_queue,
                                       self.cache_len)
        if self._door is None:
            self._door = FrontDoor(
                self.scheduler if leader else None, host=self.host,
                port=self.port,
                timeout_s=self.request_timeout_s,
                leader_addr_fn=self._leader_addr,
                advertise_host=self._advertise_host())
            door_port = self._door.start()
            if leader:
                self.port = door_port
            self.log.info(
                "serving front door listening on :%d (%s)", door_port,
                "leader" if leader else "forwarding to leader")
            if self.on_ready is not None:
                self.on_ready(door_port)
        elif leader and self._door.scheduler is None:
            self._door.scheduler = self.scheduler
            self.port = self._door.port
            self.log.info("front door :%d promoted to serving leader",
                          self.port)

    # -- leader address: publish + resolve -------------------------------

    def _advertise_host(self) -> str:
        ctx = self._elastic_ctx
        if ctx is not None:
            addr = ctx.kv.local_address()
            if addr:
                return addr
        return "127.0.0.1"

    def _leader_self_addr(self) -> str:
        return f"{self._advertise_host()}:{self.port}"

    def _publish_leader(self) -> None:
        """Rank 0: publish this door's address under the elastic-scoped
        KV key so follower doors (and late joiners) can resolve the
        leader even before the first delta frame of the epoch."""
        self._known_leader = self._leader_self_addr()
        ctx = self._elastic_ctx
        if ctx is not None:
            try:
                ctx.kv.put(ctx.key("serve/leader"), self._known_leader)
            except Exception:
                # KV briefly unreachable (e.g. failing over to a
                # standby): the delta frames still carry the address.
                self.log.warning("could not publish serving leader "
                                 "address to the KV store")

    def _leader_addr(self, refresh: bool = False) -> Optional[str]:
        """Follower doors resolve the current leader here: the cached
        frame-carried address normally, the KV key on ``refresh`` (a
        forward just failed — re-election may have moved the leader)."""
        if refresh:
            ctx = self._elastic_ctx
            if ctx is not None:
                try:
                    v = ctx.kv.get(ctx.key("serve/leader"))
                except Exception:
                    v = None
                if v:
                    self._known_leader = v
        return self._known_leader

    # -- rank 0: drive ---------------------------------------------------

    def _drive(self, eng, engine: DecodeEngine) -> None:
        seq = 0
        while True:
            work = self.scheduler.has_work()
            if not work and not self._stop.is_set():
                time.sleep(self.idle_poll_s)  # idle: no frame, no step
                continue
            stopping = not work  # drained, and stop() was asked
            seq += 1
            with _trace.span("serve.frame", step=seq):
                admissions = self.scheduler.take_admissions()
                payload = wire.encode_serve_delta(
                    seq, stopping,
                    [(slot, r.id, r.max_new, r.prompt)
                     for slot, r in admissions],
                    eng.epoch, leader_addr=self._known_leader or "")
                eng.serve_broadcast(payload)
                frame = eng.serve_recv(timeout=self.recv_timeout_s)
            if frame is None:  # own frame is in the inbox unless dying
                if self._engine_dying(eng):
                    return
                continue
            if self._apply_frame(frame, eng, engine, rank0=True):
                return

    # -- workers: follow -------------------------------------------------

    def _follow(self, eng, engine: DecodeEngine) -> None:
        while True:
            frame = eng.serve_recv(timeout=self.recv_timeout_s)
            if frame is None:
                if self._engine_dying(eng):
                    return
                continue  # plain timeout: keep listening
            if self._apply_frame(frame, eng, engine, rank0=False):
                return

    @staticmethod
    def _engine_dying(eng) -> bool:
        """None from serve_recv: timeout (keep going), clean shutdown
        (exit), or abort.  A lost-coordinator abort is re-raised as the
        RuntimeError the elastic wrapper maps to a rank-0 failure."""
        if getattr(eng, "_aborted", False):
            raise RuntimeError(
                getattr(eng, "_abort_reason", None) or "engine aborted")
        return eng._shutdown_flag.is_set() or \
            eng._shutdown_requested.is_set()

    # -- the lockstep step (identical on every rank) ---------------------

    def _apply_frame(self, frame, eng, engine, *, rank0: bool) -> bool:
        seq, stopping, admissions, epoch, leader_addr = \
            wire.decode_serve_delta_ex(frame)
        if epoch != eng.epoch:
            return False  # stale frame from a previous incarnation
        if leader_addr and not rank0:
            self._known_leader = leader_addr
        if stopping:
            return True
        # Chaos: a mid-decode stall/delay on this rank, fired before any
        # device work so the step's collective shows the gap.
        _fi.fire("serve.step", str(seq))
        # serve.apply holds the turn; prefill, decode, confirm and emit
        # are disjoint inside it, and decode holds dispatch and read
        # (docs/serving.md "Spans").
        with _trace.span("serve.apply", step=seq,
                         admitted=len(admissions)):
            self._turn(seq, admissions, engine, rank0)
        return False

    def _turn(self, seq: int, admissions, engine: DecodeEngine,
              rank0: bool) -> None:
        """One of the two orders the module docstring gives, chosen by
        what every rank sees in the frame and in its own ``_slots``."""
        if not admissions and not engine.unread:
            return  # nobody to admit, nothing to settle
        t0 = time.monotonic()
        live = sorted(self._slots)  # the slots of the unread vector
        # The shadow knows an admission as soon as its frame is applied:
        # a leader that dies while this turn settles or prefills must
        # not take the request with it.
        for slot, req_id, max_new, prompt in admissions:
            self._slots[slot] = {"id": req_id, "prompt": list(prompt),
                                 "max_new": max_new, "remaining": max_new}
        if admissions:
            if engine.unread:
                self._settle(seq, engine, rank0, live, ahead=False)
            for slot, _, _, prompt in admissions:
                with _trace.span("serve.prefill",
                                 histogram="hvd_serve_prefill_seconds",
                                 slot=slot, prompt_len=len(prompt)):
                    first = engine.prefill(slot, prompt)
                    self._emit(slot, first, engine, rank0)
                _tmx.inc_counter("hvd_serve_prefill_tokens_total",
                                 len(prompt))
            if self._slots:
                self._dispatch(engine)
        else:
            # No step for an empty table: run ahead only if a slot will
            # outlive the unread vector (retirement by count is known
            # now; one by eos_id is not, and costs one unread row).
            self._settle(seq, engine, rank0, live, ahead=any(
                st["remaining"] > 1 for st in self._slots.values()))
        if engine.unread and not self._slots:
            # The settle met the last live slot's eos_id: the step that
            # ran ahead holds retired slots' rows only.  Nothing stays
            # unread while the loop sleeps.
            with _trace.span("serve.decode", slots=0):
                self._read(engine)
        if rank0:
            t1 = time.monotonic()
            _tmx.observe("hvd_serve_token_latency_seconds", t1 - t0)
            # Staleness surface for /stats last_step_age_s — the same
            # clock read the latency observe just took.
            self.scheduler.note_step(t1)

    def _settle(self, seq: int, engine: DecodeEngine, rank0: bool,
                live, ahead: bool) -> None:
        """Read the unread token vector, confirm it with the gang, emit
        it to the ``live`` slots it was computed for: no token reaches a
        client before the gang agreed on it.  With ``ahead`` the next
        step is dispatched first, inside the same ``serve.decode`` span
        (one span a vector read; ``serve.dispatch`` and ``serve.read``
        split it into the host's queueing and the wait for the chip)."""
        with _trace.span("serve.decode", slots=len(live)):
            if ahead:
                self._dispatch(engine)
                _tmx.inc_counter("hvd_serve_steps_ahead_total")
            toks = self._read(engine)
        # The agreement allreduce's own collective spans share this
        # step's wall window; the serve.confirm span ties them to the
        # TAG_SERVE seq that caused them.
        with _trace.span("serve.confirm", step=seq,
                         slots=len(live)) as confirm:
            self._confirm(toks)
        with _trace.span("serve.emit", slots=len(live)):
            for slot in live:
                self._emit(slot, int(toks[slot]), engine, rank0)
        # Step confirm on the flight recorder: stamped with the span's
        # entry read when tracing, untimed otherwise (ring order still
        # sequences it against failure events).
        _bb.note("serve.confirm", confirm.t0, step=seq,
                 slots=len(self._slots))

    @staticmethod
    def _dispatch(engine: DecodeEngine) -> None:
        """Queue engine step ``step``: ``serve.dispatch`` is what queueing
        it costs the host (the step program and the three lazy ops)."""
        with _trace.span("serve.dispatch", step=engine.steps):
            engine.dispatch()

    @staticmethod
    def _read(engine: DecodeEngine) -> np.ndarray:
        """Read engine step ``step``, the oldest unread: ``serve.read`` is
        how long the loop waited for the chip.  On a turn that ran ahead
        it is the step before the one just queued."""
        with _trace.span("serve.read",
                         histogram="hvd_serve_read_wait_seconds",
                         step=engine.steps - engine.unread):
            return engine.read()

    def _emit(self, slot: int, token: int, engine: DecodeEngine,
              rank0: bool) -> None:
        st = self._slots[slot]
        if rank0:
            self.scheduler.on_token(slot, token)
        st["remaining"] -= 1
        if st["remaining"] <= 0 or \
                (self.eos_id is not None and token == self.eos_id):
            engine.clear(slot)
            del self._slots[slot]
            if rank0:
                self.scheduler.complete(slot)

    def _confirm(self, toks: np.ndarray) -> None:
        """Token-agreement allreduce: the step's data-plane op (deadline
        + straggler surface) and the greedy-lockstep determinism check."""
        from horovod_tpu.ops import eager

        local = np.asarray(toks, dtype=np.float64)
        agreed = eager.allreduce(local, op=ReduceOp.MAX,
                                 name="__serve.confirm")
        if not np.array_equal(np.asarray(agreed), local):
            raise RuntimeError(
                "serving token divergence: this rank's greedy tokens "
                "differ from the gang's — lockstep decode is broken "
                "(non-deterministic kernels or mismatched params?)")
