"""``@hvd.elastic.run``: in-process gang re-form.

Parity: ``horovod/common/elastic.py`` ``run_fn`` — but where the
reference re-executes the user function after a gloo re-rendezvous
driven by the launcher, here the whole reset happens **in process**: the
wrapper catches the failure, tears the engine down, re-forms the gang
through the launcher's KV rendezvous under a bumped membership epoch,
rolls the state back to the last commit, re-syncs it, and calls the
user function again.  No process is relaunched; survivors keep their
JAX compilation caches and device state.

Failure signals handled:

* :class:`~horovod_tpu.common.types.RanksFailedError` — the coordinator
  evicted dead ranks (heartbeat timeout, PR 1) and broadcast the set, so
  every survivor computes the identical new membership locally.
* A lost-coordinator abort (``RuntimeError`` with the engine's
  ``_abort_reason`` naming the coordinator) — treated as a failure of
  the current rank 0.
* :class:`~horovod_tpu.elastic.driver.HostsUpdatedInterrupt` — no
  failure; the host set changed (a joiner announced itself or the
  discovery script found new hosts), raised collectively by
  ``State.commit()``.

Re-form protocol (KV keys; they span epochs, but are prefixed with the
launch-time ``HVD_RDV_SCOPE`` — captured once as
``HVD_ELASTIC_SCOPE_BASE`` — so a ``--max-restarts`` relaunch never
reads a dead attempt's rosters):

* ``elastic/roster/0/{rank}`` — epoch-0 uid publication (later epochs
  get the roster from the world key below).
* ``elastic/pending/{uid}`` + ``elastic/notify`` — a joiner announces
  itself and bumps the notify counter the commit check polls.
* ``elastic/world/{epoch}`` — the leader (lowest surviving old rank)
  writes the new world as a JSON uid list in rank order; every member
  finds its new rank as its index.  Ordering survivors by old rank makes
  the new rank 0 the lowest surviving committed rank — ``state.sync()``
  can always root at 0.
* ``elastic/assign/{uid}`` — the leader's epoch/rank/size grant a
  polling joiner blocks on before its first ``hvd.init()``.

Each incarnation initializes under ``HVD_ELASTIC_EPOCH=<n>`` (stamped on
every wire frame; stale frames are dropped — ``common/wire.py``) and
``HVD_RDV_SCOPE=elastic-<n>`` (fresh rendezvous namespace, so re-used
ranks never read a previous incarnation's addresses).
"""

from __future__ import annotations

import functools
import json
import os
import socket
import time
from typing import List, Optional, Set

from horovod_tpu.elastic.driver import (
    ElasticDriver,
    HostDiscoveryScript,
    HostsUpdatedInterrupt,
)
from horovod_tpu.telemetry import blackbox as _bb
from horovod_tpu.telemetry import registry as _tmx
from horovod_tpu.telemetry import trace as _trace
from horovod_tpu.utils import env as env_util
from horovod_tpu.utils.logging import get_logger

_ASSIGN_TIMEOUT_S = 600.0


def _postmortem_suffix() -> str:
    """Pointer appended to terminal elastic errors: where the flight-
    recorder dumps landed, ready for tools/hvd_postmortem.py."""
    if not env_util.blackbox_enabled():
        return ""
    return f"; postmortem: {env_util.blackbox_dir()}"


def _worker_uid() -> str:
    uid = os.environ.get(env_util.ELASTIC_UID, "")
    return uid or f"{socket.gethostname()}:{os.getpid()}"


class _ElasticContext:
    """Per-process view of the gang across incarnations."""

    def __init__(self):
        from horovod_tpu.runner.http_client import KVClient

        self.uid = _worker_uid()
        addr = os.environ.get("HVD_RENDEZVOUS_ADDR", "127.0.0.1")
        port = int(os.environ.get("HVD_RENDEZVOUS_PORT", "0"))
        self.kv = KVClient(addr, port)
        self.scope = os.environ.get("HVD_ELASTIC_SCOPE_BASE", "")
        self.epoch = env_util.get_int(env_util.ELASTIC_EPOCH, 0)
        self.min_np = env_util.get_int(env_util.ELASTIC_MIN_NP, 1)
        self.max_np = env_util.get_int(env_util.ELASTIC_MAX_NP, 1 << 30)
        self.check_interval_s = env_util.get_float(
            env_util.ELASTIC_CHECK_INTERVAL_S, 0.5)
        self.rank = -1
        self.roster: List[str] = []  # uid per rank, current epoch
        self._seen_notify = 0
        self.log = get_logger(0)
        self._driver: Optional[ElasticDriver] = None
        # Host set last seen by the in-process discovery driver.  Kept
        # here (not in the driver) because the driver is restarted at
        # every re-form: its first poll is a baseline snapshot, and only
        # a change against THIS set is a real membership update —
        # otherwise every restart would re-publish and re-form forever.
        self._known_hosts: Optional[Set[str]] = None

    def key(self, suffix: str) -> str:
        """KV key under the attempt's scope base (isolates relaunches)."""
        return f"{self.scope}/{suffix}" if self.scope else suffix

    # -- update notifications ------------------------------------------

    def has_pending_update(self) -> bool:
        v = self.kv.get(self.key("elastic/notify"))
        return int(v) > self._seen_notify if v else False

    def consume_updates(self) -> None:
        """Take the updates published so far as handled -- but for a
        joiner this gang has room for and has not admitted.  It puts its
        pending key and THEN bumps the count, possibly while this rank
        was still starting or re-forming; its bump consumed, nobody would
        ever admit it.  Left unseen, it interrupts at the next commit."""
        v = self.kv.get(self.key("elastic/notify"))
        self._seen_notify = int(v) if v else 0
        if len(self.roster) < self.max_np:
            prefix = self.key("elastic/pending/")
            if any(k[len(prefix):] not in self.roster
                   for k in self.kv.list(prefix)):
                self._seen_notify -= 1

    def publish_update(self) -> None:
        v = self.kv.get(self.key("elastic/notify"))
        self.kv.put(self.key("elastic/notify"),
                    str((int(v) if v else 0) + 1))

    # -- roster ---------------------------------------------------------

    def gather_initial_roster(self) -> None:
        """Epoch 0: every rank publishes its uid and reads the others'
        (same O(size) pattern the bootstrap uses for addresses)."""
        from horovod_tpu import basics

        self.rank = basics.rank()
        size = basics.size()
        self.kv.put(self.key(f"elastic/roster/{self.epoch}/{self.rank}"),
                    self.uid)
        timeout = env_util.get_float("HVD_START_TIMEOUT", 120.0)
        self.roster = [
            self.kv.wait_get(self.key(f"elastic/roster/{self.epoch}/{r}"),
                             timeout=timeout)
            for r in range(size)]

    # -- discovery driver (rank 0, in-process notification mode) -------

    def maybe_start_driver(self) -> None:
        script = os.environ.get(env_util.HOST_DISCOVERY_SCRIPT, "")
        if not script or self.rank != 0 or self._driver is not None:
            return

        def on_update(epoch, added, removed):
            # The driver's first poll (epoch 1) reports the full current
            # set as "added"; later polls are incremental.
            current = set(added) if epoch == 1 else \
                (self._known_hosts | set(added)) - set(removed)
            if self._known_hosts is not None and \
                    current != self._known_hosts:
                # Publication only — workers agree to interrupt at a
                # commit (state.check_host_updates), never mid-step.
                self.publish_update()
            self._known_hosts = current

        self._driver = ElasticDriver(
            HostDiscoveryScript(script), self.min_np, self.max_np,
            on_hosts_updated=on_update)
        self._driver.start()

    def stop_driver(self) -> None:
        if self._driver is not None:
            self._driver.stop()
            self._driver = None


def _engine_abort_reason() -> Optional[str]:
    from horovod_tpu import basics

    eng = basics._runtime
    if eng is not None and getattr(eng, "_aborted", False):
        return getattr(eng, "_abort_reason", None) or "aborted"
    return None


def _timeline_event(name: str, **args) -> None:
    from horovod_tpu import basics

    eng = basics._runtime
    tl = getattr(eng, "timeline", None) if eng is not None else None
    if tl is not None and tl.enabled:
        tl.elastic_event(name, **args)


def _set_world_env(rank: int, size: int, epoch: int) -> None:
    # Post-reset topology is flat (each survivor is its own block):
    # hierarchical paths stay off until a full relaunch rebuilds the
    # host-grouped layout.
    os.environ["HVD_RANK"] = str(rank)
    os.environ["HVD_SIZE"] = str(size)
    os.environ["HVD_LOCAL_RANK"] = "0"
    os.environ["HVD_LOCAL_SIZE"] = "1"
    os.environ["HVD_CROSS_RANK"] = str(rank)
    os.environ["HVD_CROSS_SIZE"] = str(size)
    os.environ[env_util.ELASTIC_EPOCH] = str(epoch)
    base = os.environ.get("HVD_ELASTIC_SCOPE_BASE", "")
    os.environ["HVD_RDV_SCOPE"] = (
        f"{base}.elastic-{epoch}" if base else f"elastic-{epoch}")


def quorum_lost(roster_size: int, failed: Set[int]) -> bool:
    """True when the side of the partition this process is on does NOT
    hold a re-form quorum of the last-committed ``roster_size`` members.

    Strict majority wins; an EXACT half is broken by which side still
    holds old rank 0 (two live halves must never both win, and exactly
    one holds it).  The honest limit: when rank 0 is truly dead in an
    even split, both sides lose and the job needs a full relaunch
    (docs/fault_tolerance.md)."""
    n_alive = roster_size - len(failed)
    return (2 * n_alive < roster_size
            or (2 * n_alive == roster_size and 0 in failed))


def _reform(ctx: _ElasticContext, failed: Set[int]) -> None:
    """Tear down, compute the new world, and re-init under a new epoch."""
    from horovod_tpu import basics, process_sets

    t_reform0 = time.monotonic_ns()
    # Quorum gate (HVD_QUORUM, default on): re-form only when a STRICT
    # majority of the last-committed membership survived.  A network
    # partition makes both sides see "the others failed" — without the
    # gate each side would re-form its own sibling gang under the same
    # scope and split-brain the job.  The majority side proceeds; a
    # minority self-terminates with a PARTITION_MINORITY verdict.
    # Recorded BEFORE teardown so the still-live timeline and flight
    # recorder capture the verdict.
    n_alive = len(ctx.roster) - len(failed)
    if env_util.quorum_on() and ctx.roster \
            and quorum_lost(len(ctx.roster), failed):
        _timeline_event("PARTITION_MINORITY", alive=n_alive,
                        roster=len(ctx.roster), failed=sorted(failed))
        _bb.note("partition.minority", t_reform0, alive=n_alive,
                 roster=len(ctx.roster), failed=sorted(failed))
        _bb.dump("partition_minority",
                 f"alive={n_alive}/{len(ctx.roster)}")
        print(f"PARTITION_MINORITY: only {n_alive} of "
              f"{len(ctx.roster)} last-committed members reachable; "
              "refusing to re-form a minority gang", flush=True)
        ctx.stop_driver()
        basics.shutdown()
        raise RuntimeError(
            f"PARTITION_MINORITY: {n_alive}/{len(ctx.roster)} members "
            f"reachable after failure of rank(s) {sorted(failed)} — no "
            "strict majority of the last-committed membership; "
            "self-terminating instead of re-forming a split-brain "
            "sibling gang" + _postmortem_suffix())
    if 0 in failed:
        _tmx.inc_counter("hvd_leader_failovers_total")
        # Leader failover is a terminal event for the old incarnation:
        # dump before teardown so the evidence names the dead hub.
        _bb.note("leader.failover", t_reform0, failed=sorted(failed),
                 epoch=ctx.epoch)
        _bb.dump("leader_failover", f"failed={sorted(failed)}")
    _timeline_event("ELASTIC_RESET", failed=sorted(failed))
    ctx.stop_driver()
    basics.shutdown()
    process_sets.reset()  # ranks are renumbered; old sets are meaningless

    new_epoch = ctx.epoch + 1
    survivors = [uid for r, uid in enumerate(ctx.roster) if r not in failed]
    if ctx.uid not in survivors:
        raise RuntimeError(
            "this rank was evicted from the gang; cannot re-join the "
            "same incarnation (restart the process to re-join)"
            + _postmortem_suffix())

    if survivors and survivors[0] == ctx.uid:
        # Leader: lowest surviving old rank.  Admit pending joiners up
        # to max_np, publish the world, grant the joiners.
        prefix = ctx.key("elastic/pending/")
        pending = [k[len(prefix):] for k in ctx.kv.list(prefix)]
        pending = [u for u in pending if u not in survivors]
        room = max(0, ctx.max_np - len(survivors))
        admitted, deferred = pending[:room], pending[room:]
        world = survivors + admitted
        if len(world) < ctx.min_np:
            raise RuntimeError(
                f"only {len(world)} worker(s) left after failure of "
                f"rank(s) {sorted(failed)}, below --min-np={ctx.min_np}; "
                f"exiting for a full relaunch" + _postmortem_suffix())
        ctx.kv.put(ctx.key(f"elastic/world/{new_epoch}"), json.dumps(world))
        ctx.kv.put(ctx.key("elastic/epoch"), str(new_epoch))
        for i, uid in enumerate(world):
            if uid in admitted:
                ctx.kv.put(ctx.key(f"elastic/assign/{uid}"), json.dumps(
                    {"epoch": new_epoch, "rank": i, "size": len(world)}))
                ctx.kv.delete(ctx.key(f"elastic/pending/{uid}"))
        if deferred:
            ctx.log.info("%d joiner(s) deferred (at --max-np=%d)",
                         len(deferred), ctx.max_np)
    else:
        timeout = env_util.get_float("HVD_START_TIMEOUT", 120.0)
        world = json.loads(ctx.kv.wait_get(
            ctx.key(f"elastic/world/{new_epoch}"), timeout=timeout))
        if len(world) < ctx.min_np:
            raise RuntimeError(
                f"re-formed world of {len(world)} is below "
                f"--min-np={ctx.min_np}; exiting for a full relaunch"
                + _postmortem_suffix())

    new_rank = world.index(ctx.uid)
    _set_world_env(new_rank, len(world), new_epoch)
    basics.init()
    ctx.epoch = new_epoch
    ctx.rank = new_rank
    ctx.roster = world
    ctx.consume_updates()
    ctx.maybe_start_driver()
    _tmx.inc_counter("hvd_elastic_reforms_total")
    # Epoch change on the flight recorder (the re-formed engine's
    # from_env restamped rank/epoch on the surviving ring).
    _bb.note("elastic.reform", t_reform0, epoch=new_epoch,
             size=len(world), failed=sorted(failed))
    if 0 in failed:
        # The gang's hub died and the lowest surviving old rank was
        # elected leader by the world protocol above.  Recorded after
        # re-init: before the rank renumbering the promoted process had
        # no timeline (only rank 0 writes one), so an earlier emit
        # would land nowhere — the dead hub can't record its own death.
        _timeline_event("LEADER_FAILOVER", failed=sorted(failed),
                        epoch=ctx.epoch - 1, new_leader=new_rank == 0)
    _timeline_event("ELASTIC_REFORM", epoch=new_epoch, size=len(world))
    # Emitted AFTER basics.init(): the re-formed engine's tracer (a
    # fresh file under the same HVD_TRACE_DIR, appended by epoch) is
    # the one that exists to record it.
    _trace.emit("elastic.reform", t_reform0, time.monotonic_ns(),
                epoch=new_epoch, size=len(world), failed=sorted(failed))
    ctx.log.info("gang re-formed: epoch %d, rank %d/%d",
                 new_epoch, new_rank, len(world))


# -- evict-and-replay ---------------------------------------------------
# When the failure was a CollectiveTimeoutError, every survivor retained
# copies of the aborted fused reduction's original inputs
# (runtime_py.retain_aborted_batch); after the re-form the wrapper
# replays them so the batch is not lost with the wedged rank.

_last_replay: Optional[dict] = None


def last_replay_results() -> Optional[dict]:
    """Results of the most recent evict-and-replay (original tensor
    name -> reduced array over the re-formed gang), or None if no
    replay has run in this process."""
    return _last_replay


def _replay_aborted_batch(ctx: _ElasticContext,
                          old_roster: List[str]) -> None:
    global _last_replay
    from horovod_tpu import runtime_py
    from horovod_tpu.ops import eager

    batch = runtime_py.take_retained_batch()
    if not batch:
        return
    if not set(ctx.roster) <= set(old_roster):
        # A joiner was admitted in this re-form: it holds no retained
        # inputs, so a survivor-only replay would desync the global
        # negotiation.  Drop the batch — the training loop restarts
        # from its last commit instead.
        ctx.log.warning(
            "dropping the retained aborted batch: new worker(s) "
            "joined during the re-form")
        return
    # Async-submit the whole batch so the coordinator re-fuses it like
    # the original launch; names are epoch-scoped so the replay never
    # collides with the training loop's own tensor names.
    t_replay0 = time.monotonic_ns()
    handles = [
        (item["name"], eager.allreduce_async(
            item["array"], name=f"replay.e{ctx.epoch}.{item['name']}",
            op=item["op"], prescale_factor=item["prescale"],
            postscale_factor=item["postscale"]))
        for item in batch]
    _last_replay = {nm: eager.synchronize(h) for nm, h in handles}
    _timeline_event("ELASTIC_REPLAY", epoch=ctx.epoch,
                    tensors=len(handles))
    _trace.emit("elastic.replay", t_replay0, time.monotonic_ns(),
                epoch=ctx.epoch, tensors=len(handles))
    ctx.log.info("replayed %d aborted tensor(s) on the re-formed gang",
                 len(handles))


def _join_as_new_worker(ctx: _ElasticContext) -> None:
    """Late worker: announce, then block for an epoch assignment instead
    of bootstrapping at epoch 0."""
    from horovod_tpu import basics

    ctx.kv.put(ctx.key(f"elastic/pending/{ctx.uid}"), "1")
    ctx.publish_update()
    deadline = time.monotonic() + env_util.get_float(
        "HVD_ELASTIC_JOIN_TIMEOUT", _ASSIGN_TIMEOUT_S)
    while True:
        v = ctx.kv.get(ctx.key(f"elastic/assign/{ctx.uid}"))
        if v is not None:
            break
        if time.monotonic() > deadline:
            raise TimeoutError(
                "no gang admitted this joiner (is a job with "
                "--max-np headroom running?)")
        time.sleep(ctx.check_interval_s)
    grant = json.loads(v)
    os.environ.pop(env_util.ELASTIC_JOINER, None)
    _set_world_env(grant["rank"], grant["size"], grant["epoch"])
    basics.init()
    ctx.epoch = grant["epoch"]
    ctx.rank = grant["rank"]
    timeout = env_util.get_float("HVD_START_TIMEOUT", 120.0)
    ctx.roster = json.loads(ctx.kv.wait_get(
        ctx.key(f"elastic/world/{ctx.epoch}"), timeout=timeout))
    ctx.consume_updates()


def run(func):
    """Decorator: ``@hvd.elastic.run`` around a training function whose
    first argument is a :class:`~horovod_tpu.elastic.state.State`.

    The function is (re)invoked after every gang re-form with the state
    rolled back to its last commit and synced from the new rank 0 — it
    must resume from the state (e.g. ``state.batch``/``state.epoch``),
    not from scratch.
    """

    @functools.wraps(func)
    def wrapper(state, *args, **kwargs):
        from horovod_tpu import basics
        from horovod_tpu.common.types import (
            CollectiveTimeoutError,
            RanksFailedError,
        )

        # The native engine has no in-process reset path; elastic always
        # runs the Python engine (hvdrun does the same).
        os.environ.setdefault("HVD_TPU_CORE", "py")
        # Freeze the launch-time rendezvous scope before any re-form
        # rewrites HVD_RDV_SCOPE: every elastic/* key and every later
        # scope derives from this base, so a --max-restarts relaunch
        # (which sets a fresh attemptN scope) never collides with keys
        # from a dead attempt.
        if "HVD_ELASTIC_SCOPE_BASE" not in os.environ:
            os.environ["HVD_ELASTIC_SCOPE_BASE"] = \
                os.environ.get("HVD_RDV_SCOPE", "")
        joined = env_util.get_bool(env_util.ELASTIC_JOINER, False)
        if not joined and not basics.is_initialized():
            os.environ.setdefault(env_util.ELASTIC_EPOCH, "0")
            basics.init()
        ctx = _ElasticContext()
        state._elastic_ctx = ctx
        if joined:
            # A joiner never bootstraps the epoch-0 mesh: it blocks for
            # an epoch assignment and first initializes there.
            _join_as_new_worker(ctx)
        else:
            ctx.gather_initial_roster()
            ctx.consume_updates()
            ctx.maybe_start_driver()
        try:
            while True:
                replay = False
                try:
                    if joined:
                        # First sync delivers the gang's state (and the
                        # matching collective on the incumbents runs in
                        # their post-reset sync below).
                        state.sync()
                        joined = False
                    return func(state, *args, **kwargs)
                except RanksFailedError as e:
                    failed = set(e.ranks)
                    # A gang-agreed collective abort (hung rank, not a
                    # dead one) leaves the fused batch's inputs retained
                    # on every survivor: replay after the re-form.
                    replay = isinstance(e, CollectiveTimeoutError)
                except HostsUpdatedInterrupt:
                    failed = set()
                except RuntimeError:
                    # A dead hub surfaces twice on a worker: the training
                    # /serving thread's collective fails with a raw
                    # socket error FIRST, and the engine's own
                    # lost-coordinator abort (recv-loop EOF -> worker
                    # cycle) lands a beat later.  Poll briefly for the
                    # abort verdict before concluding this RuntimeError
                    # is not a hub failure.
                    reason = _engine_abort_reason()
                    if reason is None:
                        deadline = time.monotonic() + 2.0
                        while reason is None and \
                                time.monotonic() < deadline:
                            time.sleep(0.05)
                            reason = _engine_abort_reason()
                    if reason is None or "coordinator" not in reason:
                        raise
                    # The star's hub died: that is a failure of rank 0.
                    failed = {0}
                old_roster = list(ctx.roster)
                _reform(ctx, failed)
                state.on_reset()
                state.restore()
                state.sync()
                if replay:
                    _replay_aborted_batch(ctx, old_roster)
        finally:
            ctx.stop_driver()
            state._elastic_ctx = None

    return wrapper
