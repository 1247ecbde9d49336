"""One in-process ``ServingLoop`` on one rank with a spy on the two halves
of ``DecodeEngine.step`` and on the loop's confirm, emit and sleep: what
tests/test_serving.py and tests/test_jamba.py hold the order of a turn to
(serving/loop.py ``_turn``).

``serve()`` submits requests in waves straight into the loop's scheduler
(the first wave before the loop starts, so the first frame's admissions
are known; each later wave once the one before is answered and the loop
has slept), and returns every request's tokens, the log of events and what
the registry counted.  ``check_order()`` is the order itself.
"""

import threading
import time
from typing import List, NamedTuple, Optional, Sequence, Tuple

from horovod_tpu.common import wire
from horovod_tpu.serving import loop as loop_mod
from horovod_tpu.serving.decode import DecodeEngine
from horovod_tpu.serving.scheduler import Scheduler
from horovod_tpu.telemetry import registry as tmx

AHEAD = "hvd_serve_steps_ahead_total"
TURNS = "hvd_serve_token_latency_seconds"


class Served(NamedTuple):
    tokens: List[List[int]]     # a request's, in the order submitted
    events: List[Tuple]         # the loop thread's, in order
    ahead: float                # the registry's counter
    turns: int                  # observations of the turn histogram
    stats: dict                 # GET /stats' body, before the stop


def serve(monkeypatch, params, cfg, waves: Sequence[Sequence[Tuple]], *,
          max_batch: int, cache_len: int, eos_id: Optional[int] = None,
          stop_at_first_token: bool = False, on_engine=None) -> Served:
    """Run the waves (lists of ``(prompt, max_new)``) through one loop;
    ``on_engine`` is handed the loop's engine when it is built."""
    import horovod_tpu as hvd

    monkeypatch.setenv("HVD_TPU_CORE", "py")   # ServingLoop.run setdefaults
    for k in ("HVD_SIZE", "HVD_RANK", "HVD_RENDEZVOUS_ADDR"):
        monkeypatch.delenv(k, raising=False)
    hvd.shutdown()
    events: List[Tuple] = []
    box = {}

    class SpyEngine(DecodeEngine):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            if on_engine is not None:
                on_engine(self)

        def dispatch(self):
            events.append(("dispatch", self.unread, len(loop._slots)))
            super().dispatch()

        def read(self):
            events.append(("read", frozenset(loop._slots)))
            return super().read()

        def prefill(self, slot, prompt):
            events.append(("prefill", slot, self.unread))
            return super().prefill(slot, prompt)

    class SpyLoop(loop_mod.ServingLoop):
        def _apply_frame(self, frame, eng, engine, *, rank0):
            _, stopping, admissions, _, _ = wire.decode_serve_delta_ex(frame)
            box["engine"] = engine
            events.append(("frame", len(admissions), stopping,
                           engine.unread))
            return super()._apply_frame(frame, eng, engine, rank0=rank0)

        def _confirm(self, toks):
            events.append(("confirm",))
            super()._confirm(toks)

        def _emit(self, slot, token, engine, rank0):
            events.append(("emit", slot))
            super()._emit(slot, token, engine, rank0)

    class SleepSpy:
        """``time`` as serving/loop.py sees it: the idle poll is logged."""

        def __getattr__(self, name):
            return getattr(time, name)

        def sleep(self, seconds):
            engine = box.get("engine")
            events.append(("sleep", engine.unread if engine else 0,
                           len(loop._slots)))
            time.sleep(seconds)

    monkeypatch.setattr(loop_mod, "DecodeEngine", SpyEngine)
    monkeypatch.setattr(loop_mod, "time", SleepSpy())
    ready = threading.Event()
    loop = SpyLoop(params, cfg, port=0, max_batch=max_batch, max_queue=16,
                   cache_len=cache_len, host="127.0.0.1", eos_id=eos_id,
                   on_ready=lambda port: ready.set())
    # Made here, the scheduler takes the first wave before the first frame.
    loop.scheduler = Scheduler(max_batch, 16, cache_len)

    def run():
        try:
            loop.run()
        except BaseException as e:   # surfaced by the assert below
            box["error"] = e
            ready.set()
            raise

    tmx.configure(True)
    thread = threading.Thread(target=run, name="test-serve", daemon=True)
    requests = []
    try:
        for i, wave in enumerate(waves):
            batch = [loop.scheduler.submit(list(p), n) for p, n in wave]
            requests += batch
            if i == 0:
                thread.start()
                assert ready.wait(120) and "error" not in box, box
            if stop_at_first_token:
                while not batch[0].tokens and "error" not in box:
                    time.sleep(0.001)
                loop.stop()
                thread.join(120)
                break
            for r in batch:
                assert r.done.wait(120) and r.error is None, r.error
            time.sleep(0.05)    # silence: the loop polls every 2 ms
        stats = loop.scheduler.stats()
        snap = tmx.snapshot()
    finally:
        loop.stop()
        thread.join(60)
        hvd.shutdown()
        tmx.configure(False)
    assert not thread.is_alive() and "error" not in box, box
    assert all(r.done.is_set() and r.error is None for r in requests)
    return Served([list(r.tokens) for r in requests], events,
                  snap["counters"].get(AHEAD, 0.0),
                  snap["histograms"][TURNS]["count"], stats)


def turns(events):
    """The events of each applied frame, the frame's own first; sleeps and
    what precedes the first frame left out."""
    out = []
    for e in events:
        if e[0] == "frame":
            out.append([e])
        elif out and e[0] != "sleep":
            out[-1].append(e)
    return out


def check_order(served: Served) -> int:
    """Every turn keeps the order docs/serving.md gives it, nothing is
    unread when the loop sleeps or stops, no step is dispatched for an
    empty table, and the counter is what the spy counted.  Returns the
    number of steps that ran ahead."""
    ahead = 0
    for turn in turns(served.events):
        (_, admitted, stopping, unread), body = turn[0], turn[1:]
        kinds = [e[0] for e in body]
        if stopping:
            assert unread == 0 and not body, turn
            continue
        for e in body:
            if e[0] == "dispatch":
                assert e[2] > 0, ("a step for an empty table", turn)
                assert e[1] <= 1, ("two vectors unread", turn)
        reads = [i for i, k in enumerate(kinds) if k == "read"]
        assert len(reads) == unread or (
            # the step that ran ahead of the last slot's EOS, dropped
            len(reads) == 2 and kinds[-1] == "read"
            and kinds.count("dispatch") == 1), turn
        if reads:
            # settle: read, then the gang's confirm, then the emits
            i = reads[0]
            assert kinds[i + 1] == "confirm", turn
            assert "emit" not in kinds[:i] and "confirm" not in kinds[:i]
        if admitted:
            first = kinds.index("prefill")
            assert all(r < first for r in reads), turn
            assert all(e[2] == 0 for e in body if e[0] == "prefill"), turn
            assert "dispatch" not in kinds[:first], turn
            assert kinds.count("prefill") == admitted
            for i in reads:
                # the shadow held the admissions before the turn settled:
                # a leader that dies in the confirm loses no request
                assert {e[1] for e in body if e[0] == "prefill"} <= \
                    body[i][1], turn
            assert kinds[first:].count("dispatch") <= 1
        elif "dispatch" in kinds:
            assert kinds[:2] == ["dispatch", "read"], turn
            assert kinds.count("dispatch") == 1
            ahead += 1
        else:
            assert kinds[0] == "read", turn
    for e in served.events:
        if e[0] == "sleep":
            assert e[1] == 0 and e[2] == 0, ("unread while asleep", e)
    assert served.ahead == ahead, (served.ahead, ahead)
    assert served.turns == sum(
        1 for t in turns(served.events) if len(t) > 1)
    if served.turns:
        assert served.stats["ahead_share"] == round(ahead / served.turns, 4)
    return ahead
