"""The serving path names its own work (telemetry/trace.py ``span``).

One in-process ``ServingLoop`` at tiny sizes serves a handful of HTTP
requests under one profiler session with ``HVD_TRACE`` set and the
registry on, so the same run shows both sinks of every call site:

* the profiler trace (read back with ``jax.profiler.ProfileData``) holds
  every ``hvd:serve.*`` span; the loop thread's leaves are disjoint and
  lie inside ``serve.apply``; prefill and queue spans count one a request;
* the rank's JSONL stream holds the same phases, the same number of times;
* the operator histograms are fed from the same spans;
* ``serve.decode`` holds its two children, ``serve.dispatch`` (the host
  queueing a step) and ``serve.read`` (the loop waiting for the chip), the
  dispatch after a turn's prefills has a span too, and each carries the
  ordinal ``step`` of the engine step it queues or reads.

With neither sink on, ``span()`` reads no clock and writes nothing.  And a
handler that is still waiting for a slot is released by ``fail_all``, by
the 504 deadline (one deadline over both waits), and never parks at all
when its request is shed.
"""

import glob
import json
import os
import threading
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor

import pytest

from horovod_tpu.serving.scheduler import Scheduler
from horovod_tpu.serving.server import FrontDoor
from horovod_tpu.telemetry import registry as tmx
from horovod_tpu.telemetry import trace

LOOP_LEAVES = ("serve.frame", "serve.prefill", "serve.decode",
               "serve.confirm", "serve.emit")
INSIDE_APPLY = LOOP_LEAVES[1:]
IN_DECODE = ("serve.dispatch", "serve.read")    # serve.decode's children
HANDLER = ("serve.queued", "serve.active")
N_REQUESTS = 5          # over 2 slots: some wait for a slot
MODEL = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64)
CACHE_LEN = 32


def _http(port, method, path, body=None, timeout=30.0):
    import http.client

    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        c.request(method, path,
                  json.dumps(body) if body is not None else None)
        r = c.getresponse()
        return r.status, json.loads(r.read() or b"null")
    finally:
        c.close()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One traced run; what both sinks and the registry saw of it."""
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.serving import ServingLoop

    tmp = tmp_path_factory.mktemp("serve_spans")
    mp = pytest.MonkeyPatch()
    mp.setenv("HVD_TRACE", "1")
    mp.setenv("HVD_TRACE_DIR", str(tmp / "jsonl"))
    # ServingLoop.run() would setdefault this into the worker's environment
    # for every later test file; set here, it goes with mp.undo()
    mp.setenv("HVD_TPU_CORE", "py")
    for k in ("HVD_SIZE", "HVD_RANK", "HVD_RENDEZVOUS_ADDR"):
        mp.delenv(k, raising=False)
    cfg = tfm.TransformerConfig(max_seq_len=CACHE_LEN,
                                compute_dtype=jnp.float32, remat=False,
                                **MODEL)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    ready, box = threading.Event(), {}

    def on_ready(port):
        box["port"] = port
        ready.set()

    loop = ServingLoop(params, cfg, port=0, max_batch=2, max_queue=16,
                       cache_len=CACHE_LEN, host="127.0.0.1",
                       on_ready=on_ready)

    def serve():
        try:
            loop.run()
        except BaseException as e:   # surfaced by the assert below
            box["error"] = e
            ready.set()
            raise

    tmx.configure(True)
    thread = threading.Thread(target=serve, name="test-serve", daemon=True)
    thread.start()
    try:
        assert ready.wait(120) and "error" not in box, box.get("error")
        # Compile outside the trace: the session then holds spans, not
        # seconds of compiler events.
        assert _http(box["port"], "POST", "/generate",
                     {"prompt": [3, 14, 15], "max_new_tokens": 2})[0] == 200
        hists0 = tmx.snapshot()["histograms"]
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp / "profile"), profiler_options=opts)
        try:
            with ThreadPoolExecutor(N_REQUESTS) as pool:
                answers = list(pool.map(
                    lambda i: _http(box["port"], "POST", "/generate",
                                    {"prompt": [3 + i, 14, 15],
                                     "max_new_tokens": 3 + i % 3}),
                    range(N_REQUESTS)))
            # the last answer leaves from inside the turn's emit span:
            # let the loop thread close it before the session ends
            time.sleep(0.2)
        finally:
            jax.profiler.stop_trace()
        hists1 = tmx.snapshot()["histograms"]
        stats = _http(box["port"], "GET", "/stats")[1]
    finally:
        loop.stop()
        thread.join(60)
        hvd.shutdown()
        tmx.configure(False)
        trace.reset()
        mp.undo()
    assert not thread.is_alive() and "error" not in box
    assert [code for code, _ in answers] == [200] * N_REQUESTS

    from jax.profiler import ProfileData

    xplane = sorted(glob.glob(str(
        tmp / "profile" / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
    # (phase, host line, start_ns, end_ns); threads share the line *name*
    # "python", so a line is known by its place in the file
    spans = []
    for plane in ProfileData.from_file(xplane).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("hvd:"):
                    spans.append((e.name[4:], (plane.name, i), e.start_ns,
                                  e.start_ns + e.duration_ns))
    jsonl = []
    with open(tmp / "jsonl" / "trace_rank0.jsonl") as f:
        for text in f:
            rec = json.loads(text)
            if rec["k"] == "span":
                jsonl.append(rec)
    return {"spans": sorted(spans, key=lambda s: s[2]), "jsonl": jsonl,
            "hists": (hists0, hists1), "stats": stats}


def _count(hists, name):
    return hists.get(name, {}).get("count", 0)


@pytest.mark.timeout(300)
@pytest.mark.parametrize("phase", ("serve.apply",) + LOOP_LEAVES + HANDLER
                         + IN_DECODE)
def test_every_span_is_in_the_profiler_trace(served, phase):
    assert any(s[0] == phase for s in served["spans"]), \
        sorted({s[0] for s in served["spans"]})


def test_loop_leaves_are_disjoint_and_on_one_thread(served):
    leaves = [s for s in served["spans"] if s[0] in LOOP_LEAVES]
    assert len({s[1] for s in leaves}) == 1, {s[1] for s in leaves}
    for before, after in zip(leaves, leaves[1:]):
        assert before[3] <= after[2], (before, after)


def test_turn_leaves_lie_inside_serve_apply(served):
    applies = [s for s in served["spans"] if s[0] == "serve.apply"]
    inner = [s for s in served["spans"] if s[0] in INSIDE_APPLY]
    assert inner
    for s in inner:
        assert any(a[1] == s[1] and a[2] <= s[2] and s[3] <= a[3]
                   for a in applies), s
    # and the frame span, the leader's own, lies outside every turn
    for s in (s for s in served["spans"] if s[0] == "serve.frame"):
        assert not any(a[2] < s[3] and s[2] < a[3] for a in applies), s


def _by_start(records, phases):
    """The stream's records of ``phases``, a span before what it holds."""
    return sorted((r for r in records if r["ph"] in phases),
                  key=lambda r: (r["t0"], -r["t1"]))


def _inside(outer, records):
    return [r for r in records if r is not outer
            and outer["t0"] <= r["t0"] and r["t1"] <= outer["t1"]]


def test_the_order_of_leaves_in_both_kinds_of_turn(served):
    """A turn without admissions is decode (the next step's dispatch, then
    the unread vector's readback), confirm, emit.  A turn with admissions
    settles the unread vector the same way FIRST (a decode that holds a
    read and no dispatch), then prefills on an empty chip, then
    dispatches the step the next turn's ``serve.decode`` will read: that
    dispatch lies outside any decode and has its span all the same."""
    spans = _by_start(served["jsonl"],
                      INSIDE_APPLY + IN_DECODE + ("serve.apply",))
    ahead = ["serve.decode", "serve.dispatch", "serve.read",
             "serve.confirm", "serve.emit"]
    settle = ["serve.decode", "serve.read", "serve.confirm", "serve.emit"]
    seen = Counter()
    for turn in (r for r in spans if r["ph"] == "serve.apply"):
        inner = [r["ph"] for r in _inside(turn, spans)]
        admitted = ["serve.prefill"] * turn["admitted"] + ["serve.dispatch"]
        if not turn["admitted"]:
            # the last turn of a burst reads and queues nothing
            assert inner in (ahead, settle), (turn, inner)
            seen["ran ahead" if inner == ahead else "settled", False] += 1
        elif inner[:4] == settle:
            assert inner[4:] == admitted, (turn, inner)
            seen["settled", True] += 1
        else:   # nothing was unread: the first turn after silence
            assert inner == admitted, (turn, inner)
            seen["nothing unread", True] += 1
    assert all(seen[k] for k in (("ran ahead", False), ("settled", True),
                                 ("nothing unread", True))), seen


def test_dispatch_and_read_lie_inside_their_decode(served):
    """In both sinks: every read is in a ``serve.decode``, which holds
    one read and at most one dispatch before it; the only dispatches
    outside a decode are those that follow a turn's prefills."""
    for decodes, children in (
            ([s[1:] for s in served["spans"] if s[0] == "serve.decode"],
             [(s[0],) + s[1:] for s in served["spans"] if s[0] in IN_DECODE]),
            ([(0, r["t0"], r["t1"]) for r in served["jsonl"]
              if r["ph"] == "serve.decode"],
             [(r["ph"], 0, r["t0"], r["t1"]) for r in served["jsonl"]
              if r["ph"] in IN_DECODE])):
        assert decodes and children
        outside = Counter()
        for phase, line, t0, t1 in children:
            held = [d for d in decodes
                    if d[0] == line and d[1] <= t0 and t1 <= d[2]]
            assert len(held) <= 1
            outside[phase] += not held
        assert outside["serve.read"] == 0
        for line, d0, d1 in decodes:
            inner = [c[0] for c in children
                     if c[1] == line and d0 <= c[2] and c[3] <= d1]
            assert inner in (["serve.read"],
                             ["serve.dispatch", "serve.read"]), inner
    # the stream holds every turn since the engine was built: one
    # dispatch outside a decode for every turn that admitted
    admitting = sum(1 for r in served["jsonl"]
                    if r["ph"] == "serve.apply" and r["admitted"])
    assert outside["serve.dispatch"] == admitting > 0


def test_step_pairs_a_dispatch_with_its_read_and_states_the_run_ahead(
        served):
    """``step`` is the ordinal of the engine step: the dispatches count
    0, 1, 2 ... and so do the reads (every step is read once, in order,
    and none is unread when the loop sleeps).  On a turn that ran ahead
    the step read is the one BEFORE the step just queued."""
    dispatches = _by_start(served["jsonl"], ("serve.dispatch",))
    reads = _by_start(served["jsonl"], ("serve.read",))
    assert [r["step"] for r in dispatches] == list(range(len(dispatches)))
    assert [r["step"] for r in reads] == list(range(len(reads)))
    assert len(reads) == len(dispatches) > 0
    by_step = {r["step"]: r for r in reads}
    ran_ahead = 0
    for decode in _by_start(served["jsonl"], ("serve.decode",)):
        inner = _inside(decode, dispatches + reads)
        if len(inner) == 2:
            queued, read = sorted(inner, key=lambda r: r["t0"])
            assert (queued["ph"], read["ph"]) == ("serve.dispatch",
                                                  "serve.read")
            assert read["step"] == queued["step"] - 1
            ran_ahead += 1
    assert ran_ahead > 0
    # a step is read after it was queued, never before
    for d in dispatches:
        assert by_step[d["step"]]["t0"] >= d["t1"]


@pytest.mark.parametrize("phase", ("serve.prefill", "serve.queued",
                                   "serve.active"))
def test_one_span_a_request(served, phase):
    assert sum(s[0] == phase for s in served["spans"]) == N_REQUESTS
    if phase != "serve.prefill":
        # handler spans are on the door's threads, not the loop's
        loop_line = next(s[1] for s in served["spans"]
                         if s[0] == "serve.apply")
        assert all(s[1] != loop_line for s in served["spans"]
                   if s[0] == phase)


def test_one_emit_span_a_turn_not_a_slot(served):
    n = Counter(s[0] for s in served["spans"])
    assert n["serve.emit"] == n["serve.decode"] == n["serve.confirm"]
    assert n["serve.emit"] <= n["serve.apply"]


def test_jsonl_stream_holds_the_same_phases(served):
    """One call site, two sinks: the spans that ended inside the profiler
    session are in the JSONL stream too, which also holds the warm-up's."""
    traced = Counter(s[0] for s in served["spans"])
    written = Counter(r["ph"] for r in served["jsonl"])
    assert set(written) == set(traced)
    for phase, n in traced.items():
        assert written[phase] >= n, (phase, written, traced)
    assert written["serve.prefill"] == N_REQUESTS + 1    # + the warm-up's
    rec = next(r for r in served["jsonl"] if r["ph"] == "serve.prefill")
    assert rec["t1"] > rec["t0"] and rec["prompt_len"] == 3
    assert {"step", "admitted"} <= set(next(
        r for r in served["jsonl"] if r["ph"] == "serve.apply"))


@pytest.mark.parametrize("hist", ("hvd_serve_queue_wait_seconds",
                                  "hvd_serve_prefill_seconds",
                                  "hvd_serve_ttft_seconds"))
def test_ttft_histograms_count_one_a_request(served, hist):
    before, after = served["hists"]
    assert _count(after, hist) - _count(before, hist) == N_REQUESTS


def test_read_wait_histogram_takes_one_observation_a_read(served):
    """``hvd_serve_read_wait_seconds`` is fed by the ``serve.read`` spans:
    as many observations as reads in the session, and in all as many as
    the stream holds, of the same total length."""
    before, after = served["hists"]
    hist = "hvd_serve_read_wait_seconds"
    in_session = sum(s[0] == "serve.read" for s in served["spans"])
    assert _count(after, hist) - _count(before, hist) == in_session > 0
    reads = [r for r in served["jsonl"] if r["ph"] == "serve.read"]
    assert _count(after, hist) == len(reads)
    assert after[hist]["sum"] == pytest.approx(
        sum(r["t1"] - r["t0"] for r in reads) * 1e-9)


def test_stats_gives_the_share_of_the_turns_the_loop_waited_for_the_chip(
        served):
    _, after = served["hists"]
    waited = after["hvd_serve_read_wait_seconds"]["sum"]
    turns = after["hvd_serve_token_latency_seconds"]["sum"]
    assert served["stats"]["chip_wait_share"] == round(waited / turns, 4)
    # the reads lie inside the turns, beside their confirms and prefills
    assert 0 < served["stats"]["chip_wait_share"] < 1
    assert "ahead_share" in served["stats"]


# ---------------------------------------------------------------------------
# off means off
# ---------------------------------------------------------------------------


class _CountingTime:
    """time-module proxy (tests/test_trace.py's): counts the clock reads
    of code that resolves ``time`` through the patched module global."""

    def __init__(self):
        self.calls = 0

    def __getattr__(self, name):
        return getattr(time, name)

    def monotonic_ns(self):
        self.calls += 1
        return time.monotonic_ns()


def test_span_with_no_sink_reads_no_clock_and_writes_nothing(
        tmp_path, monkeypatch):
    monkeypatch.delenv("HVD_TRACE", raising=False)
    monkeypatch.chdir(tmp_path)
    assert trace.get() is None and not tmx.enabled()
    ct = _CountingTime()
    monkeypatch.setattr(trace, "time", ct)
    with trace.span("serve.decode", slots=3) as sp:
        with trace.span("serve.dispatch", step=7) as queued:
            pass
        with trace.span("serve.read", step=6,
                        histogram="hvd_serve_read_wait_seconds") as read:
            pass
    with trace.span("serve.prefill", histogram="hvd_serve_prefill_seconds",
                    slot=0, prompt_len=8):
        pass
    assert ct.calls == 0 and sp.t0 == queued.t0 == read.t0 == 0
    assert os.listdir(tmp_path) == []
    # with a tracer the same call site reads the clock twice and records
    tr = trace.Tracer(0, str(tmp_path / "t.jsonl"))
    monkeypatch.setattr(trace, "_TR", tr)
    with trace.span("serve.decode", slots=3) as sp:
        pass
    monkeypatch.setattr(trace, "_TR", None)
    tr.close()
    assert ct.calls == 2 and sp.t0 > 0
    recs = [json.loads(x) for x in open(tmp_path / "t.jsonl")]
    assert [(r["ph"], r["slots"]) for r in recs if r["k"] == "span"] == \
        [("serve.decode", 3)]


def test_span_records_and_propagates_when_the_body_raises(tmp_path,
                                                          monkeypatch):
    tr = trace.Tracer(0, str(tmp_path / "t.jsonl"))
    monkeypatch.setattr(trace, "_TR", tr)
    with pytest.raises(KeyError):
        with trace.span("serve.confirm", step=1):
            raise KeyError("diverged")
    monkeypatch.setattr(trace, "_TR", None)
    tr.close()
    assert '"ph":"serve.confirm"' in open(tmp_path / "t.jsonl").read()


# ---------------------------------------------------------------------------
# the two-phase wait: queued -> admitted -> done
# ---------------------------------------------------------------------------


def _park(port, body, out):
    out.append(_http(port, "POST", "/generate", body))


def _wait_queued(s, n, seconds=5.0):
    deadline = time.monotonic() + seconds
    while s.stats()["queued"] < n and time.monotonic() < deadline:
        time.sleep(0.005)
    assert s.stats()["queued"] >= n


@pytest.mark.timeout(60)
@pytest.mark.parametrize("how", ["fail_all", "shed", "deadline_queued",
                                 "deadline_admitted"])
def test_a_handler_waiting_for_a_slot_is_released(how):
    timeout_s = 0.6 if how.startswith("deadline") else 30.0
    s = Scheduler(max_batch=1, max_queue=1, cache_len=16)
    door = FrontDoor(s, host="127.0.0.1", port=0, timeout_s=timeout_s)
    port = door.start()
    out = []
    t0 = time.monotonic()
    t = threading.Thread(target=_park, daemon=True, args=(
        port, {"prompt": [1], "max_new_tokens": 2}, out))
    t.start()
    try:
        _wait_queued(s, 1)
        if how == "fail_all":
            s.fail_all("gang gone")
            t.join(10)
            assert out and out[0][0] == 500
            assert out[0][1]["error"] == "gang gone"
        elif how == "shed":
            # the queue is full: the second request is answered at once,
            # its handler never waits for a slot
            code, body = _http(port, "POST", "/generate",
                               {"prompt": [2], "max_new_tokens": 2})
            assert code == 503 and "queue full" in body["error"]
            assert time.monotonic() - t0 < 10.0
            s.fail_all("test over")
            t.join(10)
        elif how == "deadline_queued":
            t.join(10)
            assert out and out[0][0] == 504
            assert time.monotonic() - t0 < 5.0
        else:
            # admitted half-way and never finished: the 504 comes at the
            # one deadline, not a second full wait after the admission
            time.sleep(0.3)
            assert len(s.take_admissions()) == 1
            t.join(10)
            took = time.monotonic() - t0
            assert out and out[0][0] == 504
            assert took < 0.3 + 0.6, took
        assert not t.is_alive()
    finally:
        s.fail_all("test over")
        door.stop()


def test_admitted_is_set_once_and_survives_a_replay():
    tmx.configure(True)
    try:
        s = Scheduler(max_batch=1, max_queue=4, cache_len=16)
        req = s.submit([1, 2], 4)
        assert not req.admitted.is_set()
        s.take_admissions()
        assert req.admitted.is_set()
        assert s.requeue_inflight() == 1
        assert req.admitted.is_set()       # the handler's first wait is over
        s.take_admissions()
        assert req.attempts == 2
        # the wait for a slot is observed at the first admission only
        h = tmx.snapshot()["histograms"]["hvd_serve_queue_wait_seconds"]
        assert h["count"] == 1
    finally:
        tmx.configure(False)
