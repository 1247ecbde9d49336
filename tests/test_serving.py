"""horovod_tpu.serving: continuous-batching inference on the gang.

Layered like the subsystem (docs/serving.md):

* wire codec — the TAG_SERVE batch-delta frame roundtrips.
* scheduler units — FIFO packing into slots, bounded-queue shedding,
  TTFT bookkeeping, at-least-once replay ordering, fail_all hygiene.
* front door units — /health, /stats, typed shedding (400/503) and the
  ``serve.admit`` chaos hook, all against a scheduler with no gang.
* hvdrun plumbing — ``--serve-*`` parse-time validation (exit 2) and
  the ``HVD_SERVE_*`` env mapping + accessor defaults.
* registry — serving metrics and chaos sites are declared.
* single-process — ``examples/serve_lm.py --selftest`` serves real
  requests in one process; every completion must be bit-identical to
  the single-request ``generate`` oracle (same cfg, same cache length).
* the acceptance gangs — a 2-rank gang serving concurrent HTTP
  requests through continuous batching (oracle-exact outputs); a
  chaos-stalled rank evicted by the collective deadline with the
  re-formed gang replaying every in-flight request to completion; and
  a chaos-delayed rank earning a STRAGGLER timeline record while the
  gang still answers within a bounded p99.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from horovod_tpu.common import fault_injection as fi
from horovod_tpu.runner.http_server import RendezvousServer
from horovod_tpu.serving.scheduler import QueueFull, Scheduler
from horovod_tpu.serving.server import FrontDoor
from horovod_tpu.utils import env as env_util

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "serve_worker.py")

TIMEOUT_S = 2.0  # HVD_COLLECTIVE_TIMEOUT for the eviction gang

# The tiny deterministic model every serving scenario shares with
# serve_worker.py / the oracle (seed 0, float32: identical params on
# every rank and in the driving test, no broadcast needed).
CACHE_LEN = 64
MODEL = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    fi.clear()
    yield
    fi.clear()


# ---------------------------------------------------------------------------
# wire: the TAG_SERVE batch-delta frame
# ---------------------------------------------------------------------------


def test_serve_delta_roundtrip():
    from horovod_tpu.common import wire

    adm = [(0, "r12", 16, [3, 14, 15]), (3, "r13", 1, [62])]
    blob = wire.encode_serve_delta(7, False, adm, epoch=2)
    assert wire.decode_serve_delta(blob) == (7, False, adm, 2)


def test_serve_delta_stop_and_empty():
    from horovod_tpu.common import wire

    blob = wire.encode_serve_delta(1, True, [], epoch=0)
    seq, stop, adm, epoch = wire.decode_serve_delta(blob)
    assert (seq, stop, adm, epoch) == (1, True, [], 0)
    # An idle-step frame (no admissions, not stopping) is legal too —
    # rank 0 sends one whenever slots are active with nothing to admit.
    blob = wire.encode_serve_delta(9, False, [], epoch=4)
    assert wire.decode_serve_delta(blob) == (9, False, [], 4)


# ---------------------------------------------------------------------------
# scheduler: admission, packing, replay
# ---------------------------------------------------------------------------


def test_scheduler_validates_shapes():
    s = Scheduler(max_batch=2, max_queue=4, cache_len=16)
    with pytest.raises(ValueError, match="non-empty"):
        s.submit([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        s.submit([1], 0)
    with pytest.raises(ValueError, match="cache length"):
        s.submit([1, 2, 3], 14)  # 3 + 14 > 16


def test_scheduler_sheds_at_queue_bound():
    s = Scheduler(max_batch=1, max_queue=2, cache_len=16)
    s.submit([1], 2)
    s.submit([2], 2)
    with pytest.raises(QueueFull):
        s.submit([3], 2)


def test_scheduler_fifo_packing_and_refill():
    s = Scheduler(max_batch=2, max_queue=8, cache_len=32)
    r1 = s.submit([1], 4)
    r2 = s.submit([2], 4)
    r3 = s.submit([3], 4)
    adm = s.take_admissions()
    assert [(slot, r.id) for slot, r in adm] == \
        [(0, r1.id), (1, r2.id)]
    assert r1.attempts == 1 and r3.attempts == 0
    assert s.take_admissions() == []  # batch full, r3 waits
    st = s.stats()
    assert {k: st[k] for k in ("queued", "active", "slots",
                               "completed")} == \
        {"queued": 1, "active": 2, "slots": 2, "completed": 0}
    assert st["last_step_age_s"] == 0.0      # no step confirmed yet
    assert st["oldest_queued_age_s"] < 5.0   # r3 queued just now
    # Retiring slot 0 opens it for the queued request at the next
    # token boundary — continuous batching, not batch-at-a-time.
    s.on_token(0, 5)
    s.complete(0)
    assert r1.done.is_set() and r1.tokens == [5]
    adm = s.take_admissions()
    assert [(slot, r.id) for slot, r in adm] == [(0, r3.id)]
    assert s.stats()["completed"] == 1


def test_scheduler_staleness_ages():
    """The /stats staleness surface: last_step_age_s tracks the loop's
    note_step() stamps, oldest_queued_age_s the head-of-line wait — the
    two numbers an external router probes to tell a wedged gang from an
    idle one."""
    s = Scheduler(max_batch=1, max_queue=4, cache_len=16)
    st = s.stats()
    assert st["last_step_age_s"] == 0.0      # no step this incarnation
    assert st["oldest_queued_age_s"] == 0.0  # empty queue
    s.note_step(time.monotonic() - 5.0)
    assert 4.5 < s.stats()["last_step_age_s"] < 60.0
    r = s.submit([1], 2)
    r.t_submit = time.monotonic() - 2.0      # backdate the head-of-line
    assert 1.5 < s.stats()["oldest_queued_age_s"] < 60.0
    # Both land in the metrics registry as gauges.
    from horovod_tpu.telemetry import registry as tmx
    snap = tmx.snapshot()
    if snap:                                  # metrics may be disabled
        assert "hvd_serve_last_step_age_seconds" in snap
        assert "hvd_serve_oldest_queued_age_seconds" in snap


def test_scheduler_ttft_and_token_tail():
    s = Scheduler(max_batch=1, max_queue=2, cache_len=16)
    r = s.submit([1, 2], 3)
    s.take_admissions()
    assert r.t_first_token is None
    s.on_token(0, 7)
    assert r.t_first_token is not None
    s.on_token(0, 8)
    assert r.tokens == [7, 8]  # generated tail only, never the prompt


def test_scheduler_requeue_inflight_replays_in_order():
    s = Scheduler(max_batch=2, max_queue=8, cache_len=32)
    r1 = s.submit([1], 8)
    r2 = s.submit([2], 8)
    r3 = s.submit([3], 8)
    s.take_admissions()
    s.on_token(0, 9)
    s.on_token(1, 9)
    assert s.requeue_inflight() == 2
    # Both actives go back to the FRONT (original submit order), token
    # tails cleared; the never-admitted r3 keeps its place behind them.
    assert r1.tokens == [] and r2.tokens == []
    adm = s.take_admissions()
    assert [r.id for _, r in adm] == [r1.id, r2.id]
    assert r1.attempts == 2  # replay admissions count
    assert r3.attempts == 0
    assert s.requeue_inflight() == 2  # idempotent across repeated forms
    assert [r.id for _, r in s.take_admissions()] == [r1.id, r2.id]
    assert s.has_work()


def test_scheduler_fail_all_wakes_everyone():
    s = Scheduler(max_batch=1, max_queue=4, cache_len=16)
    active = s.submit([1], 4)
    s.take_admissions()
    queued = s.submit([2], 4)
    s.fail_all("gang gone")
    for r in (active, queued):
        assert r.done.is_set() and r.error == "gang gone"
    assert not s.has_work()


# ---------------------------------------------------------------------------
# front door: typed shedding without a gang
# ---------------------------------------------------------------------------


def _http(port, method, path, body=None, timeout=10.0):
    import http.client

    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        c.request(method, path,
                  json.dumps(body) if body is not None else None)
        r = c.getresponse()
        return r.status, r.read()
    finally:
        c.close()


@pytest.mark.timeout(60)
def test_front_door_health_stats_and_shed():
    s = Scheduler(max_batch=2, max_queue=1, cache_len=16)
    door = FrontDoor(s, host="127.0.0.1", port=0, timeout_s=5.0)
    port = door.start()
    try:
        assert _http(port, "GET", "/health") == (200, b"ok")
        code, body = _http(port, "GET", "/stats")
        assert code == 200
        assert json.loads(body)["slots"] == 2
        assert _http(port, "GET", "/nope")[0] == 404
        # Malformed bodies are a 400, not a stuck handler.
        assert _http(port, "POST", "/generate", {"nope": 1})[0] == 400
        assert _http(port, "POST", "/generate",
                     {"prompt": [], "max_new_tokens": 4})[0] == 400
        # Full admission queue -> 503 (the back-off signal).  No loop is
        # draining, so the first request parks and the second sheds.
        t = threading.Thread(
            target=_http, args=(port, "POST", "/generate",
                                {"prompt": [1], "max_new_tokens": 2}),
            daemon=True)
        t.start()
        deadline = time.monotonic() + 5.0
        while s.stats()["queued"] < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        code, body = _http(port, "POST", "/generate",
                           {"prompt": [2], "max_new_tokens": 2})
        assert code == 503, body
        s.fail_all("test over")
        t.join(timeout=10)
    finally:
        door.stop()


@pytest.mark.timeout(60)
def test_front_door_chaos_admission_shed():
    s = Scheduler(max_batch=1, max_queue=4, cache_len=16)
    door = FrontDoor(s, host="127.0.0.1", port=0, timeout_s=5.0)
    port = door.start()
    try:
        fi.configure({"faults": [
            {"site": "serve.admit", "kind": "error", "times": 1}]})
        assert _http(port, "GET", "/health")[0] == 503
        assert _http(port, "GET", "/health")[0] == 200  # budget spent
    finally:
        door.stop()


def test_front_door_completion_payload():
    s = Scheduler(max_batch=1, max_queue=4, cache_len=16)
    door = FrontDoor(s, host="127.0.0.1", port=0, timeout_s=10.0)
    port = door.start()
    try:
        def drain():
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                adm = s.take_admissions()
                for slot, req in adm:
                    for tok in (4, 5, 6):
                        s.on_token(slot, tok)
                    s.complete(slot)
                    return
                time.sleep(0.01)

        threading.Thread(target=drain, daemon=True).start()
        code, body = _http(port, "POST", "/generate",
                           {"prompt": [1, 2], "max_new_tokens": 3})
        assert code == 200
        out = json.loads(body)
        assert out["tokens"] == [4, 5, 6]
        assert out["attempts"] == 1
        assert out["ttft_ms"] is not None and out["latency_ms"] >= 0
    finally:
        door.stop()


# ---------------------------------------------------------------------------
# hvdrun plumbing + registry declarations
# ---------------------------------------------------------------------------


def test_cli_serve_knob_validation(capsys):
    from horovod_tpu.runner import run as run_mod

    for argv, flag in (
            (["--serve-port", "0"], "--serve-port"),
            (["--serve-port", "70000"], "--serve-port"),
            (["--serve-max-batch", "0"], "--serve-max-batch"),
            (["--serve-max-queue", "-2"], "--serve-max-queue")):
        rc = run_mod.run_commandline(
            ["-np", "1"] + argv + ["python", "-c", "pass"])
        assert rc == 2, argv
        err = capsys.readouterr().err
        assert flag in err, err


def test_cli_serve_env_mapping():
    from horovod_tpu.runner import config_parser
    from horovod_tpu.runner.run import make_parser

    assert config_parser._ARG_ENV["serve_port"] == env_util.SERVE_PORT
    assert config_parser._ARG_ENV["serve_max_batch"] == \
        env_util.SERVE_MAX_BATCH
    assert config_parser._ARG_ENV["serve_max_queue"] == \
        env_util.SERVE_MAX_QUEUE
    args = make_parser().parse_args(
        ["-np", "2", "--serve-port", "8100", "--serve-max-batch", "4",
         "--serve-max-queue", "32", "python", "x.py"])
    env = config_parser.env_from_args(args)
    assert env["HVD_SERVE_PORT"] == "8100"
    assert env["HVD_SERVE_MAX_BATCH"] == "4"
    assert env["HVD_SERVE_MAX_QUEUE"] == "32"


def test_serve_env_accessor_defaults(monkeypatch):
    for var in (env_util.SERVE_PORT, env_util.SERVE_MAX_BATCH,
                env_util.SERVE_MAX_QUEUE):
        monkeypatch.delenv(var, raising=False)
    assert env_util.serve_port() == 0       # ephemeral
    assert env_util.serve_max_batch() == 8
    assert env_util.serve_max_queue() == 64
    monkeypatch.setenv(env_util.SERVE_MAX_BATCH, "3")
    assert env_util.serve_max_batch() == 3


def test_serving_metrics_and_sites_registered():
    from horovod_tpu.telemetry.registry import KNOWN_METRICS

    for name in ("hvd_serve_requests_total", "hvd_serve_queue_depth",
                 "hvd_serve_batch_occupancy", "hvd_serve_ttft_seconds",
                 "hvd_serve_token_latency_seconds"):
        assert name in KNOWN_METRICS, name
    assert "serve.admit" in fi.KNOWN_SITES
    assert "serve.step" in fi.KNOWN_SITES


# ---------------------------------------------------------------------------
# oracles: single-request generate over the same tiny model
# ---------------------------------------------------------------------------


def _oracle_tokens(prompt, max_new):
    """What ``generate`` answers for one request, decoded alone with the
    serving cache length — the bit-exactness bar for every serving
    completion of the same prompt."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        max_seq_len=CACHE_LEN, compute_dtype=jnp.float32, remat=False,
        **MODEL)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    out = tfm.generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                       max_new_tokens=max_new, cache_len=CACHE_LEN)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


def _requests(n):
    """The scenario's request mix: distinct prompts AND distinct lengths
    so retirements stagger and admissions join mid-flight."""
    return [([3 + i, 14, 15], 6 + 2 * (i % 3)) for i in range(n)]


# ---------------------------------------------------------------------------
# single process: the example IS the smoke test
# ---------------------------------------------------------------------------


def _selftest_example():
    """``examples/serve_lm.py --selftest 3``: three concurrent requests
    over HTTP through a ServingLoop in a process of their own."""
    env = dict(os.environ)
    env.pop(fi.ENV_VAR, None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "serve_lm.py"),
         "--selftest", "3", "--vocab-size", str(MODEL["vocab_size"]),
         "--d-model", str(MODEL["d_model"]),
         "--n-layers", str(MODEL["n_layers"]),
         "--n-heads", str(MODEL["n_heads"]),
         "--d-ff", str(MODEL["d_ff"]), "--cache-len", str(CACHE_LEN),
         "--port", "0"],
        capture_output=True, text=True, timeout=200, env=env, cwd=REPO)
    assert res.returncode == 0, (res.stdout, res.stderr)
    got = {int(m.group(1)): json.loads(m.group(2))
           for m in re.finditer(r"request (\d+): (\[[^\]]*\])",
                                res.stdout)}
    assert sorted(got) == [0, 1, 2], res.stdout
    return [([3 + i, 14, 15], got[i]) for i in range(3)]


def _slot_reuse():
    """A slot retired and re-admitted with a SHORTER prompt, then decoded
    past the old request's length: the install writes the new lane in
    place over the old request's, and nothing of the old one (nor of the
    idle slot's steps between) may reach the new request's tokens.  A
    neighbour decodes in the other slot throughout."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.serving.decode import DecodeEngine

    cfg = tfm.TransformerConfig(
        max_seq_len=CACHE_LEN, compute_dtype=jnp.float32, remat=False,
        **MODEL)
    engine = DecodeEngine(tfm.init(jax.random.PRNGKey(0), cfg), cfg,
                          max_batch=2, cache_len=CACHE_LEN)

    neighbour = [9, 8, 7, 6]
    beside = [engine.prefill(1, neighbour)]

    def serve(prompt, max_new):
        tokens = [engine.prefill(0, prompt)]
        for _ in range(max_new - 1):
            step = engine.step()
            tokens.append(int(step[0]))
            beside.append(int(step[1]))
        engine.clear(0)
        return prompt, tokens

    long_prompt = [1 + (5 * i) % 60 for i in range(20)]
    return [serve(long_prompt, 6),      # rows 0..24 of slot 0's lane
            serve([3, 14, 15], 40),     # rows 0..41
            (neighbour, beside)]


@pytest.mark.timeout(240)
@pytest.mark.parametrize("scenario", [_selftest_example, _slot_reuse],
                         ids=["selftest_example", "slot_reuse"])
def test_single_process_selftest_matches_generate(scenario):
    """Every completion is bit-identical to ``generate`` decoding that
    prompt alone at the serving cache length."""
    for prompt, tokens in scenario():
        assert tokens == _oracle_tokens(prompt, len(tokens)), prompt


# ---------------------------------------------------------------------------
# the order of a turn: a step is dispatched before the last one is read
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def varied():
    """The tiny model with its matrices scaled up, so that greedy decode
    wanders over the vocabulary (the seed-0 model repeats one token) and
    an ``eos_id`` can fall in the middle of an answer.  Returns (params,
    cfg, oracle)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        max_seq_len=CACHE_LEN, compute_dtype=jnp.float32, remat=False,
        **MODEL)
    params = jax.tree.map(lambda a: a * 4.0 if a.ndim >= 2 else a,
                          tfm.init(jax.random.PRNGKey(1), cfg))

    def oracle(prompt, max_new, eos_id=None):
        out = tfm.generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                           max_new_tokens=max_new, cache_len=CACHE_LEN)
        tokens = [int(t) for t in np.asarray(out)[0, len(prompt):]]
        if eos_id in tokens:
            tokens = tokens[:tokens.index(eos_id) + 1]
        return tokens

    return params, cfg, oracle


A, B, NEIGHBOUR = [3, 14, 15], [20, 30, 40, 1, 2], [9, 8, 7, 6]
# what the loop serves -> (waves of (prompt, max_new), slots, eos of A's
# answer at this index or None, stop at the first token)
ORDER_SCENARIOS = {
    # A meets its EOS while NEIGHBOUR decodes on; B, queued, is the
    # slot's next tenant.
    "eos_beside_a_neighbour": (
        [[(A, 12), (NEIGHBOUR, 20), (B, 8)]], 2, 5, False),
    # A alone meets its EOS: the step that ran ahead is nobody's.
    "eos_alone_then_next_tenant": ([[(A, 12)], [(B, 8)]], 1, 5, False),
    # A retires by count; B takes its slot in the very next frame.
    "retired_by_count_readmitted_next_frame": (
        [[(A, 4), (NEIGHBOUR, 16), (B, 6)]], 2, None, False),
    "lone_silence_lone": ([[(A, 7)], [(B, 5)]], 2, None, False),
    "stop_with_requests_in_flight": (
        [[(A, 9), (NEIGHBOUR, 12), (B, 6)]], 2, None, True),
}


@pytest.mark.timeout(240)
@pytest.mark.parametrize("scenario", sorted(ORDER_SCENARIOS))
def test_loop_runs_a_step_ahead_and_serves_generates_tokens(
        scenario, varied, monkeypatch):
    """Through ``ServingLoop`` on one rank, with a spy on the engine's two
    halves (tests/serve_order.py): every request gets ``generate()``'s
    tokens, cut at the EOS; on a turn without admissions step k is
    dispatched before vector k-1 is read, on a turn with admissions the
    read precedes the prefill, confirm precedes emit, nothing is unread
    while the loop sleeps or stops, no step is dispatched for an empty
    table, and ``hvd_serve_steps_ahead_total`` is what the spy counted."""
    import serve_order

    params, cfg, oracle = varied
    waves, slots, eos_at, stop = ORDER_SCENARIOS[scenario]
    eos_id = None
    if eos_at is not None:
        answer = oracle(A, 12)
        eos_id = answer[eos_at]
        assert eos_id not in answer[:eos_at]    # it ends A in the middle
    served = serve_order.serve(
        monkeypatch, params, cfg, waves, max_batch=slots,
        cache_len=CACHE_LEN, eos_id=eos_id, stop_at_first_token=stop)
    asked = [r for wave in waves for r in wave]
    for (prompt, max_new), tokens in zip(asked, served.tokens):
        assert tokens == oracle(prompt, max_new, eos_id), prompt
    ahead = serve_order.check_order(served)
    turns = serve_order.turns(served.events)
    kinds = [[e[0] for e in t] for t in turns]
    # each step but a drained table's last ran ahead of its turn's read
    assert 0 < ahead < served.turns
    assert served.stats["ahead_share"] < 1
    if scenario == "eos_alone_then_next_tenant":
        # the dropped step: dispatched ahead, read with nobody to emit to
        assert any(k[-4:] == ["read", "confirm", "emit", "read"]
                   for k in kinds), kinds
    elif scenario == "retired_by_count_readmitted_next_frame":
        # B's frame follows the turn that retired A, a step in flight
        b = next(i for i, t in enumerate(turns) if i and t[0][1] == 1)
        assert turns[b][0][3] == 1 and kinds[b - 1][:3] == \
            ["frame", "dispatch", "read"], (kinds[b - 1], kinds[b])
        assert kinds[b][1:3] == ["read", "confirm"]
    elif scenario == "lone_silence_lone":
        # no step but those whose tokens were emitted; asleep in between
        steps = sum(k.count("dispatch") for k in kinds)
        assert steps == sum(n - 1 for _, n in asked)
        first_b = next(i for i, e in enumerate(served.events)
                       if e[0] == "frame" and e[1] == 1 and i)
        assert any(e[0] == "sleep" for e in served.events[:first_b])
    elif scenario == "stop_with_requests_in_flight":
        assert turns[-1][0][2] is True      # the stop frame, last
        assert [len(t) for t in served.tokens] == [9, 12, 6]


# ---------------------------------------------------------------------------
# the acceptance gangs
# ---------------------------------------------------------------------------


def _gang_env(rank, np_, port, *, min_np=None):
    env = dict(os.environ)
    env.pop(fi.ENV_VAR, None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update({
        "HVD_RANK": str(rank),
        "HVD_SIZE": str(np_),
        "HVD_LOCAL_RANK": str(rank),
        "HVD_LOCAL_SIZE": str(np_),
        "HVD_CROSS_RANK": "0",
        "HVD_CROSS_SIZE": "1",
        "HVD_RENDEZVOUS_ADDR": "127.0.0.1",
        "HVD_RENDEZVOUS_PORT": str(port),
        "JAX_PLATFORMS": "cpu",
        "HVD_TPU_CORE": "py",
        "HVD_ELASTIC_EPOCH": "0",
        "HVD_ELASTIC_MIN_NP": str(min_np or np_),
        "HVD_ELASTIC_MAX_NP": str(np_),
        "HVD_ELASTIC_UID": f"uid-{rank}",
        "HVD_ELASTIC_CHECK_INTERVAL_S": "0.05",
        "SERVE_CACHE_LEN": str(CACHE_LEN),
        "SERVE_MAX_BATCH": "2",
        "SERVE_MAX_QUEUE": "16",
    })
    return env


def _read_port(port_file, procs, deadline_s=150.0):
    """Wait for rank 0's front door to come up (the first serve request
    also pays the jax import + compile on a busy box)."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if os.path.exists(port_file):
            return int(open(port_file).read())
        for p in procs:
            if p.poll() is not None:
                out, err = p.communicate()
                raise AssertionError(
                    f"worker died before serving: "
                    f"{out.decode()}\n{err.decode()}")
        time.sleep(0.05)
    raise AssertionError("front door never came up")


def _post_all(port, reqs, results, timeout_s=150.0):
    """Concurrent closed-loop clients: one thread per request, each
    blocking on its own /generate until completion."""
    def client(i, prompt, max_new):
        try:
            results[i] = _http(port, "POST", "/generate",
                               {"prompt": prompt,
                                "max_new_tokens": max_new},
                               timeout=timeout_s)
        except Exception as e:  # surfaced by the caller's assert
            results[i] = e

    threads = [threading.Thread(target=client, args=(i, p, m),
                                daemon=True)
               for i, (p, m) in enumerate(reqs)]
    for t in threads:
        t.start()
    return threads


@pytest.mark.timeout(420)
def test_gang_serves_concurrent_requests_oracle_exact(tmp_path):
    """Two ranks serve six concurrent HTTP requests through two decode
    slots — continuous batching is forced (requests queue, join at
    token boundaries as earlier ones retire at staggered lengths) and
    every completion must be bit-identical to the single-request
    ``generate`` oracle: a slot's decode never depends on its
    neighbors."""
    np_ = 2
    reqs = _requests(6)
    port_file = str(tmp_path / "serve_port")
    server = RendezvousServer("127.0.0.1")
    rport = server.start()
    procs = []
    results = {}
    try:
        for rank in range(np_):
            env = _gang_env(rank, np_, rport)
            if rank == 0:
                env["SERVE_PORT_FILE"] = port_file
                env["SERVE_EXPECT"] = str(len(reqs))
            procs.append(subprocess.Popen(
                [sys.executable, WORKER], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE))
        port = _read_port(port_file, procs)
        threads = _post_all(port, reqs, results)
        for t in threads:
            t.join(timeout=240)
        outs = {}
        for rank, p in enumerate(procs):
            out, err = p.communicate(timeout=120)
            outs[rank] = (p.returncode, out.decode(), err.decode())
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()

    for rank in range(np_):
        code, out, err = outs[rank]
        assert code == 0, (rank, out, err)
        assert "DONE" in out, (rank, out, err)
    for i, (prompt, max_new) in enumerate(reqs):
        assert not isinstance(results.get(i), Exception), results[i]
        code, body = results[i]
        assert code == 200, (i, body)
        got = json.loads(body)
        assert got["tokens"] == _oracle_tokens(prompt, max_new), i
        assert got["attempts"] == 1, got


@pytest.mark.timeout(420)
def test_gang_evicts_stalled_rank_and_replays(tmp_path):
    """Rank 1 arms a 600 s transport stall mid-serving, wedging itself
    inside a step's token-agreement allreduce.  The collective deadline
    must evict it (the victim never finishes on its own), the elastic
    wrapper re-forms rank 0 alone, and the in-flight requests replay
    from their prompts to the oracle-identical completion — clients see
    added latency and ``attempts > 1``, never an error."""
    np_, victim = 2, 1
    reqs = [([3, 14, 15], 24), ([4, 14, 15], 24), ([5, 14, 15], 24)]
    port_file = str(tmp_path / "serve_port")
    server = RendezvousServer("127.0.0.1")
    rport = server.start()
    procs = []
    results = {}
    try:
        for rank in range(np_):
            env = _gang_env(rank, np_, rport, min_np=1)
            env.update({
                "HVD_SHM_DISABLE": "1",  # pin the tcp ring: sock.stall
                "HVD_COLLECTIVE_TIMEOUT": str(TIMEOUT_S),
                "HVD_COLLECTIVE_PROBE_TIMEOUT": "0.5",
            })
            if rank == 0:
                env["SERVE_PORT_FILE"] = port_file
                env["SERVE_EXPECT"] = str(len(reqs))
            if rank == victim:
                env["SERVE_VICTIM"] = "1"
                env["SERVE_STALL_SEQ"] = "3"
            procs.append(subprocess.Popen(
                [sys.executable, WORKER], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE))
        port = _read_port(port_file, procs)
        threads = _post_all(port, reqs, results)
        for t in threads:
            t.join(timeout=240)
        out0, err0 = procs[0].communicate(timeout=120)
        assert procs[victim].poll() is None, \
            "the victim exited on its own — the stall never wedged it"
        procs[victim].kill()
        v_out, v_err = procs[victim].communicate(timeout=30)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()

    # The victim: wedged mid-step, never drained, never done.
    assert "DONE" not in v_out.decode(), v_out.decode()
    # The survivor: clean exit after an in-process re-form (epoch 1).
    assert procs[0].returncode == 0, (out0.decode(), err0.decode())
    assert "DONE" in out0.decode(), (out0.decode(), err0.decode())
    assert "GEN_FINAL" in out0.decode()
    final = int(re.search(r"GEN_FINAL (\d+)", out0.decode()).group(1))
    assert final >= 1, out0.decode()  # a re-form actually happened
    # Every request completed, oracle-exact; the two in flight at the
    # stall were replayed (at-least-once shows up as attempts > 1).
    replayed = 0
    for i, (prompt, max_new) in enumerate(reqs):
        assert not isinstance(results.get(i), Exception), results[i]
        code, body = results[i]
        assert code == 200, (i, body)
        got = json.loads(body)
        assert got["tokens"] == _oracle_tokens(prompt, max_new), i
        replayed += int(got["attempts"] > 1)
    assert replayed >= 1, results


@pytest.mark.timeout(420)
def test_gang_straggler_named_with_bounded_latency(tmp_path):
    """Rank 1 is chaos-delayed 150 ms inside every serving step
    (``serve.step``/delay).  The gang still completes — slower, but
    bounded — and the per-step negotiation skew earns rank 1 a
    STRAGGLER record on rank 0's timeline naming it."""
    np_, laggard = 2, 1
    reqs = [([3, 14, 15], 16), ([4, 14, 15], 16)]
    tl_path = tmp_path / "serve_timeline.json"
    port_file = str(tmp_path / "serve_port")
    server = RendezvousServer("127.0.0.1")
    rport = server.start()
    procs = []
    results = {}
    try:
        for rank in range(np_):
            env = _gang_env(rank, np_, rport)
            env["HVD_METRICS"] = "1"  # the detector rides the registry
            env["HVD_STRAGGLER_WARN_MS"] = "50"
            if rank == 0:
                env["SERVE_PORT_FILE"] = port_file
                env["SERVE_EXPECT"] = str(len(reqs))
                env["HVD_TIMELINE"] = str(tl_path)
            if rank == laggard:
                env[fi.ENV_VAR] = json.dumps({"faults": [
                    {"site": "serve.step", "kind": "delay",
                     "delay_s": 0.15}]})
            procs.append(subprocess.Popen(
                [sys.executable, WORKER], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE))
        port = _read_port(port_file, procs)
        t0 = time.monotonic()
        threads = _post_all(port, reqs, results)
        for t in threads:
            t.join(timeout=240)
        wall = time.monotonic() - t0
        outs = {}
        for rank, p in enumerate(procs):
            out, err = p.communicate(timeout=120)
            outs[rank] = (p.returncode, out.decode(), err.decode())
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()

    for rank in range(np_):
        code, out, err = outs[rank]
        assert code == 0, (rank, out, err)
    lats = []
    for i, (prompt, max_new) in enumerate(reqs):
        assert not isinstance(results.get(i), Exception), results[i]
        code, body = results[i]
        assert code == 200, (i, body)
        got = json.loads(body)
        assert got["tokens"] == _oracle_tokens(prompt, max_new), i
        lats.append(got["latency_ms"])
    # Bounded p99: ~17 steps x 150 ms injected delay plus compile and
    # scheduling slack on a 1-core CI box — generous but finite.
    assert max(lats) / 1e3 < wall + 1.0
    assert wall < 240.0, wall
    tl = tl_path.read_text()
    assert "STRAGGLER" in tl, tl[-2000:]
    rec = [json.loads(line.rstrip().rstrip(","))
           for line in tl.splitlines() if "STRAGGLER" in line]
    assert any((r.get("args") or {}).get("rank") == laggard
               for r in rec), rec
