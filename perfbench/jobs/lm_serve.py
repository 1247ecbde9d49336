"""The decoder LM behind the repo's ``FrontDoor`` + ``ServingLoop``, under
an open-loop traffic mix sent over HTTP from threads of this process."""

from __future__ import annotations

import json
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import compare, loadgen
from perfbench.jobs.lm_train import model_sizes, transformer_config
from perfbench.reference import lm as ref

STEP_HIST = "hvd_serve_token_latency_seconds"


class Server:
    """The system under test, stood up in this process the way
    ``chip_smoke.py`` does: ``ServingLoop.run()`` on a thread."""

    def __init__(self, run, weights, sizes: Dict, serve: Dict,
                 registry: bool):
        from horovod_tpu.serving import ServingLoop
        from horovod_tpu.telemetry import registry as tmx

        if registry:
            tmx.configure(True)
        self._tmx = tmx
        cfg = transformer_config(sizes, serve["cache_len"], "dense")
        self._ready = threading.Event()
        self._box: Dict = {}
        self.loop = ServingLoop(
            weights, cfg, port=0, max_batch=serve["max_batch"],
            max_queue=serve["max_queue"], cache_len=serve["cache_len"],
            host="127.0.0.1", request_timeout_s=serve["request_timeout_s"],
            on_ready=self._on_ready)
        self._thread = threading.Thread(target=self._serve,
                                        name="bench-serve", daemon=True)
        self._thread.start()
        if not self._ready.wait(600) or "error" in self._box:
            raise SystemExit(f"serving loop never came up: "
                             f"{self._box.get('error')}")
        self.port = self._box["port"]

    def _on_ready(self, port: int) -> None:
        self._box["port"] = port
        self._ready.set()

    def _serve(self) -> None:
        try:
            self.loop.run()
        except BaseException as e:
            self._box["error"] = e
            self._ready.set()
            raise

    def step_histogram(self) -> Optional[Dict]:
        if not self._tmx.enabled():
            return None
        h = self._tmx.snapshot().get("histograms", {}).get(STEP_HIST)
        return {"sum": h["sum"], "count": h["count"]} if h else \
            {"sum": 0.0, "count": 0}

    def stop(self) -> None:
        import horovod_tpu as hvd

        self.loop.stop()
        self._thread.join(300)
        alive = self._thread.is_alive()
        hvd.shutdown()
        self._tmx.configure(False)
        if alive:
            raise SystemExit("serving loop did not stop")
        if "error" in self._box:
            raise self._box["error"]


def warm_up(client: loadgen.Client, traffic: Dict, serve: Dict, rng,
            vocab: int) -> None:
    """Every prompt length of the mix's grid through every slot: one
    closed wave of ``max_batch`` short requests, then the rest."""
    grid = sorted(traffic["prompt_tokens"]["grid"])
    n = max(2 * serve["max_batch"], len(grid))   # also starts the threads
    reqs = [loadgen.Request(i, 0.0,
                            rng.integers(1, vocab, size=grid[i % len(grid)]
                                         ).tolist(),
                            traffic["warmup_new_tokens"], False)
            for i in range(n)]
    now = time.perf_counter()
    outs = [f.result() for f in client.offer(reqs, now)]
    bad = [o for o in outs if o.status != 200]
    if bad:
        raise SystemExit(f"{len(bad)} warm-up requests failed "
                         f"(status {bad[0].status})")


class Poller:
    """``GET /stats`` every ``period`` seconds: slots in flight, queue."""

    def __init__(self, client: loadgen.Client, period: float):
        self.samples: List[Dict] = []
        self._stop = threading.Event()
        self._client, self._period = client, period
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-poll")

    def _loop(self) -> None:
        while not self._stop.wait(self._period):
            try:
                s = self._client.get_stats()
            except OSError:
                continue
            s["t"] = time.perf_counter()
            self.samples.append(s)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(30)


def drive(run, client: loadgen.Client, server: Server, traffic: Dict,
          seconds: float, rng, vocab: int, *, trace: bool,
          poll: bool = False) -> Dict:
    """Pre-roll, window, drain.  Returns the outcomes and what the traced
    run's readers read."""
    import jax

    requests = loadgen.plan(traffic, seconds, rng, vocab)
    preroll = float(traffic["preroll_s"])
    t_window = time.perf_counter() + preroll + 0.05
    facts: Dict = {}
    poll = poll or trace
    poller = Poller(client, float(traffic["poll_period_s"])) if poll \
        else None
    marks: Dict = {}

    def at_window_start() -> None:
        wait = t_window - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        marks["t0"] = run.setup_done()
        marks["hist0"] = server.step_histogram()
        marks["stats0"] = client.get_stats() if poll else None
        if poller:
            poller.start()
        if trace:
            time.sleep(float(traffic["trace_offset_s"]))
            run.start_trace()
            with jax.profiler.TraceAnnotation("bench:trace_window"):
                time.sleep(min(float(traffic["trace_seconds"]),
                               max(seconds - 1.0, 0.1)))
            run.stop_trace()

    marker = threading.Thread(target=at_window_start, name="bench-mark")
    marker.start()
    futures = client.offer(requests, t_window)
    wait = t_window + seconds - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    marker.join()
    run.window_done()
    marks["hist1"] = server.step_histogram()
    if poller:
        marks["stats1"] = client.get_stats()
        poller.stop()
    outcomes = [f.result() for f in futures]      # the drain
    if trace:
        run.load_trace()
    if marks["hist0"] is not None:
        dc = marks["hist1"]["count"] - marks["hist0"]["count"]
        facts["server_step_ms_mean"] = (
            (marks["hist1"]["sum"] - marks["hist0"]["sum"]) * 1e3 / dc
            if dc else None)
    if poller:
        inside = [s for s in poller.samples
                  if marks["t0"] <= s["t"] <= marks["t0"] + seconds]
        facts["in_flight_mean"] = (
            sum(s["active"] for s in inside) / len(inside)
            if inside else None)
        facts["queued_start"] = marks["stats0"]["queued"]
        facts["queued_end"] = marks["stats1"]["queued"]
    return {"outcomes": outcomes, "facts": facts}


def logit_gaps(logits, toks):
    """Per position: how far the token's logit lies below the row's best,
    in units of the row's spread (standard deviation over the vocabulary)."""
    import jax.numpy as jnp

    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(logits, axis=-1)


def reference_gaps(weights, sizes: Dict, rows: List[Dict], pad_to: int,
                   *, control: bool = False) -> Dict:
    """For each row (``prompt``, ``tokens``: the served continuation) one
    float32 forward pass of the reference over prompt + tokens; the widest
    gap by which a served token's logit lies below the reference's best at
    its position, in units of the spread (standard deviation over the
    vocabulary) of the reference's logits there.  With ``control`` the same at the tokens an int8 forward
    pass puts first."""
    import jax
    import jax.numpy as jnp

    fwd = ref.Forward(sizes)
    fwd_q = ref.Forward(sizes, quant=True) if control else None

    gaps = jax.jit(logit_gaps)
    worst, worst_q, compared = 0.0, 0.0, 0
    for row in rows:
        p, n = len(row["prompt"]), len(row["tokens"])
        seq = np.zeros((1, pad_to), np.int32)
        seq[0, :p + n] = row["prompt"] + row["tokens"]
        logits = fwd.logits(weights, jnp.asarray(seq))[0]
        at = logits[p - 1:p - 1 + n]
        g = np.asarray(gaps(at, jnp.asarray(row["tokens"], jnp.int32)))
        worst = max(worst, float(g.max()))
        compared += n
        if control:
            lq = fwd_q.logits(weights, jnp.asarray(seq))[0]
            first = jnp.argmax(lq[p - 1:p - 1 + n], axis=-1)
            worst_q = max(worst_q, float(np.asarray(gaps(at, first)).max()))
    return {"widest_gap": worst, "control_widest_gap": worst_q,
            "tokens_compared": compared}


def sample_rows(outcomes: List[loadgen.Outcome], k: int, rng) -> List[Dict]:
    """``k`` finished window requests drawn from the seed, the longest
    (prompt + answer) among them."""
    done = [o for o in outcomes if o.request.sampled and o.status == 200
            and len(o.tokens) == o.request.max_new]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (
        len(done[i].request.prompt) + len(done[i].tokens), -i))
    pick = {longest}
    for i in rng.permutation(len(done)):
        if len(pick) >= min(k, len(done)):
            break
        pick.add(int(i))
    return [{"prompt": done[i].request.prompt, "tokens": done[i].tokens}
            for i in sorted(pick)]


def run(run, control: bool = False) -> None:
    import jax

    traffic = run.cell.traffic
    serve = run.cell.params("serve")
    if run.rehearsal:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
        serve = {**serve, **serve.get("rehearsal", {})}
    sizes = model_sizes(run, "serve")
    vocab = sizes["vocab_size"]
    dev = run.devices[0]
    key = run.rng_key(0)
    make = jax.jit(lambda k: ref.make_weights(k, sizes))
    with jax.default_device(dev):
        weights = make(key)
    server = Server(run, weights, sizes, serve, registry=run.trace)
    client = loadgen.Client(server.port, annotate=run.trace,
                            timeout_s=serve["request_timeout_s"] + 30)
    try:
        warm_up(client, traffic, serve, run.numpy_rng(2), vocab)
        run.settle()
        got = drive(run, client, server, traffic, run.seconds,
                    run.numpy_rng(1), vocab, trace=run.trace)
    finally:
        client.close()
        server.stop()
    summary = loadgen.summarize(got["outcomes"])
    run.attempted, run.failed = summary["attempted"], summary["failed"]
    if summary["per_token_ms"]:
        run.end_to_end["latency_per_token_p50"] = loadgen.percentile(
            summary["per_token_ms"], 50)
        run.end_to_end["latency_per_token_p90"] = loadgen.percentile(
            summary["per_token_ms"], 90)
    run.facts.update(got["facts"])
    print(f"generator: {summary['attempted']} requests due in the window, "
          f"sent late by p99 {loadgen.percentile(summary['late_ms'], 99):.3f}"
          f" ms, at most {max(summary['late_ms']):.3f} ms",
          file=sys.stderr, flush=True)
    run.facts["late_ms"] = summary["late_ms"]
    run.facts["ttft_ms"] = summary["ttft_ms"]
    run.facts["per_token_ms"] = summary["per_token_ms"]
    # The reference, once the server and its cache are freed.
    rows = sample_rows(got["outcomes"], serve["check_requests"],
                       run.numpy_rng(3))
    del server, got
    pad_to = max(traffic["prompt_tokens"]["grid"]) \
        + traffic["output_tokens"]["max"]
    with jax.default_device(dev):
        res = reference_gaps(weights, sizes, rows, pad_to, control=control)
    if control:
        print(json.dumps({"control_widest_gap": res["control_widest_gap"],
                          "sound_widest_gap": res["widest_gap"]}), flush=True)
    checks = compare.Checks()
    checks.add("served_token_logit_gap", res["widest_gap"] if rows
               else float("inf"), serve["limits"]["logit_gap"],
               f"{res['tokens_compared']} tokens of {len(rows)} requests")
    checks.add("requests_failed_or_short", run.failed, 0)
    run.checks = checks
