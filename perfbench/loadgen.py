"""The one general traffic generator and open-loop HTTP client.

A traffic file (``perfbench/traffic/<name>.json``, ``kind: serve``) gives
the parameters; nothing here knows a mix by name.  Every run of a mix
offers *the same load*: the number of requests due in the window is
``round(rate_rps * seconds)``, and the multiset of (prompt, output) length
pairs is a function of that number and the file's ``pairing_seed`` alone.
The seed decides only the order of arrival, the arrival instants (sorted
uniform draws: a Poisson process conditioned on its count) and the token
ids.  A pre-roll of ``preroll_s`` seconds of the same process comes
before the window; it is served and never sampled.
"""

from __future__ import annotations

import http.client
import json
import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist
from typing import Dict, List, NamedTuple, Optional

import numpy as np


class Request(NamedTuple):
    index: int
    due_s: float            # relative to the window's start; < 0: pre-roll
    prompt: List[int]
    max_new: int
    sampled: bool           # due inside the window


def output_lengths(n: int, spec: Dict) -> List[int]:
    """The (i + 1/2) / n quantiles of a log-normal, clipped."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    nd = NormalDist()
    return [int(min(max(round(math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))),
                        spec["min"]), spec["max"])) for i in range(n)]


def prompt_lengths(n: int, spec: Dict) -> List[int]:
    """A log-normal snapped to ``grid`` (bins cut at the geometric
    midpoints) and dealt to exactly ``n`` by largest remainder."""
    grid = sorted(spec["grid"])
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    nd = NormalDist(mu, sigma)
    edges = [-math.inf] + [0.5 * (math.log(a) + math.log(b))
                           for a, b in zip(grid, grid[1:])] + [math.inf]
    cdf = [0.0 if e == -math.inf else 1.0 if e == math.inf else nd.cdf(e)
           for e in edges]
    share = [(cdf[i + 1] - cdf[i]) * n for i in range(len(grid))]
    counts = [int(math.floor(s)) for s in share]
    rest = sorted(range(len(grid)), key=lambda i: (counts[i] - share[i], i))
    for i in rest[:n - sum(counts)]:
        counts[i] += 1
    out: List[int] = []
    for g, c in zip(grid, counts):
        out.extend([g] * c)
    return out


def plan(traffic: Dict, seconds: float, rng: np.random.Generator,
         vocab: int) -> List[Request]:
    """All requests of a run, pre-roll first, sorted by due instant."""
    out: List[Request] = []
    for sampled, span in ((False, float(traffic["preroll_s"])),
                          (True, float(seconds))):
        n = int(round(traffic["rate_rps"] * span))
        if n == 0:
            continue
        outs = output_lengths(n, traffic["output_tokens"])
        prompts = prompt_lengths(n, traffic["prompt_tokens"])
        # The pairing of prompt with answer lengths is the file's, not the
        # seed's: every seed sends the same set of requests.
        pairing = np.random.default_rng(
            [int(traffic["pairing_seed"]), n]).permutation(n)
        prompts = [prompts[i] for i in pairing]
        order = rng.permutation(n)
        due = np.sort(rng.uniform(0.0, span, size=n))
        if not sampled:
            due = due - span
        for t, i in zip(due, order):
            ids = rng.integers(1, vocab, size=prompts[i]).tolist()
            out.append(Request(len(out), float(t), ids, outs[i], sampled))
    return out


class Outcome(NamedTuple):
    request: Request
    t_due: float            # host clock
    t_sent: float
    t_done: float
    status: int             # 0: transport error or time-out
    tokens: List[int]
    ttft_ms: Optional[float]


class Client:
    """Sends each request at its due instant from a pool of threads, one
    kept-alive connection a thread; a request is timed from the instant it
    was due."""

    def __init__(self, port: int, *, max_threads: int = 256,
                 timeout_s: float = 300.0, annotate: bool = False):
        self.port = port
        self.timeout_s = timeout_s
        self.annotate = annotate
        self._pool = ThreadPoolExecutor(max_workers=max_threads,
                                        thread_name_prefix="bench-client")
        self._local = threading.local()

    def _post(self, body: bytes):
        conn = getattr(self._local, "conn", None)
        for attempt in (0, 1):
            if conn is None:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout_s)
                self._local.conn = conn
            try:
                conn.request("POST", "/generate", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                return resp.status, resp.read()
            except (http.client.HTTPException, OSError):
                conn.close()
                conn = self._local.conn = None
                if attempt:
                    return 0, b""
        return 0, b""

    def _send(self, req: Request, t_due: float) -> Outcome:
        body = json.dumps({"prompt": req.prompt,
                           "max_new_tokens": req.max_new}).encode()
        t_sent = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation("bench:request"):
                status, raw = self._post(body)
        else:
            status, raw = self._post(body)
        t_done = time.perf_counter()
        tokens, ttft = [], None
        if status == 200:
            try:
                reply = json.loads(raw)
                tokens = [int(t) for t in reply["tokens"]]
                ttft = reply.get("ttft_ms")
            except (ValueError, KeyError, TypeError):
                status = 0
        return Outcome(req, t_due, t_sent, t_done, status, tokens, ttft)

    def get_stats(self) -> Dict:
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=10)
        try:
            conn.request("GET", "/stats")
            return json.loads(conn.getresponse().read())
        finally:
            conn.close()

    def offer(self, requests: List[Request], t_window: float) -> List:
        """Blocks until the last request has been sent; returns futures."""
        futures = []
        for req in requests:
            t_due = t_window + req.due_s
            delay = t_due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            futures.append(self._pool.submit(self._send, req, t_due))
        return futures

    def close(self) -> None:
        self._pool.shutdown(wait=True)


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile, ``q`` in (0, 100]."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))]


def summarize(outcomes: List[Outcome]) -> Dict:
    """Latency per token (response complete - instant due, over the tokens
    returned) and lateness, over the sampled requests."""
    sampled = [o for o in outcomes if o.request.sampled]
    ok = [o for o in sampled
          if o.status == 200 and len(o.tokens) == o.request.max_new]
    per_token = [(o.t_done - o.t_due) * 1e3 / len(o.tokens) for o in ok]
    late = [(o.t_sent - o.t_due) * 1e3 for o in sampled]
    ttft = [o.ttft_ms for o in ok if o.ttft_ms is not None]
    return {"attempted": len(sampled), "failed": len(sampled) - len(ok),
            "per_token_ms": per_token, "late_ms": late, "ttft_ms": ttft,
            "tokens": sum(len(o.tokens) for o in ok)}
