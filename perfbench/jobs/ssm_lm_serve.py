"""The hybrid state-space LM (``models/jamba.py``) behind the repo's
``FrontDoor`` + ``ServingLoop``, under an open-loop traffic mix sent over
HTTP from threads of this process.  The server, the load and the window
are ``lm_serve``'s, by import; what differs is the model's configuration,
the reference, the weights' type and three facts for this family's
per-layer metrics."""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional

import numpy as np

from perfbench import bytes_count, compare, loadgen
from perfbench.jobs import lm_serve
from perfbench.reference import ssm_lm as ref

PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "attn_layer_period", "attn_layer_offset",
             "mamba_d_state", "mamba_d_conv", "mamba_expand",
             "mamba_dt_rank", "rms_norm_eps")
PREFILL_HIST = "hvd_serve_prefill_seconds"
PREFILL_TOKENS = "hvd_serve_prefill_tokens_total"
STATE_BYTES = 'hvd_serve_state_bytes{kind="%s"}'


def model_sizes(run) -> Dict:
    """The configuration's published keys, under their published names."""
    cfg = dict(run.cell.config)
    if run.rehearsal:
        cfg.update(run.cell.params("serve")["rehearsal"].get("config", {}))
    return {k: cfg[k] for k in PUBLISHED}


class Server(lm_serve.Server):
    """``lm_serve.Server`` over a ``JambaConfig``.  The program counts its
    prefills in the registry; ``drive`` reads the step histogram at the
    window's two ends, so the prefill series are read there too."""

    def __init__(self, run, weights, sizes: Dict, serve: Dict,
                 registry: bool):
        import threading

        from horovod_tpu.models.jamba import JambaConfig
        from horovod_tpu.serving import ServingLoop
        from horovod_tpu.telemetry import registry as tmx

        if registry:
            tmx.configure(True)
        self._tmx = tmx
        self.prefill_marks: List[Dict] = []
        cfg = JambaConfig(max_seq_len=serve["cache_len"], **sizes)
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

    def step_histogram(self) -> Optional[Dict]:
        if self._tmx.enabled():
            snap = self._tmx.snapshot()
            self.prefill_marks.append({
                "seconds": snap["histograms"].get(
                    PREFILL_HIST, {"sum": 0.0})["sum"],
                "tokens": snap["counters"].get(PREFILL_TOKENS, 0.0)})
        return super().step_histogram()

    def state_bytes(self) -> Dict[str, Optional[float]]:
        """What the engine says it holds, by kind (None: a program that
        does not say)."""
        gauges = self._tmx.snapshot().get("gauges", {})
        return {kind: gauges.get(STATE_BYTES % kind)
                for kind in ("kv", "recurrent")}


def family_facts(server: Server, sizes: Dict, serve: Dict) -> Dict:
    """What this family's per-layer metrics read, from the program's own
    counters where it has them: a metric whose series is missing is left
    out, not guessed."""
    facts: Dict = {}
    held = server.state_bytes()
    if held["kv"] is not None:
        facts["kv_cache_gb"] = held["kv"] / 1e9
    if held["recurrent"] is not None:
        facts["recurrent_state_gb"] = held["recurrent"] / 1e9
    marks = server.prefill_marks
    if len(marks) >= 2 and marks[-1]["tokens"] > marks[0]["tokens"]:
        facts["prefill_ms_per_ktoken"] = (
            (marks[-1]["seconds"] - marks[0]["seconds"]) * 1e6
            / (marks[-1]["tokens"] - marks[0]["tokens"]))
    facts["decode_turn_bytes"] = bytes_count.ssm_lm_decode_turn_bytes(
        sizes, serve["max_batch"])
    return facts


def reference_gaps(weights, sizes: Dict, rows: List[Dict], pad_to: int,
                   *, control: bool = False) -> Dict:
    """``lm_serve.reference_gaps`` with this family's reference: one
    float32 pass over prompt + served tokens a row; the widest gap by
    which a served token's logit lies below the reference's best at its
    position, in units of the spread of the reference's logits there.
    With ``control`` the same at the tokens an int8 pass puts first."""
    import jax
    import jax.numpy as jnp

    fwd = ref.Forward(sizes)
    fwd_q = ref.Forward(sizes, quant=True) if control else None
    gaps = jax.jit(lm_serve.logit_gaps)
    worst, worst_q, compared = 0.0, 0.0, 0
    for row in rows:
        p, n = len(row["prompt"]), len(row["tokens"])
        seq = np.zeros((pad_to,), np.int32)
        seq[:p + n] = row["prompt"] + row["tokens"]
        at = fwd.logits(weights, jnp.asarray(seq))[p - 1:p - 1 + n]
        g = np.asarray(gaps(at, jnp.asarray(row["tokens"], jnp.int32)))
        worst = max(worst, float(g.max()))
        compared += n
        if control:
            lq = fwd_q.logits(weights, jnp.asarray(seq))
            first = jnp.argmax(lq[p - 1:p - 1 + n], axis=-1)
            worst_q = max(worst_q, float(np.asarray(gaps(at, first)).max()))
    return {"widest_gap": worst, "control_widest_gap": worst_q,
            "tokens_compared": compared}


def stand_up(run, registry: bool = False):
    """Weights from the seed, the server on its thread (its registry on in
    a traced run, or when asked for), a client and one warm-up wave
    through every prompt length and slot.  Returns (traffic, serve,
    sizes, weights, server, client); the caller closes the client and
    stops the server."""
    import jax

    import horovod_tpu.models.jamba  # noqa: F401  a program without it: fail now

    traffic = run.cell.traffic
    serve = run.cell.params("serve")
    if run.rehearsal:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
        serve = {**serve, **serve.get("rehearsal", {})}
    sizes = model_sizes(run)
    with jax.default_device(run.devices[0]):
        weights = ref.make_weights(run.rng_key(0), sizes)
    server = Server(run, weights, sizes, serve,
                    registry=registry or run.trace)
    client = loadgen.Client(server.port, annotate=run.trace,
                            timeout_s=serve["request_timeout_s"] + 30)
    try:
        lm_serve.warm_up(client, traffic, serve, run.numpy_rng(2),
                         sizes["vocab_size"])
    except BaseException:
        client.close()
        server.stop()
        raise
    run.settle()
    return traffic, serve, sizes, weights, server, client


def run(run, control: bool = False) -> None:
    import jax

    traffic, serve, sizes, weights, server, client = stand_up(run)
    try:
        got = lm_serve.drive(run, client, server, traffic, run.seconds,
                             run.numpy_rng(1), sizes["vocab_size"],
                             trace=run.trace)
        if run.trace:
            run.facts.update(family_facts(server, sizes, serve))
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
    # The reference, once the server and its state are freed.
    rows = lm_serve.sample_rows(got["outcomes"], serve["check_requests"],
                                run.numpy_rng(3))
    del server, got
    pad_to = max(traffic["prompt_tokens"]["grid"]) \
        + traffic["output_tokens"]["max"]
    with jax.default_device(run.devices[0]):
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
