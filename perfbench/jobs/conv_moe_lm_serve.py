"""The short-convolution / attention / routed-expert LM with every expert
held (``models/conv_moe.py``) behind the repo's ``FrontDoor`` +
``ServingLoop``, under an open-loop traffic mix sent over HTTP from threads
of this process.  The server, the load and the window are ``lm_serve``'s,
by import; what differs is the model's configuration, the reference, and
the facts for this family's per-layer metrics."""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional

import numpy as np

from perfbench import compare, loadgen
from perfbench import conv_moe_lm_count as count
from perfbench.jobs import lm_serve
from perfbench.reference import conv_moe_lm as ref

# The published keys the program takes under their names.
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers", "layer_types",
             "num_dense_layers", "num_attention_heads",
             "num_key_value_heads", "conv_L_cache", "num_experts",
             "num_experts_per_tok", "routed_scaling_factor", "norm_eps",
             "rope_theta")
# The published keys the program has no other form of: checked, not passed.
FIXED = {"conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
         "tie_word_embeddings": True, "model_type": "lfm2_moe"}
SERIES = {
    "prefill_seconds": ("histograms", "hvd_serve_prefill_seconds", "sum"),
    "prefill_tokens": ("counters", "hvd_serve_prefill_tokens_total"),
    "rows_routed": ("counters", "hvd_moe_rows_routed_total"),
    "experts_touched": ("counters", "hvd_moe_experts_touched_total"),
    "max_expert_rows": ("counters", "hvd_moe_max_expert_rows_total"),
    "layer_turns": ("counters", "hvd_moe_layer_turns_total")}
STATE_BYTES = 'hvd_serve_state_bytes{kind="%s"}'


def model_sizes(run) -> Dict:
    """The program's configuration keys from the file's published ones."""
    cfg = dict(run.cell.config)
    for key, value in FIXED.items():
        if cfg[key] != value:
            raise SystemExit(f"{key}={cfg[key]!r}: the program has only "
                             f"{value!r}")
    if run.rehearsal:
        cfg.update(run.cell.params("serve")["rehearsal"].get("config", {}))
    sizes = {k: cfg[k] for k in PUBLISHED}
    sizes["layer_types"] = tuple(sizes["layer_types"])
    return sizes


class Server(lm_serve.Server):
    """``lm_serve.Server`` over a ``ConvMoEConfig``.  ``drive`` reads the
    step histogram at the window's two ends, so the program's other series
    (its prefills, its routing) are read there too."""

    def __init__(self, run, weights, sizes: Dict, serve: Dict,
                 registry: bool):
        import threading

        from horovod_tpu.models.conv_moe import ConvMoEConfig
        from horovod_tpu.serving import ServingLoop
        from horovod_tpu.telemetry import registry as tmx

        if registry:
            tmx.configure(True)
        self._tmx = tmx
        self.marks: List[Dict] = []
        cfg = ConvMoEConfig(max_seq_len=serve["cache_len"], **sizes)
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
        if not self._ready.wait(900) or "error" in self._box:
            raise SystemExit(f"serving loop never came up: "
                             f"{self._box.get('error')}")
        self.port = self._box["port"]

    def step_histogram(self) -> Optional[Dict]:
        if self._tmx.enabled():
            snap = self._tmx.snapshot()
            mark = {}
            for key, (group, name, *field) in SERIES.items():
                v = snap.get(group, {}).get(name)
                mark[key] = v[field[0]] if field and v is not None else v
            self.marks.append(mark)
        return super().step_histogram()

    def state_bytes(self) -> Dict[str, Optional[float]]:
        """What the engine says it holds, by kind (None: a program that
        does not say)."""
        gauges = self._tmx.snapshot().get("gauges", {})
        return {kind: gauges.get(STATE_BYTES % kind)
                for kind in ("kv", "recurrent")}


def family_facts(server: Server, sizes: Dict, serve: Dict,
                 sampled: List[loadgen.Request], slots_busy: Optional[float],
                 device) -> Dict:
    """What this family's per-layer metrics read, from the program's own
    counters where it has them: a metric whose series is missing is left
    out, not guessed."""
    facts: Dict = {
        # which of the trace's grouped products are a decode turn's: their
        # rows, and how many of them a turn makes
        "routed_product_rows": serve["max_batch"]
        * sizes["num_experts_per_tok"],
        "routed_product_calls_per_turn":
        3 * count.params(sizes)["moe_layers"]}
    for kind, held in server.state_bytes().items():
        if held is not None:
            facts["kv_cache_gb" if kind == "kv"
                  else "recurrent_state_gb"] = held / 1e9
    if len(server.marks) < 2:
        return facts
    first, last = server.marks[0], server.marks[-1]

    def grew(key) -> Optional[float]:
        if first.get(key) is None or last.get(key) is None:
            return None
        return last[key] - first[key]

    seconds, tokens = grew("prefill_seconds"), grew("prefill_tokens")
    prompts = [len(r.prompt) for r in sampled]
    if seconds and tokens:
        facts["prefill_ms_per_ktoken"] = seconds * 1e6 / tokens
        if device.platform == "tpu" and prompts:
            from perfbench.peaks import peak

            # The window's prefills are the mix's lengths in the mix's
            # proportions: the needed operations a prompt token, over the
            # requests due in the window, times the tokens prefilled.
            facts["prefill_mfu_pct"] = (
                100.0 * count.mean_prefill_flops_per_token(sizes, prompts)
                * tokens / (seconds * peak(device.device_kind).bf16_flops))
    routed, touched, fullest, turns = (
        grew("rows_routed"), grew("experts_touched"),
        grew("max_expert_rows"), grew("layer_turns"))
    if turns and touched:
        facts["moe_experts_touched_mean"] = touched / turns
        facts["moe_rows_per_expert_mean"] = routed / touched
        facts["moe_expert_load_max_over_mean"] = (
            (fullest / turns) / (routed / touched))
        facts["routed_product_bytes"] = count.routed_product_bytes(
            sizes, touched / turns)
        if slots_busy and sampled:
            # a decoding request's mean position, over the tokens the
            # window's requests decode: prompt + half the answer, weighted
            # by the answer's length
            answers = sum(r.max_new for r in sampled)
            position = sum(r.max_new * (len(r.prompt) + r.max_new / 2.0)
                           for r in sampled) / answers
            facts["decode_turn_bytes"] = count.decode_turn_bytes(
                sizes, touched / turns, slots_busy, position)
    return facts


def reference_gaps(weights, sizes: Dict, rows: List[Dict], pad_to: int,
                   max_new: int, *, control: bool = False) -> Dict:
    """``ssd_moe_lm_serve.reference_gaps`` with this family's reference:
    one float32 pass over prompt + served tokens a row, its head over the
    ``max_new`` rows from the prompt's last on; the gap by which a served
    token's logit lies below the reference's best at its position, in
    units of the spread of the reference's logits there: the widest, and
    the mean over all served tokens compared.  A row whose fourth and
    fifth router scores lie within bfloat16's rounding of the hidden state
    picks another expert in the program than in the reference and its
    logits move by a step, not by a rounding: the widest gap is such a
    step's; how OFTEN the served token is not the reference's best, and by
    how much, is what a lower precision moves, and the mean reads that.
    With ``control`` the same at the tokens an int8 pass puts first."""
    import jax
    import jax.numpy as jnp

    fwd = ref.Forward(sizes)
    fwd_q = ref.Forward(sizes, quant=True) if control else None
    gaps = jax.jit(lm_serve.logit_gaps)
    out = {"widest": 0.0, "total": 0.0, "control_widest": 0.0,
           "control_total": 0.0, "compared": 0}
    for row in rows:
        p, n = len(row["prompt"]), len(row["tokens"])
        seq = np.zeros((pad_to,), np.int32)
        seq[:p + n] = row["prompt"] + row["tokens"]
        at = fwd.logits(weights, jnp.asarray(seq), p - 1, max_new)[:n]
        g = np.asarray(gaps(at, jnp.asarray(row["tokens"], jnp.int32)))
        out["widest"] = max(out["widest"], float(g.max()))
        out["total"] += float(g.sum())
        out["compared"] += n
        if control:
            lq = fwd_q.logits(weights, jnp.asarray(seq), p - 1, max_new)[:n]
            gq = np.asarray(gaps(at, jnp.argmax(lq, axis=-1)))
            out["control_widest"] = max(out["control_widest"],
                                        float(gq.max()))
            out["control_total"] += float(gq.sum())
    return out


def stand_up(run, registry: bool = False):
    """Weights from the seed, the server on its thread (its registry on in
    a traced run, or when asked for), a client and one warm-up wave
    through every prompt length and slot.  Returns (traffic, serve,
    sizes, weights, server, client); the caller closes the client and
    stops the server."""
    import jax

    import horovod_tpu.models.conv_moe  # noqa: F401  a program without it: fail now

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
            run.facts.update(family_facts(
                server, sizes, serve,
                [o.request for o in got["outcomes"] if o.request.sampled],
                got["facts"].get("in_flight_mean"), run.devices[0]))
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
    max_new = traffic["output_tokens"]["max"]
    pad_to = max(traffic["prompt_tokens"]["grid"]) + max_new
    with jax.default_device(run.devices[0]):
        res = reference_gaps(weights, sizes, rows, pad_to, max_new,
                             control=control)
    compared = max(res["compared"], 1)
    # With ``control`` the int8 pass's tokens stand in the program's place:
    # the same checks against the same limits, which they have to fail.
    which, note = ("control_", "the int8 pass's tokens, ") if control \
        else ("", "")
    if control:
        print(json.dumps({"sound_widest_gap": res["widest"],
                          "sound_mean_gap": res["total"] / compared}),
              flush=True)
    checks = compare.Checks()
    checks.add("served_token_logit_gap", res[which + "widest"] if rows
               else float("inf"), serve["limits"]["logit_gap"],
               f"{note}{res['compared']} tokens of {len(rows)} requests")
    checks.add("served_token_logit_gap_mean",
               res[which + "total"] / compared if rows else float("inf"),
               serve["limits"]["logit_gap_mean"])
    checks.add("requests_failed_or_short", run.failed, 0)
    run.checks = checks
