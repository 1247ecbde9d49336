"""The routed-expert, latent-attention LM (``models/latent_moe.py``)
behind the repo's ``FrontDoor`` + ``ServingLoop``, under an open-loop
traffic mix sent over HTTP from threads of this process.  The server, the
load and the window are ``lm_serve``'s, by import; what differs is the
model's configuration, the reference, the weights' type and the facts for
this family's per-layer metrics."""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional

import numpy as np

from perfbench import compare, loadgen, moe_lm_count
from perfbench.jobs import lm_serve
from perfbench.reference import moe_lm as ref

PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers",
             "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "n_routed_experts", "n_shared_experts",
             "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps",
             "rope_theta")
# The published keys the program has no other form of: checked, not passed.
FIXED = {"topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
         "norm_topk_prob": True, "partial_rotary_factor": 1,
         "rope_scaling": None, "tie_word_embeddings": False,
         "attention_bias": False, "hidden_act": "silu"}
SERIES = {"prefill_seconds": ("histograms", "hvd_serve_prefill_seconds"),
          "prefill_tokens": ("counters", "hvd_serve_prefill_tokens_total"),
          "rows_routed": ("counters", "hvd_moe_rows_routed_total"),
          "experts_touched": ("counters", "hvd_moe_experts_touched_total"),
          "max_expert_rows": ("counters", "hvd_moe_max_expert_rows_total"),
          "layer_turns": ("counters", "hvd_moe_layer_turns_total")}
STATE_BYTES = 'hvd_serve_state_bytes{kind="kv"}'


def model_sizes(run) -> Dict:
    """The configuration's published keys, under their published names."""
    cfg = dict(run.cell.config)
    for key, value in FIXED.items():
        if cfg[key] != value:
            raise SystemExit(f"{key}={cfg[key]!r}: the program has only "
                             f"{value!r}")
    if cfg["num_attention_heads"] != cfg["num_key_value_heads"]:
        raise SystemExit("latent attention has a key a query head")
    if run.rehearsal:
        cfg.update(run.cell.params("serve")["rehearsal"].get("config", {}))
    return {k: cfg[k] for k in PUBLISHED}


class Server(lm_serve.Server):
    """``lm_serve.Server`` over a ``LatentMoEConfig``.  ``drive`` reads
    the step histogram at the window's two ends, so the program's other
    series (its prefills, its routing) are read there too."""

    def __init__(self, run, weights, sizes: Dict, serve: Dict,
                 registry: bool):
        import threading

        from horovod_tpu.models.latent_moe import LatentMoEConfig
        from horovod_tpu.serving import ServingLoop
        from horovod_tpu.telemetry import registry as tmx

        if registry:
            tmx.configure(True)
        self._tmx = tmx
        self.marks: List[Dict] = []
        cfg = LatentMoEConfig(max_seq_len=serve["cache_len"], **sizes)
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
            for key, (group, name) in SERIES.items():
                v = snap.get(group, {}).get(name)
                mark[key] = v["sum"] if isinstance(v, dict) else v
            self.marks.append(mark)
        return super().step_histogram()

    def kv_bytes(self) -> Optional[float]:
        """What the engine says its position-indexed state holds (None: a
        program that does not say)."""
        return self._tmx.snapshot().get("gauges", {}).get(STATE_BYTES)


def family_facts(server: Server, sizes: Dict, sampled_prompts: List[int],
                 device) -> Dict:
    """What this family's per-layer metrics read, from the program's own
    counters where it has them: a metric whose series is missing is left
    out, not guessed."""
    facts: Dict = {}
    held = server.kv_bytes()
    if held is not None:
        facts["kv_cache_gb"] = held / 1e9
    if len(server.marks) < 2:
        return facts
    first, last = server.marks[0], server.marks[-1]

    def grew(key) -> Optional[float]:
        if first.get(key) is None or last.get(key) is None:
            return None
        return last[key] - first[key]

    seconds, tokens = grew("prefill_seconds"), grew("prefill_tokens")
    if seconds and tokens:
        facts["prefill_ms_per_ktoken"] = seconds * 1e6 / tokens
        if device.platform == "tpu" and sampled_prompts:
            from perfbench.peaks import peak

            # The window's prefills are the mix's lengths in the mix's
            # proportions: the needed operations a prompt token, over the
            # requests due in the window, times the tokens prefilled.
            per_token = sum(moe_lm_count.moe_lm_prefill_flops(sizes, n)
                            for n in sampled_prompts) / sum(sampled_prompts)
            facts["prefill_mfu_pct"] = 100.0 * per_token * tokens / (
                seconds * peak(device.device_kind).bf16_flops)
    routed, touched, fullest, turns = (
        grew("rows_routed"), grew("experts_touched"),
        grew("max_expert_rows"), grew("layer_turns"))
    if turns and touched:
        facts["moe_experts_touched_mean"] = touched / turns
        facts["moe_expert_load_max_over_mean"] = (
            (fullest / turns) / (routed / touched))
        facts["decode_turn_bytes"] = moe_lm_count.moe_lm_decode_turn_bytes(
            sizes, touched / turns)
    return facts


def reference_gaps(weights, sizes: Dict, rows: List[Dict], pad_to: int,
                   max_new: int, *, control: bool = False) -> Dict:
    """``lm_serve.reference_gaps`` with this family's reference: one
    float32 pass over prompt + served tokens a row, its head over the
    ``max_new`` rows from the prompt's last on; the gap by which a served
    token's logit lies below the reference's best at its position, in
    units of the spread of the reference's logits there: the widest, and
    the mean over all served tokens compared.  A row whose fourth and
    fifth router scores lie within bfloat16's rounding of the hidden state
    picks another expert in the program than in the reference and its
    logits move by a step, not by a rounding: the widest gap is such a
    step's and reads alike in every precision; how OFTEN the served token
    is not the reference's best, and by how much, is what a lower
    precision moves, and the mean reads that.  With ``control`` the same
    at the tokens an int8 pass puts first."""
    import jax
    import jax.numpy as jnp

    fwd = ref.Forward(sizes)
    fwd_q = ref.Forward(sizes, quant=True) if control else None
    gaps = jax.jit(lm_serve.logit_gaps)
    worst, worst_q, total, total_q, compared = 0.0, 0.0, 0.0, 0.0, 0
    for row in rows:
        p, n = len(row["prompt"]), len(row["tokens"])
        seq = np.zeros((pad_to,), np.int32)
        seq[:p + n] = row["prompt"] + row["tokens"]
        at = fwd.logits(weights, jnp.asarray(seq), p - 1, max_new)[:n]
        g = np.asarray(gaps(at, jnp.asarray(row["tokens"], jnp.int32)))
        worst = max(worst, float(g.max()))
        total += float(g.sum())
        compared += n
        if control:
            lq = fwd_q.logits(weights, jnp.asarray(seq), p - 1, max_new)[:n]
            gq = np.asarray(gaps(at, jnp.argmax(lq, axis=-1)))
            worst_q = max(worst_q, float(gq.max()))
            total_q += float(gq.sum())
    return {"widest_gap": worst, "control_widest_gap": worst_q,
            "mean_gap": total / max(compared, 1),
            "control_mean_gap": total_q / max(compared, 1),
            "tokens_compared": compared}


def stand_up(run, registry: bool = False):
    """Weights from the seed, the server on its thread (its registry on in
    a traced run, or when asked for), a client and one warm-up wave
    through every prompt length and slot.  Returns (traffic, serve,
    sizes, weights, server, client); the caller closes the client and
    stops the server."""
    import jax

    import horovod_tpu.models.latent_moe  # noqa: F401  a program without it: fail now

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
                server, sizes, [len(o.request.prompt)
                                for o in got["outcomes"]
                                if o.request.sampled], run.devices[0]))
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
    if control:
        print(json.dumps({"control_widest_gap": res["control_widest_gap"],
                          "sound_widest_gap": res["widest_gap"],
                          "control_mean_gap": res["control_mean_gap"],
                          "sound_mean_gap": res["mean_gap"]}), flush=True)
    checks = compare.Checks()
    checks.add("served_token_logit_gap", res["widest_gap"] if rows
               else float("inf"), serve["limits"]["logit_gap"],
               f"{res['tokens_compared']} tokens of {len(rows)} requests")
    checks.add("served_token_logit_gap_mean", res["mean_gap"] if rows
               else float("inf"), serve["limits"]["logit_gap_mean"])
    checks.add("requests_failed_or_short", run.failed, 0)
    run.checks = checks
