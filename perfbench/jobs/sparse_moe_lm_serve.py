"""The latent-attention LM with a learned indexer and ONE chip's share of
its routed experts (``models/latent_moe.py`` with ``index_topk`` and
``experts_held``) behind the repo's ``FrontDoor`` + ``ServingLoop``.  The
server, the open loop and the window are ``lm_serve``'s, by import; what
differs is the configuration, the reference, the facts for this family's
per-layer metrics, and the **resident sessions**: long requests that the
job admits one after another before the pre-roll (their 12k-token
prefills are set-up) and that decode all through the window beside the
sampled short requests.  A run whose residents are not all still
decoding at the window's end is not ``correct``: the cell measures short
requests in a table that long sessions share, and a later speed-up must
not empty that table silently."""

from __future__ import annotations

import json
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import compare, loadgen
from perfbench import sparse_moe_lm_count as count
from perfbench.jobs import lm_serve, moe_lm_serve
from perfbench.reference import sparse_moe_lm as ref

# The published keys the program takes under their names.
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers",
             "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "n_shared_experts", "num_experts_per_tok",
             "routed_scaling_factor", "rms_norm_eps", "rope_theta",
             "n_group", "topk_group", "index_n_heads", "index_head_dim",
             "index_topk", "rope_scaling")
# The published keys the program has no other form of: checked, not passed.
FIXED = {"topk_method": "noaux_tc", "scoring_func": "sigmoid",
         "norm_topk_prob": True, "tie_word_embeddings": False,
         "attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1}
SERIES = {**moe_lm_serve.SERIES,
          "rows_absent": ("counters", "hvd_moe_rows_absent_total"),
          "scored": ("counters", "hvd_serve_index_positions_scored_total"),
          "selected": ("counters", "hvd_serve_attn_positions_selected_total")}
INDEX_BYTES = 'hvd_serve_state_bytes{kind="index"}'
KERNEL = "sparse_attn"      # the Mosaic call of the step's attention


def model_sizes(run) -> Dict:
    """The program's configuration keys from the file's published ones:
    ``n_routed_experts`` in the file is what the chip HOLDS (the guide's
    cut), the router's width is under ``share``."""
    cfg = dict(run.cell.config)
    for key, value in FIXED.items():
        if cfg[key] != value:
            raise SystemExit(f"{key}={cfg[key]!r}: the program has only "
                             f"{value!r}")
    if cfg["num_attention_heads"] != cfg["num_key_value_heads"]:
        raise SystemExit("latent attention has a key a query head")
    if run.rehearsal:
        cfg.update(run.cell.params("serve")["rehearsal"].get("config", {}))
    sizes = {k: cfg[k] for k in PUBLISHED}
    sizes.update(experts_held=cfg["n_routed_experts"],
                 n_routed_experts=cfg["share"]["router_outputs"],
                 expert_first=cfg["share"]["expert_first"])
    return sizes


class Server(moe_lm_serve.Server):
    """``moe_lm_serve.Server`` (the same ``LatentMoEConfig`` from more
    keys), reading this family's series at the window's two ends."""

    def step_histogram(self) -> Optional[Dict]:
        if self._tmx.enabled():
            snap = self._tmx.snapshot()
            mark = {}
            for key, (group, name) in SERIES.items():
                v = snap.get(group, {}).get(name)
                mark[key] = v["sum"] if isinstance(v, dict) else v
            self.marks.append(mark)
        return lm_serve.Server.step_histogram(self)

    def index_bytes(self) -> Optional[float]:
        return self._tmx.snapshot().get("gauges", {}).get(INDEX_BYTES)


def family_facts(run, server: Server, sizes: Dict,
                 sampled_prompts: List[int]) -> Dict:
    """What this family's per-layer metrics read, from the program's own
    counters and the trace's own kernel calls where it has them: a metric
    whose series is missing is left out, not guessed."""
    from perfbench import trace as tr

    facts: Dict = {}
    for key, held in (("kv_cache_gb", server.kv_bytes()),
                      ("index_cache_gb", server.index_bytes())):
        if held is not None:
            facts[key] = held / 1e9
    L = sizes["num_hidden_layers"]
    t = run.facts.get("trace")
    if t is not None and t.ops:
        _, calls = tr.op_seconds(
            t, sorted(t.ops)[0],
            lambda n: tr.is_mosaic_call(n) and KERNEL in n.split("=", 1)[0],
            run.facts.get("trace_window"))
        if calls:
            facts["steps"] = calls / L
    if len(server.marks) < 2:
        return facts
    first, last = server.marks[0], server.marks[-1]

    def grew(key) -> Optional[float]:
        if first.get(key) is None or last.get(key) is None:
            return None
        return last[key] - first[key]

    device = run.devices[0]
    seconds, tokens = grew("prefill_seconds"), grew("prefill_tokens")
    if seconds and tokens:
        facts["prefill_ms_per_ktoken"] = seconds * 1e6 / tokens
        if device.platform == "tpu" and sampled_prompts:
            from perfbench.peaks import peak

            facts["prefill_mfu_pct"] = (
                100.0 * count.mean_prefill_flops_per_token(
                    sizes, sampled_prompts) * tokens
                / (seconds * peak(device.device_kind).bf16_flops))
    routed, touched, fullest, turns = (
        grew("rows_routed"), grew("experts_touched"),
        grew("max_expert_rows"), grew("layer_turns"))
    absent, scored, selected = (grew("rows_absent"), grew("scored"),
                                grew("selected"))
    if turns and touched:
        facts["moe_experts_touched_mean"] = touched / turns
        facts["moe_expert_load_max_over_mean"] = (
            (fullest / turns) / (routed / touched))
    if absent is not None and routed:
        facts["moe_rows_absent_share"] = absent / (absent + routed)
    if turns and touched and scored and selected:
        layer_steps = L * turns / (L - sizes["first_k_dense_replace"])
        facts["attn_selected_share_pct"] = 100.0 * selected / scored
        facts["decode_turn_bytes"] = count.decode_turn_bytes(
            sizes, touched / turns, scored / layer_steps,
            selected / layer_steps)
        # A step's selected reads, all layers: what ``sparse_attn`` needs.
        needed = count.selected_read(sizes, L * selected / layer_steps)
        facts["sparse_attn_bytes"] = needed["bytes"]
        facts["sparse_attn_ops"] = needed["ops"]
    return facts


def resident_requests(spec: Dict, rng, vocab: int) -> List[loadgen.Request]:
    """The resident sessions: ``count`` prompts of ``prompt_tokens`` ids
    from the seed, ``new_tokens`` to return each; never sampled."""
    return [loadgen.Request(
        -1 - i, 0.0, rng.integers(1, vocab, size=spec["prompt_tokens"]
                                  ).tolist(), spec["new_tokens"], False)
        for i in range(spec["count"])]


def admit_residents(client: loadgen.Client, requests: List[loadgen.Request],
                    traffic: Dict, rng, vocab: int) -> List:
    """Send the resident sessions, then one short request behind them, and
    wait for that one's answer: requests are admitted in the order they
    came and a frame's prefills run in that order, so when it returns
    every resident has been prefilled and decodes (``active`` alone says a
    slot was given, which is before its prefill).  Returns the residents'
    futures."""
    now = time.perf_counter()
    futures = client.offer(requests, now)
    marker = loadgen.Request(
        -1 - len(requests), 0.0, rng.integers(
            1, vocab, size=min(traffic["prompt_tokens"]["grid"])).tolist(),
        traffic["warmup_new_tokens"], False)
    behind = client.offer([marker], now)[0].result()
    if behind.status != 200 or any(f.done() for f in futures) \
            or client.get_stats()["active"] < len(requests):
        raise SystemExit("the resident sessions did not all reach "
                         "their slots")
    return futures


def tokens_by(outcome: loadgen.Outcome, instant: float) -> float:
    """About how many of a session's tokens it had returned by
    ``instant`` (host clock): its steady rate from its first token to
    its last."""
    first = outcome.t_sent + (outcome.ttft_ms or 0.0) * 1e-3
    return len(outcome.tokens) * min(
        max(instant - first, 0.0) / max(outcome.t_done - first, 1e-9), 1.0)


def reference_gaps(fwd, fwd_q, weights, rows: List[Dict], pad_to: int,
                   max_new: int) -> Dict:
    """``moe_lm_serve.reference_gaps`` with this family's reference: one
    float32 pass over prompt + served tokens a row, its head over the
    ``max_new`` rows from the prompt's last on; the widest and the mean
    gap of a served token's logit under the reference's best, in units of
    the row's spread; with ``fwd_q`` the same at the tokens an int8 pass
    puts first."""
    import jax
    import jax.numpy as jnp

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
        if fwd_q is not None:
            lq = fwd_q.logits(weights, jnp.asarray(seq), p - 1, max_new)[:n]
            gq = np.asarray(gaps(at, jnp.argmax(lq, axis=-1)))
            out["control_widest"] = max(out["control_widest"],
                                        float(gq.max()))
            out["control_total"] += float(gq.sum())
    return out


def stand_up(run, registry: bool = False):
    """Weights from the seed, the server on its thread (its registry on in
    a traced run, or when asked for), a client and the warm-up: every
    sampled prompt length through every slot, then one resident-shaped
    prompt.  Returns (traffic, serve, sizes, weights, server, client); the
    caller closes the client and stops the server."""
    import jax

    import horovod_tpu.ops.pallas_index_select  # noqa: F401  a program without it: fail now

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
        rng = run.numpy_rng(2)
        lm_serve.warm_up(client, traffic, serve, rng, sizes["vocab_size"])
        warm = resident_requests({**traffic["residents"], "count": 1,
                                  "new_tokens": traffic["warmup_new_tokens"]},
                                 rng, sizes["vocab_size"])
        if client.offer(warm, time.perf_counter())[0].result().status != 200:
            raise SystemExit("the resident-shaped warm-up request failed")
    except BaseException:
        client.close()
        server.stop()
        raise
    run.settle()
    return traffic, serve, sizes, weights, server, client


def run(run, control: bool = False) -> None:
    import jax

    traffic, serve, sizes, weights, server, client = stand_up(run)
    vocab = sizes["vocab_size"]
    try:
        rng = run.numpy_rng(4)
        residents = admit_residents(client, resident_requests(
            traffic["residents"], rng, vocab), traffic, rng, vocab)
        got = lm_serve.drive(run, client, server, traffic, run.seconds,
                             run.numpy_rng(1), vocab, trace=run.trace)
        if run.trace:
            run.facts.update(family_facts(
                run, server, sizes, [len(o.request.prompt)
                                     for o in got["outcomes"]
                                     if o.request.sampled]))
        sessions = [f.result() for f in residents]      # their drain
    finally:
        client.close()
        server.stop()
    window_end = run.t_start + run.end_to_end["setup_s"] + run.seconds
    decoding = [o for o in sessions if o.status == 200
                and len(o.tokens) == o.request.max_new
                and o.t_done > window_end]
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
          f" ms, at most {max(summary['late_ms']):.3f} ms; residents "
          f"decoding at the window's end: {len(decoding)} of "
          f"{len(sessions)}, the first to finish "
          f"{min(o.t_done for o in sessions) - window_end:.1f} s after it, "
          f"with {min(tokens_by(o, window_end) for o in sessions):.0f} to "
          f"{max(tokens_by(o, window_end) for o in sessions):.0f} of their "
          f"{sessions[0].request.max_new} tokens returned by then; "
          "rows routed to absent experts: "
          f"{run.facts.get('moe_rows_absent_share')}",
          file=sys.stderr, flush=True)
    run.facts["late_ms"] = summary["late_ms"]
    run.facts["ttft_ms"] = summary["ttft_ms"]
    run.facts["per_token_ms"] = summary["per_token_ms"]
    # The reference, once the server and its state are freed: sampled
    # requests, and resident sessions whose positions pass their prompt's.
    rng = run.numpy_rng(3)
    rows = lm_serve.sample_rows(got["outcomes"], serve["check_requests"], rng)
    long_rows = [{"prompt": decoding[i].request.prompt,
                  "tokens": decoding[i].tokens}
                 for i in sorted(rng.permutation(len(decoding))[
                     :serve["check_residents"]])]
    del server, got, sessions
    spec = traffic["residents"]
    block = min(ref.ROWS, spec["prompt_tokens"])
    groups = (
        (rows, max(traffic["prompt_tokens"]["grid"])
         + traffic["output_tokens"]["max"], traffic["output_tokens"]["max"]),
        (long_rows, -(-(spec["prompt_tokens"] + spec["new_tokens"]) // block)
         * block, spec["new_tokens"]))
    fwd = ref.Forward(sizes)
    fwd_q = ref.Forward(sizes, quant=True) if control else None
    with jax.default_device(run.devices[0]):
        res = [reference_gaps(fwd, fwd_q, weights, *group)
               for group in groups]
    compared = sum(r["compared"] for r in res)

    def over_all(key):      # the widest of the groups, or their mean
        if key.endswith("widest"):
            return max(r[key] for r in res)
        return sum(r[key] for r in res) / max(compared, 1)

    # With ``control`` the int8 pass's tokens stand in the program's place:
    # the same checks against the same limits, which they have to fail.
    which, note = ("control_", "the int8 pass's tokens, ") if control \
        else ("", "")
    if control:
        print(json.dumps({"sound_widest_gap": over_all("widest"),
                          "sound_mean_gap": over_all("total"),
                          "sound_resident_mean_gap":
                              res[1]["total"] / max(res[1]["compared"], 1)}),
              flush=True)
    ok = bool(rows) and len(long_rows) == serve["check_residents"]
    checks = compare.Checks()
    checks.add("served_token_logit_gap",
               over_all(which + "widest") if ok else float("inf"),
               serve["limits"]["logit_gap"],
               f"{note}{compared} tokens of {len(rows)} requests and "
               f"{len(long_rows)} resident sessions")
    checks.add("served_token_logit_gap_mean",
               over_all(which + "total") if ok else float("inf"),
               serve["limits"]["logit_gap_mean"])
    checks.add("resident_token_logit_gap_mean",
               res[1][which + "total"] / max(res[1]["compared"], 1) if ok
               else float("inf"), serve["limits"]["resident_gap_mean"],
               f"{res[1]['compared']} tokens at positions past "
               f"{spec['prompt_tokens']}, every one behind a selection")
    checks.add("residents_not_decoding_at_end",
               spec["count"] - len(decoding), 0,
               f"residents_decoding_at_end {len(decoding)}")
    checks.add("requests_failed_or_short", run.failed, 0)
    run.checks = checks
