"""The power-retention LM (``models/retention.py``) behind the repo's
``FrontDoor`` + ``ServingLoop``, under an open-loop traffic mix sent over
HTTP from threads of this process.  The server, the load and the window
are ``lm_serve``'s, by import; what differs is the model's configuration,
the reference, the weights' type and the facts for this family's
per-layer metrics."""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional

import numpy as np

from perfbench import compare, loadgen, retention_lm_count
from perfbench.jobs import lm_serve
from perfbench.reference import retention_lm as ref

PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta")
# The published keys the program has no other form of: checked, not passed.
FIXED = {"attention_bias": False, "hidden_act": "silu", "rope_scaling": None,
         "sliding_window": None, "use_sliding_window": False,
         "tie_word_embeddings": False}
SERIES = {"prefill_seconds": ("histograms", "hvd_serve_prefill_seconds"),
          "prefill_tokens": ("counters", "hvd_serve_prefill_tokens_total"),
          "rows_live": ("counters", "hvd_serve_state_rows_live_total"),
          "rows_held": ("counters", "hvd_serve_state_rows_held_total")}
STATE_BYTES = 'hvd_serve_state_bytes{kind="recurrent"}'
KERNEL = "retention_step"       # the Mosaic call of the step's state pass


def model_sizes(run) -> Dict:
    """The configuration's published keys, under their published names."""
    cfg = dict(run.cell.config)
    for key, value in FIXED.items():
        if cfg[key] != value:
            raise SystemExit(f"{key}={cfg[key]!r}: the program has only "
                             f"{value!r}")
    if run.rehearsal:
        cfg.update(run.cell.params("serve")["rehearsal"].get("config", {}))
    return {k: cfg[k] for k in PUBLISHED}


class Server(lm_serve.Server):
    """``lm_serve.Server`` over a ``RetentionConfig``.  ``drive`` reads
    the step histogram at the window's two ends, so the program's other
    series (its prefills, its state rows) are read there too."""

    def __init__(self, run, weights, sizes: Dict, serve: Dict,
                 registry: bool):
        import threading

        from horovod_tpu.models.retention import RetentionConfig
        from horovod_tpu.serving import ServingLoop
        from horovod_tpu.telemetry import registry as tmx

        if registry:
            tmx.configure(True)
        self._tmx = tmx
        self.marks: List[Dict] = []
        cfg = RetentionConfig(max_seq_len=serve["cache_len"], **sizes)
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

    def state_bytes(self) -> Optional[float]:
        """What the engine says its recurrent state holds (None: a
        program that does not say)."""
        return self._tmx.snapshot().get("gauges", {}).get(STATE_BYTES)


def family_facts(run, server: Server, sizes: Dict, serve: Dict,
                 sampled_prompts: List[int]) -> Dict:
    """What this family's per-layer metrics read, from the program's own
    counters and the trace's own kernel calls where it has them: a metric
    whose series is missing is left out, not guessed."""
    from perfbench import trace as tr

    facts: Dict = {}
    held = server.state_bytes()
    if held is not None:
        facts["recurrent_state_gb"] = held / 1e9
    slots = serve["max_batch"]
    facts["decode_turn_bytes"] = \
        retention_lm_count.retention_lm_decode_turn_bytes(sizes, slots)
    # The kernel runs once a layer a decode step: its calls in the traced
    # window over the layers are the window's steps, and a step's calls
    # move every slot's S in and out.
    t = run.facts.get("trace")
    if t is not None and t.ops:
        _, calls = tr.op_seconds(
            t, sorted(t.ops)[0],
            lambda n: tr.is_mosaic_call(n) and KERNEL in n.split("=", 1)[0],
            run.facts.get("trace_window"))
        if calls:
            facts["steps"] = calls / sizes["num_hidden_layers"]
            facts["state_pass_bytes"] = \
                retention_lm_count.state_pass_bytes(sizes, slots)
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
        device = run.devices[0]
        if device.platform == "tpu" and sampled_prompts:
            from perfbench.peaks import peak

            # The window's prefills are the mix's lengths in the mix's
            # proportions: the needed operations a prompt token, over the
            # requests due in the window, times the tokens prefilled.
            per_token = sum(
                retention_lm_count.retention_lm_prefill_flops(sizes, n)
                for n in sampled_prompts) / sum(sampled_prompts)
            facts["prefill_mfu_pct"] = 100.0 * per_token * tokens / (
                seconds * peak(device.device_kind).bf16_flops)
    live, rows = grew("rows_live"), grew("rows_held")
    if rows:
        facts["state_live_share_pct"] = 100.0 * live / rows
    return facts


def served_again(weights, sizes: Dict, serve: Dict, rows: List[Dict]
                 ) -> List:
    """The state each sampled request left in its slot when its last
    token was produced: (S [L, KVH, HD, D], z [L, KVH, D]) a row.  The
    server keeps no request's state (a free slot's is stepped on and the
    next tenant overwrites it), so the requests are served again, by the
    programs that served them: a ``DecodeEngine`` of the cell's slots and
    position cap (the same prefill, install and step, from the compile
    cache), each request in a slot of its own, its served tokens fed back
    one a step (a row of ``tokens`` was made by ``len(tokens) - 1`` steps,
    the last token is never fed), the slot's state copied out after its
    last step.  Rows never mix, so this is the state the request had."""
    import jax.numpy as jnp

    from horovod_tpu.models.retention import RetentionConfig
    from horovod_tpu.serving.decode import DecodeEngine

    slots = serve["max_batch"]
    engine = DecodeEngine(
        weights, RetentionConfig(max_seq_len=serve["cache_len"], **sizes),
        max_batch=slots, cache_len=serve["cache_len"])
    held: List = []
    for lo in range(0, len(rows), slots):
        wave = rows[lo:lo + slots]
        for slot, row in enumerate(wave):
            engine.prefill(slot, row["prompt"])
        left = [None] * len(wave)
        for fed in range(max(len(row["tokens"]) for row in wave)):
            tok = np.zeros((slots,), np.int32)
            for slot, row in enumerate(wave):
                if fed == len(row["tokens"]) - 1:
                    S, z = engine.state["recurrent"]
                    left[slot] = (S[:, slot], z[:, slot])
                    engine.clear(slot)
                elif fed < len(row["tokens"]) - 1:
                    tok[slot] = row["tokens"][fed]
            if all(state is not None for state in left):
                break
            engine.tok = jnp.asarray(tok)
            engine.step()
        held += left
    return held


def state_drift(held, left) -> float:
    """How far the state a request left in its slot lies from what the
    reference says its positions leave behind: the norm of the difference
    over the norm of the reference's, of ``S`` and of ``z``, a layer; the
    largest of them.  The reference's full tensors (``left_behind``) are
    packed into rows as the program says it packs them
    (``models/retention.py:phi_rows``), zero rows included.  The
    program's keys and values are bfloat16 and the reference's float32,
    more so a layer deeper: that is the floor of this number, and what it
    sees is a state that MEANS something else (a forgotten normaliser, a
    wrong fade or packing, a tenant's leftovers)."""
    import jax.numpy as jnp

    from horovod_tpu.models.retention import phi_rows

    S, z = held
    first, second, scale = phi_rows(S.shape[-2], S.shape[-1])
    off = []
    for layer, (M, n) in enumerate(left):
        want_S = M[:, :, first, second] * scale         # [KVH, HD, D]
        want_z = n[:, first, second] * scale            # [KVH, D]
        off += [float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
                for got, want in ((S[layer], want_S), (z[layer], want_z))]
    return float(np.max(off))


def twin_requests(grid: List[int], rng, vocab: int) -> List[Dict]:
    """Four requests drawn from the seed: two prompts of the mix's
    shortest length, each once alone (its state is what its prompt left)
    and once followed by the SAME tokens a step at a time, as many as
    reach the mix's second shortest length.  The twins stand at the same
    positions and are fed the same tokens from two different states."""
    short, long = sorted(grid)[:2]
    fed = rng.integers(1, vocab, size=long - short).tolist() + [0]
    prompts = [rng.integers(1, vocab, size=short).tolist() for _ in "ab"]
    return [{"prompt": p, "tokens": t} for t in ([0], fed) for p in prompts]


def step_drift(a0, b0, a1, b1) -> float:
    """What the STATE's precision moves.  In the first layer a position's
    key, value and gate depend on its token alone, so the twins of
    ``twin_requests`` add the same terms under the same gates and the
    recurrence leaves of their difference exactly the product of the
    gates: ``a1 - b1 = c (a0 - b0)``, one ``c`` a key/value head.  What
    is left of ``a1 - b1`` once the best such multiple of ``a0 - b0`` is
    taken away, over its norm (the larger of ``S``'s and ``z``'s), is the
    arithmetic of the steps alone: float32 reads about 1e-5; a state held
    in bfloat16 rounds each twin its own way a step, a thousand times
    that.  (The logits do not see a bfloat16 state: its rounding averages
    out over a query's 8256 rows.  Nor does ``state_drift``, under its
    floor; nor the step form's state against the prompt form's, whose
    keys the two programs round differently: 0.0016 against 0.0023 on the
    chip, PERF.md section 6.)"""
    import jax.numpy as jnp

    left = []
    for before, after in zip(zip(a0, b0), zip(a1, b1)):
        d0 = (before[0][0] - before[1][0]).reshape(a0[0].shape[1], -1)
        d1 = (after[0][0] - after[1][0]).reshape(d0.shape)
        c = jnp.sum(d1 * d0, axis=1) / jnp.sum(d0 * d0, axis=1)
        left.append(float(jnp.linalg.norm(d1 - c[:, None] * d0)
                          / jnp.linalg.norm(d1)))
    return float(np.max(left))      # a twin's difference gone: not a number


def reference_gaps(weights, sizes: Dict, rows: List[Dict], held: List,
                   pad_to: int, max_new: int, *, control: bool = False
                   ) -> Dict:
    """``lm_serve.reference_gaps`` with this family's reference: one
    float32 pass of the quadratic form over prompt + served tokens a row,
    its head over the ``max_new`` rows from the prompt's last on; the
    widest gap by which a served token's logit lies below the reference's
    best at its position, in units of the spread of the reference's
    logits there.  The served tokens come from the prompt form (the first)
    and then the step form against the state the prompt left: the
    reference's one pass is the test that the two are one function.  The
    same pass says what each row's positions leave behind, and
    ``state_drift`` is the widest distance of a row's state (``held``,
    ``served_again``'s) from it.  With ``control`` the gap at the tokens
    an int8 pass puts first."""
    import jax
    import jax.numpy as jnp

    fwd = ref.Forward(sizes)
    fwd_q = ref.Forward(sizes, quant=True) if control else None
    gaps = jax.jit(lm_serve.logit_gaps)
    worst, worst_q, compared, drift = 0.0, 0.0, 0, 0.0
    for i, row in enumerate(rows):
        p, n = len(row["prompt"]), len(row["tokens"])
        seq = np.zeros((pad_to,), np.int32)
        seq[:p + n] = row["prompt"] + row["tokens"]
        at, left = fwd.logits(weights, jnp.asarray(seq), p - 1, max_new,
                              left_at=p + n - 2)
        drift = float(np.max([drift, state_drift(held[i], left)]))
        at = at[:n]
        g = np.asarray(gaps(at, jnp.asarray(row["tokens"], jnp.int32)))
        worst = max(worst, float(g.max()))
        compared += n
        if control:
            lq = fwd_q.logits(weights, jnp.asarray(seq), p - 1, max_new)[:n]
            gq = np.asarray(gaps(at, jnp.argmax(lq, axis=-1)))
            worst_q = max(worst_q, float(gq.max()))
    return {"widest_gap": worst, "control_widest_gap": worst_q,
            "tokens_compared": compared, "state_drift": drift}


def stand_up(run, registry: bool = False):
    """Weights from the seed, the server on its thread (its registry on in
    a traced run, or when asked for), a client and one warm-up wave
    through every prompt length and slot.  Returns (traffic, serve,
    sizes, weights, server, client); the caller closes the client and
    stops the server."""
    import jax

    import horovod_tpu.models.retention  # noqa: F401  a program without it: fail now

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
                run, server, sizes, serve,
                [len(o.request.prompt) for o in got["outcomes"]
                 if o.request.sampled]))
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
        held = served_again(weights, sizes, serve, rows + twin_requests(
            traffic["prompt_tokens"]["grid"], run.numpy_rng(4),
            sizes["vocab_size"]))
        res = reference_gaps(weights, sizes, rows, held, pad_to, max_new,
                             control=control)
        res["step_drift"] = step_drift(*held[-4:])
    if control:
        print(json.dumps({"control_widest_gap": res["control_widest_gap"],
                          "sound_widest_gap": res["widest_gap"]}), flush=True)
    checks = compare.Checks()
    checks.add("served_token_logit_gap", res["widest_gap"] if rows
               else float("inf"), serve["limits"]["logit_gap"],
               f"{res['tokens_compared']} tokens of {len(rows)} requests")
    checks.add("slot_state_drift", res["state_drift"] if rows
               else float("inf"), serve["limits"]["state_drift"],
               f"S and z of {len(rows)} requests, every layer")
    checks.add("step_state_drift", res["step_drift"],
               serve["limits"]["step_drift"],
               "twin requests' states after the same steps, layer 0")
    checks.add("requests_failed_or_short", run.failed, 0)
    run.checks = checks
