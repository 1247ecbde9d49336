"""models/jamba.py and the two kinds of slot state behind ``DecodeEngine``.

A tiny configuration with both kinds of layer and two periods (attention
at layers 1 and 5 of 8), float32 throughout, seeded weights, on the CPU:

* the mixer: a whole prompt at once equals token by token, outputs and
  the state it ends in, at lengths that are and are not multiples of the
  scan's chunk and lengths under the convolution's width;
* ``forward`` equals the benchmark's plain reference
  (``perfbench/reference/ssm_lm.py``) on logits; its int8 control does not;
* the serving oracle: requests of different lengths admitted at different
  turns into neighbouring slots get, through ``DecodeEngine``, the logits
  ``forward`` gives each alone;
* a slot retired and given to a new request yields exactly what a fresh
  engine yields: nothing of the old recurrent or convolution state
  survives the install;
* the compiled step and install alias the donated state, and the engine
  deletes what it donated (the chip's programs are pinned to in-place
  updates in tests/test_chip_smoke.py);
* ``ServingLoop`` end to end over HTTP, chosen by the config's type.
"""

import http.client
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_probes import serve_cache_programs
from horovod_tpu.models import jamba, layers
from horovod_tpu.serving import DecodeEngine, JambaConfig, ServingLoop
from perfbench.reference import ssm_lm as ref
from test_pallas_decode_attention import \
    step_reads_blocks_and_equals_the_masked_step

SIZES = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
             num_hidden_layers=8, num_attention_heads=4,
             num_key_value_heads=1, attn_layer_period=4, attn_layer_offset=1,
             mamba_d_state=4, mamba_d_conv=4, mamba_expand=2,
             mamba_dt_rank=6, rms_norm_eps=1e-6)
CACHE_LEN = 48
CHUNK = 4
CFG = JambaConfig(max_seq_len=CACHE_LEN, scan_chunk=CHUNK,
                  compute_dtype=jnp.float32, param_dtype=jnp.float32,
                  **SIZES)


@pytest.fixture(scope="module")
def params():
    """The reference's seeded weights (bfloat16 values), held in float32:
    both sides see the same numbers, in the layout the program serves."""
    made = ref.make_weights(jax.random.PRNGKey(7), SIZES)
    return jax.tree.map(lambda a: a.astype(jnp.float32), made)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], size=n)]


def test_layer_order_is_the_published_rule():
    assert CFG.layer_kinds == ("mamba", "attn", "mamba", "mamba") * 2
    assert CFG.runs == [("mamba", 0, 1), ("attn", 0, 1), ("mamba", 1, 4),
                        ("attn", 1, 2), ("mamba", 4, 6)]
    full = JambaConfig()
    assert [i for i, k in enumerate(full.layer_kinds) if k == "attn"] \
        == [7, 21]
    assert jax.tree.map(
        lambda a: a.shape, jax.eval_shape(
            lambda: jamba.init_state(full, 64, 1536))) == {
        "kv": ((2, 64, 1, 1536, 128),) * 2,
        "recurrent": ((26, 64, 16, 5120), (26, 3, 64, 5120)),
        "counters": dict.fromkeys(layers.ATTN_COUNTERS, ())}


# -- (a) the mixer's two forms -------------------------------------------------


@pytest.mark.parametrize("length", [1, 2, 3, 4, 5, 7, 8, 9, 16, 19])
def test_prompt_form_of_the_mixer_equals_token_by_token(params, length):
    assert CFG.mamba_d_conv == 4 and CFG.scan_chunk == CHUNK
    lp = jamba._at(params["mamba"], 2)
    u = jax.random.normal(jax.random.PRNGKey(length),
                          (2, length, SIZES["hidden_size"]))
    whole, (ssm, conv) = jamba._mamba_mixer(u, lp, CFG)
    state, steps = None, []
    for t in range(length):
        y, state = jamba._mamba_mixer(u[:, t:t + 1], lp, CFG, state)
        steps.append(y)
    np.testing.assert_allclose(whole, jnp.concatenate(steps, 1),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(ssm, state[0], rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(conv, state[1])
    assert float(jnp.abs(ssm).max()) > 0


def test_a_prompt_continues_from_carried_state(params):
    """Half a sequence, then the rest against the state the first half
    ended in: the whole sequence's outputs and state."""
    lp = jamba._at(params["mamba"], 0)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 11, 32))
    whole, end = jamba._mamba_mixer(u, lp, CFG)
    first, mid = jamba._mamba_mixer(u[:, :6], lp, CFG)
    rest, end2 = jamba._mamba_mixer(u[:, 6:], lp, CFG, mid)
    np.testing.assert_allclose(whole, jnp.concatenate([first, rest], 1),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(end[0], end2[0], rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(end[1], end2[1])


# -- (b) the model against the plain reference ----------------------------------


@pytest.fixture(scope="module")
def reference_logits(params):
    tokens = jnp.asarray(_prompt(1, 23), jnp.int32)
    return tokens, ref.Forward(SIZES).logits(params, tokens)


def test_forward_equals_the_plain_reference(params, reference_logits):
    tokens, want = reference_logits
    got = jamba.forward(params, tokens[None], CFG)[0]
    assert got.shape == (23, SIZES["vocab_size"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_int8_control_is_not_the_reference(params, reference_logits):
    tokens, want = reference_logits
    control = ref.Forward(SIZES, quant=True).logits(params, tokens)
    spread = float(jnp.std(want))
    assert float(jnp.abs(control - want).max()) > 0.02 * spread
    got = jamba.forward(params, tokens[None], CFG)[0]
    assert float(jnp.abs(got - want).max()) < 1e-3 * spread


def test_a_batch_of_sequences_is_each_alone(params):
    a, b = _prompt(2, 9), _prompt(3, 9)
    both = jamba.forward(params, jnp.asarray([a, b], jnp.int32), CFG)
    for i, row in enumerate((a, b)):
        alone = jamba.forward(params, jnp.asarray([row], jnp.int32), CFG)
        np.testing.assert_allclose(both[i], alone[0], rtol=1e-5, atol=1e-6)


# -- (c), (d) the engine ---------------------------------------------------------


class Spy:
    """An engine whose two programs also hand their logits to the test."""

    def __init__(self, params, max_batch):
        self.engine = DecodeEngine(params, CFG, max_batch=max_batch,
                                   cache_len=CACHE_LEN)
        self.first, self.steps = {}, []
        step, install = self.engine._step, self.engine._install

        def spy_step(*args):
            out = step(*args)
            self.steps.append(np.asarray(out[0]))
            return out

        def spy_install(state, tok, pos, slot, logits, *rest):
            self.first[int(slot)] = np.asarray(logits)
            return install(state, tok, pos, slot, logits, *rest)

        self.engine._step, self.engine._install = spy_step, spy_install


def _served(spy, slot, first_step, n_steps):
    """The logits the engine gave ``slot``: the prefill's, then one row a
    step from ``first_step`` on."""
    rows = [spy.first[slot]] + [s[slot] for s in
                                spy.steps[first_step:first_step + n_steps]]
    return np.stack(rows)


def _alone(params, prompt, served_logits):
    """``forward`` over the prompt and the greedy tokens of the served
    logits: the rows that predict each served token and the next."""
    tokens = [int(np.argmax(r)) for r in served_logits]
    seq = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
    return np.asarray(jamba.forward(params, seq, CFG)[0, len(prompt) - 1:])


def test_neighbouring_slots_get_what_forward_gives_each_alone(params):
    """Three requests of different lengths, admitted at different turns
    into slots 0, 1, 2 of 3; the middle one retires first."""
    spy = Spy(params, 3)
    eng = spy.engine
    a, b, c = _prompt(10, 5), _prompt(11, 9), _prompt(12, 2)
    tok_a = [eng.prefill(1, a)]
    tok_a += [int(eng.step()[1]) for _ in range(3)]         # steps 0-2
    eng.prefill(0, b)
    tok_a += [int(eng.step()[1]) for _ in range(2)]         # steps 3-4
    eng.prefill(2, c)
    tok_a += [int(eng.step()[1]) for _ in range(4)]         # steps 5-8
    eng.clear(1)
    for _ in range(3):                                      # steps 9-11
        eng.step()
    for slot, prompt, first_step, n in ((1, a, 0, 9), (0, b, 3, 9),
                                        (2, c, 5, 7)):
        got = _served(spy, slot, first_step, n)
        np.testing.assert_allclose(got, _alone(params, prompt, got),
                                   rtol=2e-4, atol=2e-5, err_msg=str(slot))
    assert tok_a == [int(np.argmax(r))
                     for r in _served(spy, 1, 0, 9)]
    assert int(eng.pos[0]) == 9 + 9 and int(eng.pos[1]) <= 3


def test_a_reused_slot_holds_nothing_of_its_last_tenant(params):
    """Slot 1 serves a long request, retires, idles three steps beside a
    live neighbour and is given a short prompt: its state after the
    install, and every logit after it, are bit for bit those of an engine
    that never held the first request."""
    old, new, beside = _prompt(20, 17), _prompt(21, 3), _prompt(22, 6)

    def serve_new(engine_spy, after_old):
        eng = engine_spy.engine
        eng.prefill(0, beside)
        if after_old:
            eng.prefill(1, old)
            for _ in range(8):
                eng.step()
            eng.clear(1)
        for _ in range(3 if after_old else 11):
            eng.step()                   # the neighbour at the same position
        eng.prefill(1, new)
        mark = len(engine_spy.steps)
        state = jax.tree.map(np.asarray, eng.state)
        for _ in range(10):
            eng.step()
        return state, _served(engine_spy, 1, mark, 10)

    used_state, used = serve_new(Spy(params, 2), True)
    fresh_state, fresh = serve_new(Spy(params, 2), False)
    np.testing.assert_array_equal(used, fresh)
    ssm, conv = used_state["recurrent"]
    np.testing.assert_array_equal(ssm[:, 1], fresh_state["recurrent"][0][:, 1])
    np.testing.assert_array_equal(conv[:, :, 1],
                                  fresh_state["recurrent"][1][:, :, 1])
    assert float(np.abs(ssm[:, 1]).max()) > 0
    for got, want in zip(used_state["kv"], fresh_state["kv"]):
        np.testing.assert_array_equal(got[:, 1], want[:, 1])
    np.testing.assert_allclose(used, _alone(params, new, used),
                               rtol=2e-4, atol=2e-5)


def test_a_mesh_is_refused_by_name(params):
    from horovod_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="recurrent state"):
        DecodeEngine(params, CFG, max_batch=2, cache_len=CACHE_LEN,
                     mesh=mesh)


def test_the_engine_reports_what_it_holds_by_kind(params):
    from horovod_tpu.telemetry import registry as tmx

    tmx.configure(True)
    try:
        DecodeEngine(params, CFG, max_batch=2, cache_len=CACHE_LEN)
        gauges = tmx.snapshot()["gauges"]
    finally:
        tmx.configure(False)
    n_attn, n_mamba, d_inner = 2, 6, 64
    assert gauges['hvd_serve_state_bytes{kind="kv"}'] \
        == 2 * n_attn * 2 * CACHE_LEN * 8 * 4
    assert gauges['hvd_serve_state_bytes{kind="recurrent"}'] \
        == n_mamba * 2 * d_inner * (4 * 4 + 3 * 4)


# -- (e) the compiled programs ---------------------------------------------------

B_PIN, S_PIN = 4, 256
PIN = JambaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                  num_hidden_layers=8, num_attention_heads=2,
                  num_key_value_heads=1, attn_layer_period=4,
                  attn_layer_offset=1, mamba_d_state=8, mamba_expand=8,
                  mamba_dt_rank=4, max_seq_len=S_PIN,
                  compute_dtype=jnp.float32, param_dtype=jnp.float32)
SSM_ELEMS = 6 * B_PIN * 8 * 256          # [Lm, B, N, d_inner]
KV_ELEMS = 2 * B_PIN * 1 * S_PIN * 16    # [La, B, KVH, S, HD]


@pytest.mark.parametrize("program", ["step", "install"])
def test_compiled_program_aliases_all_the_state_it_was_given(program):
    """All four state arrays and the two counters are aliased from input
    to output of both programs, and the install produces nothing of a
    key/value stack's size besides its in-place writes.  (Inside the step's layer loop the
    CPU backend copies the recurrent state it reads and writes; the chip's
    compiler does not: tests/test_chip_smoke.py pins the step compiled for
    ``v5e``, at the benchmark's shapes, to in-place updates alone.)"""
    assert KV_ELEMS < SSM_ELEMS
    got = serve_cache_programs(PIN, B_PIN, KV_ELEMS)[program]
    conv_elems = 6 * 3 * B_PIN * 256
    assert got["alias_bytes"] == 4 * (2 * KV_ELEMS + SSM_ELEMS + conv_elems
                                      + len(layers.ATTN_COUNTERS))
    if program == "install":
        assert {op for _, op in got["big_ops"]} <= {
            "fusion:dynamic-update-slice", "dynamic-update-slice"}, got
        assert got["temp_bytes"] < 4 * KV_ELEMS, got


def test_step_reads_its_lanes_by_blocks_and_equals_the_masked_read(
        params, monkeypatch):
    """The heads-first lanes of ONE key/value head through
    ``layers.lane_reader`` (a key every query head shares) against the
    masked read of the whole lane."""
    step_reads_blocks_and_equals_the_masked_step(monkeypatch, jamba, params,
                                                 CFG)


def test_prefill_and_step_donate_the_state_they_were_given(params):
    engine = DecodeEngine(params, CFG, max_batch=2, cache_len=CACHE_LEN)
    before = jax.tree.leaves(engine.state)
    engine.prefill(1, [3, 14, 15])
    assert all(a.is_deleted() for a in before)
    before = jax.tree.leaves(engine.state)
    engine.step()
    assert all(a.is_deleted() for a in before)
    assert int(engine.pos[1]) == 4


# -- (f) the whole server --------------------------------------------------------


def _post(port, prompt, max_new):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", "/generate", json.dumps(
            {"prompt": prompt, "max_new_tokens": max_new}))
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


@pytest.mark.timeout(240)
def test_serving_loop_serves_the_config_over_http(params, monkeypatch):
    """``ServingLoop`` → ``Scheduler`` → ``DecodeEngine`` with nothing but
    the config's type to say which model: three requests at once over
    HTTP, each answered with the greedy tokens ``forward`` gives it."""
    import horovod_tpu as hvd

    monkeypatch.setenv("HVD_TPU_CORE", "py")   # ServingLoop.run setdefaults
    hvd.shutdown()
    ready, box = threading.Event(), {}

    def on_ready(port):
        box["port"] = port
        ready.set()

    loop = ServingLoop(params, CFG, port=0, max_batch=2, max_queue=16,
                       cache_len=CACHE_LEN, host="127.0.0.1",
                       on_ready=on_ready)

    def serve():
        try:
            loop.run()
        except BaseException as e:
            box["error"] = e
            ready.set()
            raise

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    requests = [(_prompt(30, 4), 6), (_prompt(31, 7), 9), (_prompt(32, 2), 5)]
    replies = [None] * len(requests)
    try:
        assert ready.wait(120) and "error" not in box, box
        posts = [threading.Thread(
            target=lambda i=i, r=r: replies.__setitem__(
                i, _post(box["port"], *r))) for i, r in enumerate(requests)]
        for t in posts:
            t.start()
        for t in posts:
            t.join(180)
    finally:
        loop.stop()
        thread.join(60)
        hvd.shutdown()
    assert not thread.is_alive() and "error" not in box, box
    for (prompt, max_new), (status, body) in zip(requests, replies):
        assert status == 200, body
        tokens = [int(t) for t in body["tokens"]]
        assert len(tokens) == max_new
        seq = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
        logits = np.asarray(
            jamba.forward(params, seq, CFG)[0, len(prompt) - 1:])
        best = np.sort(logits, axis=-1)
        assert float((best[:, -1] - best[:, -2]).min()) > 1e-4
        assert tokens == [int(t) for t in logits.argmax(-1)]


@pytest.mark.timeout(240)
def test_a_retired_slots_extra_row_reaches_nobody(params, monkeypatch):
    """The loop dispatches a step before it reads the last one's tokens
    (serving/loop.py ``_turn``), so a slot that meets its EOS has one more
    row computed: here on both kinds of state.  The request ended by the
    EOS, its neighbour, and the slot's next tenant (installed over a lane
    and a recurrent state that the extra row wrote) each get the logits and
    tokens an engine gives them alone, in the order tests/serve_order.py
    checks."""
    import serve_order

    # Output projections scaled up: greedy decode then leaves the prompt's
    # last token, and an EOS can fall in the middle of an answer.
    loud = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 12.0 if path[-1].key in (
            "w_out", "out_proj", "wo") else a, params)

    def alone(prompt, max_new, eos_id=None):
        spy = Spy(loud, 1)
        tokens = [spy.engine.prefill(0, prompt)]
        while len(tokens) < max_new and tokens[-1] != eos_id:
            tokens.append(int(spy.engine.step()[0]))
        return tokens, _served(spy, 0, 0, len(tokens) - 1)

    ended, beside, tenant = _prompt(41, 8), _prompt(40, 5), _prompt(42, 3)
    answer, _ = alone(ended, 12)
    eos_id = answer[-1]
    eos_at = answer.index(eos_id)
    assert 2 < eos_at < 11, answer          # it ends in the middle
    steps, installs = [], []                # the loop's engine, tapped

    def tap(engine):
        step, install = engine._step, engine._install

        def spy_step(*args):
            out = step(*args)
            steps.append(np.asarray(out[0]))
            return out

        def spy_install(state, tok, pos, slot, logits, *rest):
            installs.append((int(slot), len(steps), np.asarray(logits)))
            return install(state, tok, pos, slot, logits, *rest)

        engine._step, engine._install = spy_step, spy_install

    asked = [(ended, 12), (beside, 16), (tenant, 9)]
    served = serve_order.serve(monkeypatch, loud, CFG, [asked],
                               max_batch=2, cache_len=CACHE_LEN,
                               eos_id=eos_id, on_engine=tap)
    assert len(served.tokens[0]) == eos_at + 1
    assert [slot for slot, _, _ in installs] == [0, 1, 0]
    for (prompt, max_new), tokens, (slot, start, first) in zip(
            asked, served.tokens, installs):
        want_tokens, want_logits = alone(prompt, max_new, eos_id)
        assert tokens == want_tokens, prompt
        got = np.stack([first] + [s[slot] for s in
                                  steps[start:start + len(tokens) - 1]])
        np.testing.assert_allclose(got, want_logits, rtol=1e-5, atol=1e-6)
    assert 0 < serve_order.check_order(served) < served.turns
    # the step in flight when slot 0 met its EOS computed a row for it
    assert len(steps) > sum(len(t) - 1 for t in served.tokens[:1]) \
        and installs[2][1] == eos_at + 1


def test_the_seam_refuses_what_no_model_serves():
    from horovod_tpu.serving import TransformerConfig

    with pytest.raises(NotImplementedError, match="dense-FFN configs"):
        DecodeEngine(None, TransformerConfig(n_experts=4), max_batch=1)
    with pytest.raises(TypeError, match="no serving path"):
        DecodeEngine(None, object(), max_batch=1, cache_len=8)
