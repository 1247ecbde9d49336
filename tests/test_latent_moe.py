"""models/latent_moe.py and models/experts.py behind ``DecodeEngine``.

A tiny configuration (1 dense + 2 expert layers, 8 experts top-2 beside a
shared one, all four low-rank shapes of the attention), float32
throughout, seeded weights, on the CPU:

* ``forward`` equals the benchmark's plain reference
  (``perfbench/reference/moe_lm.py``) on logits; its int8 control does not;
* the two forms of the attention: a prefill (expanded) and then token by
  token through the latent cache (absorbed) equals ``forward`` over the
  whole sequence;
* the routing: selection by ``s + b``, weights from ``s``; nothing is
  dropped when every row picks the same experts; a row is routed alone
  as in a batch; a free slot is routed nowhere;
* the serving oracle: requests of different lengths admitted at different
  turns into neighbouring slots get, through ``DecodeEngine``, the logits
  ``forward`` gives each alone; a reused slot holds nothing of its last
  tenant;
* the step's attention kernel (ops/pallas_decode_attention.py) gives the
  logits of the masked read of the whole lane it replaced, over 40 steps
  of uneven slots, and counts what it read;
* the compiled step and install alias the donated state; the engine says
  what it holds; the device counters add up and reach the registry;
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
from horovod_tpu.models import experts, latent_moe
from horovod_tpu.ops import pallas_decode_attention as pda
from horovod_tpu.serving import (DecodeEngine, LatentMoEConfig, ServingLoop,
                                 decode)
from horovod_tpu.telemetry import registry as tmx
from perfbench.reference import moe_lm as ref
from test_pallas_decode_attention import masked_read, uneven_steps

SIZES = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
             moe_intermediate_size=16, num_hidden_layers=3,
             first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=12,
             kv_lora_rank=8, qk_nope_head_dim=6, qk_rope_head_dim=4,
             v_head_dim=8, n_routed_experts=8, n_shared_experts=1,
             num_experts_per_tok=2, routed_scaling_factor=1.8,
             rms_norm_eps=1e-5, rope_theta=1e6)
CACHE_LEN = 48
K, E, MOE_LAYERS = 2, 8, 2
# Query blocks of 8 rows: prompts of 9 and more take the blocked softmax.
CFG = LatentMoEConfig(max_seq_len=CACHE_LEN, attn_block=8,
                      compute_dtype=jnp.float32, param_dtype=jnp.float32,
                      **SIZES)


# Jitted once a shape: called bare, each call would trace and compile its
# layer loops again.
FORWARD = jax.jit(lambda params, tokens: latent_moe.forward(
    params, tokens, CFG))
PREFILL = jax.jit(lambda params, prompt: latent_moe.prefill_request(
    params, prompt, CFG, CACHE_LEN))
STEP = jax.jit(lambda params, tok, pos, state: latent_moe.decode_step(
    params, tok, pos, state, CFG))


@pytest.fixture(scope="module")
def made():
    """The reference's seeded weights as the benchmark hands them over:
    bfloat16, the selection bias float32."""
    # one program for all leaves (the reference compiles one a leaf)
    return jax.jit(lambda key: ref.make_weights(key, SIZES))(
        jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def params(made):
    """The same values held in float32: both sides see the same numbers,
    in the layout the program serves."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), made)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], size=n)]


# -- (a) against the plain reference ----------------------------------------------


@pytest.fixture(scope="module")
def reference_logits(params):
    tokens = jnp.asarray(_prompt(1, 23), jnp.int32)
    return tokens, ref.Forward(SIZES).logits(params, tokens)


def test_forward_equals_the_plain_reference(params, reference_logits):
    """Both are float32 on the CPU and differ in the order of their sums
    alone (a blocked softmax, grouped products, one fused key), so they
    agree to float32's rounding: were a row to pick another expert, or a
    weight to come from the biased score, the gap would be a thousand
    times this."""
    tokens, want = reference_logits
    got = FORWARD(params, tokens[None])[0]
    assert got.shape == (23, SIZES["vocab_size"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_the_int8_control_is_not_the_reference(params, reference_logits):
    tokens, want = reference_logits
    control = ref.Forward(SIZES, quant=True).logits(params, tokens)
    spread = float(jnp.std(want))
    assert float(jnp.abs(control - want).max()) > 0.02 * spread
    got = FORWARD(params, tokens[None])[0]
    assert float(jnp.abs(got - want).max()) < 1e-4 * spread


def test_the_reference_reads_the_rows_it_is_asked_for(params,
                                                      reference_logits):
    tokens, want = reference_logits
    got = ref.Forward(SIZES).logits(params, tokens, 4, 7)
    np.testing.assert_array_equal(got, want[4:11])


def test_a_batch_of_sequences_is_each_alone(params):
    a, b = _prompt(2, 9), _prompt(3, 9)
    both = FORWARD(params, jnp.asarray([a, b], jnp.int32))
    for i, row in enumerate((a, b)):
        alone = FORWARD(params, jnp.asarray([row], jnp.int32))
        np.testing.assert_allclose(both[i], alone[0], rtol=1e-5, atol=1e-6)


# -- (b) the attention's two forms --------------------------------------------------


@pytest.mark.parametrize("prompt_len", [1, 7, 8, 9, 17])
def test_prefill_then_absorbed_decode_equals_forward(params, prompt_len):
    """A prompt through the expanded form (one and several query blocks),
    then one token at a time through the latent cache, in slot 1 of 3 with
    free slots beside it: the logits ``forward`` gives the whole
    sequence."""
    seq = _prompt(4, 23)
    want = FORWARD(params, jnp.asarray([seq], jnp.int32))[0]
    logits, request = PREFILL(params,
                              jnp.asarray(seq[:prompt_len], jnp.int32))
    np.testing.assert_allclose(logits, want[prompt_len - 1], rtol=2e-4,
                               atol=2e-6)
    c, k_r = request["kv"]
    assert c.shape == (3, 1, CACHE_LEN, 8) and k_r.shape == (3, 1, CACHE_LEN, 4)
    assert float(jnp.abs(c[:, :, prompt_len:]).max()) == 0.0
    state = decode.slot_model(CFG, CACHE_LEN).install(
        latent_moe.init_state(CFG, 3, CACHE_LEN), 1, request)
    for t in range(prompt_len, len(seq)):
        logits, state = STEP(params, jnp.asarray([0, seq[t], 0], jnp.int32),
                             jnp.asarray([0, t, 0], jnp.int32), state)
        np.testing.assert_allclose(logits[1], want[t], rtol=2e-4, atol=2e-6)


def test_a_position_keeps_a_latent_and_one_rotary_key():
    """At the published sizes: 512 + 64 values a position a layer, where
    20 heads of 256 + 256 would be 10 240."""
    full = LatentMoEConfig(num_hidden_layers=7)
    shapes = jax.tree.map(lambda a: a.shape, jax.eval_shape(
        lambda: latent_moe.init_state(full, 64, 4608)))
    assert shapes["kv"] == ((7, 64, 4608, 512), (7, 64, 4608, 64))
    assert set(shapes["counters"]) == set(latent_moe.COUNTERS)


# -- (c) the routing -------------------------------------------------------------------


@pytest.fixture(scope="module")
def layer(params):
    """Rows and the first expert layer's router and experts."""
    x = jax.random.normal(jax.random.PRNGKey(3), (11, SIZES["hidden_size"]))
    lp = {k: v for k, v in params["moe"].items()}
    return x, lp


def _routed(x, lp, bias, live=None, layer=0):
    chosen, weights = experts.route(x, lp["router"][layer], bias, K, 1.8)
    y, stats = experts.routed_ffn(
        x, {k: lp[k] for k in ("w_in", "w_gate", "w_out")}, layer, chosen,
        weights, jnp.float32, live)
    return chosen, weights, y, stats


def _by_hand(x, lp, chosen, weights, layer=0):
    """Every (row, choice) pair on its own, no grouping."""
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for e, w in zip(np.asarray(chosen[t]), np.asarray(weights[t])):
            h = (x[t] @ lp["w_in"][layer, e]) * jax.nn.silu(
                x[t] @ lp["w_gate"][layer, e])
            out[t] += w * np.asarray(h @ lp["w_out"][layer, e])
    return out


def test_selection_is_by_the_biased_score_and_the_weight_by_the_unbiased(
        layer):
    x, lp = layer
    bias = lp["router_bias"][0]
    chosen, weights, y, _ = _routed(x, lp, bias)
    s = jax.nn.sigmoid(x @ lp["router"][0])
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(jax.lax.top_k(s + bias, K)[1], -1))
    picked = jnp.take_along_axis(s, chosen, -1)
    np.testing.assert_allclose(
        weights, 1.8 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.8, rtol=1e-6)
    # a bias that ranks the experts as before leaves the output to the bit
    _, _, same, _ = _routed(x, lp, bias + 0.25)
    np.testing.assert_array_equal(same, y)
    # one that changes the choice changes the output, and the weights
    # still come from s alone
    pushed = bias.at[5].add(10.0)
    chosen2, weights2, moved, _ = _routed(x, lp, pushed)
    assert bool((chosen2 == 5).any(-1).all())
    assert float(jnp.abs(moved - y).max()) > 1e-4
    picked2 = jnp.take_along_axis(s, chosen2, -1)
    np.testing.assert_allclose(
        weights2, 1.8 * picked2 / picked2.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(moved, _by_hand(x, lp, chosen2, weights2),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("which_layer", [0, 1])
def test_no_row_is_dropped_when_every_row_picks_the_same_experts(
        layer, which_layer):
    """All 11 rows on experts 2 and 6 of 8: 22 pairs in two groups, six
    experts with none, and every row gets both of its experts' outputs.
    Under a capacity of rows x k / experts = 2.75 a row, eight of the
    eleven would have been dropped."""
    x, lp = layer
    bias = jnp.zeros((E,)).at[jnp.asarray([2, 6])].set(10.0)
    chosen, weights, y, stats = _routed(x, lp, bias, layer=which_layer)
    assert sorted(np.unique(np.asarray(chosen))) == [2, 6]
    np.testing.assert_allclose(
        y, _by_hand(x, lp, chosen, weights, which_layer), rtol=1e-4,
        atol=1e-6)
    assert float(jnp.abs(y).sum(-1).min()) > 0.0
    assert [int(v) for v in stats] == [22, 2, 11]


def test_a_row_is_routed_alone_as_in_a_batch(layer):
    x, lp = layer
    bias = lp["router_bias"][0]
    _, _, y, stats = _routed(x, lp, bias)
    assert int(stats[0]) == 11 * K and int(stats[1]) <= min(E, 11 * K)
    for t in (0, 4, 10):
        _, _, alone, one = _routed(x[t:t + 1], lp, bias)
        np.testing.assert_allclose(alone[0], y[t], rtol=1e-5, atol=1e-7)
        assert [int(v) for v in one] == [K, K, 1]


def test_a_free_slot_is_routed_nowhere(layer):
    """Rows marked not live reach no expert: zero output, not counted,
    and the live rows' outputs are what they are without them."""
    x, lp = layer
    bias = lp["router_bias"][0]
    live = jnp.arange(11) % 3 == 0
    _, _, y, stats = _routed(x, lp, bias, live)
    _, _, only, want = _routed(x[live], lp, bias)
    np.testing.assert_array_equal(y[~live], 0.0)
    np.testing.assert_allclose(y[live], only, rtol=1e-5, atol=1e-7)
    assert [int(v) for v in stats] == [int(v) for v in want]
    _, _, none, stats = _routed(x, lp, bias, jnp.zeros((11,), bool))
    np.testing.assert_array_equal(none, 0.0)
    assert [int(v) for v in stats] == [0, 0, 0]


# -- (d) the engine ------------------------------------------------------------------


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
    rows = [spy.first[slot]] + [s[slot] for s in
                                spy.steps[first_step:first_step + n_steps]]
    return np.stack(rows)


def _alone(params, prompt, served_logits):
    """``forward`` over the prompt and the greedy tokens of the served
    logits: the rows that predict each served token and the next."""
    tokens = [int(np.argmax(r)) for r in served_logits]
    seq = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
    return np.asarray(FORWARD(params, seq)[0, len(prompt) - 1:])


def test_neighbouring_slots_get_what_forward_gives_each_alone(params):
    """Three requests of different lengths, admitted at different turns
    into slots 0, 1, 2 of 3; the middle one retires first.  Nothing is
    dropped, so what a slot's neighbours route never reaches it."""
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
                                   rtol=2e-4, atol=2e-6, err_msg=str(slot))
    assert tok_a == [int(np.argmax(r)) for r in _served(spy, 1, 0, 9)]
    # live rows a step: 1, 1, 1, 2, 2, 3, 3, 3, 3, 2, 2, 2
    live = [1, 1, 1, 2, 2, 3, 3, 3, 3, 2, 2, 2]
    got = eng.counters()
    assert got["hvd_moe_rows_routed_total"] == sum(live) * K * MOE_LAYERS
    assert got["hvd_moe_layer_turns_total"] == len(live) * MOE_LAYERS
    assert got["hvd_moe_experts_touched_total"] <= sum(
        min(E, n * K) for n in live) * MOE_LAYERS
    assert got["hvd_moe_experts_touched_total"] >= len(live) * MOE_LAYERS * K
    assert len(live) * MOE_LAYERS <= got["hvd_moe_max_expert_rows_total"] \
        <= sum(live) * MOE_LAYERS


def test_a_reused_slot_holds_nothing_of_its_last_tenant(params):
    """Slot 1 serves a long request, retires, idles three steps beside a
    live neighbour and is given a short prompt: its lanes after the
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
    for got, want in zip(used_state["kv"], fresh_state["kv"]):
        np.testing.assert_array_equal(got[:, 1], want[:, 1])
        assert float(np.abs(got[:, 1, :3]).max()) > 0
    np.testing.assert_allclose(used, _alone(params, new, used),
                               rtol=2e-4, atol=2e-6)


def test_a_mesh_is_refused_by_name(params):
    from horovod_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="LatentMoEConfig.*mesh"):
        DecodeEngine(params, CFG, max_batch=2, cache_len=CACHE_LEN,
                     mesh=mesh)


def test_the_dense_decoder_says_where_experts_are_served():
    from horovod_tpu.serving import TransformerConfig

    with pytest.raises(NotImplementedError, match="models/latent_moe.py"):
        DecodeEngine(None, TransformerConfig(n_experts=4), max_batch=1)


def test_the_engine_reports_what_it_holds(made):
    """``kv``: slots x cache x layers x (latent + rotary key) values;
    nothing recurrent; the parameters in the type they were given."""
    held = made
    tmx.configure(True)
    try:
        DecodeEngine(held, CFG, max_batch=2, cache_len=CACHE_LEN)
        gauges = tmx.snapshot()["gauges"]
    finally:
        tmx.configure(False)
    assert gauges['hvd_serve_state_bytes{kind="kv"}'] \
        == 2 * CACHE_LEN * 3 * (8 + 4) * 4
    assert gauges['hvd_serve_state_bytes{kind="recurrent"}'] == 0
    by_type = {}
    for leaf in jax.tree.leaves(held):
        by_type[str(leaf.dtype)] = by_type.get(str(leaf.dtype), 0) \
            + leaf.nbytes
    assert set(by_type) == {"bfloat16", "float32"}
    for dtype, nbytes in by_type.items():
        assert gauges['hvd_serve_param_bytes{dtype="%s"}' % dtype] == nbytes


def test_the_device_counters_reach_the_registry_at_an_admission(params):
    """Summed on the device by every step; read beside the read an
    admission makes anyway, and only when the registry is on."""
    eng = DecodeEngine(params, CFG, max_batch=2, cache_len=CACHE_LEN)
    eng.prefill(0, _prompt(50, 4))
    for _ in range(5):
        eng.step()
    assert eng._published == {}                 # registry off: never read
    tmx.configure(True)
    try:
        eng.prefill(1, _prompt(51, 3))
        first = dict(tmx.snapshot()["counters"])
        for _ in range(4):
            eng.step()
        eng.publish_counters()
        second = dict(tmx.snapshot()["counters"])
    finally:
        tmx.configure(False)
    assert first["hvd_moe_rows_routed_total"] == 5 * 1 * K * MOE_LAYERS
    assert first["hvd_moe_layer_turns_total"] == 5 * MOE_LAYERS
    assert second["hvd_moe_rows_routed_total"] == (5 + 4 * 2) * K * MOE_LAYERS
    assert second["hvd_moe_layer_turns_total"] == 9 * MOE_LAYERS
    assert set(latent_moe.COUNTERS) <= set(tmx.known_metrics())
    # a wrapped device counter still grows the registry's by the difference
    eng._published["hvd_moe_layer_turns_total"] += 1 << 32
    tmx.configure(True)
    try:
        eng.step()
        eng.publish_counters()
        assert tmx.snapshot()["counters"][
            "hvd_moe_layer_turns_total"] == MOE_LAYERS
    finally:
        tmx.configure(False)


# -- (e) the compiled programs ---------------------------------------------------

B_PIN, S_PIN = 4, 256
PIN = LatentMoEConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      moe_intermediate_size=16, num_hidden_layers=3,
                      num_attention_heads=2, q_lora_rank=8, kv_lora_rank=16,
                      qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                      n_routed_experts=4, num_experts_per_tok=2,
                      max_seq_len=S_PIN, compute_dtype=jnp.float32,
                      param_dtype=jnp.float32)
LANE_ELEMS = B_PIN * S_PIN * 16          # one layer's latents [B, S, 16]


def test_step_with_the_kernel_equals_the_masked_read_of_the_whole_lane(
        monkeypatch):
    """Blocks of 32 positions in a lane of 256: over the 40 steps the
    three live slots cross block ends at 32, 64, 96 and 224, beside a
    free slot; the counters say what the blocks held."""
    params = latent_moe.init(jax.random.PRNGKey(5), PIN)
    monkeypatch.setattr(pda, "BLOCK_SHARED", 32)
    lengths = [3, 0, 61, 200]

    def logits():
        return uneven_steps(
            jax.jit(lambda p: latent_moe.prefill_request(params, p, PIN,
                                                         S_PIN)),
            decode.slot_model(PIN, S_PIN).install,
            jax.jit(lambda tok, pos, state: latent_moe.decode_step(
                params, tok, pos, state, PIN)),
            latent_moe.init_state(PIN, B_PIN, S_PIN), lengths,
            PIN.vocab_size)

    got, state = logits()
    read, held = (int(state["counters"][name])
                  for name in latent_moe.COUNTERS[-2:])
    assert held == 40 * 3 * B_PIN * S_PIN
    assert read == 3 * 32 * sum(n // 32 + 1 for length in lengths if length
                                for n in range(length, length + 40))
    monkeypatch.setattr(latent_moe, "decode_attention", masked_read)
    want, _ = logits()
    live = [b for b, n in enumerate(lengths) if n]
    np.testing.assert_allclose(got[:, live], want[:, live], rtol=2e-4,
                               atol=2e-5)
    assert np.isfinite(got).all()


@pytest.mark.parametrize("program", ["step", "install"])
def test_compiled_program_aliases_all_the_state_it_was_given(program):
    """Both caches and the four counters are aliased from input to output
    of both programs, and the install produces nothing of a lane's size
    besides its in-place writes."""
    got = serve_cache_programs(PIN, B_PIN, LANE_ELEMS)[program]
    assert got["alias_bytes"] == 4 * (3 * LANE_ELEMS + 3 * B_PIN * S_PIN * 8
                                      + len(latent_moe.COUNTERS))
    if program == "install":
        assert {op for _, op in got["big_ops"]} <= {
            "fusion:dynamic-update-slice", "dynamic-update-slice"}, got
        assert got["temp_bytes"] < 4 * LANE_ELEMS, got


def test_prefill_and_step_donate_the_state_they_were_given(params):
    engine = DecodeEngine(params, CFG, max_batch=2, cache_len=CACHE_LEN)
    before = jax.tree.leaves(engine.state["kv"])
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
    """``ServingLoop`` -> ``Scheduler`` -> ``DecodeEngine`` with nothing
    but the config's type to say which model: three requests at once over
    HTTP into two slots, each answered with the greedy tokens ``forward``
    gives it."""
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
        logits = np.asarray(FORWARD(params, seq)[0, len(prompt) - 1:])
        best = np.sort(logits, axis=-1)
        assert float((best[:, -1] - best[:, -2]).min()) > 1e-5
        assert tokens == [int(t) for t in logits.argmax(-1)]
