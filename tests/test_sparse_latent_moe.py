"""models/latent_moe.py with an indexer, group-limited routing, YaRN and a
share of its experts (the keys ``deepseek-ai/DeepSeek-V3.2`` publishes),
ops/pallas_index_select.py, and the selection in
ops/pallas_decode_attention.py.

A tiny configuration (1 dense + 2 expert layers, 16 experts in 4 groups of
which 2 are kept, top-3, 4 of the 16 held; 4 index heads of 8, the 8 best
positions kept), float32 throughout, seeded weights, on the CPU:

* ``forward``, and a prefill then token by token through the three lanes
  past ``index_topk``, equal the benchmark's plain reference
  (``perfbench/reference/sparse_moe_lm.py``); its int8 control does not;
* the routing limited to groups against a hand case; the shares of a layer
  add up to the uncut layer; a vocabulary slice is a smaller vocabulary;
* YaRN's table and scale against hand values;
* the kernel ``index_select``: its scores, its cut against ``lax.top_k``,
  equal scores taken by position; the step with the kernels equals a
  masked read of the whole lane under the same selection, over 12 steps of
  uneven slots, and counts what it scored and selected;
* the compiled step and install alias the donated state, the index keys
  among it; the engine says what it holds;
* a configuration without the new keys lowers, all three programs, to the
  text it lowered to before they existed.
"""

import hashlib
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_probes import serve_cache_programs
from horovod_tpu.models import experts, latent_moe
from horovod_tpu.ops import pallas_decode_attention as pda
from horovod_tpu.ops.pallas_index_select import index_select
from horovod_tpu.serving import DecodeEngine, LatentMoEConfig, decode
from horovod_tpu.telemetry import registry as tmx
from perfbench.reference import sparse_moe_lm as ref
from test_pallas_decode_attention import masked_read, uneven_steps

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}
SIZES = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
             moe_intermediate_size=16, num_hidden_layers=3,
             first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=12,
             kv_lora_rank=8, qk_nope_head_dim=6, qk_rope_head_dim=4,
             v_head_dim=8, n_routed_experts=16, n_shared_experts=1,
             num_experts_per_tok=3, routed_scaling_factor=2.5,
             rms_norm_eps=1e-6, rope_theta=10000.0, n_group=4, topk_group=2,
             index_n_heads=4, index_head_dim=8, index_topk=8,
             rope_scaling=YARN, experts_held=4, expert_first=4)
CACHE_LEN = 64
TOP = SIZES["index_topk"]
# Query blocks of 8 rows: a prompt's rows past the eighth are selected.
CFG = LatentMoEConfig(max_seq_len=CACHE_LEN, attn_block=8,
                      compute_dtype=jnp.float32, param_dtype=jnp.float32,
                      **SIZES)

FORWARD = jax.jit(lambda params, tokens: latent_moe.forward(
    params, tokens, CFG))
PREFILL = jax.jit(lambda params, prompt: latent_moe.prefill_request(
    params, prompt, CFG, CACHE_LEN))
STEP = jax.jit(lambda params, tok, pos, state: latent_moe.decode_step(
    params, tok, pos, state, CFG))


@pytest.fixture(scope="module")
def params():
    """The reference's seeded weights held in float32: both sides see the
    same numbers, in the layout the program serves."""
    made = jax.jit(lambda key: ref.make_weights(key, SIZES))(
        jax.random.PRNGKey(7))
    return jax.tree.map(lambda a: a.astype(jnp.float32), made)


@pytest.fixture(scope="module")
def reference_logits(params):
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        1, SIZES["vocab_size"], size=40), jnp.int32)
    return tokens, ref.Forward(SIZES).logits(params, tokens)


# -- (a) against the plain reference ----------------------------------------


def test_the_programs_weights_are_the_references(params):
    mine = jax.eval_shape(lambda k: latent_moe.init(k, CFG),
                          jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, mine) \
        == jax.tree.map(lambda a: a.shape, params)
    assert params["moe"]["w_in"].shape[1] == SIZES["experts_held"]
    assert params["moe"]["router"].shape[-1] == SIZES["n_routed_experts"]


def test_forward_equals_the_plain_reference(params, reference_logits):
    """Float32 on both sides: they differ in the order of their sums alone,
    32 of the 40 rows behind a selection of 8.  Were a row to see another
    position, or to pick another expert, the gap would be a thousand times
    this."""
    tokens, want = reference_logits
    got = FORWARD(params, tokens[None])[0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_the_int8_control_and_a_wrong_selection_are_not_the_reference(
        params, reference_logits, monkeypatch):
    tokens, want = reference_logits
    spread = float(jnp.std(want))
    control = ref.Forward(SIZES, quant=True).logits(params, tokens)
    assert float(jnp.abs(control - want).max()) > 0.02 * spread

    def newest(q_i, k_i, w, lo, hi, top):
        rows, cols = jnp.arange(lo, hi)[:, None], jnp.arange(hi)[None, :]
        return jnp.broadcast_to((cols <= rows) & (cols > rows - top),
                                (q_i.shape[0], hi - lo, hi))

    monkeypatch.setattr(latent_moe, "_selected_rows", newest)
    wrong = latent_moe.forward(params, tokens[None], CFG)[0]
    assert float(jnp.abs(wrong - want).max()) > 0.02 * spread
    assert float(jnp.abs(wrong[:TOP] - want[:TOP]).max()) < 1e-4 * spread


def test_prefill_then_sparse_steps_equal_the_full_pass(
        params, reference_logits, monkeypatch):
    """A prompt of 12 (its last 4 rows selected), then 28 steps through
    the three lanes, every one past ``index_topk``, beside a free slot:
    the reference's one pass over all 40."""
    monkeypatch.setattr(pda, "BLOCK_SHARED", 16)
    tokens, want = reference_logits
    logits, request = PREFILL(params, tokens[:12])
    np.testing.assert_allclose(logits, want[11], rtol=2e-4, atol=2e-6)
    assert set(request) == {"kv", "index"}
    state = decode.slot_model(CFG, CACHE_LEN).install(
        latent_moe.init_state(CFG, 2, CACHE_LEN), 1, request)
    for t in range(12, 40):
        logits, state = STEP(params, jnp.asarray([0, tokens[t]], jnp.int32),
                             jnp.asarray([0, t], jnp.int32), state)
        np.testing.assert_allclose(logits[1], want[t], rtol=2e-4, atol=2e-6)
    counters = {k: int(v) for k, v in state["counters"].items()}
    steps, layers, k = 28, 3, SIZES["num_experts_per_tok"]
    assert counters["hvd_serve_index_positions_scored_total"] \
        == layers * sum(t + 1 for t in range(12, 40))
    assert counters["hvd_serve_attn_positions_selected_total"] \
        == layers * steps * TOP
    assert counters["hvd_moe_rows_routed_total"] \
        + counters["hvd_moe_rows_absent_total"] == 2 * steps * k


# -- (b) routing, shares, vocabulary, positions ------------------------------


def test_routing_is_limited_to_the_best_groups():
    """8 experts in 4 groups of 2, 2 groups kept, top-3.  A group's score
    is the sum of its two largest biased scores: the row's best single
    expert (7) lies in a group whose pair loses and is not chosen."""
    logit = jnp.log(jnp.asarray([[.8, .7, .6, .65, .1, .1, .05, .9]])
                    / (1 - jnp.asarray([[.8, .7, .6, .65, .1, .1, .05, .9]])))
    x, router = jnp.ones((1, 1)), logit          # sigmoid(x W) = the scores
    chosen, weights = experts.route(x, router, jnp.zeros((8,)), 3, 2.5, 4, 2)
    assert sorted(np.asarray(chosen[0])) == [0, 1, 3]
    np.testing.assert_allclose(
        np.asarray(weights[0])[np.argsort(np.asarray(chosen[0]))],
        2.5 * np.asarray([.8, .7, .65]) / (.8 + .7 + .65), rtol=1e-5)
    free, _ = experts.route(x, router, jnp.zeros((8,)), 3, 2.5)
    assert sorted(np.asarray(free[0])) == [0, 1, 7]
    # The bias selects only: it lifts group 3's pair over group 1's.
    lifted, w = experts.route(x, router, jnp.asarray(
        [0, 0, 0, 0, 0, 0, .5, 0]), 3, 2.5, 4, 2)
    assert sorted(np.asarray(lifted[0])) == [0, 1, 7]
    assert float(jnp.sum(w)) == pytest.approx(2.5)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(params):
    """Over all 4 shares of 4 experts, the routed parts add up to what a
    chip holding all 16 gives: the shared expert, which every chip computes
    alike, is outside ``routed_ffn`` and counted once by whoever adds it."""
    rng = jax.random.split(jax.random.PRNGKey(3), 4)
    rows = jax.random.normal(rng[0], (24, 32))
    whole = {k: jax.random.normal(r, (2, 16) + shape) * 0.1
             for k, r, shape in (("w_in", rng[1], (32, 16)),
                                 ("w_gate", rng[2], (32, 16)),
                                 ("w_out", rng[3], (16, 32)))}
    chosen, weights = experts.route(
        rows, params["moe"]["router"][0], params["moe"]["router_bias"][0],
        3, 2.5, 4, 2)
    live = jnp.arange(24) % 5 > 0
    want, stats = experts.routed_ffn(rows, whole, 1, chosen, weights,
                                     jnp.float32, live)
    parts, routed = 0.0, 0
    for first in range(0, 16, 4):
        share = {k: v[:, first:first + 4] for k, v in whole.items()}
        y, s = experts.routed_ffn(rows, share, 1, chosen, weights,
                                  jnp.float32, live, first=first)
        parts, routed = parts + y, routed + int(s[0])
    np.testing.assert_allclose(parts, want, rtol=1e-5, atol=1e-6)
    assert routed == int(stats[0]) == 3 * int(jnp.sum(live))
    assert not np.asarray(want)[~np.asarray(live)].any()


def test_a_vocabulary_slice_is_a_smaller_vocabulary(params, reference_logits):
    """The first 40 rows of the embedding and of the head, a configuration
    of 40 ids: the same logits over the slice."""
    tokens = reference_logits[0] % 40
    sliced = {**params, "embed": params["embed"][:40],
              "head": params["head"][:40]}
    small = LatentMoEConfig(**{**CFG.__dict__, "vocab_size": 40})
    got = latent_moe.forward(sliced, tokens[None], small)
    np.testing.assert_allclose(got, FORWARD(params, tokens[None])[..., :40],
                               rtol=1e-5, atol=1e-6)


def test_yarn_frequencies_and_scale_by_hand():
    """The published sizes: d 64, theta 10000, factor 40, original 4096,
    beta 32 and 1.  low = floor(64 ln(4096 / 64 pi) / (2 ln 10000)) = 10,
    high = ceil(64 ln(4096 / 2 pi) / (2 ln 10000)) = 23."""
    published = {**YARN, "original_max_position_embeddings": 4096}
    cfg = LatentMoEConfig(rope_scaling=published, rope_theta=10000.0,
                          qk_nope_head_dim=128, qk_rope_head_dim=64)
    f = np.asarray(latent_moe.rope_frequencies(cfg), np.float64)
    base = 10000.0 ** (-np.arange(32) / 32)
    assert 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4)) \
        == pytest.approx(10.47, abs=0.01)
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(f[17], base[17] * (6 / 13 + 7 / 13 / 40),
                               rtol=1e-6)
    np.testing.assert_allclose(
        f, ref.yarn_frequencies(64, 10000.0, published), rtol=1e-6)
    m = 0.1 * math.log(40) + 1
    assert m == pytest.approx(1.3689, abs=1e-4)
    assert latent_moe.softmax_scale(cfg) == pytest.approx(m * m / 192 ** .5)
    plain = LatentMoEConfig()
    assert latent_moe.rope_frequencies(plain) == plain.rope_theta
    assert latent_moe.softmax_scale(plain) == pytest.approx(256 ** -.5)


# -- (c) the kernels ---------------------------------------------------------


def _lane(seed, lengths, smax=256, heads=4, width=16, layers=2):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    keys = jax.random.normal(k[0], (layers, len(lengths), smax, width))
    return (jax.random.normal(k[1], (len(lengths), heads, width)),
            jax.random.normal(k[2], (len(lengths), heads)), keys,
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("lengths", [[0, 5, 100, 255], [31, 32, 33, 0],
                                     [23, 24, 25, 200]])
def test_index_select_cuts_where_top_k_cuts(lengths, monkeypatch):
    monkeypatch.setattr(pda, "BLOCK_SHARED", 32)
    q, w, keys, pos = _lane(0, lengths)
    scores, cut, tie = jax.jit(partial(index_select, top=24))(
        q, w, keys, 1, pos)
    want = jnp.einsum("bh,bhs->bs", w, jax.nn.relu(
        jnp.einsum("bhk,bsk->bhs", q, keys[1])))
    written = jnp.arange(256)[None] <= pos[:, None]
    for b, n in enumerate(lengths):
        if not n:
            continue
        np.testing.assert_allclose(scores[b, 0, :n + 1], want[b, :n + 1],
                                   rtol=1e-5, atol=1e-5)
        assert not np.isfinite(np.asarray(
            scores[b, 0, n + 1:(n // 32 + 1) * 32])).any()
        got = pda.selected(scores[b, 0], cut[b, 0, 0], tie[b, 0, 0],
                           jnp.arange(256)) & written[b]
        _, best = jax.lax.top_k(jnp.where(written[b], want[b], -jnp.inf),
                                min(24, n + 1))
        assert sorted(np.flatnonzero(np.asarray(got))) \
            == sorted(np.asarray(best))


def test_equal_scores_at_the_cut_are_taken_by_position(monkeypatch):
    """Positions 40 to 99 hold one key: sixty equal scores, the largest of
    the lane with a weight that makes them so.  The cut takes the first
    of them by position, as ``lax.top_k`` does."""
    monkeypatch.setattr(pda, "BLOCK_SHARED", 32)
    q, w, keys, pos = _lane(1, [200], heads=1)
    w = jnp.abs(w)
    keys = keys.at[0, 0, 40:100].set(3.0 * q[0, 0])
    scores, cut, tie = index_select(q, w, keys, 0, pos, top=24)
    got = np.flatnonzero(np.asarray(pda.selected(
        scores[0, 0], cut[0, 0, 0], tie[0, 0, 0], jnp.arange(256)))[:201])
    assert list(got) == list(range(40, 64))
    assert int(tie[0, 0, 0]) == 63


def test_a_prompts_rows_select_what_a_step_selects(monkeypatch):
    """Row t of a prompt (``_selected_rows``) sees the positions a step at
    position t sees (``index_select``), with equal scores crowding the cut:
    small whole numbers, whose products and sums float32 holds exactly in
    any order, give a lane of 96 positions a dozen distinct scores.  And
    the prompt form sums float32 over the heads at full precision (the
    chip's default would round both factors to bfloat16)."""
    monkeypatch.setattr(pda, "BLOCK_SHARED", 32)
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    q_i = jax.random.randint(k[0], (1, 96, 4, 16), -2, 3).astype(jnp.float32)
    k_i = jax.random.randint(k[1], (1, 96, 16), -1, 2).astype(jnp.float32)
    w = jax.random.randint(k[2], (1, 96, 4), 1, 4).astype(jnp.float32) / 4
    rows = jnp.asarray([23, 24, 25, 40, 63, 64, 95])
    seen = latent_moe._selected_rows(q_i, k_i, w, 0, 96, 24)[0]
    lane = jnp.zeros((1, len(rows), 128, 16)).at[0, :, :96].set(k_i[0])
    scores, cut, tie = index_select(q_i[0, rows], w[0, rows], lane, 0, rows,
                                    top=24)
    at = jnp.arange(128)
    step = pda.selected(scores[:, 0], cut[:, 0, :1], tie[:, 0, :1],
                        at[None]) & (at[None] <= rows[:, None])
    assert np.array_equal(np.asarray(step[:, :96]), np.asarray(seen[rows]))
    crowded = np.asarray(scores[-1, 0, :96])
    assert len(np.unique(crowded)) < 48 and int(step[-1].sum()) == 24
    text = jax.jit(partial(latent_moe._selected_rows, lo=0, hi=96, top=24)
                   ).lower(q_i, k_i, w).as_text()
    assert "HIGHEST" in text


def selecting_masked_read(q, keys, value, layer, pos, *, select=None,
                          **kw):
    """``masked_read`` of the whole lane, the unselected masked out of it
    by giving them keys that no query can prefer."""
    if select is None:
        return masked_read(q, keys, value, layer, pos, **kw)
    scores, cut, tie = select
    seen = pda.selected(scores[:, 0], cut[:, 0, :1], tie[:, 0, :1],
                        jnp.arange(scores.shape[-1])[None])
    seen = seen & (jnp.arange(scores.shape[-1])[None] <= pos[:, None])
    last = kw.get("positions_last") or (False,) * len(keys)
    ks = [jax.lax.dynamic_index_in_dim(k.swapaxes(2, 3) if t else k, layer,
                                       0, False) for k, t in zip(keys, last)]
    logits = sum(jnp.einsum("bhk,btk->bht", qp, kp)
                 for qp, kp in zip(q, ks)) * kw["scale"]
    probs = jax.nn.softmax(jnp.where(seen[:, None], logits, -1e30), -1)
    return jnp.einsum("bht,btk->bhk", probs, ks[0])


B_PIN, S_PIN = 4, 256
PIN = LatentMoEConfig(**{**SIZES, "vocab_size": 64, "index_topk": 40,
                         "max_seq_len": S_PIN,
                         "compute_dtype": jnp.float32,
                         "param_dtype": jnp.float32})


def test_step_with_the_kernels_equals_a_masked_read_under_the_selection(
        monkeypatch):
    """Blocks of 32 positions in a lane of 256, the 40 best kept: over 12
    steps slot 0 never has more positions than the cut keeps, slot 2
    crosses a block's end at 32 and the cut's onset at 40, slot 3 is far
    past both, beside a free slot."""
    params = latent_moe.init(jax.random.PRNGKey(5), PIN)
    monkeypatch.setattr(pda, "BLOCK_SHARED", 32)
    lengths, steps = [3, 0, 30, 200], 12

    prefill = jax.jit(lambda p: latent_moe.prefill_request(params, p, PIN,
                                                           S_PIN))

    def logits():       # the step traced anew: it reads the attention then
        return uneven_steps(
            prefill, decode.slot_model(PIN, S_PIN).install,
            jax.jit(lambda tok, pos, state: latent_moe.decode_step(
                params, tok, pos, state, PIN)),
            latent_moe.init_state(PIN, B_PIN, S_PIN), lengths,
            PIN.vocab_size, steps)

    got, state = logits()
    scored, selected = (int(state["counters"][name])
                        for name in latent_moe.INDEX_COUNTERS)
    written = [n + 1 for length in lengths if length
               for n in range(length, length + steps)]
    assert scored == 3 * sum(written)
    assert selected == 3 * sum(min(n, 40) for n in written)
    monkeypatch.setattr(latent_moe, "decode_attention", selecting_masked_read)
    want, _ = logits()
    live = [b for b, n in enumerate(lengths) if n]
    np.testing.assert_allclose(got[:, live], want[:, live], rtol=2e-4,
                               atol=2e-5)
    assert np.isfinite(got).all()


def test_the_steps_kernels_carry_the_names_the_metrics_match():
    """``index_select_ms_per_turn.serve`` and ``sparse_attn_*`` match the
    Mosaic calls by these names; without an indexer the step keeps
    ``decode_attn`` and has no selecting kernel."""
    def kernels(cfg):
        state = jax.eval_shape(lambda: latent_moe.init_state(cfg, 2, S_PIN))
        params = jax.eval_shape(lambda k: latent_moe.init(k, cfg),
                                jax.random.PRNGKey(0))
        text = str(jax.make_jaxpr(partial(latent_moe.decode_step, cfg=cfg))(
            params, jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32),
            state))
        return {name for name in ("index_select", "sparse_attn",
                                  "decode_attn") if f"name={name}" in text}

    assert kernels(PIN) == {"index_select", "sparse_attn"}
    assert kernels(LatentMoEConfig(**{
        **SIZES, "index_topk": None, "index_n_heads": None,
        "index_head_dim": None, "max_seq_len": S_PIN,
        "compute_dtype": jnp.float32, "param_dtype": jnp.float32})) \
        == {"decode_attn"}


# -- (d) the state ------------------------------------------------------------


LANE_ELEMS = B_PIN * S_PIN * 8           # one layer's latents [B, S, 8]


@pytest.mark.parametrize("program", ["step", "install"])
def test_compiled_program_aliases_all_the_state_it_was_given(program):
    """The three caches (latents 8, rotary keys 4, index keys 8 values a
    position) and the nine counters are aliased from input to output of
    both programs."""
    got = serve_cache_programs(PIN, B_PIN, LANE_ELEMS)[program]
    assert len(latent_moe.counter_names(PIN)) == 9
    assert got["alias_bytes"] == 4 * (3 * B_PIN * S_PIN * (8 + 4 + 8) + 9)
    if program == "install":
        assert {op for _, op in got["big_ops"]} <= {
            "fusion:dynamic-update-slice", "dynamic-update-slice"}, got


def test_the_engine_donates_its_state_and_says_what_it_holds(params):
    tmx.configure(True)
    try:
        engine = DecodeEngine(params, CFG, max_batch=2, cache_len=CACHE_LEN)
        gauges = tmx.snapshot()["gauges"]
        assert gauges['hvd_serve_state_bytes{kind="index"}'] \
            == 3 * 2 * CACHE_LEN * 8 * 4
        assert gauges['hvd_serve_state_bytes{kind="kv"}'] \
            == 3 * 2 * CACHE_LEN * (8 + 4) * 4
        before = jax.tree.leaves({k: engine.state[k]
                                  for k in ("kv", "index")})
        engine.prefill(1, [3, 14, 15])
        assert all(a.is_deleted() for a in before)
        before = jax.tree.leaves(engine.state)
        engine.step()
        assert all(a.is_deleted() for a in before)
        assert int(engine.pos[1]) == 4
        engine.publish_counters()
        counters = tmx.snapshot()["counters"]
        assert counters["hvd_serve_index_positions_scored_total"] == 3 * 4
        assert counters["hvd_moe_rows_absent_total"] \
            + counters["hvd_moe_rows_routed_total"] == 2 * 3
    finally:
        tmx.configure(False)


# -- (e) the configurations without the new keys -----------------------------


PLAIN = LatentMoEConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                        moe_intermediate_size=16, num_hidden_layers=3,
                        num_attention_heads=2, q_lora_rank=8, kv_lora_rank=16,
                        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                        n_routed_experts=4, num_experts_per_tok=2,
                        max_seq_len=S_PIN, compute_dtype=jnp.float32,
                        param_dtype=jnp.float32)
# sha256 of the three programs' lowered text (StableHLO, no locations) for
# ``PLAIN`` on the commit before the indexer, the groups, the share and
# YaRN existed (jax 0.9.0).  A change that alters what a configuration
# WITHOUT those keys computes, or the order it computes it in, lands
# here: change the digest only with the benchmark's numbers for
# ``glm-4.7-flash_serve_context`` in hand.
LOWERED_BEFORE = {
    "step": "8f742447da0fcd2fa8b0cb540ab86ea666a956efd82738c173b79b07dbe0c4ec",
    "install":
        "1cd944d75bd3c90e5a2778f9173dff53e4e996c9f7380d18ffe1bc9ee703cfc9",
    "prefill":
        "2ddf03ad061ceba802c4f89b9874c606cf3e227489778817dfb8897d1eab37bf"}


@pytest.mark.parametrize("program", list(LOWERED_BEFORE))
def test_without_the_new_keys_the_programs_lower_as_before(program,
                                                           monkeypatch):
    # The 40-token prompt's 80 pairs over 4 experts all held would take the
    # one kernel (``experts.one_kernel``: 8 pairs an expert or more; the
    # cell's 64 experts and prompts of 512 and more never do); the digests
    # are the grouped products', so the rule is off here.
    monkeypatch.setattr(experts, "RESIDENT_ROWS", 0)
    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    def specs(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    model = decode.slot_model(PLAIN, S_PIN)
    held = specs(jax.eval_shape(
        lambda k: model.held(latent_moe.init(k, PLAIN)),
        jax.random.PRNGKey(0)))
    state = specs(jax.eval_shape(lambda: model.init_state(B_PIN)))
    assert set(state) == {"kv", "counters"} and len(state["counters"]) == 6
    if program == "step":
        lowered = jax.jit(decode.named(decode.STEP_PROGRAM, model.step),
                          donate_argnums=(3,)).lower(
            held, spec((B_PIN,)), spec((B_PIN,)), state)
    elif program == "install":
        lowered = jax.jit(
            decode.named(decode.INSTALL_PROGRAM,
                         partial(decode.install, model)),
            donate_argnums=(0,)).lower(
            state, spec((B_PIN,)), spec((B_PIN,)), spec(()),
            spec((PLAIN.vocab_size,), jnp.float32),
            specs(jax.eval_shape(lambda: model.init_state(1))), spec(()))
    else:
        lowered = jax.jit(decode.named(decode.PREFILL_PROGRAM,
                                       model.prefill)).lower(
            held, spec((40,)))
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest() \
        == LOWERED_BEFORE[program]
