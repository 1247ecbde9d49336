"""models/ssd_moe.py at a small size on the CPU: all three layer kinds in
a pattern of two periods, 8 experts top-3 of which 4 are held, float32
compute.

1. ``forward`` against the plain reference (perfbench/reference/
   ssd_moe_lm.py: step-by-step recurrence, a loop over experts) on seeded
   weights;
2. prefill then decode through the slots (slots at different positions, a
   free slot, a slot used twice) against the full forward pass;
3. the chunked form of the Mamba-2 recurrence against its step-by-step
   form, outputs and end state;
4. the shares add up: experts 0-3 and 4-7 with the shared expert counted
   once equal the uncut layer;
5. ``routed_ffn``'s relu^2 form against a loop over experts, and its gated
   form to the bit what it was.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import experts, ssd_moe
from horovod_tpu.serving.decode import DecodeEngine
from perfbench.reference import ssd_moe_lm as ref
from test_pallas_decode_attention import \
    step_reads_blocks_and_equals_the_masked_step

V = 96
PATTERN = "EMEM*" * 2
CFG = ssd_moe.SsdMoEConfig(
    vocab_size=V, hidden_size=32, num_hidden_layers=len(PATTERN),
    hybrid_override_pattern=PATTERN, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
    n_routed_experts=8, num_experts_per_tok=3, routed_scaling_factor=2.5,
    max_seq_len=64, compute_dtype=jnp.float32, param_dtype=jnp.float32,
    experts_held=4, expert_first=0)
SIZES = {**{f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)},
         "experts_held": 4, "expert_first": 0}


def seeded(cfg, seed=0):
    """``init``'s weights with every gain and the mixer's ``D`` moved off
    one, so that a gain left out or misplaced shows."""
    params = jax.jit(lambda k: ssd_moe.init(k, cfg))(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 8))

    def moved(a):
        return a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype)

    for kind, names in (("mamba", ("ln", "norm", "d")), ("moe", ("ln",)),
                        ("attn", ("ln",))):
        for name in names:
            params[kind][name] = moved(params[kind][name])
    params["ln_f"] = moved(params["ln_f"])
    return params


@pytest.fixture(scope="module")
def params():
    return seeded(CFG)


def forward(params, tokens, cfg=CFG):
    """``ssd_moe.forward`` of one row, as one program a length."""
    return jax.jit(lambda p, t: ssd_moe.forward(p, t[None], cfg)[0])(
        params, jnp.asarray(tokens))


# -- 1. forward against the reference ----------------------------------------


@pytest.mark.parametrize("length", [5, 24, 37])
def test_forward_is_the_references_forward(params, length):
    """Both sides float32 on the same weights.  The program multiplies at
    the CPU's default float32 precision and sums the chunked form in
    another order than the recurrence: 2e-4 of the logits' spread (0.06 to
    0.1 here) is some hundred float32 roundings of a logit; a wrong gain,
    group, decay or expert moves them by their spread."""
    tokens = jax.random.randint(jax.random.PRNGKey(length), (length,), 1, V)
    got = forward(params, tokens)
    want = ref.Forward(SIZES).logits(params, tokens)
    assert float(jnp.std(want)) > 0.03
    np.testing.assert_allclose(got, want, atol=2e-4 * float(jnp.std(want))
                               + 2e-5, rtol=0)
    # and the tokens they put first agree wherever the reference's best
    # leads by more than that
    top2 = jnp.sort(want, axis=-1)[:, -2:]
    clear = np.asarray(top2[:, 1] - top2[:, 0] > 1e-3)
    assert clear.any()
    np.testing.assert_array_equal(np.argmax(got, -1)[clear],
                                  np.argmax(want, -1)[clear])


# -- 2. prefill, then decode through the slots -------------------------------


def test_slots_decode_what_the_full_forward_pass_gives(params):
    """Four slots: two requests at different positions, a free slot
    between them, and slot 3 used twice (its first tenant's state must not
    reach its second).  Every step's logits for a live slot are the full
    forward pass's at that position, to float32 rounding (the chunked
    prompt form against one recurrence step a token: another summation
    order)."""
    cache_len, steps = 64, 6
    prompts = {0: 11, 2: 19, 3: 11}     # two prefill programs
    rng = np.random.default_rng(0)
    rows = {s: rng.integers(1, V, size=n + steps).tolist()
            for s, n in prompts.items()}
    prefill = jax.jit(lambda p: ssd_moe.prefill_request(params, p, CFG,
                                                        cache_len))
    step = jax.jit(lambda tok, pos, state: ssd_moe.decode_step(
        params, tok, pos, state, CFG))
    from horovod_tpu.models.layers import install_request

    state = ssd_moe.init_state(CFG, 4, cache_len)
    # slot 3's first tenant: a longer request, stepped, then retired
    _, first = prefill(jnp.asarray(rng.integers(1, V, size=19)))
    state = install_request(state, 3, first, ssd_moe.SLOT_AXES)
    tok = jnp.asarray([0, 0, 0, 5])
    pos = jnp.asarray([0, 0, 0, 19])
    for _ in range(3):
        _, state = step(tok, pos, state)
        pos = jnp.where(pos > 0, pos + 1, 0)
    pos = jnp.zeros((4,), jnp.int32)
    tok = jnp.zeros((4,), jnp.int32)
    full = {s: forward(params, r) for s, r in rows.items()}
    for s, n in prompts.items():
        logits, request = prefill(jnp.asarray(rows[s][:n]))
        np.testing.assert_allclose(logits, full[s][n - 1], atol=2e-5)
        state = install_request(state, s, request, ssd_moe.SLOT_AXES)
        tok, pos = tok.at[s].set(rows[s][n]), pos.at[s].set(n)
    for i in range(steps - 1):
        logits, state = step(tok, pos, state)
        assert np.isfinite(np.asarray(logits)).all()
        for s, n in prompts.items():
            np.testing.assert_allclose(logits[s], full[s][n + i], atol=2e-5,
                                       err_msg=f"slot {s} step {i}")
            tok = tok.at[s].set(rows[s][n + i + 1])
        pos = jnp.where(pos > 0, pos + 1, 0)
    c = {k: int(v) for k, v in state["counters"].items()}
    Lm, Le = CFG.n_layers("mamba"), CFG.n_layers("moe")
    turns = 3 + steps - 1
    # the steps taken are the live slots' (the free slot's state is not
    # stepped): three turns of one request, then three requests a turn
    assert c["hvd_ssm_state_steps_total"] == c[
        "hvd_ssm_state_steps_live_total"] == Lm * (3 + 3 * (steps - 1))
    assert c["hvd_moe_layer_turns_total"] == Le * turns
    assert (c["hvd_moe_rows_routed_total"] + c["hvd_moe_rows_absent_total"]
            == Le * 3 * (3 + 3 * (steps - 1)))
    # four prefills' chunks of 8: 19, 11, 19 and 11 rows
    assert c["hvd_ssm_prefill_chunks_total"] == Lm * (3 + 2 + 3 + 2)


def test_step_reads_its_lane_by_blocks_and_equals_the_masked_read(
        params, monkeypatch):
    """The positions-first lanes through ``layers.lane_reader`` (the
    kernel's head axis: four query rows on two heads) against the masked
    read of the whole lane."""
    step_reads_blocks_and_equals_the_masked_step(monkeypatch, ssd_moe,
                                                 params, CFG)


def test_engine_serves_it_and_counts_on_the_device(params):
    """Through ``DecodeEngine`` (the one install, donated state): greedy
    tokens are the full forward pass's, and the two free slots' state is
    not stepped."""
    engine = DecodeEngine(params, CFG, max_batch=3, cache_len=32)
    prompt = [3, 14, 15, 9, 26, 5, 35]
    got = [engine.prefill(1, prompt)]
    for _ in range(4):
        got.append(int(engine.step()[1]))
    logits = forward(params, prompt + got)      # causal: one pass for all
    assert got == np.argmax(logits, -1)[len(prompt) - 1:-1].tolist()
    c = engine.counters()
    assert c["hvd_ssm_state_steps_total"] == c[
        "hvd_ssm_state_steps_live_total"] == 4 * CFG.n_layers("mamba")
    assert not np.asarray(engine.state["recurrent"][0])[:, [0, 2]].any()
    assert c["hvd_ssm_prefill_chunks_total"] == CFG.n_layers("mamba")


# -- 3. the chunked form against the recurrence ------------------------------


@pytest.mark.parametrize("length", [1, 127, 128, 129, 300])
def test_chunked_form_is_the_recurrence(length):
    """Outputs and end state, from a non-zero start state, chunks of 128.
    Float32 on both sides; the chunked form sums a row's 128 terms as a
    product and carries decays as differences of cumulative sums: 1e-4 of
    the outputs' size is some tens of float32 roundings (the decays reach
    exp(-1.6 x 128) and are not small against what they multiply)."""
    B, H, P, G, N = 2, 4, 8, 2, 16
    rng = np.random.default_rng(length)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    x, s0 = normal(length, B, H, P), normal(B, H, P, N)
    b_in, c_out = normal(length, B, G, N), normal(length, B, G, N)
    dt = jnp.asarray(np.log1p(np.exp(rng.standard_normal((length, B, H))
                                     - 2)), jnp.float32)
    a = jnp.asarray(-rng.uniform(1.0, 16.0, size=(H,)), jnp.float32)

    def step(s, t):
        y, s = ssd_moe._ssd_step(s, t[0], t[1], a, t[2], t[3])
        return s, y

    want_s, want_y = jax.jit(lambda: jax.lax.scan(
        step, s0, (x, dt, b_in, c_out)))()
    got_y, got_s = jax.jit(lambda: ssd_moe._ssd_scan(
        s0, x, dt, a, b_in, c_out, 128))()
    scale = float(jnp.max(jnp.abs(want_y)))
    np.testing.assert_allclose(got_y, want_y, atol=1e-4 * scale, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=1e-4 * float(
        jnp.max(jnp.abs(want_s))), rtol=0)


# -- 4. the shares add up ----------------------------------------------------


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(params):
    """One expert layer three ways on the same rows: experts 0-3 held,
    experts 4-7 held, all 8 held.  The routed parts of the two shares add
    up to the uncut layer's (the shared expert, computed alike on every
    chip, is no part of ``routed_ffn``: counted once by leaving it out of
    both), and every pair is routed exactly once."""
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 4))
    D, F, E, k = 32, 24, 8, 3
    rows = jax.random.normal(next(keys), (40, D))
    whole = {"w_in": 0.3 * jax.random.normal(next(keys), (2, E, D, F)),
             "w_out": 0.3 * jax.random.normal(next(keys), (2, E, F, D))}
    lp = jax.tree.map(lambda a: a[1], params["moe"])
    chosen, weights = experts.route(rows, lp["router"], lp["router_bias"], k,
                                    2.5)
    uncut, stats = experts.routed_ffn(rows, whole, 1, chosen, weights,
                                      jnp.float32)
    parts, routed = [], 0
    for first in (0, 4):
        held = jax.tree.map(lambda a: a[:, first:first + 4], whole)
        y, s = experts.routed_ffn(rows, held, 1, chosen, weights, jnp.float32,
                                  first=first)
        parts.append(y)
        routed += int(s[0])
    assert routed == int(stats[0]) == 40 * k
    assert float(jnp.max(jnp.abs(parts[0]))) > 0.1
    np.testing.assert_allclose(parts[0] + parts[1], uncut, atol=1e-5)


def test_a_share_of_the_model_is_the_references_share(params):
    """The whole model with experts 4-7 held (another ``expert_first``)
    against the reference given the same share."""
    cfg = dataclasses.replace(CFG, expert_first=4)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (21,), 1, V)
    got = forward(params, tokens, cfg)
    want = ref.Forward({**SIZES, "expert_first": 4}).logits(params, tokens)
    np.testing.assert_allclose(got, want, atol=2e-4 * float(jnp.std(want))
                               + 2e-5, rtol=0)
    other = forward(params, tokens)
    assert float(jnp.max(jnp.abs(other - got))) > 1e-3


# -- 5. the two forms of an expert -------------------------------------------


def _expert_stacks(gated):
    rng = np.random.default_rng(11)
    L, E, D, F, T, k = 2, 6, 16, 12, 30, 2

    def normal(*shape):
        return jnp.asarray(0.4 * rng.standard_normal(shape), jnp.float32)

    stack = {"w_in": normal(L, E, D, F), "w_out": normal(L, E, F, D)}
    if gated:
        stack["w_gate"] = normal(L, E, D, F)
    x = 2.5 * normal(T, D)
    chosen = jnp.asarray(rng.integers(0, E, size=(T, k)), jnp.int32)
    weights = jnp.asarray(rng.uniform(size=(T, k)), jnp.float32)
    return stack, x, chosen, weights


@pytest.mark.parametrize("live", [None, "some"])
def test_relu2_form_is_a_loop_over_experts(live):
    stack, x, chosen, weights = _expert_stacks(gated=False)
    alive = None if live is None else jnp.arange(x.shape[0]) % 3 > 0
    got, stats = experts.routed_ffn(x, stack, 1, chosen, weights,
                                    jnp.float32, alive)
    want = jnp.zeros_like(x)
    for e in range(stack["w_in"].shape[1]):
        h = jnp.square(jax.nn.relu(x @ stack["w_in"][1, e]))
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        want = want + w_e[:, None] * (h @ stack["w_out"][1, e])
    if alive is not None:
        want = jnp.where(alive[:, None], want, 0.0)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert int(stats[0]) == (x.shape[0] if alive is None
                             else int(jnp.sum(alive))) * chosen.shape[1]


def test_a_stack_padded_to_whole_tiles_gives_what_the_published_one_gives():
    """Zero rows and columns past the published widths change nothing:
    the rows are padded to match and cut again."""
    stack, x, chosen, weights = _expert_stacks(gated=False)
    want, _ = experts.routed_ffn(x, stack, 1, chosen, weights, jnp.float32)
    held = {"w_in": jnp.pad(stack["w_in"], [(0, 0), (0, 0), (0, 8), (0, 4)]),
            "w_out": jnp.pad(stack["w_out"],
                             [(0, 0), (0, 0), (0, 4), (0, 8)])}
    got, _ = experts.routed_ffn(x, held, 1, chosen, weights, jnp.float32)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert experts.padded_width(2688) == 3072
    assert experts.padded_width(1856) == 2048
    assert experts.padded_width(2048) == 2048
    assert experts.padded_width(24) == 24


def test_model_with_padded_stacks_is_the_references(monkeypatch):
    """With tiles of 16 the small model's routed stacks are held 48 x 32
    for the published 40 x 24, on both sides' ``make``; the reference
    reads the published corner."""
    monkeypatch.setattr(experts, "TILE", 16)
    monkeypatch.setattr(ref, "TILE", 16)
    cfg = dataclasses.replace(CFG, hidden_size=40, num_hidden_layers=1,
                              hybrid_override_pattern="E")
    sizes = {**SIZES, "hidden_size": 40, "num_hidden_layers": 1,
             "hybrid_override_pattern": "E"}
    params = seeded(cfg)
    assert params["moe"]["w_in"].shape == (1, 4, 48, 32)
    assert params["moe"]["w_out"].shape == (1, 4, 32, 48)
    made = ref.make_weights(jax.random.PRNGKey(0), sizes)
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, params)
    assert not np.asarray(made["moe"]["w_in"][:, :, 40:]).any()
    assert not np.asarray(made["moe"]["w_out"][:, :, 24:]).any()
    tokens = jax.random.randint(jax.random.PRNGKey(5), (19,), 1, V)
    got = forward(params, tokens, cfg)
    want = ref.Forward(sizes).logits(params, tokens)
    np.testing.assert_allclose(got, want, atol=2e-4 * float(jnp.std(want))
                               + 2e-5, rtol=0)


def test_gated_form_is_to_the_bit_what_it_was(monkeypatch):
    """The gated form as grouped products, written out as ``routed_ffn``
    made it before it took the expert's form from its stack.  (60 pairs
    over 6 experts all held would take the one kernel, which sums in
    another order: tests/test_pallas_routed_ffn.py holds that form to
    this one; here the rule is off.)"""
    stack, x, chosen, weights = _expert_stacks(gated=True)
    assert experts.one_kernel(stack, chosen.size)
    monkeypatch.setattr(experts, "RESIDENT_ROWS", 0)
    layer, dtype = 1, jnp.float32
    got, stats = experts.routed_ffn(x, stack, layer, chosen, weights, dtype)

    T, k = chosen.shape
    L, E = stack["w_in"].shape[:2]
    flat = chosen.reshape(T * k)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E]
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((L * E,), jnp.int32), counts, (layer * E,))
    xs = x.astype(dtype)[order // k]

    def grouped(rows, w):
        return jax.lax.ragged_dot(
            rows, w.reshape((L * E,) + w.shape[2:]).astype(dtype), groups)

    h = grouped(xs, stack["w_in"]) * jax.nn.silu(
        grouped(xs, stack["w_gate"]))
    ys = grouped(h, stack["w_out"])
    in_a_group = jnp.arange(T * k) < jnp.sum(counts)
    ys = jnp.where(in_a_group[:, None], ys, 0)[jnp.argsort(order)]
    want = jnp.sum(ys.reshape(T, k, -1).astype(jnp.float32)
                   * weights[..., None], axis=1).astype(dtype)
    np.testing.assert_array_equal(got, want)
    assert stats.tolist() == [T * k, int(jnp.sum(counts > 0)),
                              int(jnp.max(counts))]


# -- the configuration -------------------------------------------------------


@pytest.mark.parametrize("bad", [
    dict(hybrid_override_pattern="MEM"),
    dict(hybrid_override_pattern="EMEM*EMEMX"),
    dict(n_groups=3),
    dict(num_key_value_heads=3),
    dict(experts_held=4, expert_first=5),
    dict(num_experts_per_tok=9)])
def test_config_refuses_what_it_cannot_be(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


def test_published_defaults_are_the_published_model():
    cfg = ssd_moe.SsdMoEConfig()
    kinds = cfg.layer_kinds
    assert (kinds.count("mamba"), kinds.count("moe"), kinds.count("attn")) \
        == (23, 23, 6)
    assert (cfg.d_inner, cfg.conv_dim) == (4096, 6144)
    # the in-projection's width, and a slot's state a layer: 2.10 MB
    assert cfg.d_inner + cfg.conv_dim + cfg.mamba_num_heads == 10304
    assert 4 * 64 * 64 * 128 == 2097152
    state = jax.eval_shape(lambda: ssd_moe.init_state(
        dataclasses.replace(cfg, num_hidden_layers=9,
                            hybrid_override_pattern="EMEMEMEM*"), 96, 4096))
    ssm, conv = state["recurrent"]
    # a slot's [64, 64, 128] a layer, a group's 8 heads' channels minor
    assert ssm.shape == (4, 96, 8, 128, 512) and ssm.dtype == jnp.float32
    assert conv.shape == (4, 3, 96, 6144) and conv.dtype == jnp.bfloat16
    assert state["kv"][0].shape == (1, 96, 4096, 2, 128)
