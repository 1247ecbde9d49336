"""models/conv_moe.py at a small size on the CPU: both operator kinds, one
dense layer then a pattern of two periods, 8 experts top-3 all held,
float32 compute.

1. ``forward`` against the plain reference (perfbench/reference/
   conv_moe_lm.py: the convolution by shifted sums over the whole row, the
   rotation written apart, a loop over experts) on seeded weights;
2. prefill then decode through the slots (slots at different positions, a
   free slot, a slot used twice, prompts of 1 and of 2 tokens: shorter
   than the window) against the full forward pass;
3. the short convolution's step from a kept window against its form over
   the rows, outputs and the window kept;
4. the shared attention with norms and rotation against the reference's,
   and with neither to the bit what ``_attention_no_positions`` gave;
5. the router's choice is by the biased score and its weights by the
   unbiased one; a row's output does not change when its neighbours do.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.models import conv_moe, experts, layers
from horovod_tpu.models.layers import install_request
from perfbench.reference import conv_moe_lm as ref
from perfbench.reference import moe_lm as ref_moe
from test_pallas_decode_attention import \
    step_reads_blocks_and_equals_the_masked_step

V = 96
PERIOD = ("conv", "conv", "full_attention", "conv")
CFG = conv_moe.ConvMoEConfig(
    vocab_size=V, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=24, num_hidden_layers=9,
    layer_types=("conv",) + PERIOD * 2, num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, num_experts=8,
    num_experts_per_tok=3, max_seq_len=64, compute_dtype=jnp.float32,
    param_dtype=jnp.float32)
SIZES = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)}


def seeded(cfg, seed=0):
    """``init``'s weights with every gain moved off one, so that a gain
    left out or misplaced shows."""
    params = jax.jit(lambda k: conv_moe.init(k, cfg))(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 8))

    def moved(a):
        return a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype)

    for kind, names in (("conv", ("ln",)), ("dense", ("ln",)),
                        ("moe", ("ln",)),
                        ("attn", ("ln", "q_norm", "k_norm"))):
        for name in names:
            params[kind][name] = moved(params[kind][name])
    params["ln_f"] = moved(params["ln_f"])
    return params


@pytest.fixture(scope="module")
def params():
    return seeded(CFG)


def forward(params, tokens, cfg=CFG):
    """``conv_moe.forward`` of one row, as one program a length."""
    return jax.jit(lambda p, t: conv_moe.forward(p, t[None], cfg)[0])(
        params, jnp.asarray(tokens))


# -- 1. forward against the reference ----------------------------------------


@pytest.mark.parametrize("length", [1, 2, 24, 37])
def test_forward_is_the_references_forward(params, length):
    """Both sides float32 on the same weights.  The program multiplies at
    the CPU's default float32 precision and the reference at the highest:
    2e-4 of the logits' spread (about 0.1 here) is some hundred float32
    roundings of a logit; a wrong gain, gate, shift, rotation or expert
    moves them by their spread."""
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


def test_the_reference_reads_the_published_corner_of_a_held_stack(params):
    """Stacks held wider than published, zeros past the width (at the
    published sizes ``init`` holds them ``experts.padded_width`` wide),
    give the program and the reference the logits of stacks held as
    published: the padding is no part of the model."""
    assert params["moe"]["w_in"].shape[-1] == experts.padded_width(24) == 24
    wide = dict(params)
    wide["moe"] = {**params["moe"], **{
        k: jnp.pad(params["moe"][k], [(0, 0)] * 3 + [(0, 8)])
        for k in ("w_gate", "w_in")},
        "w_out": jnp.pad(params["moe"]["w_out"],
                         [(0, 0), (0, 0), (0, 8), (0, 0)])}
    tokens = jnp.arange(1, 20)
    np.testing.assert_allclose(forward(wide, tokens), forward(params, tokens),
                               atol=1e-6)
    np.testing.assert_array_equal(ref.Forward(SIZES).logits(wide, tokens),
                                  ref.Forward(SIZES).logits(params, tokens))


# -- 2. prefill, then decode through the slots -------------------------------


def test_slots_decode_what_the_full_forward_pass_gives(params):
    """Five slots: requests at different positions, two of them with
    prompts shorter than the convolution's window (1 and 2 tokens), a free
    slot between them, and slot 3 used twice (its first tenant's window
    and lane must not reach its second).  Every step's logits for a live
    slot are the full forward pass's at that position, to float32
    rounding (another summation order in the attention over a lane)."""
    cache_len, steps = 64, 6
    prompts = {0: 11, 2: 1, 3: 11, 4: 2}
    rng = np.random.default_rng(0)
    rows = {s: rng.integers(1, V, size=n + steps).tolist()
            for s, n in prompts.items()}
    prefill = jax.jit(lambda p: conv_moe.prefill_request(params, p, CFG,
                                                         cache_len))
    step = jax.jit(lambda tok, pos, state: conv_moe.decode_step(
        params, tok, pos, state, CFG))
    state = conv_moe.init_state(CFG, 5, cache_len)
    # slot 3's first tenant: a longer request, stepped, then retired
    _, first = prefill(jnp.asarray(rng.integers(1, V, size=19)))
    state = install_request(state, 3, first, conv_moe.SLOT_AXES)
    tok = jnp.asarray([0, 0, 0, 5, 0])
    pos = jnp.asarray([0, 0, 0, 19, 0])
    for _ in range(3):
        _, state = step(tok, pos, state)
        pos = jnp.where(pos > 0, pos + 1, 0)
    pos = jnp.zeros((5,), jnp.int32)
    tok = jnp.zeros((5,), jnp.int32)
    full = {s: forward(params, r) for s, r in rows.items()}
    for s, n in prompts.items():
        logits, request = prefill(jnp.asarray(rows[s][:n]))
        np.testing.assert_allclose(logits, full[s][n - 1], atol=2e-5)
        state = install_request(state, s, request, conv_moe.SLOT_AXES)
        tok, pos = tok.at[s].set(rows[s][n]), pos.at[s].set(n)
    before = {k: int(v) for k, v in state["counters"].items()}
    for i in range(steps - 1):
        logits, state = step(tok, pos, state)
        assert np.isfinite(np.asarray(logits)).all()
        for s, n in prompts.items():
            np.testing.assert_allclose(logits[s], full[s][n + i], atol=2e-5,
                                       err_msg=f"slot {s} step {i}")
        tok = jnp.asarray([rows[s][prompts[s] + i + 1] if s in prompts
                           else 0 for s in range(5)])
        pos = jnp.where(pos > 0, pos + 1, 0)
    grew = {k: int(v) - before[k] for k, v in state["counters"].items()}
    turns = steps - 1
    # four live rows x top-3 x 8 expert layers a turn; the free slot's row
    # is routed nowhere; of the two layers' lanes the four live slots' are
    # read, one block each (a lane of 64 is one block), the free slot's not
    assert grew["hvd_moe_rows_routed_total"] == turns * 4 * 3 * 8
    assert grew["hvd_moe_layer_turns_total"] == turns * 8
    assert grew["hvd_serve_attn_positions_held_total"] \
        == turns * 2 * 5 * cache_len
    assert grew["hvd_serve_attn_positions_read_total"] \
        == turns * 2 * 4 * cache_len


def test_step_reads_its_lanes_by_blocks_and_equals_the_masked_read(
        params, monkeypatch):
    """The merged lanes through ``layers.lane_reader`` (each query row in
    its own head's place of the two heads' 16) against the masked read of
    the whole lane, rotation and norms included."""
    step_reads_blocks_and_equals_the_masked_step(monkeypatch, conv_moe,
                                                 params, CFG)


def test_the_engine_serves_the_configuration_through_the_one_seam(params):
    """``DecodeEngine`` finds the module by its config's type, and the
    tokens it decodes greedily are the full forward pass's."""
    from horovod_tpu.serving.decode import DecodeEngine

    engine = DecodeEngine(params, CFG, max_batch=3, cache_len=32)
    prompt = [7, 3, 9, 21, 4]
    row = list(prompt) + [engine.prefill(1, prompt)]
    for _ in range(5):
        row.append(int(engine.step()[1]))
    want = np.argmax(np.asarray(forward(params, row[:-1])), -1)
    assert row[len(prompt):] == want[len(prompt) - 1:].tolist()
    assert set(engine.state) == {"kv", "recurrent", "counters"}


# -- 3. the short convolution: a step from its window, or over the rows ------


@pytest.mark.parametrize("length", [1, 2, 3, 40])
def test_short_convolution_steps_to_what_its_rows_give(params, length):
    """``_short_conv`` one token at a time from the kept window gives the
    outputs of the form over all rows, and keeps the window that form
    ends in: the gate's last two products (zeros where the sequence is
    shorter than that)."""
    lp = layers._at(params["conv"], 1)
    u = jax.random.normal(jax.random.PRNGKey(length), (2, length, 32))
    whole, window = conv_moe._short_conv(u, lp, jnp.float32)
    kept = jnp.zeros((2, 2, 32))
    outs = []
    for t in range(length):
        out, kept = conv_moe._short_conv(u[:, t:t + 1], lp, jnp.float32, kept)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), whole, atol=1e-6)
    np.testing.assert_allclose(kept, window, atol=1e-6)
    assert window.shape == (2, 2, 32)
    # the window IS the last two rows of B * x, zeros before the start
    bcx = u @ lp["in_proj"]
    z = jnp.pad(bcx[..., :32] * bcx[..., 64:], [(0, 0), (2, 0), (0, 0)])
    np.testing.assert_allclose(window, z[:, -2:].swapaxes(0, 1), atol=1e-6)
    # and the output the definition's: C * sum_j w_j z_{t-2+j}, then W_out
    c = sum(lp["conv_w"][j] * z[:, j:j + length] for j in range(3))
    np.testing.assert_allclose(
        whole, (bcx[..., 32:64] * c) @ lp["out_proj"], atol=1e-6)


# -- 4. the one attention on grouped heads -----------------------------------


def _attention_no_positions(x, lp, dtype, cache=None,
                            heads_first: bool = True):
    """``layers._attention_no_positions`` as it stood before it took a
    norm and a rotation (PR 48's text), kept here to hold
    ``layers._grouped_attention`` to it."""
    B, S, _ = x.shape
    KVH, HD = lp["wk"].shape[-2:]
    G = lp["wq"].shape[-2] // KVH
    kept_as = "bhsk" if heads_first else "bshk"
    lane = "bktd" if heads_first else "btkd"
    q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"].astype(dtype))
    k = jnp.einsum(f"bsd,dhk->{kept_as}", x, lp["wk"].astype(dtype))
    v = jnp.einsum(f"bsd,dhk->{kept_as}", x, lp["wv"].astype(dtype))
    q = q.reshape(B, S, KVH, G, HD)
    if cache is None:
        keys, values, kept = k, v, (k, v)
        valid = jnp.tril(jnp.ones((S, S), jnp.bool_))[None]
    else:
        ks, vs, layer, pos = cache
        rows = jnp.arange(B)
        if heads_first:
            ks = ks.at[layer, rows, :, pos].set(k[:, :, 0])
            vs = vs.at[layer, rows, :, pos].set(v[:, :, 0])
        else:
            ks = ks.at[layer, rows, pos].set(k[:, 0])
            vs = vs.at[layer, rows, pos].set(v[:, 0])
        keys = lax.dynamic_index_in_dim(ks, layer, 0, keepdims=False)
        values = lax.dynamic_index_in_dim(vs, layer, 0, keepdims=False)
        kept = (ks, vs)
        valid = (jnp.arange(keys.shape[2 if heads_first else 1])[None, :]
                 <= pos[:, None])[:, None]
    logits = jnp.einsum(f"bskgd,{lane}->bkgst", q, keys
                        ).astype(jnp.float32) / math.sqrt(HD)
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    ctx = jnp.einsum(f"bkgst,{lane}->bskgd", probs, values)
    ctx = ctx.reshape(B, S, KVH * G, HD)
    return jnp.einsum("bshk,hkd->bsd", ctx, lp["wo"].astype(dtype)), kept


@pytest.mark.parametrize("layout", ["heads_first", "positions_first"])
@pytest.mark.parametrize("cached", [False, True])
def test_attention_with_neither_argument_is_what_it_was(params, layout,
                                                        cached):
    """The two accepted cells' attention: bit for bit, with and without a
    lane, in both of their layouts."""
    heads_first = layout == "heads_first"
    lp = layers._at(params["attn"], 0)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 1 if cached else 9, 32))
    cache = None
    if cached:
        shape = (2, 3, 2, 16, 8) if heads_first else (2, 3, 16, 2, 8)
        cache = (*jax.random.normal(jax.random.PRNGKey(4), (2,) + shape),
                 1, jnp.asarray([0, 5, 15]))
    got = layers._grouped_attention(
        x, lp, jnp.float32, cache and (*cache, None), layout)
    want = _attention_no_positions(x, lp, jnp.float32, cache, heads_first)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


def test_attention_takes_one_of_three_layouts(params):
    lp = layers._at(params["attn"], 0)
    with pytest.raises(ValueError, match="layout"):
        layers._grouped_attention(jnp.zeros((1, 2, 32)), lp, jnp.float32,
                                  layout="heads_last")


# Lanes [La, B, Smax, ...] of two layers, two slots and 16 positions, and
# the positions' axis of a request's rows [B, ...], in each layout.
LANES = {"heads_first": ((2, 2, 2, 16, 8), 2),
         "positions_first": ((2, 2, 16, 2, 8), 1),
         "merged": ((2, 2, 16, 16), 1)}


@pytest.mark.parametrize("layout", list(LANES))
def test_attention_with_norms_and_rotation_is_the_references(params, layout):
    """Over a prompt's rows, and one token a slot against a lane filled
    from those rows, in every layout: the reference's operator (its own
    rotation, the full masked softmax), to float32 rounding; the keys are
    kept rotated."""
    lp = layers._at(params["attn"], 1)
    eps, theta = CFG.norm_eps, CFG.rope_theta
    S = 12
    shape, axis = LANES[layout]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, S, 32))
    # the reference's operator norms its input itself: hand it rows of
    # unit mean square, so that its norm with eps 0 is the identity
    unit = u * lax.rsqrt(jnp.mean(u * u, -1, keepdims=True))
    want = jnp.stack([ref.attention_operator(
        {**lp, "ln": jnp.ones((32,))}, r, eps=0.0, theta=theta) - r
        for r in unit])
    kw = dict(layout=layout, qk_norm=0.0, rope=theta)
    got, (k, v) = layers._grouped_attention(unit, lp, jnp.float32, **kw)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the last row again, as one token a slot against the lanes of the rows
    # before it: slot 0 holds row 0's sequence, slot 1 row 1's
    lanes = [lax.dynamic_update_slice(
        jnp.zeros(shape), lax.slice_in_dim(t, 0, S - 1, axis=axis)[None],
        (1,) + (0,) * (len(shape) - 1)) for t in (k, v)]
    step, (ks, _) = layers._grouped_attention(
        unit[:, -1:], lp, jnp.float32,
        (*lanes, 1, jnp.asarray([S - 1, S - 1]), None), **kw)
    np.testing.assert_allclose(step[:, 0], want[:, -1], atol=2e-6)
    np.testing.assert_allclose(
        lax.index_in_dim(ks[1], S - 1, axis, keepdims=False),
        lax.index_in_dim(k, S - 1, axis, keepdims=False), atol=1e-6)
    # the rotation is there: without it the output is another
    plain, _ = layers._grouped_attention(unit, lp, jnp.float32,
                                         layout=layout, qk_norm=0.0)
    assert float(jnp.max(jnp.abs(plain - got))) > 1e-3


# -- 5. the router, and rows that never mix ----------------------------------


def test_router_selects_by_the_biased_score_and_weighs_by_the_unbiased():
    """LFM2's router is ``experts.route`` with no groups and scale 1: a
    bias above every score (2 against a sigmoid) puts its expert among
    every row's three, and the weights are the chosen experts' own sigmoid
    scores, normalised: the bias is no part of them."""
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
    router = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    bias = jnp.zeros((8,)).at[5].set(2.0)
    chosen, weights = experts.route(x, router, bias, 3, 1.0)
    plain, _ = experts.route(x, router, jnp.zeros((8,)), 3, 1.0)
    assert (np.sort(chosen, -1) != np.sort(plain, -1)).any()
    assert (np.asarray(chosen) == 5).any(axis=-1).all()
    s = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(s, chosen, -1)
    np.testing.assert_allclose(
        weights, picked / jnp.sum(picked, -1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(weights, -1), 1.0, rtol=1e-6)
    # the reference's dense matrix of weights is the same choice
    dense = ref_moe.routing(x, router, bias, top_k=3, scale=1.0)
    np.testing.assert_allclose(
        jnp.take_along_axis(dense, chosen, -1), weights, rtol=1e-6)
    assert int(jnp.sum(dense > 0)) == 16 * 3


def test_the_seeded_bias_changes_a_choice_in_the_model(params):
    """On ``init``'s own bias (normal(0, 0.01)) some row of a prompt is
    routed otherwise than without it: choosing and weighting differ in
    what the tests above compare."""
    rows = jax.random.normal(jax.random.PRNGKey(2), (512, 32))
    lp = layers._at({k: params["moe"][k] for k in ("router", "router_bias")},
                    3)
    with_bias, _ = experts.route(rows, lp["router"], lp["router_bias"], 3,
                                 1.0)
    without, _ = experts.route(rows, lp["router"],
                               jnp.zeros_like(lp["router_bias"]), 3, 1.0)
    assert (np.sort(with_bias, -1) != np.sort(without, -1)).any()


def test_a_rows_output_does_not_change_when_its_neighbours_do(params):
    """Nothing is dropped and nothing has a capacity: slot 1's logits
    with three other requests beside it are, to the bit, those with the
    other slots free."""
    cache_len = 32
    prefill = jax.jit(lambda p: conv_moe.prefill_request(params, p, CFG,
                                                         cache_len))
    step = jax.jit(lambda tok, pos, state: conv_moe.decode_step(
        params, tok, pos, state, CFG))
    rng = np.random.default_rng(1)
    mine = rng.integers(1, V, size=9)
    alone = install_request(conv_moe.init_state(CFG, 4, cache_len), 1,
                            prefill(jnp.asarray(mine))[1], conv_moe.SLOT_AXES)
    crowded = alone
    for s in (0, 2, 3):
        crowded = install_request(
            crowded, s, prefill(jnp.asarray(rng.integers(1, V, size=9)))[1],
            conv_moe.SLOT_AXES)
    tok_a, pos_a = jnp.asarray([0, 4, 0, 0]), jnp.asarray([0, 9, 0, 0])
    tok_c, pos_c = jnp.asarray([8, 4, 17, 30]), jnp.asarray([9, 9, 9, 9])
    for _ in range(3):
        la, alone = step(tok_a, pos_a, alone)
        lc, crowded = step(tok_c, pos_c, crowded)
        np.testing.assert_array_equal(la[1], lc[1])
        pos_a, pos_c = (jnp.where(p > 0, p + 1, 0) for p in (pos_a, pos_c))
