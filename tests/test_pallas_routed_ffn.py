"""ops/pallas_routed_ffn.py, interpreted on the CPU at small shapes, and
the rule by which models/experts.py:routed_ffn takes it.

1. the kernel alone against a loop over experts in float32: an expert with
   no row (the first, one in the middle, the last), one with every row, a
   group that crosses a window of rows, the last window pulled back inside
   the array, pairs behind the last group, no pair at all, rows that are
   no whole window;
2. ``routed_ffn`` with the kernel against its ``ragged_dot`` path on the
   same inputs: free slots, a traced ``layer`` inside ``jit`` over a stack
   of three layers, stacks held wider than published with the published
   width passed (and what lies past it is never read);
3. the rule, from shapes alone, over the callers of ``routed_ffn`` at
   their cells' real sizes;
4. ``models/conv_moe.py`` at its benchmark cell's rehearsal sizes with as
   many slots as take the kernel: prefills and steps give the tokens they
   give with the rule forced off, and the counter of fused layer turns
   equals the counter of layer turns; ``latent_moe`` and ``ssd_moe`` do
   not hold that counter and their rehearsal steps lower to the text they
   lowered to on the parent commit.
"""

import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import conv_moe, experts, latent_moe, ssd_moe
from horovod_tpu.models.layers import install_request
from horovod_tpu.ops import pallas_routed_ffn as prf
from horovod_tpu.serving import decode
from perfbench import harness
from perfbench.jobs import (conv_moe_lm_serve, moe_lm_serve,
                            sparse_moe_lm_serve, ssd_moe_lm_serve)

L, E, D, F = 3, 4, 32, 256
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def stacks():
    keys = jax.random.split(jax.random.PRNGKey(50), 3)

    def normal(key, shape):
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(BF16)

    return {"w_in": normal(keys[0], (L, E, D, F)),
            "w_gate": normal(keys[1], (L, E, D, F)),
            "w_out": normal(keys[2], (L, E, F, D))}


def by_expert(xs, counts, layer, stacks, width=None):
    """Each expert's rows through its three matrices, in float32 with
    ``h`` rounded as the kernel rounds it; zeros behind the last group."""
    xs = np.asarray(xs.astype(jnp.float32))
    out = np.zeros_like(xs)
    start = 0
    for e, n in enumerate(counts):
        w_in, w_gate, w_out = (
            np.asarray(stacks[k][layer, e].astype(jnp.float32))
            for k in ("w_in", "w_gate", "w_out"))
        if width is not None:
            w_in, w_gate, w_out = (w_in[:, :width], w_gate[:, :width],
                                   w_out[:width])
        x = xs[start:start + n]
        a, g = x @ w_in, x @ w_gate
        h = jnp.asarray(a * g / (1 + np.exp(-g))).astype(BF16)
        out[start:start + n] = np.asarray(h.astype(jnp.float32)) @ w_out
        start += n
    return out


# -- 1. the kernel alone ------------------------------------------------------


@pytest.mark.parametrize("pairs,counts", [
    (48, [0, 30, 0, 18]),       # no row: the first expert, one in the middle
    (48, [20, 28, 0, 0]),       # no row: the last two
    (48, [0, 0, 48, 0]),        # one with every row
    (96, [10, 50, 30, 6]),      # groups across windows of 16 rows
    (96, [0, 0, 5, 91]),        # a long group, its last window pulled back
    (96, [13, 7, 0, 11]),       # 65 pairs behind the last group
    (48, [0, 0, 0, 0]),         # no pair at all
    (40, [9, 3, 21, 7]),        # 40 rows: no whole number of windows
], ids=["empty_first_and_middle", "empty_last", "one_with_all",
        "across_windows", "last_window_pulled_back", "behind_last_group",
        "no_pair", "rows_no_whole_window"])
def test_kernel_is_each_experts_rows_through_its_matrices(
        stacks, monkeypatch, pairs, counts):
    monkeypatch.setattr(prf, "ROWS", 16)
    xs = jax.random.normal(jax.random.PRNGKey(pairs), (pairs, D),
                           jnp.float32).astype(BF16)
    got = jax.jit(prf.routed_ffn_rows)(
        xs, jnp.asarray(counts, jnp.int32), 1, stacks["w_in"],
        stacks["w_gate"], stacks["w_out"])
    assert got.shape == xs.shape and got.dtype == xs.dtype
    want = by_expert(xs, counts, 1, stacks)
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2,
                               rtol=2e-2)
    assert not np.asarray(got.astype(jnp.float32))[sum(counts):].any()


def test_work_list_names_the_block_a_step_beside_it_has():
    """An expert with no row names the LAST tile of the expert with rows
    before it, or the FIRST tile of the first expert with rows where none
    is before: the block index does not change across it, so nothing is
    fetched for it."""
    source, column, start, counts = prf.work_list(
        jnp.asarray([0, 0, 5, 0, 7, 0], jnp.int32), tiles=7)
    assert source.tolist() == [2, 2, 2, 2, 4, 4]
    assert column.tolist() == [0, 0, -1, 6, -1, 6]
    assert start.tolist() == [0, 0, 0, 5, 5, 12]
    none = prf.work_list(jnp.zeros((4,), jnp.int32), tiles=7)
    assert none[0].tolist() == [0] * 4 and none[1].tolist() == [0] * 4


# -- 2. routed_ffn with the kernel against its ragged_dot path ---------------


def both_paths(monkeypatch, *args, **kwargs):
    """``routed_ffn`` with the kernel, then with the rule forced off."""
    assert experts.one_kernel(args[1], args[3].size, kwargs.get("first"))
    fused = jax.jit(lambda layer: experts.routed_ffn(
        args[0], args[1], layer, *args[3:], **kwargs))(args[2])
    monkeypatch.setattr(experts, "RESIDENT_ROWS", 0)
    assert not experts.one_kernel(args[1], args[3].size)
    ragged = jax.jit(lambda layer: experts.routed_ffn(
        args[0], args[1], layer, *args[3:], **kwargs))(args[2])
    return fused, ragged


def routed(key, rows, k):
    """(x [rows, D], chosen, weights) for ``rows`` rows of ``k`` experts."""
    kx, kr = jax.random.split(key)
    x = jax.random.normal(kx, (rows, D), jnp.float32).astype(BF16)
    chosen, weights = experts.route(
        x, 0.3 * jax.random.normal(kr, (D, E)), jnp.zeros((E,)), k, 1.0)
    return x, chosen, weights


@pytest.mark.parametrize("layer", [0, 2])
def test_routed_ffn_with_free_slots_and_a_traced_layer(stacks, monkeypatch,
                                                       layer):
    """24 rows x 2 of 4 experts, a third of the slots free (their pairs lie
    behind the last group), the layer traced inside ``jit``: the kernel's
    result is the grouped products' to bfloat16's rounding of ``h``, the
    free rows' zero, the stats the same."""
    monkeypatch.setattr(prf, "ROWS", 16)
    x, chosen, weights = routed(jax.random.PRNGKey(layer), 24, 2)
    live = jnp.arange(24) % 3 != 1
    (y, stats), (want, want_stats) = both_paths(
        monkeypatch, x, stacks, jnp.int32(layer), chosen, weights, BF16, live)
    np.testing.assert_allclose(y.astype(jnp.float32),
                               want.astype(jnp.float32), atol=2e-2,
                               rtol=2e-2)
    assert np.asarray(want.astype(jnp.float32))[live].any()
    assert not np.asarray(y.astype(jnp.float32))[~live].any()
    np.testing.assert_array_equal(stats, want_stats)
    assert stats.tolist()[0] == 16 * 2


def test_stacks_held_wider_than_published_are_read_to_the_width(
        stacks, monkeypatch):
    """Stacks held 384 wide for a published 256: with the width passed the
    kernel gives what the grouped products give over the held stacks (zeros
    past the width), and does not read what lies past it: the same result
    with that part overwritten."""
    pad_in = [(0, 0)] * 3 + [(0, 128)]
    pad_out = [(0, 0)] * 2 + [(0, 128), (0, 0)]
    held = {"w_in": jnp.pad(stacks["w_in"], pad_in),
            "w_gate": jnp.pad(stacks["w_gate"], pad_in),
            "w_out": jnp.pad(stacks["w_out"], pad_out)}
    junk = {"w_in": jnp.pad(stacks["w_in"], pad_in, constant_values=7),
            "w_gate": jnp.pad(stacks["w_gate"], pad_in, constant_values=7),
            "w_out": jnp.pad(stacks["w_out"], pad_out, constant_values=7)}
    x, chosen, weights = routed(jax.random.PRNGKey(7), 32, 2)
    over_junk, _ = experts.routed_ffn(x, junk, 1, chosen, weights, BF16,
                                      width=F)
    (y, _), (want, _) = both_paths(monkeypatch, x, held, 1, chosen, weights,
                                   BF16, width=F)
    np.testing.assert_array_equal(y, over_junk)
    np.testing.assert_allclose(y.astype(jnp.float32),
                               want.astype(jnp.float32), atol=2e-2,
                               rtol=2e-2)
    assert prf.columns(F, 384) == (128, 2)
    assert prf.columns(1792, 2048) == (128, 14)
    # a width no tile divides: the held columns whole, one step an expert
    assert prf.columns(24, 24) == (24, 1) and prf.columns(200, 256) == (256, 1)


# -- 3. the rule --------------------------------------------------------------


def stack_of(n_experts, gated=True):
    shape = jax.ShapeDtypeStruct((2, n_experts, 8, 8), BF16)
    return dict.fromkeys(("w_in", "w_out") + (("w_gate",) if gated else ()),
                         shape)


@pytest.mark.parametrize("who,pairs,stack,first,takes", [
    ("lfm2-8b-a1b step, 192 slots x 4", 768, stack_of(32), None, True),
    ("lfm2-8b-a1b prompt of 128", 512, stack_of(32), None, True),
    ("lfm2-8b-a1b prompt of 256", 1024, stack_of(32), None, True),
    ("lfm2-8b-a1b prompt of 512", 2048, stack_of(32), None, False),
    ("lfm2-8b-a1b prompt of 1024", 4096, stack_of(32), None, False),
    ("glm-4.7-flash step, 64 x 4", 256, stack_of(64), None, False),
    ("deepseek-v3.2 step, 24 x 8, 16 of 256 held", 192, stack_of(16), 32,
     False),
    ("nemotron-3-nano-30b-a3b step, 96 x 6, 64 of 128 held, relu2", 576,
     stack_of(64, gated=False), 0, False),
], ids=lambda v: v.split(",")[0].replace(" ", "_") if isinstance(v, str)
    else None)
def test_the_rule_takes_the_many_rows_regime_alone(who, pairs, stack, first,
                                                   takes):
    assert experts.one_kernel(stack, pairs, first) is takes, who


def test_the_rule_needs_gated_experts_all_held_and_eight_pairs_an_expert():
    assert experts.one_kernel(stack_of(32), 256)
    assert not experts.one_kernel(stack_of(32), 255)
    assert not experts.one_kernel(stack_of(32), 1025)
    assert not experts.one_kernel(stack_of(32, gated=False), 768)
    assert not experts.one_kernel(stack_of(32), 768, first=0)


# -- 4. the models ------------------------------------------------------------

CELLS = {
    "lfm2-8b-a1b_serve_assistants":
        (conv_moe_lm_serve, conv_moe.ConvMoEConfig),
    "glm-4.7-flash_serve_context":
        (moe_lm_serve, latent_moe.LatentMoEConfig),
    "deepseek-v3.2_serve_resident":
        (sparse_moe_lm_serve, latent_moe.LatentMoEConfig),
    "nemotron-3-nano-30b-a3b_serve_agents":
        (ssd_moe_lm_serve, ssd_moe.SsdMoEConfig)}


def rehearsal(cell_name):
    """(the cell's rehearsal configuration as its job builds it, its
    ``serve`` section)."""
    job, config = CELLS[cell_name]
    cell = harness.Cell(cell_name)
    serve = {**cell.params("serve"), **cell.params("serve")["rehearsal"]}
    sizes = job.model_sizes(types.SimpleNamespace(cell=cell, rehearsal=True))
    return config(max_seq_len=serve["cache_len"], **sizes), serve


def test_conv_moe_serves_the_same_tokens_with_the_kernel(monkeypatch):
    """The cell's rehearsal configuration (bfloat16, 5 expert layers of 8
    experts top-3) with 24 slots, so that a step's 72 pairs take the
    kernel, as do the prompts of 24 and 40 tokens; the prompt of 5 does
    not.  Greedy tokens of three requests over six steps, a free slot
    among them, with the kernel and with the rule forced off."""
    cfg, serve = rehearsal("lfm2-8b-a1b_serve_assistants")
    slots, cache_len, steps = 24, serve["cache_len"], 6
    params = jax.jit(lambda k: conv_moe.init(k, cfg))(jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    prompts = {s: rng.integers(1, cfg.vocab_size, size=n)
               for s, n in ((0, 24), (1, 5), (5, 40))}
    prompts.update({s: rng.integers(1, cfg.vocab_size, size=7)
                    for s in range(6, slots)})
    assert experts.one_kernel(params["moe"], slots * cfg.num_experts_per_tok)
    assert experts.one_kernel(params["moe"], 24 * cfg.num_experts_per_tok)
    assert not experts.one_kernel(params["moe"], 7 * cfg.num_experts_per_tok)

    def served():
        prefill = jax.jit(lambda p: conv_moe.prefill_request(
            params, p, cfg, cache_len))
        step = jax.jit(lambda tok, pos, state: conv_moe.decode_step(
            params, tok, pos, state, cfg))
        state = conv_moe.init_state(cfg, slots, cache_len)
        tok = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        for s, prompt in prompts.items():
            logits, request = prefill(jnp.asarray(prompt))
            state = install_request(state, s, request, conv_moe.SLOT_AXES)
            tok[s], pos[s] = int(jnp.argmax(logits)), len(prompt)
        tokens = [tok.copy()]
        for _ in range(steps):
            logits, state = step(jnp.asarray(tok), jnp.asarray(pos), state)
            tok = np.where(pos > 0, np.argmax(np.asarray(logits), -1), 0
                           ).astype(np.int32)
            pos = np.where(pos > 0, pos + 1, 0).astype(np.int32)
            tokens.append(tok.copy())
        return (np.stack(tokens),
                {k: int(v) for k, v in state["counters"].items()})

    tokens, counters = served()
    assert counters[experts.FUSED_COUNTER] \
        == counters["hvd_moe_layer_turns_total"] \
        == steps * cfg.n_layers("moe")
    monkeypatch.setattr(experts, "RESIDENT_ROWS", 0)
    want, without = served()
    np.testing.assert_array_equal(tokens, want)
    assert without[experts.FUSED_COUNTER] == 0
    assert without["hvd_moe_rows_routed_total"] \
        == counters["hvd_moe_rows_routed_total"]


# sha256 of the lowered text (StableHLO, no locations; jax 0.9.0) of the
# engine's step for the cells' rehearsal configurations and slots, taken on
# the parent commit (223e031, PR 49): the three other callers of
# ``routed_ffn`` lower to the programs they lowered to before the kernel.
# ``nemotron-3-nano-30b-a3b``'s was taken again on PR 52's finished change,
# with its cell's numbers (PERF.md section 6): its attention layer reads its
# lane through ``layers.lane_reader`` and the step holds the two
# ``hvd_serve_attn_positions_*`` counters; its grouped products are as they
# were (``tests/test_chip_smoke.py`` holds the step compiled for ``v5e`` to
# them).
STEP_ON_THE_PARENT = {
    "glm-4.7-flash_serve_context":
        "713cb58c4ed3744905e362767ee40ac6afa30ce92d9df8c5f873f4f8079e1905",
    "deepseek-v3.2_serve_resident":
        "ae685b36811ca1fff3c47ca6c37874b1b15cf97cd24849d64804722abdf4c8c5",
    "nemotron-3-nano-30b-a3b_serve_agents":
        "a4c25654c4e1d369a97ac12c0fd80adad7ad5ee4525631b7248ce4aa954789b9"}


@pytest.mark.parametrize("cell_name", list(STEP_ON_THE_PARENT))
def test_the_other_callers_steps_lower_as_on_the_parent(cell_name):
    cfg, serve = rehearsal(cell_name)
    slots = serve["max_batch"]
    model = decode.slot_model(cfg, serve["cache_len"])
    assert experts.FUSED_COUNTER not in jax.eval_shape(
        lambda: model.init_state(slots))["counters"]

    def specs(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    module = decode.MODELS[type(cfg)]
    held = specs(jax.eval_shape(
        lambda k: model.held(module.init(k, cfg)), jax.random.PRNGKey(0)))
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    lowered = jax.jit(decode.named(decode.STEP_PROGRAM, model.step),
                      donate_argnums=(3,)).lower(
        held, ints, ints, specs(jax.eval_shape(
            lambda: model.init_state(slots))))
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest() \
        == STEP_ON_THE_PARENT[cell_name]
