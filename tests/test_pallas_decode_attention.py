"""The decode attention kernel (ops/pallas_decode_attention.py) against the
masked dense read it replaces, interpreted on the CPU.

Both shapes of the one body: a cache with a head axis (16 heads on 16, K
and V arrays of their own) and a shared latent (20 heads on one latent a
position, the value too, and a 64-wide rotary key as a second part, held
with its positions last); and the three forms grouped heads take them in
(``layers.lane_reader``): 32 heads on 8 of 64 held side by side, each query
row in its own head's place of the 512; the head axis with 32 rows on 2
heads of 128; 20 rows on one shared key of 128.  Every case
runs a ragged batch, a non-zero layer of a stack of three, and lanes
filled with NaN past each slot's position: a finite output equal to the
reference's proves that the kernel is bounded by the position and not
merely masked.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import layers
from horovod_tpu.ops import pallas_decode_attention as pda

L, SMAX, BLOCK = 3, 64, 16
LAYER = 1


def masked_read(q, keys, value, layer, pos, *, scale, block=None, work=None,
                positions_last=None):
    """The parent's read under the kernel's signature: the whole lane of
    ``layer``, every position scored, those past ``pos`` masked.  The
    model tests put it in the kernel's place (tests/test_serving_cache.py,
    tests/test_latent_moe.py)."""
    last = positions_last or (False,) * len(keys)
    ks = [jax.lax.dynamic_index_in_dim(k.swapaxes(2, 3) if t else k, layer,
                                       0, False) for k, t in zip(keys, last)]
    v = ks[0] if value is None else jax.lax.dynamic_index_in_dim(
        value, layer, 0, False)
    own = ks[0].ndim == 4                             # [B, T, H, HD]
    scores = sum(jnp.einsum("bhk,bthk->bht" if own else "bhk,btk->bht", qp,
                            kp, preferred_element_type=jnp.float32)
                 for qp, kp in zip(q, ks)) * scale
    valid = jnp.arange(ks[0].shape[1])[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(valid[:, None], scores, -1e30), axis=-1)
    return jnp.einsum("bht,bthk->bhk" if own else "bht,btk->bhk",
                      probs.astype(v.dtype), v)


def uneven_steps(prefill, install, step, state, lengths, vocab, steps=40):
    """Prompts of ``lengths`` (0: the slot stays free) installed into
    neighbouring slots, then ``steps`` greedy steps of all of them: the
    logits of every step, [steps, B, V], and the state they leave."""
    pos = jnp.asarray(lengths, jnp.int32)
    tok = jnp.zeros_like(pos)
    for slot, n in enumerate(lengths):
        if n:
            prompt = (jnp.arange(n, dtype=jnp.int32) * 7 + slot) % (vocab - 1) + 1
            logits, request = prefill(prompt)
            state = install(state, slot, request)
            tok = tok.at[slot].set(jnp.argmax(logits).astype(jnp.int32))
    out = []
    for _ in range(steps):
        logits, state = step(tok, pos, state)
        tok = jnp.where(pos > 0, jnp.argmax(logits, -1), 0).astype(jnp.int32)
        pos = jnp.where(pos > 0, pos + 1, 0)
        out.append(logits)
    return np.stack(out), state


def step_reads_blocks_and_equals_the_masked_step(
        monkeypatch, module, params, cfg, lengths=(3, 0, 15, 80),
        cache_len=128, block=16, steps=40, tol=2e-5):
    """A model of grouped heads (``module``: conv_moe, ssd_moe, jamba)
    stepped from prompts of ``lengths`` in lanes of ``cache_len`` with the
    kernel at blocks of ``block``: over the steps the live slots cross
    block ends beside a free slot, the counters say what the blocks held,
    and every step's logits are those of the step that reads the lanes
    whole under a mask (``lane_reader`` None)."""
    from horovod_tpu.serving import decode

    monkeypatch.setattr(layers, "LANE_BLOCKS",
                        dict.fromkeys(layers.LAYOUTS, block))

    def logits():
        return uneven_steps(
            jax.jit(lambda p: module.prefill_request(params, p, cfg,
                                                     cache_len)),
            decode.slot_model(cfg, cache_len).install,
            jax.jit(lambda tok, pos, state: module.decode_step(
                params, tok, pos, state, cfg)),
            module.init_state(cfg, len(lengths), cache_len), lengths,
            cfg.vocab_size, steps)

    got, state = logits()
    layers_attn = cfg.n_layers("attn")
    read, held = (int(state["counters"][name])
                  for name in layers.ATTN_COUNTERS)
    assert held == steps * layers_attn * len(lengths) * cache_len
    assert read == layers_attn * block * sum(
        n // block + 1 for length in lengths if length
        for n in range(length, length + steps))
    monkeypatch.setattr(module, "lane_reader", lambda *a: None)
    want, _ = logits()
    live = [b for b, n in enumerate(lengths) if n]
    np.testing.assert_allclose(got[:, live], want[:, live], rtol=10 * tol,
                               atol=tol)
    assert np.isfinite(got).all()


def _poison(cache, pos):
    """NaN at every position past each slot's, in every layer."""
    past = jnp.arange(SMAX)[None, :] > pos[:, None]             # [B, T]
    past = past.reshape((1,) + past.shape + (1,) * (cache.ndim - 3))
    return jnp.where(past, jnp.nan, cache)


# Grouped heads: (query heads, key/value heads, head_dim, the layout of
# ``layers._grouped_attention`` that holds them).
GROUPED = {"merged_32on8of64": (32, 8, 64, "merged"),
           "head_axis_32on2of128": (32, 2, 128, "positions_first"),
           "shared_20on1of128": (20, 1, 128, "heads_first")}


CASES = {
    "parked": [0, 0, 0, 0],
    "one": [1, 1, 1, 1],
    "block_minus_1": [BLOCK - 1, 3, 0, BLOCK - 1],
    "block": [BLOCK, 0, BLOCK, 1],
    "block_plus_1": [BLOCK + 1, BLOCK, 2, 0],
    "last": [SMAX - 1, 0, BLOCK + 1, SMAX - 1],
    "ragged": [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, SMAX - 1, 37, 0],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("shape", ["heads", "latent", *GROUPED])
def test_kernel_equals_masked_dense_read(shape, case, dtype, monkeypatch):
    pos = jnp.asarray(CASES[case], jnp.int32)
    B = pos.shape[0]
    rng = iter(jax.random.split(jax.random.PRNGKey(len(case)), 8))

    def normal(*dims):
        return jax.random.normal(next(rng), dims, jnp.float32).astype(dtype)

    if shape in GROUPED:
        # keys and values [L, B, T, KVH, HD]; the reference is the dense
        # kernel-free read with a head of its own a query row
        H, KVH, HD, layout = GROUPED[shape]
        q = normal(B, KVH, H // KVH, HD)
        keys, value = normal(L, B, SMAX, KVH, HD), normal(L, B, SMAX, KVH, HD)
        scale = 1.0 / math.sqrt(HD)
        want = masked_read(
            (q.reshape(B, H, HD),), (jnp.repeat(keys, H // KVH, axis=3),),
            jnp.repeat(value, H // KVH, axis=3), LAYER, pos, scale=scale)
        held = {"merged": lambda a: a.reshape(L, B, SMAX, KVH * HD),
                "positions_first": lambda a: a,
                "heads_first": lambda a: a.swapaxes(2, 3)}[layout]
        monkeypatch.setitem(layers.LANE_BLOCKS, layout, BLOCK)
        ks, vs = held(_poison(keys, pos)), held(_poison(value, pos))
        got = layers.lane_reader(layout, ks, pos)(q, ks, vs, jnp.int32(LAYER))
    elif shape == "heads":
        H, HD = 16, 32
        q = normal(B, H, HD)
        keys, value = normal(L, B, SMAX, H, HD), normal(L, B, SMAX, H, HD)
        scale = 1.0 / math.sqrt(HD)
        want = masked_read((q,), (keys,), value, LAYER, pos, scale=scale)
        got = pda.decode_attention(
            (q,), (_poison(keys, pos),), _poison(value, pos),
            jnp.int32(LAYER), pos, scale=scale, block=BLOCK)
    else:       # a latent of 128 and a rotary key of 64 a position
        H, R, ROPE = 20, 128, 64
        q, lat = normal(B, H, R), normal(L, B, SMAX, R)
        q_r, rot = normal(B, H, ROPE), normal(L, B, SMAX, ROPE)
        scale = 1.0 / math.sqrt(192 + ROPE)
        want = masked_read((q, q_r), (lat, rot), None, LAYER, pos,
                           scale=scale)
        # the rotary part as XLA holds it on the chip: positions last
        got = pda.decode_attention(
            (q, q_r), (_poison(lat, pos), _poison(rot, pos).swapaxes(2, 3)),
            None, jnp.int32(LAYER), pos, scale=scale, block=BLOCK,
            positions_last=(False, True))
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def test_blocks_read_follow_the_positions():
    pos = jnp.asarray([0, 1, BLOCK - 1, BLOCK, SMAX - 1], jnp.int32)
    assert pda.blocks_read(pos, BLOCK).tolist() == [0, 1, 1, 2, SMAX // BLOCK]
    assert int(pda.pairs_run(pos, BLOCK)) == 4 + SMAX // BLOCK
    assert int(pda.pairs_run(jnp.zeros((3,), jnp.int32), BLOCK)) == 1
    assert pda.block_for(1536, shared=False) == 256
    assert pda.block_for(4608, shared=True) == 512
    assert pda.block_for(24, shared=False) == 24


def test_a_block_must_divide_the_lane():
    cache = jnp.zeros((1, 1, 24, 2, 8))
    with pytest.raises(ValueError, match="does not divide"):
        pda.decode_attention((jnp.zeros((1, 2, 8)),), (cache,), cache, 0,
                             jnp.ones((1,), jnp.int32), scale=1.0, block=16)
