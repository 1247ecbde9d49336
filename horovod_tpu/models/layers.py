"""What two or more model files use, said once: the norm, the rotation,
the dense gated feed-forward and the vocabulary projection every decoder
here is built from, the indexing of a stack of layers, and the part of the
serving seam (``serving/decode.py``) that is the same statement for every
model: how a request's state is written over a slot, and how a step moves
the device counters on.

The model files (``transformer``, ``jamba``, ``latent_moe``,
``retention``, ``ssd_moe``, ``conv_moe``, ``resnet``) import this module
and ``experts`` and never one another; this module imports none of them.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _rmsnorm(x, g, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g).astype(x.dtype)


def _rope_angles(theta, half: int, seq_len: int, pos):
    """The angle [(B,) S, half] that pair i of a head is turned by at each
    position: see :func:`_rope`."""
    freqs = theta if hasattr(theta, "shape") else jnp.exp(
        -math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    if pos is None:
        pos = jnp.arange(seq_len, dtype=jnp.float32)
    return pos.astype(jnp.float32)[..., None] * freqs


def _rope(x, theta, pos=None):
    """Rotary embedding over head_dim pairs; x: [B, S, H, HD].
    ``theta``: the base, pair i turning by ``theta^(-i / (HD/2))`` a
    position, or the table [HD/2] of those frequencies itself (a scaled
    one: models/latent_moe.py's ``rope_frequencies``).
    ``pos``: the absolute positions, [S] (shared by the rows) or [B, S]
    (a row's own: a serving slot rotates its one new token at its own
    offset); default ``arange(S)``."""
    B, S, H, HD = x.shape
    half = HD // 2
    ang = _rope_angles(theta, half, S, pos)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def _dense_ffn(x, lp, dtype):
    h = jnp.einsum("bsd,df->bsf", x, lp["w_in"].astype(dtype))
    g = jnp.einsum("bsd,df->bsf", x, lp["w_gate"].astype(dtype))
    h = h * jax.nn.silu(g)
    return jnp.einsum("bsf,fd->bsd", h, lp["w_out"].astype(dtype))


LAYOUTS = ("heads_first", "positions_first", "merged")


def _grouped_attention(x, lp, dtype, cache=None, layout: str = "heads_first",
                       qk_norm: Optional[float] = None,
                       rope: Optional[float] = None):
    """Grouped-query causal attention.  x: [B, S, D]; ``lp``: ``wq`` [D, H,
    HD], ``wk``, ``wv`` [D, KVH, HD], ``wo`` [H, HD, D].  With neither
    ``qk_norm`` nor ``rope`` it applies no positions (state-space layers
    beside it carry order).

    ``cache`` None: the S positions attend among themselves; returns
    (out, (k, v)) with k, v as ``layout`` holds a request's rows, for
    whoever keeps them.  ``cache`` = (ks, vs, layer, pos, read), stacked
    caches and per-slot positions [B] of THIS token (S = 1): writes the B
    new rows at ``pos[b]`` of lane [layer, b] in place and attends that
    lane up to ``pos``, by ``read`` (:func:`lane_reader`, made once a step
    for all its layers: the kernel, each lane as far as its slot has
    written it) or, where that is None, by a masked product over the
    whole lane; returns (out, (ks, vs)).

    ``layout``, how k, v and the caches are held (one of ``LAYOUTS``):

    * ``heads_first``: [B, KVH, S, HD] and [La, B, KVH, Smax, HD].  With
      several key/value heads (no model here) a step keeps the masked
      read: a head's lane is an array of its own there.
    * ``positions_first``: [B, S, KVH, HD] and [La, B, Smax, KVH, HD].
      With more than one key/value head the chip writes a step's rows into
      such a cache in place, and copies a heads-first one whole, there and
      back, around the scatter (the step compiled for ``v5e`` and traced:
      4 x 0.6 ms a turn for two lanes of 201 MB; PR 46).
    * ``merged``: a position's key/value heads side by side, [B, S, KVH
      HD] and [La, B, Smax, KVH HD].  The chip pads a last axis under its
      128 lanes: caches [.., 8, 64] compiled for ``v5e`` take twice their
      bytes and the step copies them whole (PR 49).  A step multiplies a
      lane as it lies, in the kernel and under the mask alike: each query
      row holds its values in its own head's HD of the KVH HD and zeros in
      the others', and of the product's KVH HD it keeps those.

    ``qk_norm``: the epsilon of an RMSNorm over each head's HD values of q
    and of k, gains ``lp["q_norm"]`` and ``lp["k_norm"]`` [HD] shared by
    the heads, between the projection and the scores.  ``rope``: the base
    of a rotation (:func:`_rope`, the halves' pairing) of q and k after
    that norm, at positions 0..S-1 or, against a cache, at each slot's
    ``pos``; the keys are kept rotated."""
    if layout not in LAYOUTS:
        raise ValueError(f"layout {layout!r}: one of {LAYOUTS}")
    heads_first, merged = layout == "heads_first", layout == "merged"
    B, S, _ = x.shape
    KVH, HD = lp["wk"].shape[-2:]
    G = lp["wq"].shape[-2] // KVH
    kept_as = "bhsk" if heads_first else "bshk"
    lane = "bktd" if heads_first else "btkd"
    q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"].astype(dtype))
    k = jnp.einsum(f"bsd,dhk->{kept_as}", x, lp["wk"].astype(dtype))
    v = jnp.einsum(f"bsd,dhk->{kept_as}", x, lp["wv"].astype(dtype))
    if qk_norm is not None:
        q = _rmsnorm(q, lp["q_norm"], qk_norm)
        k = _rmsnorm(k, lp["k_norm"], qk_norm)
    if rope is not None:
        at = None if cache is None else cache[3][:, None]
        q = _rope(q, rope, at)
        k = _rope(k.swapaxes(1, 2), rope, at).swapaxes(1, 2) \
            if heads_first else _rope(k, rope, at)
    q = q.reshape(B, S, KVH, G, HD)
    own = read = None
    if cache is None:
        keys, values, kept = k, v, (k, v)
        if merged:
            kept = (k.reshape(B, S, KVH * HD), v.reshape(B, S, KVH * HD))
        valid = jnp.tril(jnp.ones((S, S), jnp.bool_))[None]    # [1, S, T]
    else:
        ks, vs, layer, pos, read = cache
        rows = jnp.arange(B)
        if merged:
            ks = ks.at[layer, rows, pos].set(k[:, 0].reshape(B, KVH * HD))
            vs = vs.at[layer, rows, pos].set(v[:, 0].reshape(B, KVH * HD))
        elif heads_first:
            ks = ks.at[layer, rows, :, pos].set(k[:, :, 0])
            vs = vs.at[layer, rows, :, pos].set(v[:, :, 0])
        else:
            ks = ks.at[layer, rows, pos].set(k[:, 0])
            vs = vs.at[layer, rows, pos].set(v[:, 0])
        kept = (ks, vs)
    if read is not None:
        ctx = read(q[:, 0], ks, vs, layer)[:, None]
    else:
        if cache is not None:
            keys = lax.dynamic_index_in_dim(ks, layer, 0, keepdims=False)
            values = lax.dynamic_index_in_dim(vs, layer, 0, keepdims=False)
            valid = (jnp.arange(keys.shape[2 if heads_first else 1])[None, :]
                     <= pos[:, None])[:, None]                 # [B, 1, T]
            if merged:
                lane = "btd"
                q, own = _in_own_heads_place(q)
        logits = jnp.einsum(f"bskgd,{lane}->bkgst", q, keys
                            ).astype(jnp.float32) / math.sqrt(HD)
        logits = jnp.where(valid[:, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
        ctx = jnp.einsum(f"bkgst,{lane}->bskgd", probs, values)
        if own is not None:
            ctx = _of_own_head(ctx, own)
        ctx = ctx.reshape(B, S, KVH * G, HD)
    return jnp.einsum("bshk,hkd->bsd", ctx, lp["wo"].astype(dtype)), kept


def _in_own_heads_place(q):
    """q [..., KVH, G, HD] against a position's KVH heads side by side:
    ([..., KVH, G, KVH HD], each row's HD values in its own head's place
    and zeros in the others'; the mask that put them there)."""
    KVH, _, HD = q.shape[-3:]
    own = jnp.eye(KVH, dtype=q.dtype)[:, None, :, None]
    return (q[..., None, :] * own).reshape(*q.shape[:-1], KVH * HD), own


def _of_own_head(ctx, own):
    """Of ctx [..., KVH, G, KVH HD], a product with a position's heads
    side by side, each row's own head's HD: [..., KVH, G, HD]."""
    KVH = own.shape[0]
    return jnp.sum(ctx.reshape(*ctx.shape[:-1], KVH, -1) * own, axis=-2)


# Positions a fetch of the decode kernel holds, at most, for lanes held in
# each layout (a position is 1 KB of keys in ``lfm2-8b-a1b``'s merged
# lanes, 512 bytes in ``nemotron-3-nano-30b-a3b``'s two heads, 256 in
# ``jamba2-3b``'s one): chosen once, from ``tools/decode_attn_probe.py`` on
# a TPU v5e at each cell's lanes and load (PERF.md section 6, PR 52: 256
# unless another block is 3 % faster there; 1024 positions of two heads of
# 128 are the 512 KB of 512 merged ones).
LANE_BLOCKS = {"merged": 256, "positions_first": 1024, "heads_first": 256}


def _lane_len(layout: str, cache) -> int:
    """Positions a lane of a stacked cache held as ``layout`` holds."""
    return cache.shape[3 if layout == "heads_first" else 2]


def lane_block(layout: str, cache) -> Optional[int]:
    """Positions a fetch of the decode kernel
    (``ops/pallas_decode_attention.py``) holds for a key or value cache
    held as ``layout``, from its shape; None where a step reads the lanes
    whole under a mask: heads-first lanes of several key/value heads, each
    head's an array of its own (no model here holds such)."""
    from horovod_tpu.ops.pallas_attention import _pick_block

    if layout == "heads_first" and cache.shape[2] != 1:
        return None
    return _pick_block(_lane_len(layout, cache), LANE_BLOCKS[layout])


def lane_reader(layout: str, cache, pos):
    """How a decode step reads its slots' lanes of the stacked caches
    shaped like ``cache`` and held as ``layout``, slots at ``pos`` [B]: the
    kernel over (q [B, KVH, G, HD], ks, vs, layer) -> [B, KVH G, HD], each
    lane as far as its slot has written it, the list of blocks made HERE,
    once for all the layers of the step; or None, the masked read of the
    whole lane (:func:`lane_block`).  One algorithm at three shapes, told
    apart by what is held and how:

    * ``merged``: the query rows each in its own head's place of the KVH
      HD (what the masked read multiplies too), the lanes as they lie, a
      value array of its own; of the product each row keeps its head's HD.
    * ``heads_first`` with one key/value head: [La, B, 1, Smax, HD] is
      [La, B, Smax, HD], a key every head shares.
    * ``positions_first``: the kernel's head axis, query row h on head
      h // G.

    The models that call this hold their state on one device (no
    ``STATE_SPEC``: ``serving/decode.py`` refuses them a mesh)."""
    block = lane_block(layout, cache)
    if block is None:
        return None
    from horovod_tpu.ops import pallas_decode_attention as pda

    merged = layout == "merged"
    work = pda.work_list(pos, _lane_len(layout, cache), block)

    def read(q, ks, vs, layer):
        B, KVH, G, HD = q.shape
        if merged:
            q, own = _in_own_heads_place(q)
        elif layout == "heads_first":
            ks, vs = (a.reshape(a.shape[:2] + a.shape[3:]) for a in (ks, vs))
        ctx = pda.decode_attention(
            (q.reshape(B, KVH * G, -1),), (ks,), vs, layer, pos,
            scale=1.0 / math.sqrt(HD), block=block, work=work)
        if merged:
            ctx = _of_own_head(ctx.reshape(B, KVH, G, -1), own)
        return ctx.reshape(B, KVH * G, HD)

    return read


def _causal_conv(x, kept, w, b):
    """A depthwise causal convolution over the leading (time) axis.  x:
    [S, B, C]; ``kept``: [K - 1, B, C], the inputs before x's first (a
    sequence's start: zeros); ``w`` [K, C], ``b`` [C].  Returns (the
    convolution [S, B, C] in float32, before any activation; the window
    [K - 1 + S, B, C], whose last K - 1 rows the caller keeps)."""
    S, K = x.shape[0], w.shape[0]
    window = jnp.concatenate([kept, x], axis=0)
    w = w.astype(jnp.float32)
    return b.astype(jnp.float32) + sum(
        w[j] * window[j:j + S].astype(jnp.float32) for j in range(K)), window


def vocab_projection(x, embed):
    """Final [B,S,D] → [B,S,V] projection: compute-dtype inputs on the
    MXU, f32 accumulation (an f32xf32 dot here ran at the MXU's
    multi-pass fp32 rate and was the single hottest op of the step).
    Shared with the pipelined path (parallel/pipeline.py)."""
    return jnp.einsum("bsd,vd->bsv", x, embed.astype(x.dtype),
                      preferred_element_type=jnp.float32)


def _logits(x, gain, table, eps: float = 1e-6):
    """The head: the final norm's ``gain`` over x [B, S, D], then the
    projection on ``table`` [V, D] (the tied embedding, or a head of its
    own)."""
    return vocab_projection(_rmsnorm(x, gain, eps), table)


def _at(stacked, l):
    """Layer ``l`` of every leaf of a stack of layers (``l`` traced)."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, l, 0, keepdims=False), stacked)


def _put(stacked, l, value):
    return lax.dynamic_update_index_in_dim(stacked, value, l, 0)


# ---------------------------------------------------------------------------
# the serving seam's shared part
# ---------------------------------------------------------------------------


def install_request(state, slot, request, axes):
    """Write a request's state over slot ``slot``'s: every leaf of every
    slot kind, whole, so that nothing of the slot's last tenant is left;
    whatever else ``state`` holds at the top (the counters, which no slot
    owns) passes through, moved on by the request's ``"counted"`` (counter
    names to increments) where its prefill counted something.  ``axes``:
    the model's ``SLOT_AXES``, for each slot kind its state may hold the
    axis of each leaf that the slots lie along (a request's leaf has one
    slot there).  ``state`` donated, the
    writes are in place: one ``dynamic_update_slice`` a leaf."""
    def over(lane, new, axis):
        at = [0] * lane.ndim
        at[axis] = slot
        return lax.dynamic_update_slice(lane, new, at)

    out = {**state, **{
        kind: jax.tree.map(over, state[kind], request[kind], axes[kind])
        for kind in axes if kind in state}}
    if "counted" in request:    # what the request's prefill counted
        out["counters"] = add_counters(state["counters"],
                                       request["counted"])
    return out


def add_counters(counters, add):
    """``counters`` (a state's ``"counters"``: registry names to uint32
    scalars) moved on by ``add``, name to increment; a name ``add`` does
    not hold stays as it is.  uint32 and read as differences, so they may
    wrap between two reads but not twice."""
    return {**counters, **{name: counters[name] + a.astype(jnp.uint32)
                           for name, a in add.items()}}


# What a decode step that attends lanes of positions adds to
# ``state["counters"]``, over its layers: positions of the slots' lanes in
# the blocks its attention fetched, and positions those lanes hold
# (``max_batch x cache_len`` a layer; 2**32 positions are thousands of
# turns of the largest table here).
ATTN_COUNTERS = ("hvd_serve_attn_positions_read_total",
                 "hvd_serve_attn_positions_held_total")


def count_attention_reads(counters, pos, cache_len: int, n_layers: int,
                          block: Optional[int]):
    """``counters`` with ATTN_COUNTERS moved on by one decode step of
    ``n_layers`` layers over slots at ``pos`` [B] in lanes of
    ``cache_len``: ``block`` is the kernel's (the blocks up to each
    slot's position are read), or None where the whole lane is."""
    held = jnp.uint32(n_layers * pos.shape[0] * cache_len)
    read = held
    if block is not None:
        from horovod_tpu.ops.pallas_decode_attention import pairs_run

        read = (pairs_run(pos, block) * (n_layers * block)).astype(jnp.uint32)
    return add_counters(counters, dict(zip(ATTN_COUNTERS, (read, held))))


def count_lane_reads(counters, pos, layout: str, cache):
    """:func:`count_attention_reads` for one decode step of
    :func:`_grouped_attention`'s layers over the stacked caches shaped
    like ``cache`` [La, ...] and held as ``layout``: read as
    :func:`lane_reader` reads them."""
    return count_attention_reads(counters, pos, _lane_len(layout, cache),
                                 cache.shape[0], lane_block(layout, cache))
