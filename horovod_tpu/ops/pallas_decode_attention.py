"""Decode attention over a slot cache as ONE Pallas TPU kernel with a
length per slot (ragged decode attention).

A serving step attends one new token a slot against that slot's lane of a
stacked cache ``[L, B, Smax, ...]``.  The slots are ragged: each sits at
its own position ``pos[b]``, free ones at 0.  A batched XLA dot can only
read every lane whole and mask what lies past ``pos``; this kernel walks
each lane in blocks of ``block`` positions and fetches the blocks up to
the one that holds ``pos[b]`` and no other, so a step reads what the
slots have written and not what the table could hold.

* **The layer is addressed inside the kernel.**  The stacked caches come
  in whole; ``layer`` is a scalar-prefetch operand and a block's index is
  ``(layer, slot, block of positions, ...)``.  Cutting a layer's lanes
  out of the stack first would copy them.
* **A work list is the grid.**  Which (slot, block) pairs there are
  follows from ``pos`` alone (:func:`work_list`, made once a step for
  all its layers); XLA lists them, the
  list's LENGTH is the kernel's one grid dimension (a traced value), and
  the pairs' slot and block are scalar-prefetch operands that the block
  index maps read.  Pallas fetches pair i + 1 while pair i is multiplied,
  across slots too.  A slot's pairs are consecutive, so the online
  softmax (float32 maximum, sum and accumulator) starts at a slot's block
  0 and is written out at its last.  No pair, no grid step: nothing is
  paid for the lanes' length, fetched or not.  A slot at position 0 is
  answered without the kernel: attention over one position is that
  position's value row.
* **One body, two shapes.**  Queries come as parts ``[B, Hq, Dk_i]`` and
  the keys as the matching parts of the cache ``[L, B, Smax, Dk_i]``;
  scores are the sum over the parts.  The value is an array of its own
  or, given as ``None``, the first key part: the block is fetched ONCE
  and serves both products (latent attention's absorbed form: 20 heads
  on one shared latent, the rotary key a second part).  A part may lie
  with its positions LAST, ``[L, B, Dk_i, Smax]``: that is how XLA keeps
  an array whose rows are narrower than the chip's 128 lanes in HBM (the
  rotary key's 64), and the kernel reads it as it lies where a row-major
  view would be a copy of the cache a step.  A cache with a
  head axis, ``[L, B, Smax, H, HD]``, is read as ``Smax * H`` keys of
  width HD (a view: a position's heads are one tile), and query row h
  attends the keys of head h alone, by the mask; the MXU then does the
  per-head sums and the context comes out ``[H, HD]`` with nothing to
  transpose.  Grouped queries are the same two shapes
  (``models/layers.py:lane_reader``): G x H query rows on the head axis,
  row h on head h // G; with one key/value head, rows that share a key;
  heads narrower than the chip's lanes held side by side,
  ``[L, B, Smax, H HD]``, as a shared key of H HD with each query row in
  its own head's place of it and a value array of its own.
* **A selection.**  With ``select`` (what ``ops/pallas_index_select.py``
  returns: every position's index score, and each slot's cut) a block's
  positions that are not among the slot's selected are masked like those
  past ``pos``: the softmax runs over the selected positions alone.  The
  lane is still walked block by block: a selection by position is
  scattered over every block, and fetching a lane's rows one by one cost
  more than reading it whole (XLA's gather of 24 x 2048 rows of 1 KB:
  2.0 ms a layer on a TPU v5e, 42 ns a row, against 0.54 for this walk
  of 16 lanes of 14k positions under 128 heads: PERF.md, section 6).
* Scores, maximum and sum in float32; probabilities and the context's
  operands in the cache's type.  The partial last block is masked by
  position in the scores AND in the value rows, so nothing past
  ``pos[b]`` reaches the output whatever the lane holds there (NaN
  included: tests/test_pallas_decode_attention.py).

Mosaic compiles the kernel where the surrounding step is lowered for a
TPU; everywhere else the same body is interpreted
(``ops/pallas_attention.py:_pallas_call``).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas_attention import (_NEG_INF, _pallas_call,
                                              _pick_block, _vmem)

# Positions a block holds, at most (the largest power-of-two fraction of
# this that divides the lane is taken).  With a head axis a block is
# ``BLOCK x H`` keys: 256 positions of 16 heads of 128 are 1 MB of keys and
# as much of values, and a [16, 4096] float32 score tile.  A shared latent
# is 1 KB a position and wants longer blocks for the same bytes a DMA.
BLOCK = 256
BLOCK_SHARED = 512


def block_for(cache_len: int, shared: bool) -> int:
    """Positions a block of the kernel holds for a lane of ``cache_len``."""
    return _pick_block(cache_len, BLOCK_SHARED if shared else BLOCK)


def blocks_read(pos, block: int):
    """Blocks of ``block`` positions the kernel fetches for each slot:
    those up to the one holding ``pos[b]``; none for a slot at 0."""
    return jnp.where(pos > 0, pos // block + 1, 0).astype(jnp.int32)


def pairs_run(pos, block: int):
    """(slot, block) pairs one call of the kernel runs: the blocks read,
    and never none: for a table of free slots alone it runs the last
    slot's block 0 (right, and unused) and not a grid of nothing."""
    return jnp.maximum(jnp.sum(blocks_read(pos, block)), 1)


def work_list(pos, cache_len: int, block: int):
    """The kernel's pairs for slots at ``pos`` [B] in lanes of
    ``cache_len``: (slot [N], block [N], pos, how many of the N run),
    slot b's blocks 0 .. pos[b] // block, the slots in order.  The same
    for every layer of a step: make it once, outside the layer loop.
    Sums over a [N, B] comparison and no gather, which costs a TPU tens
    of microseconds for these few hundred entries."""
    counts = blocks_read(pos, block)
    ends = jnp.cumsum(counts)
    item = jnp.arange(pos.shape[0] * (cache_len // block), dtype=jnp.int32)
    done = ends[None, :] <= item[:, None]           # slots wholly before i
    slot = jnp.minimum(jnp.sum(done, axis=1), pos.shape[0] - 1)
    blk = jnp.maximum(item - jnp.sum(jnp.where(done, counts[None, :], 0),
                                     axis=1), 0)
    return (slot.astype(jnp.int32), blk.astype(jnp.int32),
            pos.astype(jnp.int32), pairs_run(pos, block))


def ordered(x):
    """float32 -> int32 with the same order (an involution on the bits)."""
    u = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(u < 0, u ^ 0x7fffffff, u)


def selected(scores, cut, tie, position):
    """Whether ``position`` (int32, broadcastable to ``scores``) is among
    a slot's selected, from its index ``scores`` there and its ``cut`` and
    ``tie`` (ops/pallas_index_select.py): a score above the cut, or at it
    and no further on than the tie's position."""
    u = ordered(scores)
    return (u > cut) | ((u == cut) & (position <= tie))


def _kernel(layer_ref, slot_ref, blk_ref, pos_ref, *refs,
            last, own_value, group, scale, block, selects):
    """One (slot, block) pair.  ``refs``: the slot's query parts
    [Hq, Dk_i], the block of each key part ([keys, Dk_i], or [Dk_i, keys]
    where ``last[i]``) and of the value [keys, Dv] if it is an array of
    its own, with ``selects`` the block's index scores [1, keys] and the
    slot's cut and tie [1, 128], the slot's output [Hq, Dv], and the
    softmax's maximum, sum and accumulator."""
    n_parts = len(last)
    q_refs, k_refs = refs[:n_parts], refs[n_parts:2 * n_parts]
    v_ref = refs[2 * n_parts] if own_value else k_refs[0]
    o_ref, m_scr, l_scr, acc_scr = refs[-4:]
    i = pl.program_id(0)
    j, at = blk_ref[i], pos_ref[slot_ref[i]]
    hq, keys = acc_scr.shape[0], v_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = None
    for q_ref, k_ref, t in zip(q_refs, k_refs, last):
        part = lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (0 if t else 1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [hq, keys]
        s = part if s is None else s + part
    s = s * scale
    # Key c of the block is position j * block + c // group, head
    # c % group; row h of the queries attends its own head's keys, of
    # hq / group rows a head those of head h // (hq / group).
    col = lax.broadcasted_iota(jnp.int32, (hq, keys), 1)
    ok = j * block + col // group <= at
    if group > 1:
        row = lax.broadcasted_iota(jnp.int32, (hq, keys), 0)
        if hq != group:
            row = row // (hq // group)
        ok = jnp.logical_and(ok, col % group == row)
    if selects:
        score_ref, cut_ref, tie_ref = refs[-7:-4]
        ok = jnp.logical_and(ok, selected(
            score_ref[...], cut_ref[:, :1], tie_ref[:, :1],
            j * block + lax.broadcasted_iota(jnp.int32, (1, keys), 1)))
    s = jnp.where(ok, s, _NEG_INF)
    held = j * block + lax.broadcasted_iota(
        jnp.int32, (keys, 1), 0) // group <= at
    v = jnp.where(held, v_ref[...], jnp.zeros_like(v_ref))
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = corr * acc_scr[...] + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == at // block)
    def _last():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def decode_attention(q: Sequence[jax.Array], keys: Sequence[jax.Array],
                     value: Optional[jax.Array], layer, pos, *,
                     scale: float, block: Optional[int] = None, work=None,
                     positions_last: Optional[Sequence[bool]] = None,
                     select=None):
    """Attention of one query a slot over that slot's lane, up to ``pos``.

    ``q``: the query's parts, each ``[B, Hq, Dk_i]``.  ``keys``: the
    matching parts of the stacked cache, each ``[L, B, Smax, Dk_i]``, or
    ``[L, B, Dk_i, Smax]`` where ``positions_last[i]`` (heads share a
    position's key; the first part lies positions-first), or ONE part
    ``[L, B, Smax, H, HD]`` (a head has its own): Hq is H, or G x H with
    query rows h G to h G + G - 1 on head h (grouped queries).
    ``value``: a cache shaped like a key part with its own last
    dimension, or ``None`` for the first key part.  ``layer``: the lane's
    index on the leading axis, a traced scalar.  ``pos`` ``[B]``: slot b
    attends positions 0 to ``pos[b]``; whatever its lane holds past them
    never reaches the output.

    Returns ``[B, Hq, Dv]`` in the cache's type: softmax(q k / scale) v
    with float32 scores and sums.  ``block`` (positions a fetch, a divisor
    of Smax) defaults to :func:`block_for`.  ``work``: :func:`work_list`
    of (pos, Smax, block), from a caller that made it once for all the
    layers of its step.  ``select``: (scores [B, 1, Smax], cut, tie
    [B, 1, 128]) of ``ops/pallas_index_select.py:index_select`` for the
    same slots and block: slot b attends, of its positions, the selected
    alone (heads share a position's key); the kernel is then named
    ``sparse_attn`` in the compiled step and in a profile.
    """
    B, hq = q[0].shape[:2]
    last = tuple(positions_last or (False,) * len(keys))
    heads_own = keys[0].ndim == 5
    group = keys[0].shape[3] if heads_own else 1
    if heads_own and (len(keys) != 1 or hq % group):
        raise ValueError("a cache with a head axis is one key part whose "
                         "heads divide the query's")
    smax = keys[0].shape[2]
    if block is None:
        block = block_for(smax, shared=value is None)
    if smax % block:
        raise ValueError(f"block {block} does not divide the lane {smax}")
    if select is not None and heads_own:
        raise ValueError("a selection is of positions whose key the heads "
                         "share")
    caches = list(keys) + ([] if value is None else [value])
    if heads_own:       # [L, B, Smax, H, HD] read as [L, B, Smax * H, HD]
        caches = [a.reshape(*a.shape[:2], smax * group, a.shape[4])
                  for a in caches]
    values = caches[-1] if value is not None else caches[0]
    dv = values.shape[-1]

    slot, blk, at, pairs = work or work_list(pos, smax, block)

    def of_slot(i, layer_ref, slot_ref, blk_ref, pos_ref):
        return slot_ref[i], 0, 0

    def of_pair(i, layer_ref, slot_ref, blk_ref, pos_ref):
        return layer_ref[0], slot_ref[i], blk_ref[i], 0

    def of_pair_last(i, layer_ref, slot_ref, blk_ref, pos_ref):
        return layer_ref[0], slot_ref[i], 0, blk_ref[i]

    def of_scores(i, layer_ref, slot_ref, blk_ref, pos_ref):
        return slot_ref[i], 0, blk_ref[i]

    select_specs = [] if select is None else [
        pl.BlockSpec((None, 1, block), of_scores),
        pl.BlockSpec((None, 1, select[1].shape[-1]), of_slot),
        pl.BlockSpec((None, 1, select[2].shape[-1]), of_slot)]
    out = _pallas_call(
        "decode_attn" if select is None else "sparse_attn",
        functools.partial(
            _kernel, last=last, own_value=value is not None, group=group,
            scale=scale, block=block, selects=select is not None),
        jnp.asarray(layer, jnp.int32).reshape(1), slot, blk, at, *q, *caches,
        *(select or ()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(pairs,),
            in_specs=[pl.BlockSpec((None, hq, a.shape[-1]), of_slot)
                      for a in q]
            + [pl.BlockSpec((None, None, a.shape[2], block), of_pair_last)
               if t else
               pl.BlockSpec((None, None, block * group, a.shape[-1]), of_pair)
               for a, t in zip(caches, last + (False,))]
            + select_specs,
            out_specs=pl.BlockSpec((None, hq, dv), of_slot),
            scratch_shapes=[_vmem((hq, 1)), _vmem((hq, 1)),
                            _vmem((hq, dv))]),
        out_shape=jax.ShapeDtypeStruct((B, hq, dv), values.dtype))
    # One position: its value row is the answer (softmax of one score).
    # No pair of the list is such a slot's, and its row of ``out`` is
    # whatever the buffer held.
    first = lax.dynamic_slice(values, (layer, 0, 0, 0),
                              (1, B, group, dv))[0]        # [B, group, Dv]
    if 1 < group < hq:                  # a head's row for each of its queries
        first = jnp.repeat(first, hq // group, axis=1)
    return jnp.where((pos == 0)[:, None, None],
                     jnp.broadcast_to(first, out.shape), out)
