"""A learned indexer's scores over a slot cache, and the exact cut at the
``top`` best of them, as ONE Pallas TPU kernel (``index_select``).

A sparse-attention decode step (models/latent_moe.py with ``index_topk``)
scores every position a slot has written with a small indexer and lets
its attention see the ``top`` best alone::

    I[b, s] = sum_h w[b, h] * relu(q[b, h] . k[layer, b, s])      s <= pos[b]

over a cache of ONE index key a position, ``[L, B, Smax, Dk]``.  The
selection is the cost, not the scores: for 24 slots of 18 432 positions
on a TPU v5e ``lax.top_k`` of 2048 is 0.41 ms a layer (a sort), a
scatter that compacts the selected 2.1, and 33 counting passes over the
scores 0.055 (a probe of XLA's forms; this kernel, scores and passes
together, reads 0.26 ms a layer in the benchmark's cell: PERF.md,
section 5).  So nothing is sorted and no index list is made:

* the grid is ``ops/pallas_decode_attention.py``'s work list of (slot,
  block) pairs, so a lane is scored as far as its slot has written it and
  the layer is addressed inside the kernel;
* a pair's scores go out (``[B, 1, Smax]`` float32, minus infinity past
  ``pos``) and into a row of a scratch that holds the slot's whole lane;
* at a slot's last block the kernel finds the ``top``-th largest score
  EXACTLY: float32 maps to int32 with the same order, and the answer's
  32 bits are settled one at a time, each by one count over the lane in
  fast memory.  Equal scores at the cut are taken by position, lowest
  first, as ``lax.top_k`` takes them: 15 more counts find the position of
  the last one taken.

What comes back is the scores, the cut (in the int32 order) and the tie's
position: position ``s`` of slot ``b`` is selected iff ``u > cut`` or
(``u == cut`` and ``s <= tie``) with ``u = ordered(scores[b, 0, s])``, which
is what ``pallas_decode_attention.selected`` computes and the attention
kernel applies to its blocks (``decode_attention(..., select=)``).  A slot
with no more than ``top`` positions selects them all (cut at minus
infinity).  A slot at position 0 is in no pair: its row is not written.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas_attention import _pallas_call, _vmem
from horovod_tpu.ops.pallas_decode_attention import (block_for, ordered,
                                                     work_list)

_INT_MIN = -(1 << 31)
LANES = 128         # the cut and the tie come back as rows of one tile


def _count(cond):
    """How many of a 2-D mask are set, [1, 1] float32 (exact to 2**24)."""
    ones = jnp.where(cond, 1.0, 0.0)
    return jnp.sum(jnp.sum(ones, axis=0, keepdims=True), axis=1,
                   keepdims=True)


def _kernel(layer_ref, slot_ref, blk_ref, pos_ref, q_ref, w_ref, k_ref,
            s_ref, cut_ref, tie_ref, lane_scr, *, block, top):
    """One (slot, block) pair: the block's scores; at the slot's last
    block, the cut over the whole lane."""
    i = pl.program_id(0)
    j, at = blk_ref[i], pos_ref[slot_ref[i]]
    s = lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)    # [H, block]
    score = jnp.sum(jnp.maximum(s, 0.0) * w_ref[...], axis=0,
                    keepdims=True)                              # [1, block]
    col = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
    score = jnp.where(col <= at, score, -jnp.inf)
    s_ref[...] = score
    lane_scr[pl.ds(j, 1), :] = score

    @pl.when(j == at // block)
    def _cut():
        shape = lane_scr.shape
        position = (lax.broadcasted_iota(jnp.int32, shape, 0) * block
                    + lax.broadcasted_iota(jnp.int32, shape, 1))
        # Rows past the slot's last block are another slot's leftovers.
        u = ordered(jnp.where(position <= at, lane_scr[...], -jnp.inf))
        want = jnp.float32(top)
        cut = jnp.where(_count(u >= 0) >= want, 0, _INT_MIN)   # the sign
        for bit in range(30, -1, -1):       # then the 31 bits under it
            cand = cut | (1 << bit)
            cut = jnp.where(_count(u >= cand) >= want, cand, cut)
        # Equal scores at the cut: the first ``need`` by position.
        need = want - _count(u > cut)
        equal = u == cut
        tie = jnp.zeros((1, 1), jnp.int32)
        for bit in range((shape[0] * block - 1).bit_length() - 1, -1, -1):
            cand = tie | (1 << bit)
            tie = jnp.where(_count(equal & (position < cand)) < need,
                            cand, tie)
        everything = at < top           # no more positions than ``top``
        cut_ref[...] = jnp.broadcast_to(
            jnp.where(everything, _INT_MIN, cut), cut_ref.shape)
        tie_ref[...] = jnp.broadcast_to(
            jnp.where(everything, -1, tie), tie_ref.shape)


def index_select(q, w, keys, layer, pos, *, top: int, work=None):
    """``q`` [B, H, Dk] (the cache's type) and ``w`` [B, H] float32: the
    indexer's queries and head weights of one new token a slot; ``keys``
    [L, B, Smax, Dk]: the stacked index-key cache; ``layer`` a traced
    scalar; ``pos`` [B]: slot b is scored over positions 0 to ``pos[b]``.
    ``work``: ``work_list(pos, Smax, block_for(Smax, shared=True))`` from
    a caller that made it once for all the layers of its step.

    Returns (scores [B, 1, Smax] float32, cut [B, 1, 128] int32, tie
    [B, 1, 128] int32): see the module docstring."""
    B, H, dk = q.shape
    smax = keys.shape[2]
    block = block_for(smax, shared=True)
    slot, blk, at, pairs = work or work_list(pos, smax, block)
    rows = -(-(smax // block) // 8) * 8         # whole tiles of 8 rows

    def of_slot(i, layer_ref, slot_ref, blk_ref, pos_ref):
        return slot_ref[i], 0, 0

    def of_pair(i, layer_ref, slot_ref, blk_ref, pos_ref):
        return layer_ref[0], slot_ref[i], blk_ref[i], 0

    def of_scores(i, layer_ref, slot_ref, blk_ref, pos_ref):
        return slot_ref[i], 0, blk_ref[i]

    return _pallas_call(
        "index_select",
        functools.partial(_kernel, block=block, top=top),
        jnp.asarray(layer, jnp.int32).reshape(1), slot, blk, at,
        q, w.astype(jnp.float32)[:, :, None], keys,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(pairs,),
            in_specs=[pl.BlockSpec((None, H, dk), of_slot),
                      pl.BlockSpec((None, H, 1), of_slot),
                      pl.BlockSpec((None, None, block, dk), of_pair)],
            out_specs=[pl.BlockSpec((None, 1, block), of_scores),
                       pl.BlockSpec((None, 1, LANES), of_slot),
                       pl.BlockSpec((None, 1, LANES), of_slot)],
            scratch_shapes=[_vmem((rows, block))]),
        out_shape=[jax.ShapeDtypeStruct((B, 1, smax), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, LANES), jnp.int32)])
