"""The step form's pass over a retention layer's state as ONE Pallas TPU
kernel: a single read and a single write of ``S``.

``S`` is ``[L, B, KVH, HD, D]`` float32, a slot's matrix a key/value head
with the ``D`` rows of the symmetric square MINOR.  A decode step needs,
for every slot and head, the old state's answer to the group's queries,
``S_old phi(q)``, and the new state ``decay S_old + v phi(k)^T``.  Written
as a dot and then an update XLA reads ``S`` twice; here a block ``[HD,
block]`` of it is fetched once, answers the queries and is written back
updated, in place (the input is aliased to the output, and the layer is
addressed inside the kernel: cutting a layer out of the stack would copy
it).

* The grid is (slot, head, block of rows); the answers accumulate over
  the row blocks in the output's own block.
* ``phi`` comes in made (``[B, KVH, R, D]``: the group's queries' rows
  first, then the key's), lane-dense like ``S``.  The answer to query g
  is ``sum_d S[:, d] phi[g, d]``: the products are the VPU's (a product
  of 5 vectors with a matrix leaves the MXU idle for its weight loads),
  summed over the lanes' 128-wide chunks into ``[HD, 128]`` partial sums,
  and only those go through the MXU, against a row of ones, to come out
  lane-dense.
* All float32; the one matrix product is at ``precision=highest``.

Mosaic compiles the kernel where the surrounding step is lowered for a
TPU; everywhere else the same body is interpreted
(``ops/pallas_attention.py:_pallas_call``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas_attention import _pallas_call

LANES = 128
HI = lax.Precision.HIGHEST
# Rows of S a grid step holds, at most: [128, 1664] float32 is 0.85 MB,
# and Pallas keeps two of the input and two of the output.
BLOCK = 1664
# Rows of a block's HD the body holds in registers at a time (8, 16 and 32
# read within 1.3 % of each other on the chip: PERF.md, PR 32).
TILE = 8


def block_for(rows: int) -> int:
    """The largest multiple of 128 that divides ``rows`` and is at most
    ``BLOCK``."""
    chunks = rows // LANES
    best = max(c for c in range(1, chunks + 1)
               if chunks % c == 0 and c * LANES <= BLOCK)
    return best * LANES


def _kernel(layer_ref, phi_ref, v_ref, decay_ref, s_ref, acc_ref, out_ref,
            part_scr, *, n_q: int):
    """One (slot, head, block of rows).  phi [R, block]; v [HD, 1]; decay
    [1, 1]; s, out [HD, block]; acc [R, HD], the slot's answers, summed
    over the blocks; part_scr [n_q, HD, 128]."""
    r = pl.program_id(2)
    hd, block = s_ref.shape
    chunks = block // LANES

    @pl.when(r == 0)
    def _init():
        part_scr[...] = jnp.zeros_like(part_scr)

    decay = decay_ref[...]

    def rows_of(i, carry):
        at = pl.ds(pl.multiple_of(i * TILE, TILE), TILE)
        s = s_ref[at, :]                                     # [TILE, block]
        for g in range(n_q):
            p = s * phi_ref[g:g + 1, :]
            part = p[:, :LANES]
            for c in range(1, chunks):
                part = part + p[:, c * LANES:(c + 1) * LANES]
            part_scr[g, at, :] += part
        out_ref[at, :] = decay * s + v_ref[at, :] * phi_ref[n_q:n_q + 1, :]
        return carry

    lax.fori_loop(0, hd // TILE, rows_of, 0)

    @pl.when(r == pl.num_programs(2) - 1)
    def _answers():
        ones = jnp.ones((acc_ref.shape[0], LANES), jnp.float32)
        for g in range(n_q):
            # Row sums of the partial sums, lane-dense: ones . part^T.
            acc_ref[g:g + 1, :] = lax.dot_general(
                ones, part_scr[g], (((1,), (1,)), ((), ())), precision=HI,
                preferred_element_type=jnp.float32)[:1]


def retention_step(S, layer, phi, v, decay, *, n_q: int):
    """``S`` [L, B, KVH, HD, D] float32 with layer ``layer`` (a traced
    scalar) stepped in place: ``S[layer] <- decay S[layer] + v phi_k^T``.
    ``phi`` [B, KVH, R, D]: rows [0, n_q) the queries' and row n_q the
    key's ``phi`` (R a multiple of 8, the other rows unused); ``v`` [B,
    KVH, HD]; ``decay`` [B, KVH].  Returns (S, the OLD state's answers
    [B, KVH, n_q, HD] = S_old phi_q)."""
    L, B, KVH, HD, D = S.shape
    R = phi.shape[2]
    block = block_for(D)

    def of_slot(b, h, r, layer_ref):
        return b, h, 0, 0

    def of_phi(b, h, r, layer_ref):
        return b, h, 0, r

    def of_state(b, h, r, layer_ref):
        return layer_ref[0], b, h, 0, r

    acc, S = _pallas_call(
        "retention_step",
        functools.partial(_kernel, n_q=n_q),
        jnp.asarray(layer, jnp.int32).reshape(1), phi, v[..., None],
        decay[..., None, None], S,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, KVH, D // block),
            in_specs=[pl.BlockSpec((None, None, R, block), of_phi),
                      pl.BlockSpec((None, None, HD, 1), of_slot),
                      pl.BlockSpec((None, None, 1, 1), of_slot),
                      pl.BlockSpec((None, None, None, HD, block), of_state)],
            out_specs=[pl.BlockSpec((None, None, R, HD), of_slot),
                       pl.BlockSpec((None, None, None, HD, block),
                                    of_state)],
            scratch_shapes=[pltpu.VMEM((n_q, HD, LANES), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, KVH, R, HD), jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)],
        # operand 4 (after the scalar prefetch: index 4) is S; output 1
        input_output_aliases={4: 1})
    return S, acc[:, :, :n_q]
