"""One decode turn's Mamba-2 recurrence, for one layer, as ONE Pallas TPU
kernel over the slots that hold a request: a single read and a single
write of a live slot's state, in place.

For slot ``b`` and head ``h`` (group ``g = h // (H / G)``) a step is::

    S[b, h] <- exp(dt[b, h] a[h]) S[b, h]
               + (dt[b, h] x[b, h, :]) (x) B[b, g, :]
    y[b, h, :] = S[b, h] C[b, g, :]             the NEW state's answer

Written as an update and then a sum XLA makes three passes over the state
of every slot (the update in place, then the read-out reads what it
wrote).  Here a slot's state is fetched once, updated, answers ``C`` and
is written back (the input is aliased to the output, and the layer is
addressed inside the kernel: cutting a layer out of the stack would copy
it).

* **The state lies with the sum's axis down the sublanes**: ``[L, B, G, N,
  W]`` float32, ``W = (H / G) P`` a group's heads' channels side by side
  along the lanes (:func:`from_heads`).  ``B`` and ``C`` are then a scalar a
  row, the decay and ``dt x`` a value a lane, the read-out's sum over ``N``
  is plain VPU adds of whole tiles with one 8-row fold at the end, and
  ``y`` comes out lane-dense in the order ``[H, P]`` has.  (With ``N``
  along the lanes the sum is a lane reduction a row or a trip through the
  MXU at six bfloat16 passes for float32: PERF.md, PR 47.)
* **The grid is the live slots** (:func:`live_slots`, made once a turn for
  all its layers): grid step ``i`` works on slot ``slots[i]``; steps past
  the count name the last live slot's block again, which Pallas neither
  fetches nor writes anew, and do nothing.  A free slot's state is neither
  read nor written; its row of ``y``, which the kernel does not write, is
  given as zeros.  With no live slot at all the one block that the grid
  names is copied through, so that what is written back is what was there.
* ``B`` and ``C`` come as rows ``[2 G, N]`` a slot and are turned into
  columns inside the kernel, once a slot.
* All float32, nothing on the MXU.

Mosaic compiles the kernel where the surrounding step is lowered for a
TPU; everywhere else the same body is interpreted
(``ops/pallas_attention.py:_pallas_call``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas_attention import _pallas_call

# Rows of a group's N a trip of the body's loop steps: [32, 512] of the state
# and as much of the read-out's partial sums are half the register file.  On
# the chip a turn of 64 live slots read 3.17 ms with 8 rows a trip (the
# loop's own steps, not the bytes), 1.86 with 16 and 1.80 with 32
# (tools/ssd_step_probe.py; PERF.md, PR 47).
TILE = 32


def from_heads(ssm, groups: int):
    """``[..., H, P, N]`` (a head's matrix, ``N`` minor) as the kernel
    holds it: ``[..., G, N, (H / G) P]``."""
    *lead, H, P, N = ssm.shape
    s = ssm.reshape(*lead, groups, H // groups, P, N)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, groups, N,
                                           (H // groups) * P)


def to_heads(s, heads: int):
    """The inverse of :func:`from_heads`: ``[..., G, N, W]`` as ``[..., H,
    P, N]``."""
    *lead, G, N, W = s.shape
    R = heads // G
    return jnp.moveaxis(s.reshape(*lead, G, N, R, W // R), -3, -1).reshape(
        *lead, heads, W // R, N)


def live_slots(live):
    """The kernel's work list for slots of which ``live`` [B] (bool) hold
    a request: (slots [B] int32, count [1] int32, ``live``), the live slots
    in order and then the last of them again (slot 0 where none is live).
    The same for every layer of a turn: make it once, outside the layer
    loop.  Sums over a [B, B] comparison and no gather
    (``ops/pallas_decode_attention.py:work_list``)."""
    n = live.shape[0]
    ends = jnp.cumsum(live.astype(jnp.int32))
    item = jnp.arange(n, dtype=jnp.int32)
    slot = jnp.sum(ends[None, :] <= item[:, None], axis=1)
    last = jnp.max(jnp.where(live, item, 0))
    return jnp.minimum(slot, last).astype(jnp.int32), ends[-1:], live


def _kernel(layer_ref, slots_ref, count_ref, bc_ref, decay_ref, dx_ref,
            s_ref, y_ref, out_ref, cols_scr):
    """One live slot.  bc [2 G, N]: the groups' B rows, then their C rows;
    decay, dx, y [G, W]; s, out [G, N, W]; cols_scr [N, 2 G]."""
    i = pl.program_id(0)
    count = count_ref[0]
    G, N, W = s_ref.shape
    tile = min(TILE, N)

    @pl.when(i < count)
    def _step():
        cols_scr[...] = bc_ref[...].T
        for g in range(G):
            decay = decay_ref[g:g + 1, :]
            dx = dx_ref[g:g + 1, :]

            def rows_of(t, acc):
                at = pl.ds(pl.multiple_of(t * tile, tile), tile)
                s = decay * s_ref[g, at, :] + cols_scr[at, g:g + 1] * dx
                out_ref[g, at, :] = s
                return acc + s * cols_scr[at, G + g:G + g + 1]

            acc = lax.fori_loop(0, N // tile, rows_of,
                                jnp.zeros((tile, W), jnp.float32))
            y_ref[g:g + 1, :] = jnp.sum(acc, axis=0, keepdims=True)

    @pl.when((count == 0) & (i == 0))
    def _through():
        out_ref[...] = s_ref[...]


def ssd_step(S, layer, work, x, dt, a, b_in, c_out):
    """``S`` [L, B, G, N, W] float32 with layer ``layer`` (a static or
    traced scalar) stepped in place for the slots of ``work``
    (:func:`live_slots`).  x: [B, H, P]; dt: [B, H]; a: [H]; b_in, c_out:
    [B, G, N]; all float32.  Returns (S, y [B, H, P] without the ``D x``
    term: zeros for the slots not in ``work``)."""
    L, B, G, N, W = S.shape
    slots, count, live = work
    lanes = (B, G, W)
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], x.shape)
    dx = dt[..., None] * x
    bc = jnp.concatenate([b_in, c_out], axis=1)

    def of_slot(i, layer_ref, slots_ref, count_ref):
        return slots_ref[i], 0, 0

    def of_state(i, layer_ref, slots_ref, count_ref):
        return layer_ref[0], slots_ref[i], 0, 0, 0

    rows = pl.BlockSpec((None, G, W), of_slot)
    state = pl.BlockSpec((None, None, G, N, W), of_state)
    y, S = _pallas_call(
        "ssd_step", _kernel,
        jnp.asarray(layer, jnp.int32).reshape(1), slots, count, bc,
        decay.reshape(lanes), dx.reshape(lanes), S,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[pl.BlockSpec((None, 2 * G, N), of_slot), rows, rows,
                      state],
            out_specs=[rows, state],
            scratch_shapes=[pltpu.VMEM((N, 2 * G), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(lanes, jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)],
        # operand 6 (after the three scalar prefetches) is S; output 1
        input_output_aliases={6: 1})
    return S, jnp.where(live[:, None, None], y.reshape(x.shape), 0.0)
