"""Rotary embedding along the lanes of ``[B, S, H * HD]`` (a Pallas TPU
kernel).

The LM training layer keeps q and k as ``[B, S, H * HD]``, a head's ``HD``
values side by side, because that is what its projections write and the
attention kernel reads in place (``ops/pallas_attention.py``).  Pair i of a
head is its lanes i and i + HD/2, so the rotation needs each lane's partner
``HD/2`` lanes away: a rotation of the head's lanes by half their number
(which way round does not matter).  XLA has no cheap form of that on a TPU:
it moves lanes through memory (two slices of 64 lanes, padded to 128, and a
concatenate: three passes, one of them float32, where the rotation is one),
or it picks another tiling for the array and copies it there and back,
which is what ``models/layers.py:_rope`` on ``[B, S, H, HD]`` cost
around the attention kernel.  In a kernel it is one ``pltpu.roll`` of a
``[rows, HD]`` tile in vector registers: the array is read once and written
once, in the type it has, float32 inside.

``HD`` has to be a whole number of the chip's 128-lane tiles (a head's
slice of a row is then tile-aligned); the caller keeps any other head
dimension on ``_rope``.  On every other platform the same body is
interpreted (``pallas_attention._pallas_call``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas_attention import _pallas_call, _pick_block

# Rows of a row block: 256 rows of 2048 bfloat16 lanes are 1 MB in and 1 MB
# out a grid step, double-buffered 4 MB of the 16 MB a kernel may hold.
BLOCK_ROWS = 256


def _rotate_kernel(x_ref, cos_ref, sin_ref, o_ref, *, n_heads):
    head_dim = cos_ref.shape[1]
    cos, sin = cos_ref[...], sin_ref[...]               # [rows, HD] f32
    for h in range(n_heads):
        lanes = slice(h * head_dim, (h + 1) * head_dim)
        x = x_ref[:, lanes].astype(jnp.float32)
        partner = pltpu.roll(x, head_dim // 2, 1)
        o_ref[:, lanes] = (x * cos + partner * sin).astype(o_ref.dtype)


def _rotate(x, cos, sin, n_heads):
    B, S, F = x.shape
    rows = _pick_block(S, BLOCK_ROWS)
    by_row = pl.BlockSpec((None, rows, F), lambda b, i: (b, i, 0))
    table = pl.BlockSpec((rows, F // n_heads), lambda b, i: (i, 0))
    return _pallas_call(
        "rope_lanes", functools.partial(_rotate_kernel, n_heads=n_heads),
        x, cos, sin, grid=(B, S // rows), in_specs=[by_row, table, table],
        out_specs=by_row, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def rotate(x, cos, sin, n_heads):
    """``x`` [B, S, H * HD] with every head's pairs (i, i + HD/2) turned by
    the angles whose cosines and sines are ``cos``, ``sin`` [S, HD/2]
    (float32, the rows share their positions): the products and sums of
    ``models/layers.py:_rope`` on ``x`` as [B, S, H, HD], in ``x``'s
    type and shape.  ``HD`` a multiple of 128."""
    # Lane j of a head takes x[j] cos + x[partner] (-sin | +sin): the
    # first half's partner carries a minus, the second half's a plus.
    return _rotate(x, jnp.concatenate([cos, cos], axis=-1),
                   jnp.concatenate([-sin, sin], axis=-1), n_heads)


def _rotate_fwd(x, cos, sin, n_heads):
    return rotate(x, cos, sin, n_heads), (cos, sin)


def _rotate_bwd(n_heads, tables, g):
    # A rotation's transpose is the rotation back: the same pass with the
    # sines negated.  The tables are functions of the positions alone.
    cos, sin = tables
    return (rotate(g, cos, -sin, n_heads), jnp.zeros_like(cos),
            jnp.zeros_like(sin))


rotate.defvjp(_rotate_fwd, _rotate_bwd)
