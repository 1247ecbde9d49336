"""One layer's routed gated experts over a turn's rows as ONE Pallas TPU
kernel: each expert's three matrices streamed once past the rows that
chose it, the product between them never leaving fast memory.

For the (row, expert) pairs of a turn, sorted by expert, expert ``e``'s
rows ``x_e`` give::

    y_e = ((x_e w_in[l, e]) * silu(x_e w_gate[l, e])) w_out[l, e]

As three grouped products (``jax.lax.ragged_dot``) each call reads a third
of every touched expert and the ``[pairs, F]`` product between them goes
out to main memory and comes back; with a few dozen rows an expert the
turn is the weights' read, and the three calls reach half the chip's
memory rate (PERF.md, PR 49).  Here:

* **The grid is (expert, tile of F).**  The rows ``[pairs, D]`` and a
  float32 result ``[pairs, D]`` stay resident; grid step ``(e, f)`` has
  ``w_in[l, e, :, f]``, ``w_gate[l, e, :, f]`` and ``w_out[l, e, f, :]``
  fetched for it ONCE (the pipeline fetches the next step's while this
  one's products run), forms ``h`` for expert ``e``'s rows in float32,
  rounds it to the operands' dtype for the last product and adds that
  product to the rows' result.  After the last step the result is written
  out in the operands' dtype.
* **Layer and expert are addressed in the index maps** from
  scalar-prefetched values, on the stacks ``[L, E, D, F]`` whole: a layer
  cut out of its stack in front of the call would be a copy of all of it.
* **An expert with no row** names the block of the step before it again,
  which Pallas does not fetch anew, and does nothing
  (``ops/pallas_ssd.py`` does the same for its free slots).  Rows behind
  the last group (free slots' pairs) are in no product and come back as
  zeros.
* **A group's rows are where the sort put them.**  The kernel walks
  windows of ``ROWS`` rows from the 16-row boundary at or under the
  group's first row (a whole sublane tile of bfloat16, so every load and
  store is aligned), takes the products over a whole window and keeps,
  under a mask, the rows that are the group's.  The last window is
  pulled back inside the array and the mask leaves out what an earlier
  window already gave.  No scatter into padded groups in front of the
  call, no gather behind it.
* **The stacks may be held wider than published** (zeros up to
  ``experts.padded_width``): the grid stops at the published ``width``,
  so the zero columns are never fetched.

A window is ``ROWS`` (64) rows and a grid step ``COLUMNS`` (128) columns,
chosen on the chip, where neither moves a turn by 3 % (the timings stand
at the constants; ``tools/routed_ffn_probe.py``; PERF.md, PR 50): the
masked windows were kept over a scatter into padded groups because they
need nothing in front of the call or behind it and cost nothing at any
size tried.

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

from horovod_tpu.ops.pallas_attention import _pallas_call, _product

# Rows a window of the walk, and columns of F a grid step (the widest of
# them that divides the width is taken).  On the chip, a turn of the
# ``lfm2-8b-a1b`` cell's 12 layers (32 experts x 3 x [2048, 1792] of stacks
# held 2048 wide, 464 of 768 pairs in a group): 11.45 ms with 128 columns a
# step, 11.76 with 256, 11.54 with 896, 11.46 with 1792 (one step an
# expert), and the same to 0.02 ms for windows of 16, 32, 64 and 128 rows:
# 739 GB/s of the experts' published bytes, where the three ``ragged_dot``
# calls take 20.43 ms, 414 GB/s (tools/routed_ffn_probe.py; PERF.md, PR 50).
ROWS = 64
COLUMNS = (128,)
# A bfloat16 sublane tile: what a window's first row is a multiple of.
SUBLANES = 16
# What the kernel may hold in fast memory: the resident rows and result,
# two buffers of each weight tile, the products of a window (v5e: 128 MiB).
VMEM_LIMIT = 100 * 1024 * 1024


def columns(width: int, held: int):
    """(the columns of F a grid step has, the steps an expert) for stacks
    ``held`` wide of which ``width`` are published: tiles up to the width
    or, where no tile divides it, the held columns whole."""
    for c in COLUMNS:
        if width % c == 0:
            return c, width // c
    return held, 1


def work_list(counts, tiles: int):
    """What the index maps and the body read, from the groups' ``counts``
    [E]: (``source`` [E], ``column`` [E], ``start`` [E], ``counts``), all
    int32.  An expert with rows fetches its own blocks (``column`` -1: the
    grid's own tile); one without names the last block of the nearest
    expert with rows before it, or the first block of the first expert
    with rows where there is none before: the block the step before it
    had, or the step after it will have."""
    E = counts.shape[0]
    has = counts > 0
    ids = jnp.arange(E, dtype=jnp.int32)
    before = lax.cummax(jnp.where(has, ids, -1))
    source = jnp.where(before >= 0, before, jnp.argmax(has).astype(jnp.int32))
    column = jnp.where(has, -1, jnp.where(before >= 0, tiles - 1, 0))
    start = jnp.cumsum(counts) - counts
    return (source.astype(jnp.int32), column.astype(jnp.int32),
            start.astype(jnp.int32), counts.astype(jnp.int32))


def _kernel(layer_ref, source_ref, column_ref, start_ref, count_ref, x_ref,
            w_in_ref, w_gate_ref, w_out_ref, y_ref, acc_scr, *, rows):
    """Grid step (e, f).  x, y [pairs, D]; w_in, w_gate [D, c]; w_out
    [c, D]; acc_scr [pairs, D] float32."""
    e, f = pl.program_id(0), pl.program_id(1)
    pairs = x_ref.shape[0]
    start, count = start_ref[e], count_ref[e]

    @pl.when((e == 0) & (f == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(count > 0)
    def _expert():
        end = start + count
        first = (start // SUBLANES) * SUBLANES

        def window(i, carry):
            low = first + i * rows
            at = pl.multiple_of(jnp.minimum(low, pairs - rows), SUBLANES)
            x = x_ref[pl.ds(at, rows), :]
            h = _product(x, w_in_ref[...]) * jax.nn.silu(
                _product(x, w_gate_ref[...]))
            y = _product(h.astype(x.dtype), w_out_ref[...])
            row = at + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            mine = (row >= jnp.maximum(low, start)) & (row < end)
            acc_scr[pl.ds(at, rows), :] += jnp.where(mine, y, 0.0)
            return carry

        lax.fori_loop(0, pl.cdiv(end - first, rows), window, 0)

    @pl.when((e == pl.num_programs(0) - 1) & (f == pl.num_programs(1) - 1))
    def _done():
        y_ref[...] = acc_scr[...].astype(y_ref.dtype)


def routed_ffn_rows(xs, counts, layer, w_in, w_gate, w_out, width=None):
    """xs: [pairs, D], the pairs' rows in expert order; ``counts`` [E]
    int32: how many of them each expert has, the rest (behind the last
    group) none; ``layer``: which of the L (static or traced); w_in,
    w_gate: [L, E, D, F], w_out: [L, E, F, D] in xs's dtype; ``width``:
    the columns of F that are not zero by construction (None: all).
    Returns [pairs, D] in xs's dtype, zeros behind the last group."""
    pairs, D = xs.shape
    L, E, _, held = w_in.shape
    c, tiles = columns(held if width is None else width, held)
    rows = min(ROWS, -(-pairs // SUBLANES) * SUBLANES)
    padded = -(-pairs // rows) * rows
    if padded != pairs:
        xs = jnp.pad(xs, ((0, padded - pairs), (0, 0)))

    def column(f, e, column_ref):
        return jnp.where(column_ref[e] < 0, f, column_ref[e])

    def of_in(e, f, layer_ref, source_ref, column_ref, *_):
        return layer_ref[0], source_ref[e], 0, column(f, e, column_ref)

    def of_out(e, f, layer_ref, source_ref, column_ref, *_):
        return layer_ref[0], source_ref[e], column(f, e, column_ref), 0

    whole = pl.BlockSpec((padded, D), lambda e, f, *_: (0, 0))
    into = pl.BlockSpec((None, None, D, c), of_in)
    y = _pallas_call(
        "routed_ffn_rows", functools.partial(_kernel, rows=rows),
        jnp.asarray(layer, jnp.int32).reshape(1), *work_list(counts, tiles),
        xs, w_in, w_gate, w_out,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(E, tiles),
            in_specs=[whole, into, into,
                      pl.BlockSpec((None, None, c, D), of_out)],
            out_specs=whole,
            scratch_shapes=[pltpu.VMEM((padded, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((padded, D), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT))
    return y[:pairs] if padded != pairs else y
