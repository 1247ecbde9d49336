"""Flash attention as a Pallas TPU kernel (forward + backward).

The hot op of the flagship transformer, written for the hardware: the
blockwise online-softmax algorithm keeps every [block_q, block_k] score
tile in VMEM and never materializes the [S, S] attention matrix in HBM —
O(S) memory instead of O(S^2), with the two matmuls per tile landing on
the MXU.  The backward pass recomputes score tiles from the saved
logsumexp (the standard flash recipe): one kernel accumulates dQ over key
blocks, a second accumulates dK/dV over query blocks.

This is a TPU-native extension, not a reference port (the reference has
no attention code at all — SURVEY.md §2.8); the algorithm is the public
FlashAttention-2 blockwise recipe re-derived for Pallas.  Composition:

* ``attn_impl="flash"`` on :class:`TransformerConfig` routes the
  non-sequence-parallel attention path here.
* Under sequence parallelism the ring attention layer
  (``parallel/ring_attention.py``) rotates K/V blocks over the ``sp``
  ring with the same online-softmax update — this kernel is the
  single-chip analog of one ring hop.

A computation lowered for TPU devices carries the Mosaic-compiled kernel;
lowered for any other platform (tests run on the CPU backend) the same
kernel body is interpreted.  The choice follows the lowering platform, not
``jax.default_backend()``: see :func:`_pallas_call`.

The schedule of the three calls (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``; the arithmetic is the recipe's):

* **The operands are read where they lie.**  q, k, v, the context, its
  cotangent and the three gradients are ``[B, S, H * D]``, a head's ``D``
  values side by side: what a 2-D projection writes and the output
  projection reads (``models/transformer.py:_flash_attention``).  A call's
  block stays ``(bq, D)`` / ``(bk, D)``; its index map picks head ``h`` of
  row ``b`` as lane block ``h`` of row ``b`` (:func:`_block_specs`), ``bq``
  rows of ``D`` lanes at a stride of ``H * D``.  That needs ``D`` to be a
  whole number of the chip's 128-lane tiles.  With any other ``D`` (64: no
  model the repo measures) the heads are folded in front of the SAME
  calls, ``[B * H, S, D]``, the case ``H = 1`` of the same index maps, at
  the cost of a transposition of every operand and result; so is the 4-D
  form ``[B, S, H, D]`` reshaped, which on a TPU tiles its last two
  dimensions and is a copy away from ``[B, S, H * D]``.  Before, every
  caller's arrays were folded: 67 MB a transposition at the training
  cell's shape (134 MB for a float32 gradient), eight of them a layer in
  the step compiled for the chip.  The rotary embedding of such an array
  is ``ops/pallas_rope.py``'s.
* **A list of pairs is the grid** (:func:`block_pairs`, made at trace
  time, in scalar memory): a causal call steps the (query block, key
  block) pairs that hold a key at or under a query and no other, so the
  pairs above the diagonal are neither stepped nor fetched (a ``pl.when``
  around the body would skip their work and still fetch their blocks); a
  non-causal call, a ring hop, lists every pair.
  Every listed pair of a causal call is masked, as on the full grid: a
  second, unmasked body for the pairs wholly under the diagonal read
  0.00 ms at the shape below and was not kept.
* **The per-row numbers lie along the lanes.**  The log-sum-exp, ``delta``
  and an lse cotangent travel as ``[BH, S // bq, 1, bq]`` (:func:`_rows`),
  never with a trailing dimension of one, which the chip pads to 128
  lanes.  ``flash_bwd_dkv`` holds its scores transposed (keys down,
  queries along the lanes), so a query block's row broadcasts over them as
  it comes and ``dv``, ``dk`` are plain products; ``flash_fwd`` and
  ``flash_bwd_dq`` turn a column into a row, or back, once a query block.

Dot inputs keep their storage dtype (f32 upcasts before the dots ran the
MXU at its multi-pass fp32 rate); the default blocks are 512x512.  Measured
alone on one TPU v5e chip at the LM training cell's shape (B=8 S=2048 H=16
D=128 bf16, causal; ``tools/flash_attn_probe.py``, PR 43; ms a call, and
the share of the call's 2, 3 and 4 products' time at the chip's peak):
``flash_fwd`` 2.43 (29 %), ``flash_bwd_dq`` 1.95 (54 %), ``flash_bwd_dkv``
2.22 (63 %); 2.72, 2.80 and 3.63 on the full grid with ``[BH, S, 1]``
vectors.  The rows' layout gave -0.30 and -0.97 ms to the two backward
calls, the list of pairs -0.32, -0.55 and -0.45 to the three.  Those are
readings of the folded form ``[B * H, S, D]``; the in-place form's are in
``PERF.md`` (section 6, PR 44).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# What a step of a call's schedule is, beside its pair of blocks: the first
# or the last of its row (the blocks that share the call's accumulator).
_FIRST, _LAST = 1, 2

# The most pairs a call lists.  Its three vectors ride in scalar memory,
# which the arithmetic grid before them did not need, so there is a ceiling
# now and this names it: 4096 pairs (three vectors of 16 KB) Mosaic compiles
# for a v5e, non-causal S 32768 at the default blocks and S 8192 in blocks
# of 128; more was not tried.  Every shape the repo runs is under 100.  An S
# that ``_pick_block`` can only cut into tiny blocks meets it first.
MAX_PAIRS = 4096


@functools.lru_cache(maxsize=None)
def block_pairs(seq_len: int, block_q: int, block_k: int, causal: bool,
                by_key: bool = False):
    """The (query block, key block) pairs one call steps for one head, in
    the order it steps them, as three int32 vectors (query block, key
    block, flags) that ride in scalar memory: the call's second grid
    dimension is their LENGTH, its block index maps read the first two and
    its body the third.  A causal call lists the pairs that hold a key at
    or under some query's position and no other (10 of 16 at four blocks a
    row), so a pair wholly above the diagonal is neither stepped nor
    fetched; a non-causal call (a ring hop) lists them all.  A query
    block's pairs are consecutive with their key blocks ascending
    (``by_key``: a key block's, query blocks ascending: ``flash_bwd_dkv``)
    and the flags say where such a row starts and ends.  The vectors are
    shared between calls (the cache) and read-only; more than
    :data:`MAX_PAIRS` pairs is refused by name."""
    pairs = [(qi, ki) for qi in range(seq_len // block_q)
             for ki in range(seq_len // block_k)
             if not causal or ki * block_k < (qi + 1) * block_q]
    if len(pairs) > MAX_PAIRS:
        raise ValueError(
            f"flash attention over seq_len {seq_len} in blocks of {block_q} x "
            f"{block_k} steps {len(pairs)} pairs a head, more than the "
            f"{MAX_PAIRS} its schedule holds in scalar memory: take larger "
            f"blocks, or pad seq_len to a multiple of them")
    row = 1 if by_key else 0
    pairs.sort(key=lambda pair: (pair[row], pair[1 - row]))
    flags = []
    for n, (qi, ki) in enumerate(pairs):
        first = n == 0 or pairs[n - 1][row] != pairs[n][row]
        last = n + 1 == len(pairs) or pairs[n + 1][row] != pairs[n][row]
        flags.append(_FIRST * first | _LAST * last)
    columns = tuple(np.asarray(column, np.int32)
                    for column in (*zip(*pairs), flags))
    for column in columns:
        column.setflags(write=False)
    return columns


def _pick_block(seq_len: int, want: int) -> int:
    b = min(want, seq_len)
    while seq_len % b:
        b //= 2
    return max(b, 1)


def _pallas_call(name, kernel, *args, **kwargs):
    """``pl.pallas_call(kernel, name=name, **kwargs)(*args)``, compiled by
    Mosaic where the surrounding computation is lowered for a TPU and
    interpreted on every other platform.  ``name`` becomes the compiled
    step's instruction name (``flash_fwd.3``), which is what a profiler
    trace shows and the benchmark's per-kernel metrics match.
    ``lax.platform_dependent`` resolves the branch at lowering time, so a
    step lowered for TPU devices from a CPU process (AOT, or a chip JAX
    failed to make the default) never carries the interpreter in place of
    the kernel."""
    def branch(interpret):
        return lambda *a: pl.pallas_call(
            kernel, interpret=interpret, name=name, **kwargs)(*a)

    return jax.lax.platform_dependent(
        *args, tpu=branch(False), default=branch(True))


def _vmem(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _scores(a, b):
    """``a b^T`` in float32.  Dot inputs keep their storage dtype (bf16 in
    the flagship model) so the MXU runs at its native rate; accumulation
    is always f32 via preferred_element_type."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _product(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _causal_mask(s, q_start, k_start, q_axis):
    """``s`` with the keys past their query's position at ``_NEG_INF``;
    its queries lie along ``q_axis`` from position ``q_start``, its keys
    along the other from ``k_start``."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                               1 - q_axis)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _pair(qi_ref, ki_ref, flag_ref):
    """This grid step of a call over :func:`block_pairs`: its query block,
    its key block, whether it is the first and the last of its row."""
    i = pl.program_id(1)
    flags = flag_ref[i]
    return qi_ref[i], ki_ref[i], flags & _FIRST != 0, flags & _LAST != 0


def _grid_spec(pairs, batch_heads, in_specs, out_specs, scratch_shapes):
    """A call over ``pairs`` (:func:`block_pairs`) for every head: the
    three vectors are scalar-prefetch operands, the grid (heads, pairs)."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(pairs), grid=(batch_heads, len(pairs[0])),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)


def _block_specs(heads, bq, bk, D):
    """The one set of block specs of the three calls, ``(by_q, by_k,
    by_row)``: a query block ``(bq, D)`` and a key block ``(bk, D)`` of an
    operand ``[B', S, heads * D]`` and a query block's ``(1, bq)`` of a
    vector of per-row numbers (:func:`_rows`).  The grid's first
    coordinate ``bh`` counts the ``B' * heads`` heads and its second the
    steps of :func:`block_pairs`, whose vectors the index maps read in
    scalar memory: head ``bh`` is row ``bh // heads`` of the operand and
    the ``D`` lanes from ``(bh % heads) * D``, so a block is ``bq`` rows
    of ``D`` lanes at a stride of ``heads * D`` and nothing is turned
    round to make it contiguous.  ``heads`` 1 is the head-major array
    ``[B * H, S, D]`` that an operand is folded to where ``D`` is not a
    whole number of lane tiles (:func:`_run_flash`)."""
    heads = np.int32(heads)

    def of_q(bh, i, qi_ref, ki_ref, flag_ref):
        return jax.lax.div(bh, heads), qi_ref[i], jax.lax.rem(bh, heads)

    def of_k(bh, i, qi_ref, ki_ref, flag_ref):
        return jax.lax.div(bh, heads), ki_ref[i], jax.lax.rem(bh, heads)

    def of_q_row(bh, i, qi_ref, ki_ref, flag_ref):
        return bh, qi_ref[i], 0, 0

    return (pl.BlockSpec((None, bq, D), of_q),
            pl.BlockSpec((None, bk, D), of_k),
            pl.BlockSpec((None, None, 1, bq), of_q_row))


def _rows(x, block_q):
    """``[BH, S]`` per-row numbers (log-sum-exp, delta, an lse cotangent)
    as the kernels take and give them: ``[BH, S // bq, 1, bq]``, a query
    block's numbers along the LANES of a ``(1, bq)`` block whose two minor
    dimensions are the array's own, so it tiles whatever ``bq`` is.  A
    trailing dimension of one (``[BH, S, 1]``, blocks ``(bq, 1)``) the chip
    pads to 128 lanes: 134 MB a layer at the training cell's shape for
    1 MB of numbers, fetched on every step of ``flash_bwd_dkv``."""
    return x.reshape(x.shape[0], x.shape[1] // block_q, 1, block_q)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(qi_ref, ki_ref, flag_ref, q_ref, k_ref, v_ref, o_ref,
                lse_ref, m_scr, l_scr, acc_scr, *, scale, causal):
    qi, ki, first, last = _pair(qi_ref, ki_ref, flag_ref)
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    v = v_ref[...]                                      # [bk, d]
    s = _scores(q_ref[...], k_ref[...]) * scale         # [bq, bk]
    if causal:
        s = _causal_mask(s, qi * block_q, ki * block_k, 0)
    # Softmax math is f32.
    m_prev = m_scr[...]                                 # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                              # [bq, bk]
    corr = jnp.exp(m_prev - m_new)                      # [bq, 1]
    l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = corr * acc_scr[...] + _product(p.astype(v.dtype), v)
    m_scr[...] = m_new

    @pl.when(last)
    def _finalize():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        # the one turn of a column into a row, once a QUERY block
        lse_ref[...] = (m_scr[...] + jnp.log(l)).T          # [1, bq]


def _flash_fwd(q, k, v, heads, scale, causal, block_q, block_k,
               out_f32=False):
    """``(o, lse)`` of ``q``, ``k``, ``v`` ``[B', S, heads * D]``
    (:func:`_block_specs`): ``o`` as they lie, ``lse``
    ``[B' * heads, S // bq, 1, bq]`` (:func:`_rows`)."""
    S = q.shape[1]
    BH, D = q.shape[0] * heads, q.shape[2] // heads
    bq = _pick_block(S, block_q)
    bk = _pick_block(S, block_k)
    pairs = block_pairs(S, bq, bk, causal)
    by_q, by_k, by_row = _block_specs(heads, bq, bk, D)
    return _pallas_call(
        "flash_fwd",
        functools.partial(_fwd_kernel, scale=scale, causal=causal),
        *pairs, q, k, v,
        grid_spec=_grid_spec(
            pairs, BH, in_specs=[by_q, by_k, by_k], out_specs=[by_q, by_row],
            scratch_shapes=[_vmem((bq, 1)), _vmem((bq, 1)),
                            _vmem((bq, D))]),
        out_shape=[
            # out_f32: emit fp32 partials (ring composition carries them
            # through the logsumexp combine without per-hop rounding).
            jax.ShapeDtypeStruct(q.shape,
                                 jnp.float32 if out_f32 else q.dtype),
            jax.ShapeDtypeStruct((BH, S // bq, 1, bq), jnp.float32)])


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(qi_ref, ki_ref, flag_ref, q_ref, k_ref, v_ref, do_ref,
               lse_ref, delta_ref, dlse_ref, dq_ref, acc_scr, cols_scr,
               *, scale, causal):
    qi, ki, first, last = _pair(qi_ref, ki_ref, flag_ref)
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]

    @pl.when(first)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # The query block's three rows as columns, turned once for all of
        # its key blocks.
        for n, row_ref in enumerate((lse_ref, delta_ref, dlse_ref)):
            cols_scr[n] = row_ref[...].T                    # [bq, 1]

    k = k_ref[...]
    s = _scores(q_ref[...], k) * scale                  # [bq, bk]
    if causal:
        s = _causal_mask(s, qi * block_q, ki * block_k, 0)
    p = jnp.exp(s - cols_scr[0])                        # [bq, bk]
    dp = _scores(do_ref[...], v_ref[...])               # [bq, bk]
    # d lse_i / d s_ij = p_ij, so an lse cotangent adds p * dlse.
    ds = p * (dp - cols_scr[1] + cols_scr[2])
    acc_scr[...] += _product(ds.astype(k.dtype), k) * scale

    @pl.when(last)
    def _finalize():
        dq_ref[...] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(qi_ref, ki_ref, flag_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, dlse_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal):
    qi, ki, first, last = _pair(qi_ref, ki_ref, flag_ref)
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # The scores lie TRANSPOSED, keys down and queries along the lanes: the
    # query block's rows broadcast over them as they come, and dv and dk are
    # plain products (contracting p and ds over their first dimension would
    # turn a tile around twice a step).
    q = q_ref[...]
    do = do_ref[...]
    s = _scores(k_ref[...], q) * scale                  # [bk, bq]
    if causal:
        s = _causal_mask(s, qi * block_q, ki * block_k, 1)
    p = jnp.exp(s - lse_ref[...])                       # [bk, bq]
    dv_scr[...] += _product(p.astype(do.dtype), do)     # [bk, d]
    dp = _scores(v_ref[...], do)                        # [bk, bq]
    ds = p * (dp - delta_ref[...] + dlse_ref[...])
    dk_scr[...] += _product(ds.astype(q.dtype), q) * scale

    @pl.when(last)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _delta(do, o, heads):
    """``delta_i = rowsum(dO_i * O_i)`` over each head's D lanes, [BH, S]
    float32, of ``do``, ``o`` [B', S, heads * D] — cheap, fused by XLA
    outside pallas.  A head's lanes are cut out of the row where they lie
    (a tile-aligned slice where D is a multiple of 128), so each head is
    one multiply-and-reduce over what it reads: summing a reshape to
    ``[.., heads, D]`` makes XLA copy the float32 product to another
    tiling first, 134 MB twice a layer at the training cell's shape."""
    D = do.shape[2] // heads
    heads_major = [
        jnp.sum(do[..., h * D:(h + 1) * D].astype(jnp.float32)
                * o[..., h * D:(h + 1) * D].astype(jnp.float32), axis=-1)
        for h in range(heads)]                      # heads x [B', S]
    return jnp.stack(heads_major, axis=1).reshape(-1, do.shape[1])


def _flash_bwd(res, g, heads, scale, causal, block_q, block_k):
    q, k, v, o, lse = res           # lse [BH, S]
    do, dlse = g                    # dlse as the forward gave lse: _rows
    Bp, S, F = q.shape
    BH, D = Bp * heads, F // heads
    bq = _pick_block(S, block_q)
    bk = _pick_block(S, block_k)
    operands = (q, k, v, do, _rows(lse, bq), _rows(_delta(do, o, heads), bq),
                dlse.astype(jnp.float32))
    by_q, by_k, by_row = _block_specs(heads, bq, bk, D)
    in_specs = [by_q, by_k, by_k, by_q, by_row, by_row, by_row]

    pairs = block_pairs(S, bq, bk, causal)
    dq = _pallas_call(
        "flash_bwd_dq",
        functools.partial(_dq_kernel, scale=scale, causal=causal),
        *pairs, *operands,
        grid_spec=_grid_spec(
            pairs, BH, in_specs=in_specs, out_specs=by_q,
            scratch_shapes=[_vmem((bq, D)), _vmem((3, bq, 1))]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype))

    pairs = block_pairs(S, bq, bk, causal, by_key=True)
    dk, dv = _pallas_call(
        "flash_bwd_dkv",
        functools.partial(_dkv_kernel, scale=scale, causal=causal),
        *pairs, *operands,
        grid_spec=_grid_spec(
            pairs, BH, in_specs=in_specs, out_specs=[by_k, by_k],
            scratch_shapes=[_vmem((bk, D)), _vmem((bk, D))]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)])
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


# The two residuals of the model's call that are dear to rebuild, by the
# names a checkpoint policy saves them under (models/transformer.py:
# remat_layer): the kernel's output and its log-sum-exp.  q, k and v carry
# no name: a checkpointed layer recomputes them, three cheap matmuls.
SAVED_NAMES = ("flash_o", "flash_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, heads, scale, causal, block_q, block_k, out_f32, named):
    return _flash_fwd(q, k, v, heads, scale, causal, block_q, block_k,
                      out_f32)


def _flash_vjp_fwd(q, k, v, heads, scale, causal, block_q, block_k, out_f32,
                   named):
    o, lse = _flash_fwd(q, k, v, heads, scale, causal, block_q, block_k,
                        out_f32)
    # lse is kept as [BH, S], a reshape of the kernel's own output (what
    # tiles a stack of [.., 1, bq] blocks over a scan's layers would get
    # is XLA's to choose; [L, BH, S] pads nothing).  q, k, v and o are
    # kept as they lie: where the operands are [B, S, H * D] so are the
    # residuals, the stacked o a scan saves among them.
    rows = lse.reshape(lse.shape[0], -1)
    if named:
        # The NAMED o is also the primal output, so that under a policy
        # that saves the names nothing downstream of the kernel asks the
        # re-forward for it and the second flash_fwd call is dead code.
        o = checkpoint_name(o, SAVED_NAMES[0])
        rows = checkpoint_name(rows, SAVED_NAMES[1])
    return (o, lse), (q, k, v, o, rows)


def _flash_vjp_bwd(heads, scale, causal, block_q, block_k, out_f32, named,
                   res, g):
    return _flash_bwd(res, g, heads, scale, causal, block_q, block_k)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _run_flash(q, k, v, n_heads, causal, scale, block_q, block_k,
               out_f32=False, named=False):
    """The three calls over ``q``, ``k``, ``v`` ``[B, S, H, D]`` or
    ``[B, S, H * D]`` with ``n_heads`` = H: ``(o, lse)``, ``o`` shaped as
    ``q`` and ``lse`` ``[B, S, H]``.  Which way the calls read them is
    read off ``D``: a whole number of lane tiles (``D % 128 == 0``) and a
    head's block is cut out of ``[B, S, H * D]`` where it lies
    (:func:`_block_specs`); any other and the heads are folded in front of
    the SAME calls, ``[B * H, S, D]`` with one head a row, which is a
    transposition of every operand and result."""
    if q.ndim == 4:
        B, S, H, D = q.shape
        if n_heads not in (None, H):
            raise ValueError(f"n_heads={n_heads} with operands {q.shape}")
    elif n_heads is None or q.shape[2] % n_heads:
        raise ValueError(
            f"operands {q.shape} take n_heads, a divisor of their width")
    else:
        (B, S, F), H = q.shape, n_heads
        D = F // H
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    in_place = D % 128 == 0

    def fold(x):
        if in_place:
            return x.reshape(B, S, H * D)
        return jnp.moveaxis(x.reshape(B, S, H, D), 2, 1).reshape(B * H, S, D)

    o, lse = _flash(fold(q), fold(k), fold(v), H if in_place else 1,
                    float(scale), bool(causal), int(block_q), int(block_k),
                    bool(out_f32), bool(named))
    if not in_place:
        o = jnp.moveaxis(o.reshape(B, H, S, D), 1, 2)
    lse = jnp.moveaxis(lse.reshape(B, H, S), 1, 2)   # [B, S, H]
    return o.reshape(q.shape), lse


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    n_heads: Optional[int] = None):
    """Blockwise flash attention.  ``q/k/v``: [B, S, H, D], or
    [B, S, H * D] (a head's D values side by side, the heads in order: what
    a 2-D projection ``"bsd,df->bsf"`` writes) with ``n_heads`` = H.

    Returns the context, shaped as ``q``.  Differentiable (custom VJP
    running the flash backward kernels).  Its output and log-sum-exp carry
    the names :data:`SAVED_NAMES` among the backward kernels' residuals: a
    ``jax.checkpoint`` whose policy saves those names runs the forward
    kernel once, not again in its re-forward.

    Where ``D`` is a multiple of 128 the kernels read and write
    ``[B, S, H * D]`` in place, so operands given in that form are never
    copied, nor the results, nor the gradients (on a TPU ``[B, S, H, D]``
    is another tiling of memory and the reshape between the two a copy:
    the 4-D form pays it on the way in and out).  With any other ``D``
    every operand and result is transposed to ``[B * H, S, D]`` and back.
    """
    o, _ = _run_flash(q, k, v, n_heads, causal, scale, block_q, block_k,
                      named=True)
    return o


def flash_attention_lse(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        block_q: int = 512, block_k: int = 512,
                        n_heads: Optional[int] = None):
    """Like :func:`flash_attention` but also returns the per-query
    logsumexp ``[B, S, H]`` (fp32).  The pair ``(o, lse)`` is what
    blockwise composition needs: partial attentions over disjoint key
    sets combine exactly via logsumexp weights, which is how
    ``parallel.ring_attention`` chains this kernel across ``sp`` hops.
    Both outputs carry gradients (the lse cotangent adds the ``p·dlse``
    term in the backward kernels).  The partial output is emitted in
    fp32 (no per-hop rounding when partials are combined).  Its
    residuals carry no name: a checkpointed ring keeps none of its
    ``sp`` hops' partials."""
    return _run_flash(q, k, v, n_heads, causal, scale, block_q, block_k,
                      out_f32=True)
