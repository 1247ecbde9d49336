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

Tuning (measured on one TPU v5e chip, B=8 S=1024 H=16 D=64 bf16):
dot inputs keep their storage dtype (f32 upcasts before the dots ran
the MXU at its multi-pass fp32 rate) and the default blocks are
512x512 — together fwd+bwd went 15.0 ms → 7.8 ms vs 45.4 ms for the
XLA dense-softmax path on the same shapes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

_NEG_INF = -1e30



def _block_needed(qi, ki, block_q, block_k, causal):
    """False only for key blocks strictly above the causal diagonal."""
    if not causal:
        return True
    return ki * block_k < (qi + 1) * block_q


def _causal_mask(s, qi, ki, block_q, block_k):
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(rows >= cols, s, _NEG_INF)


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


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: key blocks strictly above the diagonal contribute nothing.
    needed = _block_needed(qi, ki, block_q, block_k, causal)

    @pl.when(needed)
    def _tile():
        # Dot inputs keep their storage dtype (bf16 in the flagship
        # model) so the MXU runs at its native rate; accumulation is
        # always f32 via preferred_element_type.  Softmax math is f32.
        q = q_ref[0]                               # [bq, d]
        k = k_ref[0]                               # [bk, d]
        v = v_ref[0]                               # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        m_prev = m_scr[:]                          # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                     # [bq, bk]
        corr = jnp.exp(m_prev - m_new)             # [bq, 1]
        l_scr[:] = corr * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = corr * acc_scr[:] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)         # [bq, 1]


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, out_f32=False):
    BH, S, D = q.shape
    bq = _pick_block(S, block_q)
    bk = _pick_block(S, block_k)
    grid = (BH, S // bq, S // bk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk)
    o, lse = _pallas_call(
        "flash_fwd", kernel, q, k, v,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            # lse rides as [BH, S, 1]: a 2-D (1, bq) block over [BH, S]
            # is not Mosaic-tileable (second-minor must be 8-divisible
            # or the full dim); a trailing singleton lane dim is.
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            # out_f32: emit fp32 partials (ring composition carries them
            # through the logsumexp combine without per-hop rounding).
            jax.ShapeDtypeStruct((BH, S, D),
                                 jnp.float32 if out_f32 else q.dtype),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((bq, 1)),
            _vmem((bq, 1)),
            _vmem((bq, D)),
        ],
    )
    return o, lse


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
               dq_ref, acc_scr, *, scale, causal, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    needed = _block_needed(qi, ki, block_q, block_k, causal)

    @pl.when(needed)
    def _tile():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                           # [bq, 1]
        delta = delta_ref[0]                       # [bq, 1]
        dlse = dlse_ref[0]                         # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        p = jnp.exp(s - lse)                       # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bq, bk]
        # d lse_i / d s_ij = p_ij, so an lse cotangent adds p * dlse.
        ds = p * (dp - delta + dlse)
        acc_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dlse_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, block_q, block_k):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    needed = _block_needed(qi, ki, block_q, block_k, causal)

    @pl.when(needed)
    def _tile():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                           # [bq, 1]
        delta = delta_ref[0]                       # [bq, 1]
        dlse = dlse_ref[0]                         # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        p = jnp.exp(s - lse)                       # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta + dlse)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(res, g, scale, causal, block_q, block_k):
    q, k, v, o, lse = res
    do, dlse = g
    BH, S, D = q.shape
    bq = _pick_block(S, block_q)
    bk = _pick_block(S, block_k)
    # delta_i = rowsum(dO_i * O_i) — cheap, fused by XLA outside pallas;
    # keepdims so the [BH, S, 1] layout matches lse's Mosaic-tileable
    # trailing-singleton blocks.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)         # [BH, S, 1]
    dlse = dlse.astype(jnp.float32)

    dq = _pallas_call(
        "flash_bwd_dq",
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        q, k, v, do, lse, delta, dlse,
        grid=(BH, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[_vmem((bq, D))],
    )

    dk, dv = _pallas_call(
        "flash_bwd_dkv",
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        q, k, v, do, lse, delta, dlse,
        grid=(BH, S // bk, S // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        scratch_shapes=[_vmem((bk, D)), _vmem((bk, D))],
    )
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


# The two residuals of the model's call that are dear to rebuild, by the
# names a checkpoint policy saves them under (models/transformer.py:
# remat_layer): the kernel's output and its log-sum-exp.  q, k and v carry
# no name: a checkpointed layer recomputes them, three cheap matmuls.
SAVED_NAMES = ("flash_o", "flash_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, out_f32, named):
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k, out_f32)


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, out_f32,
                   named):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, out_f32)
    # lse is kept as [BH, S]: stacked over a scan's layers in the kernel's
    # [BH, S, 1] the chip would pad each row of one to a 128-lane tile.
    rows = lse[..., 0]
    if named:
        # The NAMED o is also the primal output, so that under a policy
        # that saves the names nothing downstream of the kernel asks the
        # re-forward for it and the second flash_fwd call is dead code.
        o = checkpoint_name(o, SAVED_NAMES[0])
        rows = checkpoint_name(rows, SAVED_NAMES[1])
    return (o, lse), (q, k, v, o, rows)


def _flash_vjp_bwd(scale, causal, block_q, block_k, out_f32, named, res, g):
    q, k, v, o, rows = res
    return _flash_bwd((q, k, v, o, rows[..., None]), g, scale, causal,
                      block_q, block_k)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _run_flash(q, k, v, causal, scale, block_q, block_k, out_f32=False,
               named=False):
    B, S, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    def fold(x):
        return jnp.moveaxis(x, 2, 1).reshape(B * H, S, D)

    o, lse = _flash(fold(q), fold(k), fold(v), float(scale),
                    bool(causal), int(block_q), int(block_k),
                    bool(out_f32), bool(named))
    o = jnp.moveaxis(o.reshape(B, H, S, D), 1, 2)
    lse = jnp.moveaxis(lse.reshape(B, H, S), 1, 2)   # [B, S, H]
    return o, lse


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512):
    """Blockwise flash attention.  ``q/k/v``: [B, S, H, D].

    Returns [B, S, H, D] context.  Differentiable (custom VJP running the
    flash backward kernels).  Its output and log-sum-exp carry the names
    :data:`SAVED_NAMES` among the backward kernels' residuals: a
    ``jax.checkpoint`` whose policy saves those names runs the forward
    kernel once, not again in its re-forward.
    """
    o, _ = _run_flash(q, k, v, causal, scale, block_q, block_k, named=True)
    return o


def flash_attention_lse(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        block_q: int = 512, block_k: int = 512):
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
    return _run_flash(q, k, v, causal, scale, block_q, block_k,
                      out_f32=True)
