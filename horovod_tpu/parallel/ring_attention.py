"""Sequence parallelism: ring attention and Ulysses head-exchange.

The reference has no sequence/context parallelism (SURVEY.md §5 long-context
row: absent; scaling axis is the batch).  A complete TPU framework needs
long-context support as a first-class citizen, and the ICI torus is built
for it:

* **Ring attention** (`ring_attention`): K/V blocks rotate around the ``sp``
  ring via ``lax.ppermute`` (one ICI-neighbor hop per step); each hop's
  local attention runs the Pallas flash kernel
  (``ops.pallas_attention.flash_attention_lse`` — MXU-tiled, O(block)
  score memory) and hops compose exactly through logsumexp weights, fp32.
  Communication is overlapped by XLA: the next block transfers while the
  current one is being used — the TPU-native equivalent of what the
  reference's background thread + streams did for allreduce overlap.
* **Ulysses** (`ulysses_attention`): one ``all_to_all`` turns
  sequence-sharding into head-sharding, full attention runs locally per
  head group, a second ``all_to_all`` restores sequence-sharding.  Cheaper
  for moderate sequence lengths; requires ``heads % sp_size == 0``.

Both are written for use inside ``shard_map`` bodies (axis names, like
``horovod_tpu.ops.collective``); ``make_sharded_attention`` wraps one in
``shard_map`` over a mesh for direct use.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel.shard import shard_map


def _combine_partials(o1, lse1, o2, lse2):
    """Exactly merge two partial attentions over disjoint key sets.

    ``o_i`` are normalized partial outputs [B, S, H, D]; ``lse_i`` their
    per-query logsumexps [B, S, H] (``-inf`` marks an empty/skipped key
    set).  Standard logsumexp composition, fp32."""
    m = jnp.maximum(lse1, lse2)
    # Guard the fully-masked query rows (both -inf): weights become 0/0
    # otherwise; such rows keep -inf lse and a zero output.
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.exp(lse1 - m_safe)
    w2 = jnp.exp(lse2 - m_safe)
    tot = w1 + w2
    norm = jnp.where(tot > 0.0, tot, 1.0)
    o = (o1.astype(jnp.float32) * (w1 / norm)[..., None]
         + o2.astype(jnp.float32) * (w2 / norm)[..., None])
    lse = m + jnp.log(norm)
    return o, lse


def ring_attention(q, k, v, axis: str = "sp", causal: bool = True):
    """Blockwise ring attention over the ``axis`` ring (inside shard_map).

    q/k/v: [B, S_local, H, D] — the local sequence shard.  Returns the
    attention output [B, S_local, H, D] in q's dtype.

    Each hop's local block runs the Pallas flash kernel
    (``ops.pallas_attention.flash_attention_lse`` — MXU-tiled, O(block)
    score memory) and hops compose exactly via logsumexp weights
    (:func:`_combine_partials`); K/V rotate one ICI neighbor per step
    via ``lax.ppermute``, which XLA overlaps with the current hop's
    compute.  The result is exact — identical to full attention on the
    gathered sequence up to fp accumulation order.  This is the
    ring-flash composition: the kernel's (o, lse) pair is the per-hop
    partial, the ring is the reduction tree.
    """
    from horovod_tpu.ops.pallas_attention import flash_attention_lse

    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    perm = [(i, (i + 1) % n) for i in range(n)]  # send to next neighbor

    # Step 0 is the self-block (no hop): causal triangle when causal.
    # Partials are fp32 end-to-end (the kernel emits fp32, the combine
    # runs fp32), so no per-hop rounding enters the composition.
    o, lse = flash_attention_lse(q, k, v, causal=causal, scale=scale)

    def body(step, carry):
        k_cur, v_cur, o, lse = carry
        k_cur = lax.ppermute(k_cur, axis, perm)
        v_cur = lax.ppermute(v_cur, axis, perm)
        # After `step` hops we hold the block of rank (my - step) mod n.
        owner = (my - step) % n
        o_hop, lse_hop = flash_attention_lse(q, k_cur, v_cur,
                                             causal=False, scale=scale)
        if causal:
            # owner > my holds future tokens: the hop contributes
            # nothing (lse -inf zeroes its combination weight).
            lse_hop = jnp.where(owner < my, lse_hop, -jnp.inf)
        o, lse = _combine_partials(o, lse, o_hop, lse_hop)
        return k_cur, v_cur, o, lse

    _, _, o, lse = lax.fori_loop(1, n, body, (k, v, o, lse))
    return o.astype(q.dtype)


def ulysses_attention(q, k, v, axis: str = "sp", causal: bool = True):
    """Ulysses sequence parallelism: all-to-all head exchange (inside
    shard_map).  q/k/v: [B, S_local, H, D] with H divisible by the axis
    size; returns [B, S_local, H, D]."""
    n = lax.axis_size(axis)
    B, S, H, D = q.shape
    if H % n != 0:
        raise ValueError(f"heads {H} not divisible by axis size {n}")

    def seq_to_heads(x):
        # [B, S_local, H, D] -> [B, S_global, H/n, D]
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    Sg = qg.shape[1]
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", qg, kg).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sg, Sg), jnp.bool_))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(vg.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vg)
    return heads_to_seq(out)


def full_attention(q, k, v, causal: bool = True):
    """Single-device reference attention (the oracle for tests)."""
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def make_sharded_attention(mesh, impl: str = "ring", axis: str = "sp",
                           causal: bool = True,
                           head_axis: Optional[str] = None):
    """Wrap ring/ulysses attention in shard_map over ``mesh``.

    Returns ``fn(q, k, v) -> out`` taking/returning global [B, S, H, D]
    arrays sequence-sharded over ``axis``, batch over ``dp`` when the mesh
    has it, and heads over ``head_axis`` when given (tensor parallelism
    composed with sequence parallelism).
    """
    fns = {"ring": ring_attention, "ulysses": ulysses_attention}
    if impl not in fns:
        raise ValueError(f"impl must be one of {sorted(fns)}")
    if head_axis is not None and head_axis not in mesh.shape:
        head_axis = None
    inner = functools.partial(fns[impl], axis=axis, causal=causal)
    batch_ax = "dp" if "dp" in mesh.shape else None
    spec = P(batch_ax, axis, head_axis, None)

    def fn(q, k, v):
        return shard_map(inner, mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)

    return fn
