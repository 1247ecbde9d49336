"""Decoder-only Transformer LM — the flagship multi-axis-parallel model.

The reference framework is data-parallel only (SURVEY.md §2.8); a complete
TPU framework must also scale model size (tp), sequence length (sp), and
experts (ep).  This model is built so that every one of those axes is a
*sharding decision*, not a code path:

* Layers are stacked along a leading axis and iterated with ``lax.scan`` —
  one compiled layer body regardless of depth (and the natural substrate
  for pipeline parallelism: split the stacked axis over the ``pp`` mesh
  axis, see ``horovod_tpu.parallel.pipeline``).
* ``param_specs(config)`` gives a PartitionSpec pytree: attention heads and
  FFN hidden dim sharded over ``tp`` (Megatron layout: column-parallel in,
  row-parallel out — XLA inserts exactly the two psums per block), experts
  over ``ep``.
* Activations carry ``P('dp', 'sp', None)`` constraints: batch over data
  ranks, sequence over the sp axis.  Attention under GSPMD all-gathers K/V
  over sp; the ring-attention path (``horovod_tpu.parallel.ring_attention``)
  replaces that with neighbor ``ppermute`` exchanges when activated.
* bf16 compute, fp32 params/norms, RoPE positions, pre-RMSNorm blocks,
  causal masking via static ``lax`` ops only — no dynamic shapes anywhere.
* One attention (``_attention``: projections, rotation, output; with a
  cache or without), one rotation (``_rope``, with the norm, the dense
  feed-forward and the head in ``models/layers.py``, which every served
  model shares), one softmax core and one layer body (``_layer``).
  Training (``apply``), the prefill and the decode step are that layer
  under three loops, so a change to what attention reads or how heads
  are grouped is written once.  The one
  branch that holds its arrays otherwise is the training kernel's
  (``_flash_attention``: ``attn_impl="flash"``, no cache): q, k, v and
  the context stay [B, S, H * HD] from the projections (one 2-D product
  each) through the rotation (``_rope_flat``: ``_rope``'s numbers along
  the lanes) to ``wo``, because the kernel reads that array in place and
  on a TPU [B, S, H, HD] is a copy away from it.  The parameters keep
  their shapes; the other branches' programs do not change.
* Cached decode is a seam, not a second model: ``init_state`` with
  ``SLOT_AXES``, ``prefill_request``, ``decode_step``, ``STATE_SPEC`` and
  ``serving_params``, the names and signatures every served module gives
  its own.  ``serving/decode.py`` builds its engine from them;
  ``generate()`` is ``decode_step`` with one position for all rows, and
  the serving tests' oracle.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models.layers import (ATTN_COUNTERS, _dense_ffn, _logits,
                                       _rmsnorm, _rope, _rope_angles,
                                       count_attention_reads,
                                       vocab_projection)

Params = Dict[str, Any]


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 2048
    n_experts: int = 0          # 0 → dense FFN; >0 → Switch-style MoE
    capacity_factor: float = 1.25
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    compute_dtype: Any = jnp.bfloat16
    # "dense": GSPMD attention (XLA all-gathers K/V over sp);
    # "ring": blockwise ring attention via ppermute over the sp ring;
    # "ulysses": all-to-all head exchange (see parallel/ring_attention.py);
    # "flash": Pallas blockwise flash-attention kernel
    #   (ops/pallas_attention.py) — O(S) memory, MXU-tiled; runs per batch
    #   shard on any mesh with tp = sp = 1.  With tp or sp > 1 dense
    #   attention runs instead (XLA cannot split a pallas_call over heads
    #   or sequence) and the swap is logged: see warn_flash_runs_dense.
    attn_impl: str = "dense"
    # Rematerialize each layer in the backward pass (jax.checkpoint):
    # a layer keeps its input and, where its attention is the flash
    # kernel, the kernel's output and log-sum-exp (remat_layer); the rest
    # of the layer's forward (norms, projections, rotary, feed-forward) is
    # computed again.  Turn off when the model fits with every activation
    # kept.
    remat: bool = True

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def init(rng, cfg: TransformerConfig) -> Params:
    k = iter(jax.random.split(rng, 16))
    L, D, H, HD, F = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                      cfg.head_dim, cfg.d_ff)
    std = 0.02
    out_std = std / math.sqrt(2 * L)
    layer: Params = {
        "ln1": jnp.ones((L, D), jnp.float32),
        "ln2": jnp.ones((L, D), jnp.float32),
        "wq": _normal(next(k), (L, D, H, HD), std),
        "wk": _normal(next(k), (L, D, H, HD), std),
        "wv": _normal(next(k), (L, D, H, HD), std),
        "wo": _normal(next(k), (L, H, HD, D), out_std),
    }
    if cfg.n_experts:
        E = cfg.n_experts
        layer["router"] = _normal(next(k), (L, D, E), std)
        layer["w_in"] = _normal(next(k), (L, E, D, F), std)
        layer["w_gate"] = _normal(next(k), (L, E, D, F), std)
        layer["w_out"] = _normal(next(k), (L, E, F, D), out_std)
    else:
        layer["w_in"] = _normal(next(k), (L, D, F), std)
        layer["w_gate"] = _normal(next(k), (L, D, F), std)
        layer["w_out"] = _normal(next(k), (L, F, D), out_std)
    return {
        "embed": _normal(next(k), (cfg.vocab_size, D), std),
        "layers": layer,
        "ln_f": jnp.ones((D,), jnp.float32),
    }


def param_specs(cfg: TransformerConfig) -> Params:
    """PartitionSpec pytree (Megatron tp layout + ep experts).

    The leading stacked-layer axis is left unsharded here; the pipeline
    wrapper reshards it over ``pp`` when pipelining is on.
    """
    layer: Params = {
        "ln1": P(None, None),
        "ln2": P(None, None),
        "wq": P(None, None, "tp", None),
        "wk": P(None, None, "tp", None),
        "wv": P(None, None, "tp", None),
        "wo": P(None, "tp", None, None),
    }
    if cfg.n_experts:
        layer["router"] = P(None, None, None)
        layer["w_in"] = P(None, "ep", None, "tp")
        layer["w_gate"] = P(None, "ep", None, "tp")
        layer["w_out"] = P(None, "ep", "tp", None)
    else:
        layer["w_in"] = P(None, None, "tp")
        layer["w_gate"] = P(None, None, "tp")
        layer["w_out"] = P(None, "tp", None)
    return {
        "embed": P("tp", None),
        "layers": layer,
        "ln_f": P(None),
    }


ACT_SPEC = P("dp", "sp", None)  # [batch, seq, d_model]


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------


def _constrain(x, spec: Optional[P], mesh):
    """Apply a sharding constraint, keeping only axes present in ``mesh``.

    ``mesh`` is threaded explicitly (static Python value) instead of read
    from ambient context so the model works under plain ``jit`` with
    ``in_shardings`` on any JAX version.
    """
    if spec is None or mesh is None:
        return x
    from horovod_tpu.parallel.mesh import filter_spec

    fixed = filter_spec(spec, mesh)
    if all(ax is None for ax in fixed):
        return x
    return lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, fixed))


def _rope_flat(x, n_heads: int, theta):
    """:func:`_rope` of ``x`` [B, S, H * HD], a head's HD values side by
    side, at positions 0 to S - 1, without leaving that shape: the same
    products and sums (bit for bit in bfloat16; in float32 to the last
    bit, where a compiler contracts the multiply-adds its own way).
    Where HD is a whole number of the chip's
    128-lane tiles it is a kernel's one pass (ops/pallas_rope.py: each
    lane's partner is HD/2 lanes away, which XLA only reaches through
    memory or through another tiling of the array); any other HD goes
    through [B, S, H, HD] and back."""
    B, S, F = x.shape
    head_dim = F // n_heads
    if head_dim % 128:
        return _rope(x.reshape(B, S, n_heads, head_dim), theta).reshape(
            B, S, F)
    from horovod_tpu.ops.pallas_rope import rotate

    ang = _rope_angles(theta, head_dim // 2, S, None)
    return rotate(x, jnp.cos(ang), jnp.sin(ang), n_heads)


@functools.lru_cache(maxsize=None)
def warn_flash_runs_dense(axis: str, size: int, where: str) -> None:
    """Say, once per process and cause, that ``attn_impl="flash"`` was
    replaced by dense attention while ``where`` was being built, and
    which mesh axis caused it."""
    from horovod_tpu.utils.logging import get_logger

    get_logger().warning(
        "attn_impl='flash' runs as DENSE attention in %s: mesh axis %r "
        "has size %d and the Pallas kernel is not partitioned over it",
        where, axis, size)


def _softmax_attention(q, k, v, valid, cfg: TransformerConfig):
    """Dense attention: q [B, S, H, HD] against k, v [B, T, H, HD] where
    ``valid`` (broadcastable to [B, H, S, T]) says so.  Scores and softmax
    in float32, probabilities and context in the compute type."""
    scale = 1.0 / math.sqrt(cfg.head_dim)
    logits = jnp.einsum("bshk,bthk->bhst", q, k).astype(jnp.float32) * scale
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(cfg.compute_dtype)
    return jnp.einsum("bhst,bthk->bshk", probs, v)


def _flash_attention(x, lp, cfg: TransformerConfig, mesh):
    """:func:`_attention`'s branch for the kernel of
    ops/pallas_attention.py (no cache, ``tp`` = ``sp`` = 1), the same
    numbers with q, k, v and the context held as [B, S, H * HD] from the
    projections to the output projection: each projection is ONE 2-D
    product against its weight reshaped at the use ([D, H, HD] is
    [D, H * HD] laid out otherwise; ``wqkv`` is cut where its three
    parts lie), the rotation is :func:`_rope_flat`, the kernel cuts a
    head's block out of that array where it lies, and ``wo`` contracts
    it whole.  On a TPU [B, S, H, HD] tiles its last TWO dimensions, so
    the 4-D form cost a transposition of every operand, result and
    gradient on its way into and out of the kernel's three calls."""
    from horovod_tpu.ops.pallas_attention import flash_attention

    B, S, D = x.shape
    H, HD = cfg.n_heads, cfg.head_dim
    dtype = cfg.compute_dtype
    if "wqkv" in lp:
        qkv = jnp.einsum("bsd,df->bsf", x, lp["wqkv"].astype(dtype))
        q, kk, v = jnp.split(qkv, 3, axis=-1)
    else:
        q, kk, v = (
            jnp.einsum("bsd,df->bsf", x,
                       lp[name].astype(dtype).reshape(D, H * HD))
            for name in ("wq", "wk", "wv"))

    def core(q, kk, v):
        q = _rope_flat(q, H, cfg.rope_theta)
        kk = _rope_flat(kk, H, cfg.rope_theta)
        return flash_attention(q, kk, v, causal=True, n_heads=H), kk

    if mesh is not None and mesh.size > 1:
        # A pallas_call has no GSPMD partitioning rule, and Mosaic
        # refuses a kernel that any automatic mesh axis could split,
        # so the kernels run inside a shard_map that is manual over
        # EVERY axis: the batch split the way the activations are
        # (ACT_SPEC's batch axes), replicated over the rest
        # (ep, dcn, ...; tp and sp are 1 here).
        from horovod_tpu.parallel.mesh import filter_spec
        from horovod_tpu.parallel.shard import shard_map

        batch = filter_spec(P(ACT_SPEC[0]), mesh)
        core = shard_map(core, mesh, in_specs=(batch, batch, batch),
                         out_specs=(batch, batch))
    ctx, kk = core(q, kk, v)
    out = jnp.einsum("bsf,fd->bsd", ctx,
                     lp["wo"].astype(dtype).reshape(H * HD, D))
    return out, (kk.reshape(B, S, H, HD), v.reshape(B, S, H, HD))


def _attention(x, lp, cfg: TransformerConfig, mesh=None, cache=None):
    """Causal self-attention, the model's only one.  x: [B, S, D].

    ``lp`` holds the input projections as ``init`` makes them (``wq``,
    ``wk``, ``wv``, each [D, H, HD]: three products) or as a serving
    engine holds them (``wqkv`` [D, 3 H HD], :func:`serving_params`: one
    product, split into q, k, v afterwards); which is read off its keys.
    ``cache`` None: the S positions (0 to S - 1) attend among themselves
    by ``cfg.attn_impl``; returns (out, (k, v)) with the rotated k, v
    [B, S, H, HD] for whoever keeps them.
    ``cache`` = (ks, vs, layer, pos, read), stacked caches
    [L, B, Smax, H, HD] and the positions [B] of THIS token in each row
    (S = 1): writes the B new rows at [layer, b, pos[b]] and reads layer
    ``layer``'s lane where it lies: by ``read`` (:func:`lane_reader`: the
    kernel of ops/pallas_decode_attention.py, each row's lane as far as
    its ``pos`` and no further) or, ``read`` None, the whole lane under a
    position mask.  Nothing of the cache's or a lane's size is produced
    besides the caches themselves, which the caller carries (and
    donates), so XLA updates them in place.  Returns (out, (ks, vs)).
    Rows never mix, so a row's output depends on its own lane alone."""
    B, S, D = x.shape
    dtype = cfg.compute_dtype
    own = None if cache is None else cache[3][:, None]   # [B, 1] positions
    if cache is None:
        if cfg.attn_impl not in ("dense", "ring", "ulysses", "flash"):
            raise ValueError(
                f"attn_impl must be dense/ring/ulysses/flash, "
                f"got {cfg.attn_impl!r}")
        if cfg.attn_impl == "flash":
            split = [ax for ax in ("tp", "sp")
                     if mesh is not None and mesh.shape.get(ax, 1) > 1]
            for ax in split:
                warn_flash_runs_dense(ax, mesh.shape[ax], "the model")
            if not split:
                return _flash_attention(x, lp, cfg, mesh)
    if "wqkv" in lp:
        qkv = jnp.einsum("bsd,df->bsf", x, lp["wqkv"].astype(dtype))
        q, kk, v = (a.reshape(B, S, cfg.n_heads, cfg.head_dim)
                    for a in jnp.split(qkv, 3, axis=-1))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"].astype(dtype))
        kk = jnp.einsum("bsd,dhk->bshk", x, lp["wk"].astype(dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"].astype(dtype))
    q = _rope(q, cfg.rope_theta, own)
    kk = _rope(kk, cfg.rope_theta, own)
    if cache is None:
        kept = (kk, v)
        use_sp = (cfg.attn_impl in ("ring", "ulysses") and mesh is not None
                  and mesh.shape.get("sp", 1) > 1)
        if use_sp:
            # Sequence-parallel attention: K/V never gather; blocks rotate
            # the sp ring (ring) or heads exchange via all-to-all (ulysses).
            from horovod_tpu.parallel import ring_attention as ra

            ctx = ra.make_sharded_attention(
                mesh, impl=cfg.attn_impl, axis="sp", causal=True,
                head_axis="tp")(q, kk, v)
        else:
            tri = jnp.tril(jnp.ones((S, S), jnp.bool_))
            ctx = _softmax_attention(q, kk, v, tri[None, None], cfg)
    else:
        ks, vs, layer, pos, read = cache
        rows = jnp.arange(B)
        ks = ks.at[layer, rows, pos].set(kk[:, 0])
        vs = vs.at[layer, rows, pos].set(v[:, 0])
        if read is not None:
            ctx = read(q[:, 0], ks, vs, layer)[:, None]
        else:
            k_cache = lax.dynamic_index_in_dim(ks, layer, 0, keepdims=False)
            v_cache = lax.dynamic_index_in_dim(vs, layer, 0, keepdims=False)
            valid = jnp.arange(k_cache.shape[1])[None, :] <= pos[:, None]
            ctx = _softmax_attention(q, k_cache, v_cache,
                                     valid[:, None, None, :], cfg)
        kept = (ks, vs)
    return jnp.einsum("bshk,hkd->bsd", ctx, lp["wo"].astype(dtype)), kept


def lane_reader(cfg: TransformerConfig, mesh, pos, cache_len: int):
    """How a decode step under ``mesh`` reads its slots' lanes, slots at
    ``pos`` [B] in lanes of ``cache_len``: the kernel over (q [B, H, HD],
    ks, vs, layer), each lane as far as the slot has written it, its list
    of blocks made here once for all the layers; or None, the masked read
    of the whole lane, where ``tp`` or ``sp`` split the cache's heads or
    the sequence (a pallas_call is not partitioned over them), which is
    said once.  Under a mesh of several devices the kernel runs inside a
    shard_map that is manual over every axis, all operands replicated
    (see the training kernel's, above)."""
    for ax in ("tp", "sp"):
        if mesh is not None and mesh.shape.get(ax, 1) > 1:
            warn_flash_runs_dense(ax, mesh.shape[ax], "the decode step")
            return None
    from horovod_tpu.ops import pallas_decode_attention as pda

    work = pda.work_list(pos, cache_len, pda.block_for(cache_len, shared=False))

    def read(q, ks, vs, layer, pos, work):
        return pda.decode_attention(
            (q,), (ks,), vs, layer, pos, work=work,
            scale=1.0 / math.sqrt(cfg.head_dim))

    if mesh is not None and mesh.size > 1:
        from horovod_tpu.parallel.shard import shard_map

        read = shard_map(read, mesh, in_specs=(P(),) * 6, out_specs=P())
    return lambda q, ks, vs, layer: read(q, ks, vs, layer, pos, work)


def _moe_ffn(x, lp, cfg: TransformerConfig):
    """Switch-style top-1 MoE with static capacity.

    Dispatch/combine are dense einsums against one-hot masks — fully static
    shapes, so XLA shards the expert dimension over ``ep`` and turns the
    einsums into all-to-alls.  Re-derivation of the standard Switch layer
    (public Mesh-TF/Flaxformer pattern), not a port.
    """
    B, S, D = x.shape
    E = cfg.n_experts
    dtype = cfg.compute_dtype
    C = max(1, int(cfg.capacity_factor * S * B / E))

    xf = x.reshape(B * S, D)
    router_logits = (xf.astype(jnp.float32)
                     @ lp["router"].astype(jnp.float32))      # [T, E]
    gates = jax.nn.softmax(router_logits, axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)                    # [T]
    gate = jnp.max(gates, axis=-1)                             # [T]
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [T, E]
    # Position of each token within its expert's capacity buffer.
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0            # [T, E]
    keep = (pos < C) & (onehot > 0)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C,
                            dtype=jnp.float32) * keep[..., None]
    dispatch = pos_oh                                           # [T, E, C]
    combine = dispatch * gate[:, None, None]                    # [T, E, C]

    xe = jnp.einsum("tec,td->ecd", dispatch.astype(dtype), xf)  # [E, C, D]
    h = jnp.einsum("ecd,edf->ecf", xe, lp["w_in"].astype(dtype))
    g = jnp.einsum("ecd,edf->ecf", xe, lp["w_gate"].astype(dtype))
    h = h * jax.nn.silu(g)
    ye = jnp.einsum("ecf,efd->ecd", h, lp["w_out"].astype(dtype))
    y = jnp.einsum("tec,ecd->td", combine.astype(dtype), ye)
    # Auxiliary load-balancing loss (Switch eq. 4).
    density = jnp.mean(onehot, axis=0)
    density_proxy = jnp.mean(gates, axis=0)
    aux = E * jnp.sum(density * density_proxy)
    return y.reshape(B, S, D), aux


def _layer(x, lp, cfg: TransformerConfig, mesh=None, cache=None):
    """The layer, the model's only one: norm, attention, residual, norm,
    feed-forward, residual.  ``cache`` as :func:`_attention` takes it.
    Returns (x, the experts' auxiliary loss, the keys and values
    :func:`_attention` returns)."""
    with jax.named_scope("attention"):
        y, kept = _attention(_rmsnorm(x, lp["ln1"]), lp, cfg, mesh, cache)
        x = _constrain(x + y, ACT_SPEC, mesh)
    with jax.named_scope("ffn"):
        h = _rmsnorm(x, lp["ln2"])
        if cfg.n_experts:
            y, aux = _moe_ffn(h, lp, cfg)
        else:
            y, aux = _dense_ffn(h, lp, cfg.compute_dtype), 0.0
        x = _constrain(x + y, ACT_SPEC, mesh)
    return x, aux, kept


def remat_layer():
    """:func:`_layer` under ``jax.checkpoint``: of a layer's forward pass
    the backward pass is handed the layer's input and whatever carries one
    of the attention kernel's names (ops/pallas_attention.py:SAVED_NAMES,
    its output and log-sum-exp), and computes the rest again.  With those
    two kept the re-forward has no use for the kernel's forward call, so
    it runs once a layer a step.  Where the attention is not the kernel
    (dense, ring, ulysses, or flash swapped for dense under tp or sp)
    nothing carries a name and the layer's input alone is kept."""
    from horovod_tpu.ops.pallas_attention import SAVED_NAMES

    return jax.checkpoint(
        _layer, static_argnums=(2, 3),
        policy=jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES))


def apply(params: Params, tokens, cfg: TransformerConfig,
          *, mesh=None, remat: Optional[bool] = None):
    """Forward pass.  ``tokens``: [B, S] int32.  Returns
    ``(logits_fp32, aux_loss)``.  ``remat`` defaults to ``cfg.remat``."""
    if remat is None:
        remat = cfg.remat
    dtype = cfg.compute_dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(dtype)[tokens]
        x = _constrain(x, ACT_SPEC, mesh)

    layer_fn = remat_layer() if remat else _layer

    def body(carry, lp):
        h, aux_sum = carry
        h, aux, _ = layer_fn(h, lp, cfg, mesh)
        return (h, aux_sum + aux), None

    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                           params["layers"])
    with jax.named_scope("head_loss"):
        return _logits(x, params["ln_f"], params["embed"]), aux


def softmax_xent(logits, targets):
    """Mean softmax cross-entropy in logsumexp form: one pass over the
    [B, S, V] logits instead of materializing a full log_softmax tensor
    of the same size (identical math:
    -logp[target] = lse(logits) - logits[target])."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    target_logit = jnp.take_along_axis(logits, targets[..., None],
                                       axis=-1)[..., 0]
    return jnp.mean(lse - target_logit)


def loss_fn(params, tokens, targets, cfg: TransformerConfig,
            *, mesh=None, aux_weight: float = 0.01):
    logits, aux = apply(params, tokens, cfg, mesh=mesh)
    with jax.named_scope("head_loss"):
        return softmax_xent(logits, targets) + aux_weight * aux


# ---------------------------------------------------------------------------
# Cached decode: generate() and the serving seam (horovod_tpu.serving)
# ---------------------------------------------------------------------------
#
# The reference is a training framework with no inference path; a complete
# model family needs one.  Decode is the classic two-phase shape: one
# prefill pass caches every layer's rotated K/V for the prompt, then one
# token a step attends a single query against the cache: O(S) per token
# instead of O(S^2) recompute.  Dense single-host math (the parallel axes
# exist for training; under a mesh the cache shards its heads over tp).
#
# The cache is a STATE, ``{"kv": (ks, vs)}``, each [L, B, Smax, H, HD],
# and the three functions that make, fill and step it are the seam
# serving/decode.py builds a DecodeEngine from, under the names and
# signatures every served module gives its own (the install is the shared
# one, models/layers.py, told where the slots lie: SLOT_AXES).  A serving
# batch is ragged (each slot joined at a different step and sits at its
# own offset), so decode_step takes a position per row; generate() is the
# same step with one position for all rows.  Rows never mix, which is what
# keeps a continuously batched decode bit-identical to a request decoded
# alone.


KV_CACHE_SPEC = P(None, None, None, "tp", None)  # [L, B, Smax, H, HD]
STATE_SPEC = {"kv": (KV_CACHE_SPEC, KV_CACHE_SPEC),
              "counters": {name: P() for name in ATTN_COUNTERS}}
# The axis of each slot-kind leaf that the slots lie along.
SLOT_AXES = {"kv": (1, 1)}


def _refuse_experts(cfg: TransformerConfig):
    """Whoever makes a state, or holds parameters for one, says so first:
    dense-FFN configs only (``n_experts=0``).  ``_moe_ffn`` drops rows
    over an expert's capacity, which ties a slot's output to its
    neighbours'; the experts that are served (models/latent_moe.py) drop
    nothing."""
    if cfg.n_experts:
        raise NotImplementedError(
            "cached decode (generate() and serving) supports dense-FFN "
            "configs: this decoder's experts drop rows over their capacity, "
            "so a slot's output would depend on its neighbours; experts "
            "that drop nothing are served by models/latent_moe.py "
            "(models/experts.py: routed_ffn)")


def init_state(cfg: TransformerConfig, max_batch: int, cache_len: int):
    """Zeros for ``max_batch`` slots."""
    _refuse_experts(cfg)
    lane = (cfg.n_layers, max_batch, cache_len, cfg.n_heads, cfg.head_dim)
    return {"kv": (jnp.zeros(lane, cfg.compute_dtype),
                   jnp.zeros(lane, cfg.compute_dtype)),
            "counters": {name: jnp.zeros((), jnp.uint32)
                         for name in ATTN_COUNTERS}}


def _prefill(params, tokens, cfg, Smax):
    """Forward over the prompts [B, S]: next-token logits [B, V] of the
    last position and the rows' state, keys and values at [0, S) and zero
    past them.  Attends densely whatever ``cfg.attn_impl`` says."""
    _refuse_experts(cfg)
    S = tokens.shape[1]
    cfg = replace(cfg, attn_impl="dense")
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    pad = [(0, 0), (0, Smax - S), (0, 0), (0, 0)]

    def body(h, lp):
        h, _, (k, v) = _layer(h, lp, cfg)
        return h, (jnp.pad(k, pad), jnp.pad(v, pad))

    x, kv = lax.scan(body, x, params["layers"])
    x = _rmsnorm(x, params["ln_f"])
    return vocab_projection(x[:, -1:], params["embed"])[:, 0], {"kv": kv}


def prefill_request(params, prompt, cfg: TransformerConfig, cache_len: int):
    """Prefill ONE request.  ``prompt``: [S0] int32.  Returns (next-token
    logits [V] f32, the request's state: ``init_state`` for one slot, its
    lane [L, 1, cache_len, H, HD] filled through the prompt)."""
    logits, state = _prefill(params, prompt[None], cfg, cache_len)
    return logits[0], state


def decode_step(params, tok, pos, state, cfg: TransformerConfig, mesh=None):
    """One step of every row: embed ``tok`` [B], attend each row at its
    own ``pos`` [B], return (next-token logits [B, V] f32, the state
    updated in place when donated).  The caches are the layer loop's
    CARRY, indexed by layer, not its ``xs``/``ys``: a scan that slices a
    lane out and stacks it back copies the whole cache every step.
    ``mesh``: the one the state is sharded over, if any.  A state that
    has ``"counters"`` (``init_state``'s) gets them moved on."""
    x = params["embed"].astype(cfg.compute_dtype)[tok[:, None]]
    read = lane_reader(cfg, mesh, pos, state["kv"][0].shape[2])

    def layer(carry, layer_in):
        h, kv = carry
        lp, l = layer_in
        h, _, kv = _layer(h, lp, cfg, cache=(*kv, l, pos, read))
        return (h, kv), None

    (x, kv), _ = lax.scan(
        layer, (x, state["kv"]),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = _rmsnorm(x, params["ln_f"])
    after = {"kv": kv}
    if "counters" in state:
        from horovod_tpu.ops.pallas_decode_attention import block_for

        cache_len = kv[0].shape[2]
        after["counters"] = count_attention_reads(
            state["counters"], pos, cache_len, cfg.n_layers,
            None if read is None else block_for(cache_len, shared=False))
    return vocab_projection(x, params["embed"])[:, 0], after


def generate(params, prompt, cfg: TransformerConfig, *,
             max_new_tokens: int, temperature: float = 0.0,
             rng=None, cache_len: Optional[int] = None):
    """Autoregressive decode.  ``prompt``: [B, S0] int32.  Returns
    [B, S0 + max_new_tokens] (prompt + generated).  ``temperature=0``
    is greedy argmax; otherwise softmax sampling with ``rng``.

    ``cache_len`` pins the KV-cache length (default: exactly
    ``S0 + max_new_tokens``).  The extra positions are masked out, but
    the cache length still shapes XLA's reduction tree — callers that
    compare against a fixed-length serving cache (serving/decode.py)
    pass the serving length here to keep the comparison bit-exact.

    Dense-FFN configs only (:func:`_refuse_experts`).
    """
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs rng")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, "
                         f"got {max_new_tokens}")
    B, S0 = prompt.shape
    Smax = S0 + max_new_tokens
    if Smax > cfg.max_seq_len:
        raise ValueError(
            f"prompt + new tokens ({Smax}) exceeds max_seq_len "
            f"({cfg.max_seq_len})")
    if cache_len is not None:
        if cache_len < Smax:
            raise ValueError(
                f"cache_len ({cache_len}) is shorter than prompt + new "
                f"tokens ({Smax})")
        Smax = cache_len
    logits0, state = _prefill(params, prompt, cfg, Smax)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def sample(logits, key):
        if temperature > 0.0:
            return jax.random.categorical(key, logits / temperature,
                                          axis=-1)
        return jnp.argmax(logits, axis=-1)

    def step(carry, key):
        tok, pos, state = carry
        logits, state = decode_step(params, tok, jnp.full((B,), pos),
                                    state, cfg)
        nxt = sample(logits, key).astype(prompt.dtype)
        return (nxt, pos + 1, state), nxt

    keys = jax.random.split(rng, max_new_tokens)
    first = sample(logits0, keys[0]).astype(prompt.dtype)
    if max_new_tokens == 1:
        return jnp.concatenate([prompt, first[:, None]], axis=1)
    _, rest = lax.scan(step, (first, jnp.asarray(S0), state), keys[1:])
    out = jnp.concatenate(
        [prompt, first[:, None], rest.swapaxes(0, 1)], axis=1)
    return out


# Every leaf the forward casts to ``cfg.compute_dtype`` at its use: the
# operands of the matmuls (the experts' alike) and the tied embedding.  The
# norm gains and the router are used in float32 and are not among them.
COMPUTE_DTYPE_LEAVES = frozenset(
    {"embed", "wq", "wk", "wv", "wqkv", "wo", "w_in", "w_gate", "w_out"})


def _heads_split(leaf) -> bool:
    """Whether ``leaf`` [L, D, H, HD] lies split over its heads (under
    ``param_specs`` with ``tp`` > 1).  A traced leaf has no sharding."""
    sharding = getattr(leaf, "sharding", None)
    return (sharding is not None
            and sharding.shard_shape(leaf.shape)[2] != leaf.shape[2])


def serving_params(params: Params, cfg: TransformerConfig) -> Params:
    """``params`` as a serving engine holds them: each of
    COMPUTE_DTYPE_LEAVES rounded to ``cfg.compute_dtype`` once, every other
    leaf as given, and the three input projections of the attention as
    ONE leaf ``wqkv`` [L, D, 3 H HD] (q's columns, then k's, then v's).
    The rounding is the one the forward's cast at the use makes, so
    decode_step and prefill_request return from this pytree what they
    return from ``params``, and their cast is then a no-op: inside
    decode_step's layer scan XLA otherwise hoists the casts of ALL layers'
    float32 weights out of the loop and runs them, whole, on every call.
    A leaf already in the compute type comes back as the same buffer, and
    a sharded leaf keeps its sharding (``astype``).

    Why one leaf: the chip's compiler stages each layer's ``wq``, ``wk``
    and ``wv`` in fast memory with an op of its own before the windowed
    convolution that ``"bsd,dhk->bshk"`` becomes, a seventh of a decode
    step of the 8-layer OLMo-1B, while the plain 2-D product
    ``"bsd,df->bsf"`` reads its layer out of the stack in place, as
    ``wo``'s and the feed-forward's do (serving/decode.py has the compile
    probe's account).  Where the given ``wq`` lies split over its heads
    (``tp`` > 1) the joined columns would not split by heads, and the
    three are held as given."""
    _refuse_experts(cfg)

    def held(path, leaf):
        cast = path[-1].key in COMPUTE_DTYPE_LEAVES
        return leaf.astype(cfg.compute_dtype) if cast else leaf

    out = jax.tree_util.tree_map_with_path(held, params)
    layers = out["layers"]
    if "wq" in layers and not _heads_split(layers["wq"]):
        L, D = layers["wq"].shape[:2]
        three = [layers.pop(name) for name in ("wq", "wk", "wv")]
        layers["wqkv"] = jnp.concatenate(
            [w.reshape(L, D, -1) for w in three], axis=-1)
    return out
