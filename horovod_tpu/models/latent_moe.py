"""A decoder with latent attention (MLA) and routed experts beside a
shared one: the serving path of the ``glm4_moe_lite`` / DeepSeek-V3 line
(``zai-org/GLM-4.7-Flash``'s ``config.json`` gives every size; so does
``deepseek-ai/DeepSeek-V3.2``'s, whose keys ``index_*``, ``n_group``,
``topk_group`` and ``rope_scaling`` are read here under their names).

``forward`` (the tests' oracle), ``prefill_request`` and ``decode_step``
are built from ONE attention function with two forms and ONE routed
feed-forward (:mod:`horovod_tpu.models.experts`).  Training it is not
supported (the grouped product has no backward pass written for it).

* Attention, every layer, H heads.  With ``x`` a normalised row::

      c_q         = RMSNorm(x W_qa)                 wq_a  [D, q_lora_rank]
      [q_n | q_r] = c_q W_qb  per head              wq_b  [q_lora_rank, H, nope + rope]
      [c | k_r]   = x W_kva                         wkv_a [D, kv_lora_rank + rope]
      c = RMSNorm(c);  k_r = RoPE(k_r)              ONE rotary key for all heads
      [k_n | v]   = c W_kvb  per head               w_uk  [kv_lora_rank, H, nope]
                                                    w_uv  [kv_lora_rank, H, v]
      q_h = [q_n,h | RoPE(q_r,h)]   k_h = [k_n,h | k_r]
      out = concat_h(softmax_causal(q_h k_h^T / sqrt(nope + rope)) v_h) W_o

  ``W_kvb`` is held as its two halves ``w_uk``, ``w_uv``, cut where the
  absorbed form cuts it.  **What a position keeps** a layer: ``c`` after
  its norm and ``k_r`` after its rotation (512 + 64 values for the
  published sizes, against 20 x (256 + 256) full-width).

  *Expanded form* (a prompt: ``cache`` None): ``k_n`` and ``v`` are made
  from ``c`` and attention is the ordinary one, query blocks of
  ``attn_block`` rows against the keys up to their last row (a blocked
  softmax in ``jnp``; the scores of one block exist at a time).
  *Absorbed form* (one token a slot against the cache)::

      score_h,t = (q_n,h W_uk,h^T) . c_t + RoPE(q_r,h) . k_r,t
      ctx_h     = (sum_t p_h,t c_t) W_uv,h

  the step reads a cached position once for all heads and never expands
  a key: ONE kernel (ops/pallas_decode_attention.py) walks each slot's
  lane in blocks as far as the slot's position, and a block of latents
  it has fetched serves the scores and the context both.  Both forms
  give the same attention (tests/test_latent_moe.py).
* **The indexer** (``index_topk`` set: DeepSeek sparse attention), every
  layer: a position attends the ``index_topk`` positions before it that
  a small scorer likes best, and all of them while there are no more::

      q_I = c_q W_Iq  per index head, RoPE on its first rope dims
                                                    wi_q  [q_lora_rank, HI, DI]
      k_I = LayerNorm(x W_Ik), RoPE on its first rope dims
                                                    wi_k  [D, DI]   ONE index key a position
      w   = x W_Iw / sqrt(HI DI)                    wi_w  [D, HI]
      I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s])   float32, s <= t
      S_t = the min(index_topk, t + 1) positions of largest I[t, .],
            equal scores by position;   softmax over s in S_t alone

  **What a position keeps** a layer is then a third array, ``k_I`` after
  its norm and rotation (128 values).  *Prompt* (``index_scores``): the
  scores of a block of query rows against the keys before them, key
  block by key block (a row's 64 heads times its keys exist for one key
  block at a time), the exact cut a row by 32 counting passes over the
  scores' bits, and the ordinary attention with the unselected masked;
  the heads in blocks (a block's scores and its expanded keys and values
  exist at a time).  *Step* (``index_select``, ``sparse_attn``): ONE
  kernel (ops/pallas_index_select.py) scores each slot's lane as far as
  its position and finds the cut; the attention kernel masks its blocks
  by it.  The lane is read whole either way: a gather of 2048 scattered
  rows a slot costs this chip more than the read (the kernel's
  docstring has the measurement).
* Rotary frequencies: ``theta^(-2i/d)``, or with ``rope_scaling`` of type
  ``yarn`` that table bent between ``beta_fast`` and ``beta_slow``
  (:func:`rope_frequencies`) with the softmax's scale times ``mscale``
  squared; one ``_rope`` takes either.
* Feed-forward: the first ``first_k_dense_replace`` layers the dense
  gated one; the others ``experts.routed_ffn`` (top-k of a biased
  sigmoid score, inside the ``topk_group`` best of ``n_group`` groups
  where there are groups, nothing dropped) plus a shared expert.  With
  ``experts_held`` the chip holds experts ``expert_first`` to
  ``expert_first + experts_held`` of ``n_routed_experts``: the router
  keeps its width, the layer computes its own experts' part, and what
  the other chips' experts would add is left out (the ``model-configs``
  guide's cut; no code stands in for the exchange).
* The stack follows models/jamba.py: layers of a kind stacked on a
  leading axis, each run ONE ``fori_loop`` with all state as its carry
  and the layer indexed dynamically.

State of a served batch (``init_state``)::

    {"kv": (c, k_r)     [L, B, cache_len, kv_lora_rank],
                        [L, B, cache_len, qk_rope_head_dim]  compute_dtype
     "index": k_I       [L, B, cache_len, index_head_dim], with an indexer
     "counters": {...}  uint32 scalars, summed on the device by
                        ``decode_step``: see :func:`counter_names`}

A request's state (``prefill_request``) is the slot kinds with B = 1.
A slot whose position is 0 is free (``DecodeEngine.clear``): its row is
kept out of the routing, so a free slot pulls no expert's weights through
the chip, and out of the counters.  The module omits what
``serving/decode.py:MODELS`` lets it: no sharding of this state is written
(experts under ep, heads of the absorbed form under tp), and the weights
come in ``param_dtype``, which is for the caller to choose.
"""

from __future__ import annotations

import functools
import math
import operator
from dataclasses import dataclass
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.models import experts
from horovod_tpu.models.layers import (ATTN_COUNTERS, _at, _dense_ffn,
                                       _logits, _rmsnorm, _rope,
                                       add_counters, count_attention_reads)
from horovod_tpu.ops.pallas_decode_attention import (block_for,
                                                     decode_attention,
                                                     ordered, work_list)
from horovod_tpu.ops.pallas_index_select import index_select

Params = Dict[str, Any]
State = Dict[str, Any]

# What ``decode_step`` adds to ``state["counters"]`` a step: the routing's
# (``experts.MOE_COUNTERS``, and ``experts.ABSENT_COUNTER`` with a share of
# the experts) and, over all layers, what the attention read of the lanes
# (ATTN_COUNTERS).
MOE_COUNTERS, ABSENT_COUNTER = experts.MOE_COUNTERS, experts.ABSENT_COUNTER
COUNTERS = MOE_COUNTERS + ATTN_COUNTERS
# With an indexer, over all layers: the positions its step scored (what the
# live slots have written) and the positions its attention then saw.
INDEX_COUNTERS = ("hvd_serve_index_positions_scored_total",
                  "hvd_serve_attn_positions_selected_total")
# (row, expert) pairs of a prompt gathered for the grouped products at
# once, and bytes of a prompt's attention scores that exist at once: the
# longer prompt, or the more heads, goes in blocks.
PAIRS_AT_ONCE = 16384
SCORE_BYTES_AT_ONCE = 1 << 29


@dataclass(frozen=True)
class LatentMoEConfig:
    """The published keys, under their published names."""
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    # Positions a served request may reach (the server's default cache).
    max_seq_len: int = 4608
    # Query rows of a prompt whose scores exist at once.
    attn_block: int = 1024
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # Routing limited to groups (1: no limit).
    n_group: int = 1
    topk_group: int = 1
    # The indexer (None: none, every cached position is attended).
    index_n_heads: Optional[int] = None
    index_head_dim: Optional[int] = None
    index_topk: Optional[int] = None
    # None, or the published ``rope_scaling`` of type ``yarn``.
    rope_scaling: Optional[Mapping[str, Any]] = None
    # What one chip holds of a layer's routed experts (None: all of them).
    experts_held: Optional[int] = None
    expert_first: int = 0

    def __post_init__(self):
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace counts leading layers")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("more experts a token than experts")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary dims turn in pairs")
        if self.n_routed_experts % self.n_group or not (
                1 <= self.topk_group <= self.n_group):
            raise ValueError("groups of equal size, some of them kept")
        if self.num_experts_per_tok > (
                self.topk_group * self.n_routed_experts // self.n_group):
            raise ValueError("more experts a token than the kept groups hold")
        if self.experts_held is not None and not (
                0 <= self.expert_first
                <= self.n_routed_experts - self.experts_held):
            raise ValueError("the held experts are some of the routed ones")
        if self.index_topk is not None and (
                not self.index_n_heads or not self.index_head_dim
                or self.index_head_dim < self.qk_rope_head_dim):
            raise ValueError("an indexer has heads as wide as its rotary "
                             "part at least")
        if self.rope_scaling is not None and \
                self.rope_scaling.get("type") != "yarn":
            raise ValueError("rope_scaling: yarn or none")

    def n_layers(self, kind: str) -> int:
        dense = self.first_k_dense_replace
        return dense if kind == "dense" else self.num_hidden_layers - dense


def counter_names(cfg: LatentMoEConfig) -> Tuple[str, ...]:
    """The device counters ``cfg``'s state holds."""
    return (COUNTERS
            + ((ABSENT_COUNTER,) if cfg.experts_held is not None else ())
            + (INDEX_COUNTERS if cfg.index_topk is not None else ()))


def rope_frequencies(cfg: LatentMoEConfig):
    """What ``_rope`` turns by: the base ``rope_theta`` where nothing
    scales it, else YaRN's table of the ``qk_rope_head_dim / 2``
    frequencies: ``theta^(-2i/d)`` kept where a turn is shorter than
    ``original / beta_fast`` positions, divided by ``factor`` where it is
    longer than ``original / beta_slow``, a ramp between."""
    rs = cfg.rope_scaling
    if rs is None:
        return cfg.rope_theta
    d = cfg.qk_rope_head_dim
    base = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def turns_at(beta):     # the dim whose wavelength is original / beta
        return d * math.log(rs["original_max_position_embeddings"]
                            / (beta * 2 * math.pi)) / (
                                2 * math.log(cfg.rope_theta))

    low = max(math.floor(turns_at(rs["beta_fast"])), 0)
    high = min(math.ceil(turns_at(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return jnp.asarray(base * ((1 - ramp) + ramp / rs["factor"]),
                       jnp.float32)


def softmax_scale(cfg: LatentMoEConfig) -> float:
    """1 / sqrt(the width q . k runs over), times YaRN's ``mscale``
    squared where the positions are scaled (``mscale_all_dim``'s, the
    published inference code's; the rotary's own amplitude, ``mscale /
    mscale_all_dim``, is 1 for the published pair and is not applied)."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    rs = cfg.rope_scaling
    if rs is not None and rs.get("mscale_all_dim"):
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale *= m * m
    return scale


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init(rng, cfg: LatentMoEConfig) -> Params:
    """Matrices normal(0, 0.02), output projections scaled by 1/sqrt(2 L),
    gains one, the router's selection bias normal(0, 0.01) in float32.
    Every other leaf is held in ``param_dtype``.  With ``experts_held``
    the routed experts' stacks hold that many (the router keeps its
    ``n_routed_experts`` outputs); with an indexer every layer has its
    three projections and its key's LayerNorm."""
    D, V = cfg.hidden_size, cfg.vocab_size
    F, Fe = cfg.intermediate_size, cfg.moe_intermediate_size
    Fs = cfg.n_shared_experts * Fe
    H, E = cfg.num_attention_heads, cfg.n_routed_experts
    Eh = E if cfg.experts_held is None else cfg.experts_held
    Rq, Rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    Ld, Lm = cfg.n_layers("dense"), cfg.n_layers("moe")
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(rng, 48))
    dt = cfg.param_dtype

    def normal(shape, s, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * s).astype(dtype)

    def attention(n):
        lp = {"ln1": jnp.ones((n, D), dt),
              "wq_a": normal((n, D, Rq), std),
              "q_norm": jnp.ones((n, Rq), dt),
              "wq_b": normal((n, Rq, H, nope + rope), std),
              "wkv_a": normal((n, D, Rkv + rope), std),
              "kv_norm": jnp.ones((n, Rkv), dt),
              "w_uk": normal((n, Rkv, H, nope), std),
              "w_uv": normal((n, Rkv, H, vd), std),
              "wo": normal((n, H, vd, D), out_std),
              "ln2": jnp.ones((n, D), dt)}
        if cfg.index_topk is not None:
            HI, DI = cfg.index_n_heads, cfg.index_head_dim
            lp.update({"wi_q": normal((n, Rq, HI, DI), std),
                       "wi_k": normal((n, D, DI), std),
                       "wi_k_gain": jnp.ones((n, DI), dt),
                       "wi_k_bias": jnp.zeros((n, DI), dt),
                       "wi_w": normal((n, D, HI), std)})
        return lp

    dense = {**attention(Ld), "w_in": normal((Ld, D, F), std),
             "w_gate": normal((Ld, D, F), std),
             "w_out": normal((Ld, F, D), out_std)}
    moe = {**attention(Lm), "router": normal((Lm, D, E), std),
           "router_bias": normal((Lm, E), 0.01, jnp.float32),
           "w_in": normal((Lm, Eh, D, Fe), std),
           "w_gate": normal((Lm, Eh, D, Fe), std),
           "w_out": normal((Lm, Eh, Fe, D), out_std),
           "shared_in": normal((Lm, D, Fs), std),
           "shared_gate": normal((Lm, D, Fs), std),
           "shared_out": normal((Lm, Fs, D), out_std)}
    return {"embed": normal((V, D), std), "dense": dense, "moe": moe,
            "ln_f": jnp.ones((D,), dt), "head": normal((V, D), std)}


# ---------------------------------------------------------------------------
# attention: ONE function, two forms
# ---------------------------------------------------------------------------


def _indexer(x, c_q, lp, cfg: LatentMoEConfig, freqs, own):
    """The indexer's side of a layer for rows ``x`` [B, S, D] (normalised)
    with their query latents ``c_q``: (q_I [B, S, HI, DI], k_I [B, S, DI]
    in the compute type, w [B, S, HI] float32)."""
    dtype, f32, rope = cfg.compute_dtype, jnp.float32, cfg.qk_rope_head_dim
    q = jnp.einsum("bsr,rhk->bshk", c_q, lp["wi_q"].astype(dtype))
    q = jnp.concatenate([_rope(q[..., :rope], freqs, own), q[..., rope:]], -1)
    k = jnp.einsum("bsd,dk->bsk", x, lp["wi_k"].astype(dtype)).astype(f32)
    k = k - jnp.mean(k, -1, keepdims=True)
    k = k * lax.rsqrt(jnp.mean(k * k, -1, keepdims=True) + 1e-6)
    k = (k * lp["wi_k_gain"] + lp["wi_k_bias"]).astype(dtype)
    k = jnp.concatenate(
        [_rope(k[..., None, :rope], freqs, own)[:, :, 0], k[..., rope:]], -1)
    w = jnp.einsum("bsd,dh->bsh", x, lp["wi_w"].astype(dtype),
                   preferred_element_type=f32) / math.sqrt(
                       cfg.index_n_heads * cfg.index_head_dim)
    return q, k, w


def _selected_rows(q_i, k_i, w, lo: int, hi: int, top: int):
    """Which of the keys [0, hi) each query row of [lo, hi) attends,
    [B, hi - lo, hi] bool: the ``top`` of largest index score among those
    before it (equal scores by position), all of them where there are no
    more.  The scores of 64 heads exist for one block of keys at a time;
    the cut is exact: 32 counting passes over the scores' bits, as
    ops/pallas_index_select.py makes them for a step."""
    B, T, H = q_i.shape[0], hi - lo, q_i.shape[2]
    with jax.named_scope("index_scores"):
        width = max(SCORE_BYTES_AT_ONCE // (4 * B * H * T), 128)
        parts = []
        for k0 in range(0, hi, width):
            s = jnp.einsum("bthk,bsk->bhts", q_i[:, lo:hi],
                           k_i[:, k0:min(k0 + width, hi)],
                           preferred_element_type=jnp.float32)
            # float32 against float32: the chip's default would round
            # both to bfloat16, and the step's kernel sums them exactly.
            parts.append(jnp.einsum("bth,bhts->bts", w[:, lo:hi],
                                    jax.nn.relu(s),
                                    precision=lax.Precision.HIGHEST))
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        u = ordered(jnp.where(causal, jnp.concatenate(parts, -1), -jnp.inf))

        def settle(i, cut):     # the sign, then the 31 bits under it
            cand = jnp.where(i == 0, 0, cut | (1 << (31 - i)))
            enough = jnp.sum(u >= cand[..., None], -1) >= top
            return jnp.where(enough, cand, cut)

        cut = lax.fori_loop(
            0, 32, settle, jnp.full((B, T), -(1 << 31), jnp.int32))[..., None]
        need = top - jnp.sum(u > cut, -1, keepdims=True)
        equal = u == cut
        return causal & ((u > cut) | (
            equal & (jnp.cumsum(equal, -1, dtype=jnp.int32) <= need)))


def _causal_attention(q_n, q_r, k_n, k_r, v, seen, T: int, scale: float,
                      dtype):
    """The expanded form's core: q_n, q_r [B, S, H, .] against k_n, v
    [B, S, H, .] and the shared k_r [B, S, rope], query blocks of ``T``
    rows against the keys up to their last row.  ``seen[i]``, where not
    None, is block i's [B, T, hi] mask of the keys its rows may see
    (within the causal ones).  Returns [B, S, H, v]."""
    f32, S = jnp.float32, q_n.shape[1]
    blocks = []
    for i, lo in enumerate(range(0, S, T)):     # static: a program a length
        hi = min(lo + T, S)
        scores = (
            jnp.einsum("bshk,bthk->bhst", q_n[:, lo:hi], k_n[:, :hi],
                       preferred_element_type=f32)
            + jnp.einsum("bshk,btk->bhst", q_r[:, lo:hi], k_r[:, :hi],
                         preferred_element_type=f32)) * scale
        valid = (jnp.arange(hi)[None, :]
                 <= jnp.arange(lo, hi)[:, None])            # [s, t]
        if seen[i] is not None:
            valid = seen[i][:, None]
        probs = jax.nn.softmax(jnp.where(valid, scores, -1e30), axis=-1)
        blocks.append(jnp.einsum("bhst,bthk->bshk", probs.astype(dtype),
                                 v[:, :hi]))
    return jnp.concatenate(blocks, axis=1)


def _attention(x, lp, cfg: LatentMoEConfig, cache=None):
    """x: [B, S, D], normalised.

    ``cache`` None, the expanded form: the S positions start at 0 and
    attend among themselves; returns (out, kept): the latents
    [B, S, kv_lora_rank], rotated keys [B, S, rope] and, with an indexer,
    index keys [B, S, index_head_dim] for whoever keeps them.  ``cache`` =
    (lanes, layer, pos, work), the stacked caches [L, B, Smax, .] in that
    order, the position [B] of THIS token (S = 1) and the kernels'
    ``work_list`` of those positions, the absorbed form: writes the B new
    rows at [layer, b, pos[b]] in place and attends lane ``layer``, each
    slot's as far as its ``pos`` (with an indexer: the selected of those
    positions); returns (out, lanes)."""
    dtype = cfg.compute_dtype
    eps, theta = cfg.rms_norm_eps, rope_frequencies(cfg)
    B, S, _ = x.shape
    Rkv, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    scale = softmax_scale(cfg)
    own = None if cache is None else cache[2][:, None]          # [B, 1]
    c_q = _rmsnorm(jnp.einsum("bsd,dr->bsr", x, lp["wq_a"].astype(dtype)),
                   lp["q_norm"], eps)
    q = jnp.einsum("bsr,rhk->bshk", c_q, lp["wq_b"].astype(dtype))
    q_n, q_r = q[..., :nope], _rope(q[..., nope:], theta, own)
    ckr = jnp.einsum("bsd,dr->bsr", x, lp["wkv_a"].astype(dtype))
    c = _rmsnorm(ckr[..., :Rkv], lp["kv_norm"], eps)
    k_r = _rope(ckr[..., None, Rkv:], theta, own)[:, :, 0]      # [B, S, rope]
    w_uk, w_uv = lp["w_uk"].astype(dtype), lp["w_uv"].astype(dtype)
    index = None
    if cfg.index_topk is not None:
        index = _indexer(x, c_q, lp, cfg, theta, own)
    if cache is None:
        T, H = min(cfg.attn_block, S), cfg.num_attention_heads
        # Which keys a block's rows see, beyond the causal rule: a
        # selection only where there are more keys than it keeps.
        seen = [None if index is None or min(lo + T, S) <= cfg.index_topk
                else _selected_rows(*index, lo, min(lo + T, S),
                                    cfg.index_topk)
                for lo in range(0, S, T)]
        heads = H
        while 4 * B * heads * T * S > SCORE_BYTES_AT_ONCE and heads % 2 == 0:
            heads //= 2
        if heads == H:
            k_n = jnp.einsum("bsc,chk->bshk", c, w_uk)
            v = jnp.einsum("bsc,chk->bshk", c, w_uv)
            ctx = _causal_attention(q_n, q_r, k_n, k_r, v, seen, T, scale,
                                    dtype)                  # [B, S, H, v]
        else:
            # The heads in blocks: a block's scores, and its keys and
            # values expanded from the latents, exist at a time.
            def some_heads(first):
                def of(a, axis):
                    return lax.dynamic_slice_in_dim(a, first, heads, axis)

                return _causal_attention(
                    of(q_n, 2), of(q_r, 2),
                    jnp.einsum("bsc,chk->bshk", c, of(w_uk, 1)), k_r,
                    jnp.einsum("bsc,chk->bshk", c, of(w_uv, 1)), seen, T,
                    scale, dtype)

            ctx = lax.map(some_heads, jnp.arange(0, H, heads))
            ctx = jnp.moveaxis(ctx, 0, 2).reshape(B, S, H, -1)
        kept = (c, k_r) if index is None else (c, k_r, index[1])
    else:
        lanes, layer, pos, work = cache
        cs, krs = lanes[:2]
        rows = jnp.arange(B)
        cs = cs.at[layer, rows, pos].set(c[:, 0])
        # Row by row, not one scatter: XLA keeps rows of 64 values with
        # the positions minor in HBM, a scatter wants its rows minor, and
        # the two layouts of the whole array are a copy each way a step.
        for b in range(B):
            krs = lax.dynamic_update_slice(
                krs, k_r[b][None, None], (layer, b, pos[b], 0))
        q_c = jnp.einsum("bhk,chk->bhc", q_n[:, 0], w_uk)       # absorbed
        kept, selection = (cs, krs), {}
        if index is not None:
            q_i, k_i, w = index
            kis = lanes[2].at[layer, rows, pos].set(k_i[:, 0])
            kept, selection = (cs, krs, kis), {
                "select": index_select(q_i[:, 0], w[:, 0], kis, layer, pos,
                                       top=cfg.index_topk, work=work)}
        # The latents are keys and values both, fetched once a block;
        # the rotary keys are the keys' second part, read as they lie.
        ctx_c = decode_attention(
            (q_c, q_r[:, 0]), (cs, krs.swapaxes(2, 3)), None, layer, pos,
            scale=scale, work=work, positions_last=(False, True),
            **selection)
        ctx = jnp.einsum("bhc,chk->bhk", ctx_c, w_uv)[:, None]  # [B, 1, H, v]
    return jnp.einsum("bshk,hkd->bsd", ctx, lp["wo"].astype(dtype)), kept


# ---------------------------------------------------------------------------
# the stack, and the state it carries
# ---------------------------------------------------------------------------


def init_state(cfg: LatentMoEConfig, max_batch: int, cache_len: int
               ) -> State:
    """Zeros for ``max_batch`` slots; see the module docstring."""
    L = cfg.num_hidden_layers

    def lane(width):
        return jnp.zeros((L, max_batch, cache_len, width), cfg.compute_dtype)

    state = {"kv": (lane(cfg.kv_lora_rank), lane(cfg.qk_rope_head_dim))}
    if cfg.index_topk is not None:
        state["index"] = lane(cfg.index_head_dim)
    return {**state, "counters": {name: jnp.zeros((), jnp.uint32)
                                  for name in counter_names(cfg)}}


# The axis of each slot-kind leaf that the slots lie along.
SLOT_AXES = {"kv": (1, 1), "index": 1}


def _lanes(state: State) -> Tuple:
    """A state's position-indexed arrays, in ``_attention``'s order."""
    return state["kv"] + ((state["index"],) if "index" in state else ())


def _slots(lanes: Tuple) -> State:
    """``_lanes``'s inverse: the slot kinds of a state."""
    state = {"kv": tuple(lanes[:2])}
    if len(lanes) > 2:
        state["index"] = lanes[2]
    return state


_EXPERTS = ("w_in", "w_gate", "w_out")


def _stack(params: Params, x, cfg: LatentMoEConfig, kv=None, pos=None):
    """x [B, S, D] through every layer.  ``pos`` None: the sequences
    start here (position 0); ``kv`` (``_lanes`` of a state), if given,
    receives their latents, rotated keys and index keys at rows [0, S).
    ``pos`` [B]: one token a slot continuing ``kv``, which is read and
    written at its layer; rows at position 0 are free slots and are routed
    nowhere.  Returns (x, kv, the routing's stats [3] summed over the
    expert layers)."""
    dtype, eps = cfg.compute_dtype, cfg.rms_norm_eps
    start = pos is None
    keeps = kv is not None
    live = None if start else pos > 0
    if not start:       # the attention kernel's blocks, once for all layers
        cache_len = kv[0].shape[2]
        work = work_list(pos, cache_len, block_for(cache_len, shared=True))
    B, S, D = x.shape
    Ld = cfg.n_layers("dense")
    # The routed experts stay in their stack (experts.routed_ffn indexes
    # the layer inside the grouped product); everything else of a layer
    # is cut out at its index.
    small = {k: v for k, v in params["moe"].items() if k not in _EXPERTS}
    routed = {k: params["moe"][k] for k in _EXPERTS}
    share = {} if cfg.experts_held is None else {"first": cfg.expert_first}
    # A prompt's rows go through the grouped products in runs of so many.
    run = max(PAIRS_AT_ONCE // cfg.num_experts_per_tok, 1)

    def attend(h, lp, kv, at):
        y = _rmsnorm(h, lp["ln1"], eps)
        if start:
            y, kept = _attention(y, lp, cfg)
            if keeps:
                kv = tuple(lax.dynamic_update_slice(lane, new[None],
                                                    (at, 0, 0, 0))
                           for lane, new in zip(kv, kept))
        else:
            y, kv = _attention(y, lp, cfg, (kv, at, pos, work))
        return h + y, kv

    def dense_layer(l, carry):
        h, kv = carry
        lp = _at(params["dense"], l)
        h, kv = attend(h, lp, kv, l)
        return h + _dense_ffn(_rmsnorm(h, lp["ln2"], eps), lp, dtype), kv

    def moe_layer(l, carry):
        h, kv, stats = carry
        lp = _at(small, l)
        h, kv = attend(h, lp, kv, Ld + l)
        u = _rmsnorm(h, lp["ln2"], eps)
        rows = u.reshape(B * S, D)
        chosen, weights = experts.route(
            rows, lp["router"], lp["router_bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.n_group, cfg.topk_group)
        ys, news = zip(*(
            experts.routed_ffn(
                rows[lo:lo + run], routed, l, chosen[lo:lo + run],
                weights[lo:lo + run], dtype,
                live if live is None else live[lo:lo + run], **share)
            for lo in range(0, B * S, run)))
        y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
        shared = _dense_ffn(u, {"w_in": lp["shared_in"],
                                "w_gate": lp["shared_gate"],
                                "w_out": lp["shared_out"]}, dtype)
        return (h + y.reshape(B, S, D) + shared, kv,
                stats + functools.reduce(operator.add, news))

    kv = kv if keeps else ()
    x, kv = lax.fori_loop(0, Ld, dense_layer, (x, kv))
    x, kv, stats = lax.fori_loop(0, cfg.n_layers("moe"), moe_layer,
                                 (x, kv, jnp.zeros((3,), jnp.int32)))
    return x, (kv if keeps else None), stats


# ---------------------------------------------------------------------------
# the three entry points
# ---------------------------------------------------------------------------


def forward(params: Params, tokens, cfg: LatentMoEConfig):
    """tokens [B, S] int32 -> logits [B, S, V] float32.  No state kept:
    the tests' oracle, not a fast path."""
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    x, _, _ = _stack(params, x, cfg)
    return _logits(x, params["ln_f"], params["head"], cfg.rms_norm_eps)


def prefill_request(params: Params, prompt, cfg: LatentMoEConfig,
                    cache_len: int):
    """Prefill ONE request.  ``prompt``: [S0] int32.  Returns (next-token
    logits [V] float32, the request's state: the slot kinds of
    ``init_state`` for one slot, latents, rotated keys and index keys at
    rows [0, S0) and zero past them)."""
    x = params["embed"].astype(cfg.compute_dtype)[prompt[None]]
    x, kv, _ = _stack(params, x, cfg, _lanes(init_state(cfg, 1, cache_len)))
    return _logits(x[:, -1:], params["ln_f"], params["head"],
                   cfg.rms_norm_eps)[0, 0], _slots(kv)


def decode_step(params: Params, tok, pos, state: State,
                cfg: LatentMoEConfig):
    """One continuous-batching step: embed ``tok`` [B], run each slot one
    token on from its own lanes at its own ``pos`` [B] (the absorbed
    form).  Returns (next-token logits [B, V] float32, the state updated
    in place when donated).  Rows never mix: nothing is dropped, so a
    slot's output depends on its own lanes alone (with a share of the
    experts: this chip's part of it)."""
    x = params["embed"].astype(cfg.compute_dtype)[tok[:, None]]
    x, kv, stats = _stack(params, x, cfg, _lanes(state), pos)
    L, Lm = cfg.num_hidden_layers, cfg.n_layers("moe")
    add = dict(zip(MOE_COUNTERS, (*stats.astype(jnp.uint32), jnp.uint32(Lm))))
    live = pos > 0
    if cfg.experts_held is not None:    # the live pairs no group here took
        add[ABSENT_COUNTER] = (Lm * cfg.num_experts_per_tok
                               * jnp.sum(live) - stats[0])
    if cfg.index_topk is not None:
        written = jnp.where(live, pos + 1, 0)
        add[INDEX_COUNTERS[0]] = L * jnp.sum(written)
        add[INDEX_COUNTERS[1]] = L * jnp.sum(
            jnp.minimum(written, cfg.index_topk))
    cache_len = kv[0].shape[2]
    counters = count_attention_reads(
        add_counters(state["counters"], add), pos, cache_len, L,
        block_for(cache_len, shared=True))
    return (_logits(x, params["ln_f"], params["head"],
                    cfg.rms_norm_eps)[:, 0],
            {**_slots(kv), "counters": counters})
