"""A decoder with latent attention (MLA) and routed experts beside a
shared one: the serving path of the ``glm4_moe_lite`` / DeepSeek-V3 line
(``zai-org/GLM-4.7-Flash``'s ``config.json`` gives every size).

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
* Feed-forward: the first ``first_k_dense_replace`` layers the dense
  gated one; the others ``experts.routed_ffn`` (top-k of a biased
  sigmoid score, nothing dropped) plus a shared expert.
* The stack follows models/jamba.py: layers of a kind stacked on a
  leading axis, each run ONE ``fori_loop`` with all state as its carry
  and the layer indexed dynamically.

State of a served batch (``init_state``)::

    {"kv": (c, k_r)     [L, B, cache_len, kv_lora_rank],
                        [L, B, cache_len, qk_rope_head_dim]  compute_dtype
     "counters": {...}  six uint32 scalars, summed on the device by
                        ``decode_step``: see ``COUNTERS``}

A request's state (``prefill_request``) is the ``"kv"`` part with B = 1.
A slot whose position is 0 is free (``DecodeEngine.clear``): its row is
kept out of the routing, so a free slot pulls no expert's weights through
the chip, and out of the counters.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models import experts
from horovod_tpu.models.jamba import _at
from horovod_tpu.models.transformer import (ATTN_COUNTERS, _dense_ffn,
                                            _rmsnorm, _rope,
                                            count_attention_reads,
                                            vocab_projection)
from horovod_tpu.ops.pallas_decode_attention import (block_for,
                                                     decode_attention,
                                                     work_list)

Params = Dict[str, Any]
State = Dict[str, Any]

# What ``decode_step`` adds to ``state["counters"]`` a step, over the
# expert layers: (row, expert) pairs routed, experts with at least one
# row, the fullest expert's rows, and the expert layers stepped; and, over
# all layers, what the attention read of the lanes (ATTN_COUNTERS).
MOE_COUNTERS = ("hvd_moe_rows_routed_total", "hvd_moe_experts_touched_total",
                "hvd_moe_max_expert_rows_total", "hvd_moe_layer_turns_total")
COUNTERS = MOE_COUNTERS + ATTN_COUNTERS


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

    def __post_init__(self):
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace counts leading layers")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("more experts a token than experts")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary dims turn in pairs")

    def n_layers(self, kind: str) -> int:
        dense = self.first_k_dense_replace
        return dense if kind == "dense" else self.num_hidden_layers - dense


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init(rng, cfg: LatentMoEConfig) -> Params:
    """Matrices normal(0, 0.02), output projections scaled by 1/sqrt(2 L),
    gains one, the router's selection bias normal(0, 0.01) in float32.
    Every other leaf is held in ``param_dtype``."""
    D, V = cfg.hidden_size, cfg.vocab_size
    F, Fe = cfg.intermediate_size, cfg.moe_intermediate_size
    Fs = cfg.n_shared_experts * Fe
    H, E = cfg.num_attention_heads, cfg.n_routed_experts
    Rq, Rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    Ld, Lm = cfg.n_layers("dense"), cfg.n_layers("moe")
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(rng, 40))
    dt = cfg.param_dtype

    def normal(shape, s, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * s).astype(dtype)

    def attention(n):
        return {"ln1": jnp.ones((n, D), dt),
                "wq_a": normal((n, D, Rq), std),
                "q_norm": jnp.ones((n, Rq), dt),
                "wq_b": normal((n, Rq, H, nope + rope), std),
                "wkv_a": normal((n, D, Rkv + rope), std),
                "kv_norm": jnp.ones((n, Rkv), dt),
                "w_uk": normal((n, Rkv, H, nope), std),
                "w_uv": normal((n, Rkv, H, vd), std),
                "wo": normal((n, H, vd, D), out_std),
                "ln2": jnp.ones((n, D), dt)}

    dense = {**attention(Ld), "w_in": normal((Ld, D, F), std),
             "w_gate": normal((Ld, D, F), std),
             "w_out": normal((Ld, F, D), out_std)}
    moe = {**attention(Lm), "router": normal((Lm, D, E), std),
           "router_bias": normal((Lm, E), 0.01, jnp.float32),
           "w_in": normal((Lm, E, D, Fe), std),
           "w_gate": normal((Lm, E, D, Fe), std),
           "w_out": normal((Lm, E, Fe, D), out_std),
           "shared_in": normal((Lm, D, Fs), std),
           "shared_gate": normal((Lm, D, Fs), std),
           "shared_out": normal((Lm, Fs, D), out_std)}
    return {"embed": normal((V, D), std), "dense": dense, "moe": moe,
            "ln_f": jnp.ones((D,), dt), "head": normal((V, D), std)}


# ---------------------------------------------------------------------------
# attention: ONE function, two forms
# ---------------------------------------------------------------------------


def _attention(x, lp, cfg: LatentMoEConfig, cache=None):
    """x: [B, S, D], normalised.

    ``cache`` None, the expanded form: the S positions start at 0 and
    attend among themselves; returns (out, (c, k_r)), the latents
    [B, S, kv_lora_rank] and rotated keys [B, S, rope] for whoever keeps
    them.  ``cache`` = (cs, krs, layer, pos, work), the stacked caches
    [L, B, Smax, .], the position [B] of THIS token (S = 1) and the
    kernel's ``work_list`` of those positions, the
    absorbed form: writes the B new rows at [layer, b, pos[b]] in place
    and attends lane ``layer``, each slot's as far as its ``pos``;
    returns (out, (cs, krs))."""
    dtype, f32 = cfg.compute_dtype, jnp.float32
    eps, theta = cfg.rms_norm_eps, cfg.rope_theta
    B, S, _ = x.shape
    Rkv, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    scale = 1.0 / math.sqrt(nope + cfg.qk_rope_head_dim)
    own = None if cache is None else cache[3][:, None]          # [B, 1]
    c_q = _rmsnorm(jnp.einsum("bsd,dr->bsr", x, lp["wq_a"].astype(dtype)),
                   lp["q_norm"], eps)
    q = jnp.einsum("bsr,rhk->bshk", c_q, lp["wq_b"].astype(dtype))
    q_n, q_r = q[..., :nope], _rope(q[..., nope:], theta, own)
    ckr = jnp.einsum("bsd,dr->bsr", x, lp["wkv_a"].astype(dtype))
    c = _rmsnorm(ckr[..., :Rkv], lp["kv_norm"], eps)
    k_r = _rope(ckr[..., None, Rkv:], theta, own)[:, :, 0]      # [B, S, rope]
    w_uk, w_uv = lp["w_uk"].astype(dtype), lp["w_uv"].astype(dtype)
    if cache is None:
        k_n = jnp.einsum("bsc,chk->bshk", c, w_uk)
        v = jnp.einsum("bsc,chk->bshk", c, w_uv)
        T = min(cfg.attn_block, S)
        blocks = []
        for lo in range(0, S, T):           # static: a program a length
            hi = min(lo + T, S)
            scores = (
                jnp.einsum("bshk,bthk->bhst", q_n[:, lo:hi], k_n[:, :hi],
                           preferred_element_type=f32)
                + jnp.einsum("bshk,btk->bhst", q_r[:, lo:hi], k_r[:, :hi],
                             preferred_element_type=f32)) * scale
            valid = (jnp.arange(hi)[None, :]
                     <= jnp.arange(lo, hi)[:, None])            # [s, t]
            probs = jax.nn.softmax(jnp.where(valid, scores, -1e30), axis=-1)
            blocks.append(jnp.einsum("bhst,bthk->bshk", probs.astype(dtype),
                                     v[:, :hi]))
        ctx = jnp.concatenate(blocks, axis=1)                   # [B, S, H, v]
        kept = (c, k_r)
    else:
        cs, krs, layer, pos, work = cache
        rows = jnp.arange(B)
        cs = cs.at[layer, rows, pos].set(c[:, 0])
        # Row by row, not one scatter: XLA keeps rows of 64 values with
        # the positions minor in HBM, a scatter wants its rows minor, and
        # the two layouts of the whole array are a copy each way a step.
        for b in range(B):
            krs = lax.dynamic_update_slice(
                krs, k_r[b][None, None], (layer, b, pos[b], 0))
        q_c = jnp.einsum("bhk,chk->bhc", q_n[:, 0], w_uk)       # absorbed
        # The latents are keys and values both, fetched once a block;
        # the rotary keys are the keys' second part, read as they lie.
        ctx_c = decode_attention(
            (q_c, q_r[:, 0]), (cs, krs.swapaxes(2, 3)), None, layer, pos,
            scale=scale, work=work, positions_last=(False, True))
        ctx = jnp.einsum("bhc,chk->bhk", ctx_c, w_uv)[:, None]  # [B, 1, H, v]
        kept = (cs, krs)
    return jnp.einsum("bshk,hkd->bsd", ctx, lp["wo"].astype(dtype)), kept


# ---------------------------------------------------------------------------
# the stack, and the state it carries
# ---------------------------------------------------------------------------


def init_state(cfg: LatentMoEConfig, max_batch: int, cache_len: int
               ) -> State:
    """Zeros for ``max_batch`` slots; see the module docstring."""
    L = cfg.num_hidden_layers
    return {
        "kv": (jnp.zeros((L, max_batch, cache_len, cfg.kv_lora_rank),
                         cfg.compute_dtype),
               jnp.zeros((L, max_batch, cache_len, cfg.qk_rope_head_dim),
                         cfg.compute_dtype)),
        "counters": {name: jnp.zeros((), jnp.uint32) for name in COUNTERS}}


_EXPERTS = ("w_in", "w_gate", "w_out")


def _stack(params: Params, x, cfg: LatentMoEConfig, kv=None, pos=None):
    """x [B, S, D] through every layer.  ``pos`` None: the sequences
    start here (position 0); ``kv``, if given, receives their latents and
    rotated keys at rows [0, S).  ``pos`` [B]: one token a slot
    continuing ``kv``, which is read and written at its layer; rows at
    position 0 are free slots and are routed nowhere.  Returns (x, kv,
    the routing's stats [3] summed over the expert layers)."""
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

    def attend(h, lp, kv, at):
        y = _rmsnorm(h, lp["ln1"], eps)
        if start:
            y, (c, k_r) = _attention(y, lp, cfg)
            if keeps:
                kv = (lax.dynamic_update_slice(kv[0], c[None], (at, 0, 0, 0)),
                      lax.dynamic_update_slice(kv[1], k_r[None],
                                               (at, 0, 0, 0)))
        else:
            y, kv = _attention(y, lp, cfg, (*kv, at, pos, work))
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
            cfg.routed_scaling_factor)
        y, new = experts.routed_ffn(rows, routed, l, chosen, weights, dtype,
                                    live)
        shared = _dense_ffn(u, {"w_in": lp["shared_in"],
                                "w_gate": lp["shared_gate"],
                                "w_out": lp["shared_out"]}, dtype)
        return h + y.reshape(B, S, D) + shared, kv, stats + new

    kv = kv if keeps else ()
    x, kv = lax.fori_loop(0, Ld, dense_layer, (x, kv))
    x, kv, stats = lax.fori_loop(0, cfg.n_layers("moe"), moe_layer,
                                 (x, kv, jnp.zeros((3,), jnp.int32)))
    return x, (kv if keeps else None), stats


def _logits(params: Params, x, cfg: LatentMoEConfig):
    return vocab_projection(_rmsnorm(x, params["ln_f"], cfg.rms_norm_eps),
                            params["head"])


# ---------------------------------------------------------------------------
# the three entry points
# ---------------------------------------------------------------------------


def forward(params: Params, tokens, cfg: LatentMoEConfig):
    """tokens [B, S] int32 -> logits [B, S, V] float32.  No state kept:
    the tests' oracle, not a fast path."""
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    x, _, _ = _stack(params, x, cfg)
    return _logits(params, x, cfg)


def prefill_request(params: Params, prompt, cfg: LatentMoEConfig,
                    cache_len: int):
    """Prefill ONE request.  ``prompt``: [S0] int32.  Returns (next-token
    logits [V] float32, the request's state: the ``"kv"`` part of
    ``init_state`` for one slot, latents and rotated keys at rows [0, S0)
    and zero past them)."""
    x = params["embed"].astype(cfg.compute_dtype)[prompt[None]]
    x, kv, _ = _stack(params, x, cfg, init_state(cfg, 1, cache_len)["kv"])
    return _logits(params, x[:, -1:], cfg)[0, 0], {"kv": kv}


def install_request(state: State, slot, request: State) -> State:
    """Write a request's lanes over slot ``slot``'s, whole, so that
    nothing of the slot's last tenant is left.  ``state`` donated, the
    writes are in place; the counters pass through."""
    (cs, krs), (c1, kr1) = state["kv"], request["kv"]
    return {"kv": (lax.dynamic_update_slice(cs, c1, (0, slot, 0, 0)),
                   lax.dynamic_update_slice(krs, kr1, (0, slot, 0, 0))),
            "counters": state["counters"]}


def decode_step(params: Params, tok, pos, state: State,
                cfg: LatentMoEConfig):
    """One continuous-batching step: embed ``tok`` [B], run each slot one
    token on from its own lanes at its own ``pos`` [B] (the absorbed
    form).  Returns (next-token logits [B, V] float32, the state updated
    in place when donated).  Rows never mix: nothing is dropped, so a
    slot's output depends on its own lanes alone."""
    x = params["embed"].astype(cfg.compute_dtype)[tok[:, None]]
    x, kv, stats = _stack(params, x, cfg, state["kv"], pos)
    add = (*stats.astype(jnp.uint32),
           jnp.uint32(cfg.n_layers("moe")))
    counters = {**state["counters"],
                **{name: state["counters"][name] + a
                   for name, a in zip(MOE_COUNTERS, add)}}
    cache_len = kv[0].shape[2]
    counters = count_attention_reads(
        counters, pos, cache_len, cfg.num_hidden_layers,
        block_for(cache_len, shared=True))
    return _logits(params, x, cfg)[:, 0], {"kv": kv, "counters": counters}


# The state's sharding: none is written (experts under ep, heads of the
# absorbed form under tp), so serving/decode.py refuses a mesh.
STATE_SPEC = None


def serving_params(params: Params, cfg: LatentMoEConfig) -> Params:
    """``params`` as a serving engine holds them: as given.  The weights
    come in ``param_dtype``, which is for the caller to choose."""
    return params
