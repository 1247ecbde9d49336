"""A decoder whose every layer is an OPERATOR and a FEED-FORWARD, each
chosen on its own by depth: the operator by a list of kinds (a gated
short convolution, ``conv``, or grouped-query attention with a norm a
head and a rotation, ``full_attention``), the feed-forward dense below
``num_dense_layers`` and routed experts from there on.  The serving path
of the ``lfm2_moe`` line (``LiquidAI/LFM2-8B-A1B``'s ``config.json`` gives
every size, under the names used here).

``forward`` (the tests' oracle), ``prefill_request`` and ``decode_step``
are built from ONE function an operator kind, each taking optional state
in and giving state out.  Training it is not supported (the grouped
product has no backward pass written for it).

* Block ``l``: ``h <- h + op_l(RMSNorm(h))``, then ``h <- h +
  ff_l(RMSNorm(h))``; after the last a final RMSNorm and the head, the
  embedding transposed.
* ``conv``, the gated short convolution, no recurrence at all::

      [B | C | x] = u W_in               three parts of hidden_size, no bias
      z_t = B_t x_t
      c_t = sum_j w_j z_{t - (K - 1) + j}      depthwise, causal, K =
                                               conv_L_cache, no activation
      out = (C_t c_t) W_out

  *One token* keeps ``z``'s last ``K - 1`` rows, the slot's whole memory
  of the past in such a layer; *a prompt* runs the same sum over its rows
  from a window of zeros (``layers._causal_conv`` both times).  The sum
  is float32; ``z``, the gate and the kept window are ``compute_dtype``.
* ``full_attention``: ``layers._grouped_attention`` with its norm a head
  on q and k and its rotation, ``num_attention_heads`` on
  ``num_key_value_heads`` of ``hidden_size / num_attention_heads``, the
  caches with a position's key/value heads side by side (a head of 64
  alone would be padded to the chip's 128 lanes).  A step reads each
  slot's lanes as far as the slot has written them: ``layers.lane_reader``
  makes the list of blocks once a step and every attention layer's read is
  the kernel ``ops/pallas_decode_attention.py:decode_attention`` on the
  stacked caches as they lie, each query row in its own head's place of a
  position's ``KVH HD``.
* Dense feed-forward: ``layers._dense_ffn``, width ``intermediate_size``.
* Routed feed-forward: ``experts.route`` (sigmoid scores, a bias that
  selects only, normalised weights) and ``experts.routed_ffn`` in its
  gated form over ALL ``num_experts`` experts, no shared one.  The stacks
  are held ``experts.padded_width(moe_intermediate_size)`` wide (zeros
  past the published width: whole tiles of the grouped product); with 8
  (row, expert) pairs an expert or more, up to 1024 pairs (a served
  batch's step, a short prompt), the three products of a layer run as one
  kernel that stops at the published width
  (``ops/pallas_routed_ffn.py``; ``experts.one_kernel``).
* The stack: layers of a kind are stacked on a leading axis
  (``params["conv"]``, ``params["attn"]``, ``params["dense"]``,
  ``params["moe"]``; each holds the gain of the norm ahead of it) and
  laid out one after another at static indices.  The routed experts stay
  in their stack (``routed_ffn`` indexes the layer inside the grouped
  product).

State of a served batch (``init_state``)::

    {"kv": (k, v)          [La, B, cache_len, KVH HD]   compute_dtype
     "recurrent": (conv,)  [Lc, K - 1, B, hidden_size]  compute_dtype
     "counters": {...}     uint32 scalars, summed on the device}

A request's state (``prefill_request``) is the slot kinds with B = 1.  A
slot whose position is 0 is free (``DecodeEngine.clear``): its row is kept
out of the routing; what a step writes into its window and its lane the
next install overwrites.  The module omits what
``serving/decode.py:MODELS`` lets it: no sharding of this state is
written, and the weights come in ``param_dtype``, which is for the caller
to choose.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models import experts
from horovod_tpu.models.layers import (ATTN_COUNTERS, _at, _causal_conv,
                                       _dense_ffn, _grouped_attention,
                                       _logits, _put, _rmsnorm,
                                       add_counters, count_lane_reads,
                                       lane_reader)

Params = Dict[str, Any]
State = Dict[str, Any]

OPERATORS = {"conv": "conv", "full_attention": "attn"}
LFM2_LAYER_TYPES = tuple(
    "full_attention" if l in (2, 6, 10, 14, 18, 21) else "conv"
    for l in range(24))


@dataclass(frozen=True)
class ConvMoEConfig:
    """The published keys, under their published names."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = LFM2_LAYER_TYPES
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    num_experts: int = 32
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    # Positions a served request may reach (the server's default cache).
    max_seq_len: int = 2048
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - set(OPERATORS):
            raise ValueError("layer_types: conv or full_attention a layer")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide hidden_size, key/value "
                             "heads the query heads")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("the dense layers are the first of the layers")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("more experts a token than experts")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(operator, feed-forward) of each layer, as the stacks' names."""
        return tuple((OPERATORS[t], "dense" if l < self.num_dense_layers
                      else "moe") for l, t in enumerate(self.layer_types))

    def n_layers(self, kind: str) -> int:
        return sum(kind in pair for pair in self.layer_kinds)


def counter_names(cfg: ConvMoEConfig) -> Tuple[str, ...]:
    """The device counters ``cfg``'s state holds."""
    return experts.MOE_COUNTERS + (experts.FUSED_COUNTER,) + ATTN_COUNTERS


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init(rng, cfg: ConvMoEConfig) -> Params:
    """Matrices normal(0, 0.02), the output projections (the
    convolution's, attention o, every feed-forward's and expert's down)
    scaled by 1/sqrt(2 L); the convolution uniform in
    +-1/sqrt(conv_L_cache); gains one; the router's selection bias
    normal(0, 0.01) in float32.  Every other leaf is held in
    ``param_dtype``; the embedding is the head; the experts' stacks are
    ``experts.padded_width`` wide, zeros past the published width."""
    D, V = cfg.hidden_size, cfg.vocab_size
    H, KVH, HD = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    F, Fe, E, K = (cfg.intermediate_size, cfg.moe_intermediate_size,
                   cfg.num_experts, cfg.conv_L_cache)
    Lc, La, Ld, Le = (cfg.n_layers(k) for k in ("conv", "attn", "dense",
                                                "moe"))
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(rng, 24))
    dt = cfg.param_dtype

    def normal(shape, s, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * s).astype(dtype)

    def held(w, axis):      # zeros past the published width of ``axis``
        pad = [(0, 0)] * w.ndim
        pad[axis] = (0, experts.padded_width(Fe) - Fe)
        return jnp.pad(w, pad)

    bound = 1.0 / math.sqrt(K)
    conv = {
        "ln": jnp.ones((Lc, D), dt),
        "in_proj": normal((Lc, D, 3 * D), std),
        "conv_w": jax.random.uniform(next(keys), (Lc, K, D), jnp.float32,
                                     -bound, bound).astype(dt),
        "out_proj": normal((Lc, D, D), out_std)}
    attn = {
        "ln": jnp.ones((La, D), dt),
        "wq": normal((La, D, H, HD), std),
        "wk": normal((La, D, KVH, HD), std),
        "wv": normal((La, D, KVH, HD), std),
        "q_norm": jnp.ones((La, HD), dt),
        "k_norm": jnp.ones((La, HD), dt),
        "wo": normal((La, H, HD, D), out_std)}
    dense = {
        "ln": jnp.ones((Ld, D), dt),
        "w_gate": normal((Ld, D, F), std),
        "w_in": normal((Ld, D, F), std),
        "w_out": normal((Ld, F, D), out_std)}
    moe = {
        "ln": jnp.ones((Le, D), dt),
        "router": normal((Le, D, E), std),
        "router_bias": normal((Le, E), 0.01, jnp.float32),
        "w_gate": held(normal((Le, E, D, Fe), std), 3),
        "w_in": held(normal((Le, E, D, Fe), std), 3),
        "w_out": held(normal((Le, E, Fe, D), out_std), 2)}
    return {"embed": normal((V, D), std), "conv": conv, "attn": attn,
            "dense": dense, "moe": moe, "ln_f": jnp.ones((D,), dt)}


# ---------------------------------------------------------------------------
# the gated short convolution: ONE function, a prompt's rows or one step
# ---------------------------------------------------------------------------


def _short_conv(u, lp, dtype, kept=None):
    """u: [B, S, D].  ``kept`` None: the sequences start here (a window of
    zeros); else [K - 1, B, D], the last ``z`` rows before u's first.
    Returns (out [B, S, D], the window's last K - 1 rows).  Inside, time
    is the leading axis."""
    B, S, D = u.shape
    K = lp["conv_w"].shape[0]
    if kept is None:
        kept = jnp.zeros((K - 1, B, D), dtype)
    bcx = jnp.einsum("bsd,de->sbe", u, lp["in_proj"].astype(dtype))
    gate_in, gate_out, x = (bcx[..., i * D:(i + 1) * D] for i in range(3))
    c, window = _causal_conv(gate_in * x, kept, lp["conv_w"],
                             jnp.zeros((D,), jnp.float32))
    y = (gate_out.astype(jnp.float32) * c).astype(dtype)
    return (jnp.einsum("sbe,ed->bsd", y, lp["out_proj"].astype(dtype)),
            window[S:])


# ---------------------------------------------------------------------------
# the stack, and the state it carries
# ---------------------------------------------------------------------------


def init_state(cfg: ConvMoEConfig, max_batch: int, cache_len: int) -> State:
    """Zeros for ``max_batch`` slots; see the module docstring."""
    lane = (cfg.n_layers("attn"), max_batch, cache_len,
            cfg.num_key_value_heads * cfg.head_dim)
    return {
        "kv": (jnp.zeros(lane, cfg.compute_dtype),
               jnp.zeros(lane, cfg.compute_dtype)),
        "recurrent": (jnp.zeros(
            (cfg.n_layers("conv"), cfg.conv_L_cache - 1, max_batch,
             cfg.hidden_size), cfg.compute_dtype),),
        "counters": {name: jnp.zeros((), jnp.uint32)
                     for name in counter_names(cfg)}}


# The axis of each slot-kind leaf that the slots lie along: the convolution
# keeps its window's rows ahead of them.
SLOT_AXES = {"kv": (1, 1), "recurrent": (2,)}

_EXPERTS = ("w_gate", "w_in", "w_out")


def _stack(params: Params, x, cfg: ConvMoEConfig, state: Optional[State],
           pos=None):
    """x [B, S, D] through every layer.  ``pos`` None: the sequences
    start here (position 0, a window of zeros); ``state``, if given,
    receives what they end in (keys and values at rows [0, S), the last
    rows of each window).  ``pos`` [B]: one token a slot continuing
    ``state``, which is read and written at its layer; rows at position 0
    are free slots, routed nowhere.  Returns (x, the state's slot kinds,
    the routing's stats [3] summed over the expert layers)."""
    dtype, eps = cfg.compute_dtype, cfg.norm_eps
    start = pos is None
    carries = state is not None
    kv = state["kv"] if carries else ()
    (kept,) = state["recurrent"] if carries else (None,)
    live = None if start else pos > 0
    read = None if start else lane_reader("merged", kv[0], pos)
    B, S, D = x.shape
    small = {k: v for k, v in params["moe"].items() if k not in _EXPERTS}
    routed = {k: params["moe"][k] for k in _EXPERTS}
    stats = jnp.zeros((3,), jnp.int32)
    seen = dict.fromkeys(("conv", "attn", "dense", "moe"), 0)
    for op, ff in cfg.layer_kinds:
        l = seen[op]
        seen[op] += 1
        lp = _at(params[op], l)
        y = _rmsnorm(x, lp["ln"], eps)
        if op == "conv":
            with jax.named_scope("short_conv"):
                y, window = _short_conv(y, lp, dtype,
                                        None if start else kept[l])
            if carries:
                kept = _put(kept, l, window)
        else:
            with jax.named_scope("grouped_attention"):
                y, new = _grouped_attention(
                    y, lp, dtype, None if start else (*kv, l, pos, read),
                    layout="merged", qk_norm=eps, rope=cfg.rope_theta)
            if not start:
                kv = new
            elif carries:
                at = (l, 0, 0, 0)
                kv = tuple(lax.dynamic_update_slice(lane, rows[None], at)
                           for lane, rows in zip(kv, new))
        x = x + y
        l = seen[ff]
        seen[ff] += 1
        if ff == "dense":
            lp = _at(params["dense"], l)
            y = _dense_ffn(_rmsnorm(x, lp["ln"], eps), lp, dtype)
        else:
            lp = _at(small, l)
            rows = _rmsnorm(x, lp["ln"], eps).reshape(B * S, D)
            chosen, weights = experts.route(
                rows, lp["router"], lp["router_bias"],
                cfg.num_experts_per_tok, cfg.routed_scaling_factor)
            with jax.named_scope("routed_ffn"):
                y, new = experts.routed_ffn(
                    rows, routed, l, chosen, weights, dtype, live,
                    width=cfg.moe_intermediate_size)
            stats = stats + new
            y = y.reshape(B, S, D)
        x = x + y
    return (x, {"kv": kv, "recurrent": (kept,)} if carries else None, stats)


# ---------------------------------------------------------------------------
# the three entry points
# ---------------------------------------------------------------------------


def forward(params: Params, tokens, cfg: ConvMoEConfig):
    """tokens [B, S] int32 -> logits [B, S, V] float32.  No state kept:
    the tests' oracle, not a fast path."""
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    x, _, _ = _stack(params, x, cfg, None)
    return _logits(x, params["ln_f"], params["embed"], cfg.norm_eps)


def prefill_request(params: Params, prompt, cfg: ConvMoEConfig,
                    cache_len: int):
    """Prefill ONE request.  ``prompt``: [S0] int32.  Returns (next-token
    logits [V] float32, the request's state: the slot kinds of
    ``init_state`` for one slot, keys and values at rows [0, S0) and zero
    past them, each window's last rows)."""
    x = params["embed"].astype(cfg.compute_dtype)[prompt[None]]
    x, slots, _ = _stack(params, x, cfg, init_state(cfg, 1, cache_len))
    return (_logits(x[:, -1:], params["ln_f"], params["embed"],
                    cfg.norm_eps)[0, 0], slots)


def decode_step(params: Params, tok, pos, state: State, cfg: ConvMoEConfig):
    """One continuous-batching step: embed ``tok`` [B], run each slot one
    token on from its own ``state`` at its own ``pos`` [B].  Returns
    (next-token logits [B, V] float32, the state updated in place when
    donated).  Rows never mix: nothing is dropped, so a slot's output
    depends on its own state alone."""
    x = params["embed"].astype(cfg.compute_dtype)[tok[:, None]]
    x, slots, stats = _stack(params, x, cfg, state, pos)
    turns = cfg.n_layers("moe")
    fused = experts.one_kernel(
        params["moe"], tok.shape[0] * cfg.num_experts_per_tok)
    counters = add_counters(state["counters"], {
        **dict(zip(experts.MOE_COUNTERS,
                   (*stats.astype(jnp.uint32), jnp.uint32(turns)))),
        experts.FUSED_COUNTER: jnp.uint32(turns if fused else 0)})
    counters = count_lane_reads(counters, pos, "merged", state["kv"][0])
    return (_logits(x, params["ln_f"], params["embed"], cfg.norm_eps)[:, 0],
            {**slots, "counters": counters})
