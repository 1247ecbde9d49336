"""Jamba: Mamba-1 layers beside attention, one period of two layer kinds.

The model of ``ai21labs/AI21-Jamba2-3B``'s ``config.json``: every
``attn_layer_period``-th layer (at ``attn_layer_offset``) is attention,
the others are Mamba-1 mixers with Jamba's three inner RMSNorms; every
layer ends in the dense gated feed-forward (``num_experts`` is 1).  This
file is the serving path's: ``forward`` (the tests' oracle),
``prefill_request`` and ``decode_step``, all three built from ONE
attention function (``layers._grouped_attention``) and ONE mixer
function, each taking optional state in and giving state out.  Training
it is not supported (the chunked scan has
no backward pass written for it).

* Attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads, causal softmax, **no positional
  encoding** (the Mamba layers carry order).  A step reads a slot's lanes
  as far as the slot has written them (``layers.lane_reader``: with ONE
  key/value head, the decode kernel's shared-key form).
* Mixer: ``(x, z) = split(u W_in)``; a causal depthwise convolution of
  width ``mamba_d_conv`` and SiLU give ``c``; ``(delta, B, C) =
  split(c W_x)``, each RMS-normed; ``Delta = softplus(delta W_dt + b)``;
  ``h_t = exp(Delta_t A) h_{t-1} + (Delta_t c_t) B_t``; ``y_t = h_t C_t +
  D c_t``; out ``= (y silu(z)) W_out``.  A whole prompt runs as a scan
  over chunks of ``scan_chunk`` steps (only one chunk's ``[T, N, d_inner]``
  decays exist at a time); one token is the same recurrence, one step.
  Recurrence, ``Delta``, ``exp`` and ``softplus`` are float32; matmul
  operands are ``compute_dtype``.
* The stack: layers of a kind are stacked on a leading axis
  (``params["mamba"]``, ``params["attn"]``) and each run of like layers
  is one ``fori_loop`` whose body indexes its layer's weights and state
  dynamically: one compiled body a run, and all state is the loop's
  CARRY (PR 25: state as ``xs``/``ys`` copies all of it every step).

State of a served batch (``init_state``), by kind, laid out so that the
chip's (8, 128) tiles hold no padding::

    {"kv": (k, v)                [La, B, KVH, cache_len, HD]  compute_dtype
     "recurrent": (ssm, conv)    [Lm, B, N, d_inner] float32,
                                 [Lm, d_conv - 1, B, d_inner] compute_dtype
     "counters": {...}           uint32 scalars, summed on the device:
                                 ``layers.ATTN_COUNTERS``}

A request's state (``prefill_request``) is the slot kinds with B = 1.
The module omits what ``serving/decode.py:MODELS`` lets it: no sharding
of this state is written (recurrent state under tp), and the weights come
in ``param_dtype``, which is for the caller to choose.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models.layers import (ATTN_COUNTERS, _at, _causal_conv,
                                       _dense_ffn, _grouped_attention,
                                       _logits, _put, _rmsnorm,
                                       count_lane_reads, lane_reader)

Params = Dict[str, Any]
State = Dict[str, Any]


@dataclass(frozen=True)
class JambaConfig:
    """The published keys, under their published names."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    rms_norm_eps: float = 1e-6
    # Positions a served request may reach (the server's default cache).
    max_seq_len: int = 2048
    # Steps of the prompt scan whose decays are held at once.
    scan_chunk: int = 64
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.rms_norm_eps != 1e-6:
            raise ValueError("the shared _rmsnorm has eps 1e-6")
        if self.hidden_size % self.num_attention_heads or \
                self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide hidden_size, key/value "
                             "heads the query heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Hugging Face ``JambaConfig.layers_block_type``."""
        return tuple(
            "attn" if i % self.attn_layer_period == self.attn_layer_offset
            else "mamba" for i in range(self.num_hidden_layers))

    @property
    def runs(self) -> List[Tuple[str, int, int]]:
        """Runs of like layers in stack order: (kind, first, past-last),
        indices into that kind's stacked weights and state."""
        seen = {"mamba": 0, "attn": 0}
        out: List[Tuple[str, int, int]] = []
        for kind in self.layer_kinds:
            if out and out[-1][0] == kind:
                out[-1] = (kind, out[-1][1], out[-1][2] + 1)
            else:
                out.append((kind, seen[kind], seen[kind] + 1))
            seen[kind] += 1
        return out

    def n_layers(self, kind: str) -> int:
        return self.layer_kinds.count(kind)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init(rng, cfg: JambaConfig) -> Params:
    """Matrices normal(0, 0.02), output projections scaled by
    1/sqrt(2 L); the mixer's own leaves by Mamba's rule: ``A_log =
    log(1..N)`` a channel, ``D = 1``, ``b_dt`` the inverse softplus of a
    log-uniform step in [1e-3, 1e-1], the convolution uniform in
    +-1/sqrt(d_conv); gains one.  Every leaf is made in float32 and held
    in ``param_dtype``."""
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H, KVH, HD = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    Di, N, K, R = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                   cfg.mamba_dt_rank)
    Lm, La = cfg.n_layers("mamba"), cfg.n_layers("attn")
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(rng, 20))
    dt = cfg.param_dtype

    def normal(shape, s):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * s).astype(dt)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def ones(shape):
        return jnp.ones(shape, dt)

    def ffn(L):
        return {"ln2": ones((L, D)), "w_in": normal((L, D, F), std),
                "w_gate": normal((L, D, F), std),
                "w_out": normal((L, F, D), out_std)}

    step = jnp.exp(uniform((Lm, Di), math.log(1e-3), math.log(1e-1)))
    bound = 1.0 / math.sqrt(K)
    mamba = {
        "ln1": ones((Lm, D)),
        "in_proj": normal((Lm, D, 2 * Di), std),
        "conv_w": uniform((Lm, K, Di), -bound, bound).astype(dt),
        "conv_b": uniform((Lm, Di), -bound, bound).astype(dt),
        "x_proj": normal((Lm, Di, R + 2 * N), std),
        "dt_norm": ones((Lm, R)), "b_norm": ones((Lm, N)),
        "c_norm": ones((Lm, N)),
        "dt_proj": normal((Lm, R, Di), std),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
            (Lm, N, Di)).astype(dt),
        "d": ones((Lm, Di)),
        "out_proj": normal((Lm, Di, D), out_std),
        **ffn(Lm)}
    attn = {
        "ln1": ones((La, D)),
        "wq": normal((La, D, H, HD), std),
        "wk": normal((La, D, KVH, HD), std),
        "wv": normal((La, D, KVH, HD), std),
        "wo": normal((La, H, HD, D), out_std),
        **ffn(La)}
    return {"embed": normal((V, D), std), "mamba": mamba, "attn": attn,
            "ln_f": ones((D,))}


# ---------------------------------------------------------------------------
# the mixer: ONE function, state optional (the attention is layers.py's)
# ---------------------------------------------------------------------------


def _selective_scan(h, delta, c, b_in, c_out, a, chunk: int):
    """``h_t = exp(delta_t a) h_{t-1} + (delta_t c_t) b_t``, ``y_t = h_t .
    c_out_t``, over the leading (time) axis.  h: [B, N, Di]; delta, c:
    [S, B, Di]; b_in, c_out: [S, B, N]; a: [N, Di]; all float32.  Returns
    (y [S, B, Di], h after the last step).

    S > 1 scans chunks of ``chunk`` steps: a chunk's decays and inputs
    ([T, B, N, Di]) are made at once, its steps run in order, its outputs
    are read out at once.  A ragged tail is padded with ``delta = 0``
    steps, which leave ``h`` exactly as it was."""

    def terms(delta, c, b_in):
        return (jnp.exp(delta[..., None, :] * a),
                (delta * c)[..., None, :] * b_in[..., None])

    S = delta.shape[0]
    if S == 1:
        decay, inp = terms(delta[0], c[0], b_in[0])
        h = decay * h + inp
        return jnp.sum(h * c_out[0][..., None], axis=1)[None], h

    T = min(chunk, S)
    pad = -S % T

    def chunks(t):
        t = jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
        return t.reshape((-1, T) + t.shape[1:])

    def one_chunk(h, xs):
        delta, c, b_in, c_out = xs
        decay, inp = terms(delta, c, b_in)

        def step(h, t):
            h = t[0] * h + t[1]
            return h, h

        h, hs = lax.scan(step, h, (decay, inp))
        return h, jnp.sum(hs * c_out[..., None], axis=2)

    h, ys = lax.scan(one_chunk, h,
                     tuple(chunks(t) for t in (delta, c, b_in, c_out)))
    return ys.reshape((-1,) + ys.shape[2:])[:S], h


def _mamba_mixer(u, lp, cfg: JambaConfig, state=None):
    """The Mamba-1 mixer with Jamba's inner norms.  u: [B, S, D];
    ``state`` None (a sequence's start: zeros) or (ssm [B, N, Di] float32,
    conv [d_conv - 1, B, Di]: the last inputs of the convolution).
    Returns (out [B, S, D], the state after the last position).  One
    token against a carried state (S = 1) and a whole prompt (S > 1) are
    this same function; inside it time is the leading axis."""
    dtype = cfg.compute_dtype
    f32 = jnp.float32
    B, S, _ = u.shape
    Di, N, K, R = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                   cfg.mamba_dt_rank)
    if state is None:
        state = (jnp.zeros((B, N, Di), f32), jnp.zeros((K - 1, B, Di), dtype))
    ssm, conv = state
    xz = jnp.einsum("bsd,de->sbe", u, lp["in_proj"].astype(dtype))
    x, z = xz[..., :Di], xz[..., Di:]
    c, window = _causal_conv(x, conv, lp["conv_w"], lp["conv_b"])
    c = jax.nn.silu(c)
    dbc = jnp.einsum("sbe,er->sbr", c.astype(dtype),
                     lp["x_proj"].astype(dtype), preferred_element_type=f32)
    delta = _rmsnorm(dbc[..., :R], lp["dt_norm"])
    b_in = _rmsnorm(dbc[..., R:R + N], lp["b_norm"])
    c_out = _rmsnorm(dbc[..., R + N:], lp["c_norm"])
    delta = jax.nn.softplus(
        jnp.einsum("sbr,re->sbe", delta.astype(dtype),
                   lp["dt_proj"].astype(dtype), preferred_element_type=f32)
        + lp["dt_bias"].astype(f32))
    a = -jnp.exp(lp["a_log"].astype(f32))
    y, ssm = _selective_scan(ssm, delta, c, b_in, c_out, a, cfg.scan_chunk)
    y = (y + lp["d"].astype(f32) * c) * jax.nn.silu(z.astype(f32))
    out = jnp.einsum("sbe,ed->bsd", y.astype(dtype),
                     lp["out_proj"].astype(dtype))
    return out, (ssm, window[S:])


# ---------------------------------------------------------------------------
# the stack, and the state it carries
# ---------------------------------------------------------------------------


def init_state(cfg: JambaConfig, max_batch: int, cache_len: int) -> State:
    """Zeros for ``max_batch`` slots; see the module docstring."""
    La, Lm = cfg.n_layers("attn"), cfg.n_layers("mamba")
    lane = (La, max_batch, cfg.num_key_value_heads, cache_len, cfg.head_dim)
    return {
        "kv": (jnp.zeros(lane, cfg.compute_dtype),
               jnp.zeros(lane, cfg.compute_dtype)),
        "recurrent": (
            jnp.zeros((Lm, max_batch, cfg.mamba_d_state, cfg.d_inner),
                      jnp.float32),
            jnp.zeros((Lm, cfg.mamba_d_conv - 1, max_batch, cfg.d_inner),
                      cfg.compute_dtype)),
        "counters": {name: jnp.zeros((), jnp.uint32)
                     for name in ATTN_COUNTERS}}


# The axis of each slot-kind leaf that the slots lie along: the convolution
# state keeps its window's rows ahead of them.
SLOT_AXES = {"kv": (1, 1), "recurrent": (1, 2)}


def _stack(params: Params, x, cfg: JambaConfig, state: Optional[State],
           pos=None):
    """x [B, S, D] through every layer.  ``pos`` None: the sequences
    start here (position 0, zero state); ``state``, if given, receives
    what they end in (keys and values at rows [0, S), the recurrent
    state after row S - 1).  ``pos`` [B]: one token a slot continuing
    ``state``, which is read and written at its layer.  Returns (x,
    the state's slot kinds)."""
    dtype = cfg.compute_dtype
    start = pos is None
    carries = state is not None
    kv = state["kv"] if carries else ()
    rec = state["recurrent"] if carries else ()
    read = None if start else lane_reader("heads_first", kv[0], pos)

    def attn_layer(l, carry):
        h, kv = carry
        lp = _at(params["attn"], l)
        y = _rmsnorm(h, lp["ln1"])
        if start:
            y, (k, v) = _grouped_attention(y, lp, dtype)
            if carries:
                at = (l, 0, 0, 0, 0)
                kv = (lax.dynamic_update_slice(kv[0], k[None], at),
                      lax.dynamic_update_slice(kv[1], v[None], at))
        else:
            y, kv = _grouped_attention(y, lp, dtype, (*kv, l, pos, read))
        h = h + y
        return h + _dense_ffn(_rmsnorm(h, lp["ln2"]), lp, dtype), kv

    def mamba_layer(l, carry):
        h, rec = carry
        lp = _at(params["mamba"], l)
        y, new = _mamba_mixer(_rmsnorm(h, lp["ln1"]), lp, cfg,
                              None if start else _at(rec, l))
        if carries:
            rec = (_put(rec[0], l, new[0]), _put(rec[1], l, new[1]))
        h = h + y
        return h + _dense_ffn(_rmsnorm(h, lp["ln2"]), lp, dtype), rec

    for kind, first, last in cfg.runs:
        if kind == "attn":
            x, kv = lax.fori_loop(first, last, attn_layer, (x, kv))
        else:
            x, rec = lax.fori_loop(first, last, mamba_layer, (x, rec))
    return x, ({"kv": kv, "recurrent": rec} if carries else None)


# ---------------------------------------------------------------------------
# the three entry points
# ---------------------------------------------------------------------------


def forward(params: Params, tokens, cfg: JambaConfig):
    """tokens [B, S] int32 -> logits [B, S, V] float32.  No state kept:
    the tests' oracle, not a fast path."""
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    x, _ = _stack(params, x, cfg, None)
    return _logits(x, params["ln_f"], params["embed"])


def prefill_request(params: Params, prompt, cfg: JambaConfig,
                    cache_len: int):
    """Prefill ONE request.  ``prompt``: [S0] int32.  Returns (next-token
    logits [V] float32, the request's state: ``init_state`` for one slot,
    keys and values at rows [0, S0) and zero past them)."""
    x = params["embed"].astype(cfg.compute_dtype)[prompt[None]]
    x, state = _stack(params, x, cfg, init_state(cfg, 1, cache_len))
    return _logits(x[:, -1:], params["ln_f"], params["embed"])[0, 0], state


def decode_step(params: Params, tok, pos, state: State, cfg: JambaConfig):
    """One continuous-batching step: embed ``tok`` [B], run each slot one
    token on from its own ``state`` at its own ``pos`` [B].  Returns
    (next-token logits [B, V] float32, the state updated in place when
    donated).  Rows never mix: a slot's output depends on its own state
    alone."""
    x = params["embed"].astype(cfg.compute_dtype)[tok[:, None]]
    x, slots = _stack(params, x, cfg, state, pos)
    counters = count_lane_reads(state["counters"], pos, "heads_first",
                                state["kv"][0])
    return (_logits(x, params["ln_f"], params["embed"])[:, 0],
            {**slots, "counters": counters})
