"""A decoder whose layers are ONE mixer or ONE feed-forward each, by a
string of kinds: Mamba-2 (``M``), routed experts beside a shared one
(``E``) and grouped-query attention without positions (``*``).  The
serving path of the ``nemotron_h`` line
(``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``'s ``config.json`` gives
every size, under the names used here).

``forward`` (the tests' oracle), ``prefill_request`` and ``decode_step``
are built from ONE function a layer kind, each taking optional state in
and giving state out.  Training it is not supported (neither the chunked
scan nor the grouped product has a backward pass written for it).

* Block: ``h <- h + mixer_l(RMSNorm_l(h))``, ``mixer_l`` by letter ``l``
  of ``hybrid_override_pattern``; a final RMSNorm and an untied head.
* ``M``, the Mamba-2 mixer, ``H = mamba_num_heads`` heads of ``P =
  mamba_head_dim`` channels (``d_inner = H P``), ``G = n_groups`` groups
  (head ``h`` reads group ``h // (H / G)``), ``N = ssm_state_size``::

      [z | xBC | dt] = u W_in                    d_inner | d_inner + 2 G N | H
      xBC = silu(conv(xBC) + b)                  depthwise, causal, conv_kernel
      x [H, P], B [G, N], C [G, N] = split(xBC)
      D_t = softplus(dt_t + dt_bias)             a head
      S_t = exp(D_t A) S_{t-1} + (D_t x_t) (x) B_t      A = -exp(A_log), a head
      y_t = S_t C_t + D x_t
      out = RMSNorm_grouped(y silu(z)) W_out     the gate first, then a
                                                 norm a group of d_inner / G

  *One token* against a carried state is that recurrence, one step: ONE
  kernel a layer over the slots that hold a request
  (``ops/pallas_ssd.py:ssd_step``: a slot's state read once, stepped,
  read out and written back in place; ``_ssd_step`` here is the same in
  plain JAX over every slot, the tests' oracle).  *A prompt* runs the
  **chunked form** of the same
  (``ssd_scan``: state-space duality), chunks of ``chunk_size`` rows:
  inside a chunk ``Y = (L o C B^T)(D x)`` with ``L_ts = exp(sum_{s<r<=t}
  D_r A)``, three batched matrix products on the MXU; a chunk's end state
  from its start state and its own rows, carried chunk to chunk by a scan
  of ``S / chunk_size`` steps; the start state's part ``exp(sum_{r<=t} D_r
  A) C_t S_start`` added.  Both give the same outputs and the same end
  state (tests/test_ssd_moe.py).  The decays, ``softplus``, the state and
  every accumulation are float32; the products' operands are
  ``compute_dtype``.
* ``*``: ``layers._grouped_attention`` (``num_attention_heads`` on
  ``num_key_value_heads`` of ``head_dim``: wider together than
  ``hidden_size``), its caches with the positions ahead of the two
  key/value heads; a step reads a slot's lane as far as the slot has
  written it (``layers.lane_reader``: the decode kernel's head axis, 16
  query rows a key/value head).
* ``E``: ``y = sum_{i in top-k} w_i E_i(x) + E_shared(x)``, ``E(x) =
  relu(x W_up)^2 W_down``: ``experts.route`` and ``experts.routed_ffn`` in
  its two-product form, the routed stacks held with zeros up to whole
  tiles of the grouped product (``experts.padded_width``).  With
  ``experts_held`` the chip holds experts ``expert_first`` to
  ``expert_first + experts_held`` of ``n_routed_experts``: the router
  keeps its width, the layer computes its
  own experts' part, and what the other chips' experts would add is left
  out (the ``model-configs`` guide's cut; no code stands in for the
  exchange).
* The stack: layers of a kind are stacked on a leading axis
  (``params["mamba"]``, ``params["moe"]``, ``params["attn"]``); the
  pattern has no runs of a kind to loop over, so the layers are laid out
  one after another at static indices.  The routed experts stay in their
  stack (``routed_ffn`` indexes the layer inside the grouped product).

State of a served batch (``init_state``)::

    {"kv": (k, v)              [La, B, cache_len, KVH, HD]  compute_dtype
     "recurrent": (ssm, conv)  [Lm, B, G, N, (H / G) P] float32: a
                               group's heads' channels side by side, the
                               read-out's sum down the rows
                               (``pallas_ssd.from_heads`` of [H, P, N]);
                               [Lm, conv_kernel - 1, B, d_inner + 2 G N]
                               compute_dtype
     "counters": {...}         uint32 scalars, summed on the device: see
                               :func:`counter_names`}

A request's state (``prefill_request``) is the slot kinds with B = 1 and
``"counted"``: what the prefill itself counted (its chunks), which the
install adds to the batch's counters.  A slot whose position is 0 is free
(``DecodeEngine.clear``): its row is kept out of the routing and of the
live counts, and its recurrent state is neither read nor written (the
state step's grid is the live slots; the install overwrites the slot at
admission).  The module omits what ``serving/decode.py:MODELS`` lets it: no
sharding of this state is written, and the weights come in
``param_dtype``, which is for the caller to choose.
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
                                       _grouped_attention, _logits, _put,
                                       _rmsnorm, add_counters,
                                       count_lane_reads, lane_reader)
from horovod_tpu.ops import pallas_ssd

Params = Dict[str, Any]
State = Dict[str, Any]

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
# What ``decode_step`` adds to ``state["counters"]`` a step besides the
# routing's: (slot, Mamba-2 layer) state steps taken, and those of slots
# with a request in them (the same number: the step's grid is the live
# slots).  What ``prefill_request`` counts: the chunks its prompt
# ran through the chunked form, times the Mamba-2 layers.
STATE_COUNTERS = ("hvd_ssm_state_steps_total",
                  "hvd_ssm_state_steps_live_total")
CHUNK_COUNTER = "hvd_ssm_prefill_chunks_total"


@dataclass(frozen=True)
class SsdMoEConfig:
    """The published keys, under their published names."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    n_group: int = 1            # the router's groups (the mixer's: n_groups)
    topk_group: int = 1
    layer_norm_epsilon: float = 1e-5
    # Positions a served request may reach (the server's default cache).
    max_seq_len: int = 4096
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # What one chip holds of a layer's routed experts (None: all of them).
    experts_held: Optional[int] = None
    expert_first: int = 0

    def __post_init__(self):
        if len(self.hybrid_override_pattern) != self.num_hidden_layers \
                or set(self.hybrid_override_pattern) - set(KINDS):
            raise ValueError("hybrid_override_pattern: one of M, E, * a "
                             "layer")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.mamba_num_heads % self.n_groups:
            raise ValueError("key/value heads divide the query heads, "
                             "groups the mixer's heads")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("more experts a token than experts")
        if self.experts_held is not None and not (
                0 <= self.expert_first
                <= self.n_routed_experts - self.experts_held):
            raise ValueError("the held experts are some of the routed ones")

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(KINDS[c] for c in self.hybrid_override_pattern)

    def n_layers(self, kind: str) -> int:
        return self.layer_kinds.count(kind)


def counter_names(cfg: SsdMoEConfig) -> Tuple[str, ...]:
    """The device counters ``cfg``'s state holds."""
    return (experts.MOE_COUNTERS
            + ((experts.ABSENT_COUNTER,) if cfg.experts_held is not None
               else ())
            + STATE_COUNTERS + (CHUNK_COUNTER,) + ATTN_COUNTERS)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init(rng, cfg: SsdMoEConfig) -> Params:
    """Matrices normal(0, 0.02), the three output projections (Mamba-2
    out, attention o, every expert's down) scaled by 1/sqrt(2 L); the
    mixer's own leaves by Mamba-2's rule: ``A_log = log(uniform(1, 16))``
    a head, ``D = 1``, ``dt_bias`` the inverse softplus of a log-uniform
    step in [time_step_min, time_step_max] floored at time_step_floor,
    the convolution uniform in +-1/sqrt(conv_kernel); gains one; the
    router's selection bias normal(0, 0.01) in float32.  Every other leaf
    is held in ``param_dtype``.  With ``experts_held`` the routed experts'
    stacks hold that many (the router keeps its ``n_routed_experts``
    outputs); their two width axes are held at ``experts.padded_width``
    (2688 x 1856 as 3072 x 2048, the padding zeros)."""
    D, V = cfg.hidden_size, cfg.vocab_size
    H, KVH, HD = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    Hm, Di, C, K = (cfg.mamba_num_heads, cfg.d_inner, cfg.conv_dim,
                    cfg.conv_kernel)
    Fe, Fs = cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size
    E = cfg.n_routed_experts
    Eh = E if cfg.experts_held is None else cfg.experts_held
    Lm, Le, La = (cfg.n_layers(k) for k in ("mamba", "moe", "attn"))
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(rng, 24))
    dt = cfg.param_dtype

    def normal(shape, s, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * s).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def held(w):        # [Le, Eh, a, b] with zeros up to whole tiles
        pad = [(0, experts.padded_width(n) - n) for n in w.shape[2:]]
        return jnp.pad(w, [(0, 0), (0, 0)] + pad)

    step = jnp.maximum(
        jnp.exp(uniform((Lm, Hm), math.log(cfg.time_step_min),
                        math.log(cfg.time_step_max))), cfg.time_step_floor)
    bound = 1.0 / math.sqrt(K)
    mamba = {
        "ln": jnp.ones((Lm, D), dt),
        "in_proj": normal((Lm, D, Di + C + Hm), std),
        "conv_w": uniform((Lm, K, C), -bound, bound).astype(dt),
        "conv_b": uniform((Lm, C), -bound, bound).astype(dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "a_log": jnp.log(uniform((Lm, Hm), 1.0, 16.0)).astype(dt),
        "d": jnp.ones((Lm, Hm), dt),
        "norm": jnp.ones((Lm, Di), dt),
        "out_proj": normal((Lm, Di, D), out_std)}
    moe = {
        "ln": jnp.ones((Le, D), dt),
        "router": normal((Le, D, E), std),
        "router_bias": normal((Le, E), 0.01, jnp.float32),
        "w_in": held(normal((Le, Eh, D, Fe), std)),
        "w_out": held(normal((Le, Eh, Fe, D), out_std)),
        "shared_in": normal((Le, D, Fs), std),
        "shared_out": normal((Le, Fs, D), out_std)}
    attn = {
        "ln": jnp.ones((La, D), dt),
        "wq": normal((La, D, H, HD), std),
        "wk": normal((La, D, KVH, HD), std),
        "wv": normal((La, D, KVH, HD), std),
        "wo": normal((La, H, HD, D), out_std)}
    return {"embed": normal((V, D), std), "mamba": mamba, "moe": moe,
            "attn": attn, "ln_f": jnp.ones((D,), dt),
            "head": normal((V, D), std)}


# ---------------------------------------------------------------------------
# the Mamba-2 mixer: ONE function, two forms of its recurrence
# ---------------------------------------------------------------------------


def _ssd_step(ssm, x, dt, a, b_in, c_out):
    """One step of the recurrence for every slot, in plain JAX: what
    ``pallas_ssd.ssd_step`` is held to (tests/test_pallas_ssd.py) and the
    chunked form's oracle; no program calls it.  ssm: [B, H, P, N]
    float32; x: [B, H, P]; dt: [B, H]; a: [H]; b_in, c_out: [B, G, N]; all
    float32.  Returns (y [B, H, P] without the ``D x`` term, the new
    state)."""
    B, H, P, N = ssm.shape
    G = b_in.shape[1]
    s = ssm.reshape(B, G, H // G, P, N)         # a group's heads share B, C
    decay = jnp.exp(dt * a).reshape(B, G, H // G, 1, 1)
    dx = (dt[..., None] * x).reshape(B, G, H // G, P, 1)
    s = decay * s + dx * b_in[:, :, None, None, :]
    y = jnp.sum(s * c_out[:, :, None, None, :], axis=-1)
    return y.reshape(B, H, P), s.reshape(B, H, P, N)


def _ssd_scan(ssm, x, dt, a, b_in, c_out, chunk: int):
    """The same recurrence over the leading (time) axis in its chunked
    form.  ssm: [B, H, P, N] float32, the state before the first row; x:
    [S, B, H, P] and b_in, c_out: [S, B, G, N] in the products' type; dt:
    [S, B, H] float32; a: [H].  Returns (y [S, B, H, P] float32 without
    the ``D x`` term, the state after the last row).

    A ragged tail is padded with ``dt = 0`` rows, which leave the state
    exactly as it was.  What exists at once is ``[S / Q, Q, Q, B, H]``
    decays (Q = ``chunk``): 67 MB in float32 for 2048 rows of 64 heads."""
    f32, dtype = jnp.float32, x.dtype
    S, B, H, P = x.shape
    G, N = b_in.shape[2:]
    R = H // G
    Q = min(chunk, S)
    pad = -S % Q

    def chunks(t):      # [S, ...] -> [S / Q, Q, ...]
        t = jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
        return t.reshape((-1, Q) + t.shape[1:])

    x, dt, b_in, c_out = (chunks(t) for t in (x, dt, b_in, c_out))
    cum = jnp.cumsum(dt * a, axis=1)            # [c, Q, B, H]: sum_{r<=t}
    dx = dt[..., None] * x.astype(f32)          # [c, Q, B, H, P]
    # Inside a chunk: (L o C B^T)(D x), a group's C B^T shared by its heads.
    cb = jnp.einsum("ctbgn,csbgn->cbgts", c_out, b_in,
                    preferred_element_type=f32)
    seg = cum[:, :, None] - cum[:, None, :]     # [c, t, s, B, H]
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decays = jnp.exp(jnp.where(causal[None, :, :, None, None], seg, -jnp.inf))
    w = (cb[:, :, :, None] * jnp.transpose(decays, (0, 3, 4, 1, 2)).reshape(
        -1, B, G, R, Q, Q)).astype(dtype)       # [c, B, G, R, t, s]
    dxg = dx.reshape(dx.shape[:3] + (G, R, P))
    y = jnp.einsum("cbgrts,csbgrp->ctbgrp", w, dxg.astype(dtype),
                   preferred_element_type=f32)
    # A chunk's own rows' part of its end state, then chunk to chunk.
    to_end = jnp.exp(cum[:, -1:] - cum).reshape(cum.shape[:3] + (G, R, 1))
    own = jnp.einsum("csbgrp,csbgn->cbgrpn", (dxg * to_end).astype(dtype),
                     b_in, preferred_element_type=f32)
    whole = jnp.exp(cum[:, -1]).reshape(-1, B, G, R, 1, 1)

    def carry(s, t):
        return t[0] * s + t[1], s               # emits the START state

    ssm, starts = lax.scan(carry, ssm.reshape(B, G, R, P, N), (whole, own))
    # The start state's part of a chunk's outputs.
    y = y + jnp.einsum(
        "ctbgn,cbgrpn->ctbgrp", c_out, starts.astype(dtype),
        preferred_element_type=f32) * jnp.exp(cum).reshape(
            cum.shape[:3] + (G, R, 1))
    return (y.reshape((-1, B, H, P))[:S], ssm.reshape(B, H, P, N))


def _mamba_mixer(u, lp, cfg: SsdMoEConfig, carried=None):
    """The Mamba-2 mixer.  u: [B, S, D].  ``carried`` None: the sequences
    start here (zero state) and run the recurrence's chunked form; returns
    (out [B, S, D], (ssm [B, G, N, W] float32, conv [conv_kernel - 1, B,
    conv_dim]: the last inputs of the convolution) after the last
    position).  ``carried`` = (ssm, layer, work, conv): one token a slot (S
    = 1) against layer ``layer`` of the STACKED states ``ssm`` [Lm, B, G,
    N, W], stepped in place for the slots of ``work``
    (``pallas_ssd.live_slots``; every other slot's state is left as it
    is and answers zeros), and that layer's ``conv``; returns (out, (the
    stack, conv)).  Inside, time is the leading axis."""
    dtype, f32 = cfg.compute_dtype, jnp.float32
    B, S, _ = u.shape
    H, P, G, N = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                  cfg.ssm_state_size)
    Di, C = cfg.d_inner, cfg.conv_dim
    start = carried is None
    if start:
        carried = (jnp.zeros((B, H, P, N), f32), None, None,
                   jnp.zeros((cfg.conv_kernel - 1, B, C), dtype))
    ssm, layer, work, conv = carried
    # (Rows first, then time to the front: XLA's CPU runtime has no
    # bfloat16 product into float32 that also transposes its result.)
    zxd = jnp.einsum("bsd,de->bse", u, lp["in_proj"].astype(dtype),
                     preferred_element_type=f32).swapaxes(0, 1)
    z, dt = zxd[..., :Di], zxd[..., Di + C:]
    xbc, window = _causal_conv(zxd[..., Di:Di + C].astype(dtype), conv,
                               lp["conv_w"], lp["conv_b"])
    xbc = jax.nn.silu(xbc).astype(dtype)
    x = xbc[..., :Di].reshape(S, B, H, P)
    b_in = xbc[..., Di:Di + G * N].reshape(S, B, G, N)
    c_out = xbc[..., Di + G * N:].reshape(S, B, G, N)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))
    a = -jnp.exp(lp["a_log"].astype(f32))
    if start:
        with jax.named_scope("ssd_scan"):
            y, ssm = _ssd_scan(ssm, x, dt, a, b_in, c_out, cfg.chunk_size)
            ssm = pallas_ssd.from_heads(ssm, G)
    else:
        with jax.named_scope("ssd_step"):
            ssm, y = pallas_ssd.ssd_step(
                ssm, layer, work, x[0].astype(f32), dt[0], a,
                b_in[0].astype(f32), c_out[0].astype(f32))
            y = y[None]
    y = y + lp["d"].astype(f32)[:, None] * x.astype(f32)
    y = y.reshape(S, B, Di) * jax.nn.silu(z)
    # The gate first, then a norm over each group's channels apart.
    y = y.reshape(S, B, G, Di // G)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                      + cfg.layer_norm_epsilon)
    y = y.reshape(S, B, Di) * lp["norm"].astype(f32)
    out = jnp.einsum("sbe,ed->bsd", y.astype(dtype),
                     lp["out_proj"].astype(dtype))
    return out, (ssm, window[S:])


def _relu2_ffn(x, w_in, w_out, dtype):
    h = jnp.einsum("bsd,df->bsf", x, w_in.astype(dtype))
    return jnp.einsum("bsf,fd->bsd", jnp.square(jax.nn.relu(h)),
                      w_out.astype(dtype))


# ---------------------------------------------------------------------------
# the stack, and the state it carries
# ---------------------------------------------------------------------------


def init_state(cfg: SsdMoEConfig, max_batch: int, cache_len: int) -> State:
    """Zeros for ``max_batch`` slots; see the module docstring."""
    La, Lm = cfg.n_layers("attn"), cfg.n_layers("mamba")
    lane = (La, max_batch, cache_len, cfg.num_key_value_heads, cfg.head_dim)
    return {
        "kv": (jnp.zeros(lane, cfg.compute_dtype),
               jnp.zeros(lane, cfg.compute_dtype)),
        "recurrent": (
            jnp.zeros((Lm, max_batch, cfg.n_groups, cfg.ssm_state_size,
                       cfg.d_inner // cfg.n_groups), jnp.float32),
            jnp.zeros((Lm, cfg.conv_kernel - 1, max_batch, cfg.conv_dim),
                      cfg.compute_dtype)),
        "counters": {name: jnp.zeros((), jnp.uint32)
                     for name in counter_names(cfg)}}


# The axis of each slot-kind leaf that the slots lie along: the convolution
# state keeps its window's rows ahead of them.
SLOT_AXES = {"kv": (1, 1), "recurrent": (1, 2)}

_EXPERTS = ("w_in", "w_out")


def _stack(params: Params, x, cfg: SsdMoEConfig, state: Optional[State],
           pos=None):
    """x [B, S, D] through every layer.  ``pos`` None: the sequences
    start here (position 0, zero state); ``state``, if given, receives
    what they end in (keys and values at rows [0, S), the recurrent state
    after row S - 1).  ``pos`` [B]: one token a slot continuing ``state``,
    which is read and written at its layer; rows at position 0 are free
    slots: routed nowhere, their recurrent state not stepped.  Returns (x,
    the state's slot kinds, the routing's stats [3] summed over the expert
    layers)."""
    dtype, eps = cfg.compute_dtype, cfg.layer_norm_epsilon
    start = pos is None
    carries = state is not None
    kv = state["kv"] if carries else ()
    rec = state["recurrent"] if carries else ()
    live = None if start else pos > 0
    work = None if start else pallas_ssd.live_slots(live)
    read = None if start else lane_reader("positions_first", kv[0], pos)
    B, S, D = x.shape
    small = {k: v for k, v in params["moe"].items() if k not in _EXPERTS}
    routed = {k: params["moe"][k] for k in _EXPERTS}
    share = {} if cfg.experts_held is None else {"first": cfg.expert_first}
    stats = jnp.zeros((3,), jnp.int32)
    seen = dict.fromkeys(KINDS.values(), 0)
    for kind in cfg.layer_kinds:
        l = seen[kind]
        seen[kind] += 1
        if kind == "mamba":
            lp = _at(params["mamba"], l)
            y, new = _mamba_mixer(
                _rmsnorm(x, lp["ln"], eps), lp, cfg,
                None if start else (rec[0], l, work, _at(rec[1], l)))
            if carries:
                rec = (_put(rec[0], l, new[0]) if start else new[0],
                       _put(rec[1], l, new[1]))
        elif kind == "attn":
            lp = _at(params["attn"], l)
            y = _rmsnorm(x, lp["ln"], eps)
            if start:
                y, (k, v) = _grouped_attention(y, lp, dtype,
                                               layout="positions_first")
                if carries:
                    at = (l, 0, 0, 0, 0)
                    kv = (lax.dynamic_update_slice(kv[0], k[None], at),
                          lax.dynamic_update_slice(kv[1], v[None], at))
            else:
                y, kv = _grouped_attention(
                    y, lp, dtype, (*kv, l, pos, read),
                    layout="positions_first")
        else:
            lp = _at(small, l)
            u = _rmsnorm(x, lp["ln"], eps)
            rows = u.reshape(B * S, D)
            chosen, weights = experts.route(
                rows, lp["router"], lp["router_bias"],
                cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                cfg.n_group, cfg.topk_group)
            with jax.named_scope("routed_ffn"):
                y, new = experts.routed_ffn(rows, routed, l, chosen, weights,
                                            dtype, live, **share)
            stats = stats + new
            y = y.reshape(B, S, D) + _relu2_ffn(u, lp["shared_in"],
                                                lp["shared_out"], dtype)
        x = x + y
    return x, ({"kv": kv, "recurrent": rec} if carries else None), stats


# ---------------------------------------------------------------------------
# the three entry points
# ---------------------------------------------------------------------------


def forward(params: Params, tokens, cfg: SsdMoEConfig):
    """tokens [B, S] int32 -> logits [B, S, V] float32.  No state kept:
    the tests' oracle, not a fast path."""
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    x, _, _ = _stack(params, x, cfg, None)
    return _logits(x, params["ln_f"], params["head"], cfg.layer_norm_epsilon)


def prefill_request(params: Params, prompt, cfg: SsdMoEConfig,
                    cache_len: int):
    """Prefill ONE request.  ``prompt``: [S0] int32.  Returns (next-token
    logits [V] float32, the request's state: the slot kinds of
    ``init_state`` for one slot, keys and values at rows [0, S0) and zero
    past them, and what this prefill counted)."""
    x = params["embed"].astype(cfg.compute_dtype)[prompt[None]]
    zeros = init_state(cfg, 1, cache_len)
    x, slots, _ = _stack(params, x, cfg, zeros)
    chunks = -(-prompt.shape[0] // cfg.chunk_size) * cfg.n_layers("mamba")
    return (_logits(x[:, -1:], params["ln_f"], params["head"],
                    cfg.layer_norm_epsilon)[0, 0],
            {**slots, "counted": {CHUNK_COUNTER: jnp.uint32(chunks)}})


def decode_step(params: Params, tok, pos, state: State, cfg: SsdMoEConfig):
    """One continuous-batching step: embed ``tok`` [B], run each slot one
    token on from its own ``state`` at its own ``pos`` [B].  Returns
    (next-token logits [B, V] float32, the state updated in place when
    donated).  Rows never mix: nothing is dropped, so a slot's output
    depends on its own state alone (with a share of the experts: this
    chip's part of it)."""
    x = params["embed"].astype(cfg.compute_dtype)[tok[:, None]]
    x, slots, stats = _stack(params, x, cfg, state, pos)
    Lm, Le = cfg.n_layers("mamba"), cfg.n_layers("moe")
    live = jnp.sum(pos > 0)
    add = dict(zip(experts.MOE_COUNTERS,
                   (*stats.astype(jnp.uint32), jnp.uint32(Le))))
    if cfg.experts_held is not None:    # the live pairs no group here took
        add[experts.ABSENT_COUNTER] = (Le * cfg.num_experts_per_tok * live
                                       - stats[0])
    add[STATE_COUNTERS[0]] = add[STATE_COUNTERS[1]] = Lm * live
    counters = count_lane_reads(add_counters(state["counters"], add), pos,
                                "positions_first", state["kv"][0])
    return (_logits(x, params["ln_f"], params["head"],
                    cfg.layer_norm_epsilon)[:, 0],
            {**slots, "counters": counters})
