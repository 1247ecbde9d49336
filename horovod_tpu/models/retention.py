"""A decoder whose mixer is power retention: linear attention of degree 2
with a scalar gate a key/value head, served from a recurrent state and
from no cache of positions (``manifestai/Brumby-14B-Base``'s
``config.json`` gives every size; the retention's own settings are
this module's, each listed under ``assumed`` in
``perfbench/configs/brumby-14b.json``).

``forward`` (the tests' oracle), ``prefill_request`` and ``decode_step``
are built from ONE mixer function with two forms.  Training it is not
supported (neither form has a backward pass written for it).

* The layer, with ``u = RMSNorm(x)``: ``q = u Wq`` (H heads), ``k = u
  Wk``, ``v = u Wv`` (KVH heads; query head h reads group ``h // (H /
  KVH)``), ``gamma = log_sigmoid(u Wg + b_g)`` a key/value head, float32;
  q and k are RMS-normed over the head with a gain each and rotated
  (``_rope``).  With ``Gamma_t`` the running sum of ``gamma``::

      w_ts = exp(Gamma_t - Gamma_s) (q_t . k_s)^2     s <= t, else 0
      o_t  = sum_s w_ts v_s / (sum_s w_ts + eps)

  then ``x += concat_h(o) Wo`` and the dense gated feed-forward.
* The same as a recurrence.  ``phi: R^HD -> R^D`` is the symmetric square
  (:func:`phi`: entries ``x_i x_j``, ``i <= j``, the off-diagonal ones
  times sqrt 2, zero rows up to the next multiple of 128), so that
  ``phi(q) . phi(k) = (q . k)^2``::

      S_t = e^gamma_t S_{t-1} + v_t phi(k_t)^T   [HD, D]
      z_t = e^gamma_t z_{t-1} + phi(k_t)         [D]
      o_t = S_t phi(q_t) / (z_t . phi(q_t) + eps)

* *Prompt form* (``state`` None): the quadratic form, query blocks of
  ``PROMPT_BLOCK`` rows against the keys up to their last row (the weights
  of one block exist at a time), then the state the prompt ENDS in,
  ``S = sum_s e^(Gamma_last - Gamma_s) v_s phi(k_s)^T`` and ``z``
  likewise, in blocks of positions.  *Step form* (one token a slot
  against its state): one read and one write of ``S``
  (:func:`_state_pass`: the kernel ``ops/pallas_retention.py``).  Both give the same function
  (tests/test_retention.py).
* q and k leave their norm and rotation in ``compute_dtype``; everything
  after is float32: ``phi`` (products of two bfloat16 values are exact),
  ``exp``, the running gates, the products with ``S`` (at
  ``precision=highest``: S is never rounded to bfloat16) and the
  quotient.
* The stack follows models/jamba.py: the layers are stacked on a leading
  axis and run as ONE ``fori_loop`` with all state as its carry and the
  layer indexed dynamically (PR 25).

State of a served batch (``init_state``)::

    {"recurrent": (S [L, B, KVH, HD, D] float32, z [L, B, KVH, D] float32)
     "counters": {...}   two uint32 scalars, see ``COUNTERS``}

``D`` is ``cfg.state_rows``: 8256 rows of the symmetric square of 128,
padded to 8320 = 65 x 128 so that it is the MINOR axis of both arrays
with no padding by the chip's (8, 128) tiles; 34.3 MB a slot a layer for
the published sizes.  There is no ``"kv"``: ``cache_len`` is the position
cap (the rotation's) and sizes nothing.  A request's state
(``prefill_request``) is the ``"recurrent"`` part with B = 1.  A slot
whose position is 0 is free (``DecodeEngine.clear``): its state is
stepped too (rows never mix, and a gate under one keeps it bounded) and
the next install overwrites all of it.  The module omits what
``serving/decode.py:MODELS`` lets it: no sharding of this state is written
(its heads under tp), and the weights come in ``param_dtype``, which is
for the caller to choose.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.models.layers import (_at, _dense_ffn, _logits, _put,
                                       _rmsnorm, _rope, add_counters)
from horovod_tpu.ops.pallas_retention import retention_step

Params = Dict[str, Any]
State = Dict[str, Any]
HI = lax.Precision.HIGHEST
F32 = jnp.float32

# What ``decode_step`` adds to ``state["counters"]`` a step: slots with a
# request in them (position > 0), and slots held, each times the layers:
# the share of the state pass that served a request.
COUNTERS = ("hvd_serve_state_rows_live_total",
            "hvd_serve_state_rows_held_total")
# The retention's own settings, which the published config has no key for
# (degree 2: the state is the symmetric SQUARE): the normaliser's epsilon,
# and the gate's bias at init (sigmoid(6) = 0.9975: a seeded gate forgets
# over ~400 positions).
RETENTION_EPS = 1e-6
GATE_BIAS_INIT = 6.0
# Query rows of a prompt whose weights exist at once; positions whose
# phi(k) exist at once when the prompt's final state is summed.
PROMPT_BLOCK = 256


@dataclass(frozen=True)
class RetentionConfig:
    """The published keys, under their published names."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    # Positions a served request may reach (the server's position cap).
    max_seq_len: int = 4736
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.rms_norm_eps != 1e-6:
            raise ValueError("the shared _rmsnorm has eps 1e-6")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("key/value heads must divide the query heads")
        if self.head_dim % 2:
            raise ValueError("the rotary dims turn in pairs")

    @property
    def state_rows(self) -> int:
        """Rows of ``phi``: HD (HD + 1) / 2, up to a multiple of 128."""
        return -(-(self.head_dim * (self.head_dim + 1) // 2) // 128) * 128


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init(rng, cfg: RetentionConfig) -> Params:
    """Matrices normal(0, 0.02), output projections scaled by 1/sqrt(2 L),
    gains one, the gate's bias ``GATE_BIAS_INIT``.  Every leaf is made in
    float32, one at a time, and held in ``param_dtype``."""
    D, F, V, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_hidden_layers)
    H, KVH, HD = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    std = 0.02
    out_std = std / math.sqrt(2 * L)
    keys = iter(jax.random.split(rng, 12))
    dt = cfg.param_dtype

    def normal(shape, s):
        return (jax.random.normal(next(keys), shape, F32) * s).astype(dt)

    layers = {
        "ln1": jnp.ones((L, D), dt), "ln2": jnp.ones((L, D), dt),
        "wq": normal((L, D, H, HD), std), "wk": normal((L, D, KVH, HD), std),
        "wv": normal((L, D, KVH, HD), std),
        "wo": normal((L, H, HD, D), out_std),
        "q_norm": jnp.ones((L, HD), dt), "k_norm": jnp.ones((L, HD), dt),
        "wg": normal((L, D, KVH), std),
        "bg": jnp.full((L, KVH), GATE_BIAS_INIT, dt),
        "w_in": normal((L, D, F), std), "w_gate": normal((L, D, F), std),
        "w_out": normal((L, F, D), out_std)}
    return {"embed": normal((V, D), std), "layers": layers,
            "ln_f": jnp.ones((D,), dt), "head": normal((V, D), std)}


# ---------------------------------------------------------------------------
# the symmetric square
# ---------------------------------------------------------------------------


def phi_rows(head_dim: int, rows: int):
    """(first, second, scale): row r of ``phi``, and so of a slot's ``S``
    and ``z``, is ``scale[r] x[first[r]] x[second[r]]``: the pairs i <= j
    in order, the diagonal ones at 1 and the others at sqrt 2, then zero
    rows.  The benchmark's state check reads the packing from here."""
    i, j = np.triu_indices(head_dim)
    pad = rows - i.size
    scale = np.where(i == j, 1.0, math.sqrt(2.0)).astype(np.float32)
    return (np.pad(i, (0, pad)), np.pad(j, (0, pad)), np.pad(scale, (0, pad)))


def phi(x, rows: int):
    """x [..., HD] -> [..., rows] float32 with ``phi(a) . phi(b) = (a .
    b)^2``.  The two factors of a row are picked by a product with a
    one-hot matrix, which is exact for every type (one term a sum) and is
    the MXU's work: a gather along the lanes is not the chip's."""
    hd = x.shape[-1]
    first, second, scale = phi_rows(hd, rows)
    pick = jnp.arange(hd)[:, None]
    a = jnp.einsum("...i,ir->...r", x, (pick == first).astype(x.dtype),
                   precision=HI, preferred_element_type=F32)
    b = jnp.einsum("...i,ir->...r", x, (pick == second).astype(x.dtype),
                   precision=HI, preferred_element_type=F32)
    return a * b * scale


# ---------------------------------------------------------------------------
# the mixer: ONE function, two forms
# ---------------------------------------------------------------------------


def _state_pass(S, z, layer, phi_all, n_q: int, v, decay):
    """The step form's pass over layer ``layer`` of the stacked state:
    ``S <- decay S + v phi_k^T`` and ``z <- decay z + phi_k`` in place,
    and the old state's answer to the queries, ``(S_old phi_q, z_old .
    phi_q)``.  S [L, B, KVH, HD, D], z [L, B, KVH, D]; phi_all [B, KVH,
    R, D]: rows [0, n_q) the queries' and row n_q the key's; v [B, KVH,
    HD]; decay [B, KVH]; all float32.  ONE read and one write of S (the
    kernel ``retention_step``; as a dot and then an update XLA reads it
    twice); z, 1/HD of it, is XLA's.  Returns (S, z, num [B, KVH, n_q,
    HD], den [B, KVH, n_q])."""
    S, num = retention_step(S, layer, phi_all, v, decay, n_q=n_q)
    z_old = _at(z, layer)
    den = jnp.einsum("bkgd,bkd->bkg", phi_all[:, :, :n_q], z_old,
                     precision=HI)
    z_new = decay[..., None] * z_old + phi_all[:, :, n_q]
    return S, _put(z, layer, z_new), num, den


def _retention(x, lp, cfg: RetentionConfig, state=None):
    """x: [B, S, D], normalised.

    ``state`` None, the prompt form: the S positions start at 0 and
    attend among themselves; returns (out, (S [B, KVH, HD, D], z [B, KVH,
    D])), the state after the last position.  ``state`` = (Ss, zs, layer,
    pos), the stacked state and the position [B] of THIS token (S = 1),
    the step form: updates layer ``layer`` of the state in place;
    returns (out, (Ss, zs))."""
    dtype = cfg.compute_dtype
    B, S, _ = x.shape
    KVH, HD, rows = cfg.num_key_value_heads, cfg.head_dim, cfg.state_rows
    G = cfg.num_attention_heads // KVH
    eps = RETENTION_EPS
    own = None if state is None else state[3][:, None]          # [B, 1]
    q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"].astype(dtype)).astype(F32)
    gamma = jax.nn.log_sigmoid(
        jnp.einsum("bsd,dk->bsk", x, lp["wg"].astype(dtype),
                   preferred_element_type=F32) + lp["bg"].astype(F32))
    q = _rope(_rmsnorm(q, lp["q_norm"]), cfg.rope_theta, own)
    k = _rope(_rmsnorm(k, lp["k_norm"]), cfg.rope_theta, own)
    q = q.reshape(B, S, KVH, G, HD)
    if state is None:
        with jax.named_scope("retention_prompt"):
            ctx, kept = _prompt_form(q, k, v, gamma, PROMPT_BLOCK, rows,
                                     eps)
    else:
        with jax.named_scope("retention_step"):
            Ss, zs, layer, _ = state
            q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]
            decay = jnp.exp(gamma[:, 0])                        # [B, KVH]
            # The group's queries and the key in rows of eight, squared
            # together: one product with the one-hot matrices.
            both = jnp.concatenate([q1, k1[:, :, None]], axis=2)
            both = jnp.pad(both, [(0, 0), (0, 0), (0, -(G + 1) % 8), (0, 0)])
            Ss, zs, num, den = _state_pass(
                Ss, zs, layer, phi(both, rows), G, v1, decay)
            # The new position's own term: phi(q) . phi(k) = (q . k)^2.
            own_w = jnp.einsum("bkgd,bkd->bkg", q1, k1, precision=HI,
                               preferred_element_type=F32) ** 2
            num = decay[..., None, None] * num \
                + own_w[..., None] * v1[:, :, None]
            den = decay[..., None] * den + own_w
            ctx = (num / (den[..., None] + eps))[:, None]   # [B, 1, KVH, G, HD]
            kept = (Ss, zs)
    ctx = ctx.astype(dtype).reshape(B, S, KVH * G, HD)
    return jnp.einsum("bshk,hkd->bsd", ctx, lp["wo"].astype(dtype)), kept


def _prompt_form(q, k, v, gamma, block: int, rows: int, eps: float):
    """The quadratic form over a whole prompt and the state it ends in.
    q [B, S, KVH, G, HD], k [B, S, KVH, HD] (their products are exact in
    float32), v [B, S, KVH, HD] and gamma [B, S, KVH] float32.  Returns
    (ctx [B, S, KVH, G, HD] float32, (S [B, KVH, HD, D], z [B, KVH,
    D]))."""
    S = q.shape[1]
    run = jnp.cumsum(gamma, axis=1)                             # Gamma
    T = min(block, S)
    out = []
    for lo in range(0, S, T):               # static: a program a length
        hi = min(lo + T, S)
        scores = jnp.einsum("bskgd,btkd->bkgst", q[:, lo:hi], k[:, :hi],
                            precision=HI, preferred_element_type=F32)
        valid = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        fade = jnp.exp(jnp.where(
            valid, (run[:, lo:hi, None] - run[:, None, :hi]
                    ).transpose(0, 3, 1, 2), -jnp.inf))         # [B, KVH, s, t]
        w = scores * scores * fade[:, :, None]
        num = jnp.einsum("bkgst,btkv->bskgv", w, v[:, :hi], precision=HI)
        den = jnp.sum(w, axis=-1).transpose(0, 3, 1, 2)         # [B, s, KVH, G]
        out.append(num / (den[..., None] + eps))
    # What the prompt leaves behind: every position's phi(k) v^T, faded
    # from its position to the last.
    left = jnp.exp(run[:, -1:] - run)                           # [B, S, KVH]
    state, norm = None, None
    for lo in range(0, S, T):
        hi = min(lo + T, S)
        pk = phi(k[:, lo:hi], rows) * left[:, lo:hi, :, None]   # [B, t, KVH, D]
        s_part = jnp.einsum("btkv,btkd->bkvd", v[:, lo:hi], pk, precision=HI)
        z_part = jnp.sum(pk, axis=1)
        state = s_part if state is None else state + s_part
        norm = z_part if norm is None else norm + z_part
    return jnp.concatenate(out, axis=1), (state, norm)


# ---------------------------------------------------------------------------
# the stack, and the state it carries
# ---------------------------------------------------------------------------


def init_state(cfg: RetentionConfig, max_batch: int, cache_len: int
               ) -> State:
    """Zeros for ``max_batch`` slots; see the module docstring.
    ``cache_len`` sizes nothing: the model keeps no position."""
    del cache_len
    L, KVH, rows = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                    cfg.state_rows)
    return {
        "recurrent": (
            jnp.zeros((L, max_batch, KVH, cfg.head_dim, rows), F32),
            jnp.zeros((L, max_batch, KVH, rows), F32)),
        "counters": {name: jnp.zeros((), jnp.uint32) for name in COUNTERS}}


# The axis of each slot-kind leaf that the slots lie along.
SLOT_AXES = {"recurrent": (1, 1)}


def _stack(params: Params, x, cfg: RetentionConfig,
           rec: Optional[Tuple] = None, pos=None):
    """x [B, S, D] through every layer.  ``pos`` None: the sequences
    start here (position 0, zero state); ``rec``, if given, receives the
    state they end in.  ``pos`` [B]: one token a slot continuing ``rec``,
    which is read and written at its layer.  Returns (x, rec)."""
    dtype = cfg.compute_dtype
    start = pos is None
    keeps = rec is not None

    def layer(l, carry):
        h, rec = carry
        lp = _at(params["layers"], l)
        y = _rmsnorm(h, lp["ln1"])
        if start:
            y, (s_end, z_end) = _retention(y, lp, cfg)
            if keeps:
                rec = (_put(rec[0], l, s_end), _put(rec[1], l, z_end))
        else:
            y, rec = _retention(y, lp, cfg, (*rec, l, pos))
        h = h + y
        return h + _dense_ffn(_rmsnorm(h, lp["ln2"]), lp, dtype), rec

    x, rec = lax.fori_loop(0, cfg.num_hidden_layers, layer,
                           (x, rec if keeps else ()))
    return x, (rec if keeps else None)


# ---------------------------------------------------------------------------
# the three entry points
# ---------------------------------------------------------------------------


def forward(params: Params, tokens, cfg: RetentionConfig):
    """tokens [B, S] int32 -> logits [B, S, V] float32.  No state kept:
    the tests' oracle, not a fast path."""
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    x, _ = _stack(params, x, cfg)
    return _logits(x, params["ln_f"], params["head"])


def prefill_request(params: Params, prompt, cfg: RetentionConfig,
                    cache_len: int):
    """Prefill ONE request.  ``prompt``: [S0] int32.  Returns (next-token
    logits [V] float32, the request's state: the ``"recurrent"`` part of
    ``init_state`` for one slot, after the prompt's last position)."""
    x = params["embed"].astype(cfg.compute_dtype)[prompt[None]]
    x, rec = _stack(params, x, cfg,
                    init_state(cfg, 1, cache_len)["recurrent"])
    return (_logits(x[:, -1:], params["ln_f"], params["head"])[0, 0],
            {"recurrent": rec})


def decode_step(params: Params, tok, pos, state: State,
                cfg: RetentionConfig):
    """One continuous-batching step: embed ``tok`` [B], run each slot one
    token on from its own state at its own ``pos`` [B] (the step form).
    Returns (next-token logits [B, V] float32, the state updated in place
    when donated).  Rows never mix: a slot's output depends on its own
    state alone."""
    x = params["embed"].astype(cfg.compute_dtype)[tok[:, None]]
    x, rec = _stack(params, x, cfg, state["recurrent"], pos)
    L = cfg.num_hidden_layers
    counters = add_counters(state["counters"], dict(zip(COUNTERS, (
        jnp.sum(pos > 0) * L, jnp.uint32(L * pos.shape[0])))))
    return (_logits(x, params["ln_f"], params["head"])[:, 0],
            {"recurrent": rec, "counters": counters})
