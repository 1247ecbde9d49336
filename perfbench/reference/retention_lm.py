"""Plain float32 reference of the power-retention LM the ``brumby-14b`` cell
serves (``manifestai/Brumby-14B-Base``'s ``config.json``, ``model_type``
``brumby``; the catalog's ``described_as.attention``: "power retention
layers").

Independent of ``horovod_tpu``: no state carried, no chunks, no cache, no
kernel, no symmetric square.  One sequence at a time; every matmul is float32 at
``precision="highest"``.  The model, from the configuration's keys
(RMSNorm has a gain, eps ``rms_norm_eps``; no bias but the gate's):

* ``num_hidden_layers`` pre-norm residual blocks ``h += Retention(
  RMSNorm(h)); h += W_down(silu(W_gate u) * W_up u)``, ``u = RMSNorm(h)``;
  a final RMSNorm; an **untied** head;
* retention, every layer, ``H = num_attention_heads`` query heads on
  ``KVH = num_key_value_heads`` key/value heads of ``head_dim`` (query
  head h reads group ``h // (H / KVH)``), with ``u`` a normalised row::

      q = u Wq   k = u Wk   v = u Wv      gamma = log_sigmoid(u Wg + b_g)   [KVH]
      q, k: RMSNorm over the head with a gain each, then RoPE (theta rope_theta)
      Gamma_t = sum_{s <= t} gamma_s
      w_ts = exp(Gamma_t - Gamma_s) (q_t . k_s)^2      s <= t, else 0
      o_t  = sum_s w_ts v_s / (sum_s w_ts + 1e-6)      out = concat_h(o) Wo

  computed as written, the quadratic form over the whole sequence, query
  rows in blocks of ``ROWS`` so that 4736 positions x 40 heads fit.

Departures from the published description, all under ``assumed`` in the
configuration file.  The config has the Qwen3-14B key set and no key for
the retention itself:

* degree 2 (the family's published default): the weight is the SQUARE of
  ``q . k``;
* one scalar gate a key/value head a position, a linear map ``Wg [D,
  KVH]`` with a bias through log-sigmoid;
* the output is normalised by the sum of the weights (eps 1e-6); the
  1/sqrt(head_dim) scale of ``q . k`` cancels in the quotient and is
  left out;
* q/k norms and RoPE kept from the lineage the config's keys come from;
  the rotary pairing is dim ``i`` with ``i + head_dim / 2`` (with seeded
  weights the same distribution as the interleaved one);
* the weights are seeded, not the checkpoint's: ``Wg`` normal(0, 0.02)
  and the gate's bias +6, so that a seeded gate is sigmoid(6) = 0.9975 and
  forgets over some 400 positions, not two;
* the library's switch to a key/value cache for short contexts is an
  inference optimisation, not the model: neither here nor in the program.

Weights are made here from the seed, leaf by leaf, in **bfloat16** (never
whole in float32) and in the layout the program serves (the layers
stacked on a leading axis), and handed to both sides.  The reference
upcasts one layer at a time.

``quant`` rounds every matmul operand to int8 (symmetric, absmax scale
along the contraction axis, float32 accumulation): the control that
``correct`` has to fail (the configuration states bfloat16).  The gates,
``exp`` and the quotient stay float32.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from perfbench.reference.int8 import fake_quant as _fq

HI = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
GATE_BIAS = 6.0
RETENTION_EPS = 1e-6
ROWS = 512          # query rows whose weights exist at once
F32 = jnp.float32


def make_weights(key, sizes: Dict) -> Dict:
    """Seeded bfloat16 weights: matrices normal(0, 0.02), output
    projections (retention o, feed-forward down) scaled by 1/sqrt(2 L),
    gains one, the gate's bias +6.  One small program a leaf, so that no
    float32 copy of more than one leaf exists at a time."""
    D, F, V, L = (sizes["hidden_size"], sizes["intermediate_size"],
                  sizes["vocab_size"], sizes["num_hidden_layers"])
    H, KVH, HD = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    out_std = INIT_STD / math.sqrt(2 * L)
    keys = iter(jax.random.split(key, 12))
    bf16 = jnp.bfloat16

    def normal(shape, std):
        return jax.jit(lambda k: (jax.random.normal(k, shape, F32) * std
                                  ).astype(bf16))(next(keys))

    layers = {
        "ln1": jnp.ones((L, D), bf16), "ln2": jnp.ones((L, D), bf16),
        "wq": normal((L, D, H, HD), INIT_STD),
        "wk": normal((L, D, KVH, HD), INIT_STD),
        "wv": normal((L, D, KVH, HD), INIT_STD),
        "wo": normal((L, H, HD, D), out_std),
        "q_norm": jnp.ones((L, HD), bf16), "k_norm": jnp.ones((L, HD), bf16),
        "wg": normal((L, D, KVH), INIT_STD),
        "bg": jnp.full((L, KVH), GATE_BIAS, bf16),
        "w_in": normal((L, D, F), INIT_STD),
        "w_gate": normal((L, D, F), INIT_STD),
        "w_out": normal((L, F, D), out_std)}
    return {"embed": normal((V, D), INIT_STD), "layers": layers,
            "ln_f": jnp.ones((D,), bf16), "head": normal((V, D), INIT_STD)}


# ---------------------------------------------------------------------------
# the model, one sequence, one layer at a time
# ---------------------------------------------------------------------------


def _mm(spec: str, a, b, a_axes, b_axes, quant: bool):
    return jnp.einsum(spec, _fq(a, a_axes, quant), _fq(b, b_axes, quant),
                      precision=HI, preferred_element_type=F32)


def _rmsnorm(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta: float):
    """x [S, H, HD]: dim i turns with dim i + HD / 2 by position x
    theta^(-i / (HD / 2))."""
    S, _, HD = x.shape
    half = HD // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs             # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _heads(lp: Dict, x, *, eps: float, theta: float, quant: bool):
    """x [S, D] float32 -> (q [S, H, HD], k and v [S, KVH, HD], gamma [S,
    KVH]) of one block, with ``lp`` in float32: the projections of the
    normalised rows, q and k normed and rotated, the gate's log."""
    u = _rmsnorm(x, lp["ln1"], eps)
    q = _mm("sd,dhk->shk", u, lp["wq"], (1,), (0,), quant)
    k = _mm("sd,dhk->shk", u, lp["wk"], (1,), (0,), quant)
    v = _mm("sd,dhk->shk", u, lp["wv"], (1,), (0,), quant)
    gamma = jax.nn.log_sigmoid(
        _mm("sd,dk->sk", u, lp["wg"], (1,), (0,), quant) + lp["bg"])
    q = _rope(_rmsnorm(q, lp["q_norm"], eps), theta)
    k = _rope(_rmsnorm(k, lp["k_norm"], eps), theta)
    return q, k, v, gamma


def layer(lp: Dict, x, *, eps: float, theta: float, quant: bool = False):
    """x: [S, D] float32 through one block."""
    lp = {k: v.astype(F32) for k, v in lp.items()}
    S = x.shape[0]
    H = lp["wq"].shape[1]
    KVH = lp["wk"].shape[1]
    q, k, v, gamma = _heads(lp, x, eps=eps, theta=theta, quant=quant)
    k = jnp.repeat(k, H // KVH, axis=1)                         # [S, H, HD]
    v = jnp.repeat(v, H // KVH, axis=1)
    run = jnp.repeat(jnp.cumsum(gamma, axis=0), H // KVH, axis=1)  # [S, H]
    out = []
    for lo in range(0, S, ROWS):
        hi = min(lo + ROWS, S)
        scores = _mm("shk,thk->hst", q[lo:hi], k[:hi], (2,), (2,), quant)
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        fade = jnp.exp(jnp.where(causal[None],
                                 run[lo:hi].T[:, :, None]
                                 - run[:hi].T[:, None, :], -jnp.inf))
        w = scores * scores * fade                              # [H, s, t]
        num = _mm("hst,thk->shk", w, v[:hi], (2,), (0,), quant)
        den = jnp.sum(w, axis=-1).T                             # [s, H]
        out.append(num / (den[..., None] + RETENTION_EPS))
    ctx = jnp.concatenate(out, axis=0)
    x = x + _mm("shk,hkd->sd", ctx, lp["wo"], (1, 2), (0, 1), quant)
    u = _rmsnorm(x, lp["ln2"], eps)
    up = _mm("sd,df->sf", u, lp["w_in"], (1,), (0,), quant)
    gate = _mm("sd,df->sf", u, lp["w_gate"], (1,), (0,), quant)
    return x + _mm("sf,fd->sd", up * jax.nn.silu(gate), lp["w_out"],
                   (1,), (0,), quant)


def left_behind(lp: Dict, x, last, *, eps: float, theta: float):
    """What the positions 0..``last`` of x [S, D] (the block's input)
    leave behind for a later query, as the closed form of the recurrence
    the published description gives, in float32 and with no symmetric
    square: for each key/value head the full tensors::

        M[v, i, j] = sum_{s <= last} exp(Gamma_last - Gamma_s) v_sv k_si k_sj
        n[i, j]    = sum_{s <= last} exp(Gamma_last - Gamma_s) k_si k_sj

    so that a query's numerator is ``sum_ij M[:, i, j] q_i q_j`` and its
    normaliser ``sum_ij n[i, j] q_i q_j``.  A program that carries a
    state holds these numbers, packed its own way.  Returns (M [KVH, HD,
    HD, HD], n [KVH, HD, HD]); one head at a time (a head's ``k_si k_sj``
    over 4736 positions are 310 MB)."""
    lp = {k: v.astype(F32) for k, v in lp.items()}
    _, k, v, gamma = _heads(lp, x, eps=eps, theta=theta, quant=False)
    run = jnp.cumsum(gamma, axis=0)                             # [S, KVH]
    kept = (jnp.arange(x.shape[0]) <= last)[:, None]
    fade = jnp.where(kept, jnp.exp(jnp.where(kept, run[last] - run, 0.0)),
                     0.0)                                       # [S, KVH]

    def head(args):
        k, v, fade = args                           # [S, HD], [S, HD], [S]
        kk = k[:, :, None] * k[:, None, :] * fade[:, None, None]
        return (jnp.einsum("sv,sij->vij", v, kk, precision=HI),
                jnp.sum(kk, axis=0))

    return jax.lax.map(head, (k.transpose(1, 0, 2), v.transpose(1, 0, 2),
                              fade.T))


def head_logits(head, ln_f, x, *, eps: float, quant: bool = False):
    return _mm("sd,vd->sv", _rmsnorm(x, ln_f.astype(F32), eps),
               head.astype(F32), (1,), (1,), quant)


class Forward:
    """Full forward passes over one padded token row, one compile a
    shape.  Padding at the end of a row never reaches an earlier position
    (the weights are causal; the feed-forward is row by row)."""

    def __init__(self, sizes: Dict, *, quant: bool = False):
        eps = float(sizes["rms_norm_eps"])
        self._embed = jax.jit(lambda e, t: e[t].astype(F32))
        self._layer = jax.jit(partial(
            layer, eps=eps, theta=float(sizes["rope_theta"]), quant=quant))
        self._head = jax.jit(partial(head_logits, eps=eps, quant=quant))
        self._left = jax.jit(partial(
            left_behind, eps=eps, theta=float(sizes["rope_theta"])))
        self._rows = jax.jit(jax.lax.dynamic_slice_in_dim,
                             static_argnums=(2,))

    def logits(self, weights: Dict, tokens, first: Optional[int] = None,
               count: Optional[int] = None, left_at: Optional[int] = None):
        """tokens [S] int32 -> logits [S, V] float32; of the ``count`` rows
        from row ``first`` on, where given (the head over 4736 rows of
        151 936 is 2.9 GB that nobody reads).  With ``left_at`` also what
        the positions up to that one leave behind in every block:
        (logits, [``left_behind`` a layer])."""
        x = self._embed(weights["embed"], tokens)
        stacked = weights["layers"]
        left = []
        for i in range(stacked["ln1"].shape[0]):
            lp = {k: v[i] for k, v in stacked.items()}
            if left_at is not None:
                left.append(self._left(lp, x, left_at))
            x = self._layer(lp, x)
        if first is not None:
            x = self._rows(x, first, count)
        logits = self._head(weights["head"], weights["ln_f"], x)
        return logits if left_at is None else (logits, left)
