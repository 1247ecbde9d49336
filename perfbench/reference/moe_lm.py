"""Plain float32 reference of the routed-expert, latent-attention LM the
``glm-4.7-flash`` cell serves (``zai-org/GLM-4.7-Flash``'s ``config.json``,
``model_type`` ``glm4_moe_lite``).

Independent of ``horovod_tpu``: no cache, no batching, no grouped product,
no absorbed form, no kernel.  One sequence at a time; every matmul is
float32 at ``precision="highest"``.  The model, from the configuration's
keys (RMSNorm has a gain, eps ``rms_norm_eps``; no biases):

* ``num_hidden_layers`` pre-norm residual blocks ``h += Attn(RMSNorm(h));
  h += FFN(RMSNorm(h))``, a final RMSNorm, an **untied** head.  The first
  ``first_k_dense_replace`` layers' feed-forward is dense and gated, width
  ``intermediate_size``; the others are expert layers;
* attention (MLA), every layer, ``H = num_attention_heads`` heads, with
  ``x`` a normalised row::

      c_q         = RMSNorm(x W_qa)                 W_qa  [D, q_lora_rank]
      [q_n | q_r] = c_q W_qb  per head              W_qb  [q_lora_rank, H, nope + rope]
      [c | k_r]   = x W_kva                         W_kva [D, kv_lora_rank + rope]
      c = RMSNorm(c);  k_r = RoPE(k_r)              one rotary key, shared by all heads
      [k_n | v]   = c W_kvb  per head               W_kvb [kv_lora_rank, H, nope + v]
      q_h = [q_n,h | RoPE(q_r,h)]   k_h = [k_n,h | k_r]
      out = concat_h(softmax_causal(q_h k_h^T / sqrt(nope + rope)) v_h) W_o

  RoPE theta ``rope_theta`` over the ``qk_rope_head_dim`` rotary dims
  (``partial_rotary_factor`` 1, ``rope_scaling`` null).  ``W_kvb`` is held
  as its two halves, ``w_uk`` (the ``k_n`` columns) and ``w_uv`` (the
  ``v`` columns): the same matrix, cut where the absorbed form cuts it;
* routed feed-forward (``topk_method`` ``noaux_tc``, ``n_group`` 1,
  ``topk_group`` 1: no group limit)::

      s      = sigmoid(x W_r)  in float32           W_r [D, n_routed_experts]
      chosen = top-k of (s + b)                     b: e_score_correction_bias, selects only
      w_i    = routed_scaling_factor * s_i / (sum_{j in chosen} s_j + 1e-20)
      y      = sum_{i in chosen} w_i E_i(x) + E_shared(x)
      E(x)   = W_down(silu(W_gate x) * W_up x)      width moe_intermediate_size

  (``norm_topk_prob`` true.)  No capacity, no dropped row.  Here every
  expert is computed for every row and masked by its weight (zero where
  the row did not choose it): 16 times the needed work, and plain.

Departures from the published model, all under ``assumed`` in the
configuration file: the weights are seeded, not the checkpoint's; the
rotary pairing is dim ``i`` with ``i + rope/2`` (the checkpoint's
interleaved pairing is a fixed permutation of ``W_qb``'s and ``W_kva``'s
rotary columns: with seeded weights the same distribution); ``b`` is
seeded (normal, sigma 0.01), not zero, so that selecting by ``s + b`` and
weighting by ``s`` differ; the multi-token-prediction layer
(``num_nextn_predict_layers``) is no part of the next-token forward pass
and is neither held nor run.

Weights are made here from the seed, leaf by leaf, in **bfloat16** (never
whole in float32) and in the layout the program serves (layers of a kind
stacked on a leading axis), and handed to both sides.  The reference
upcasts one layer at a time, an expert layer's experts one at a time.

``quant`` rounds every matmul operand that the program holds in bfloat16
to int8 (symmetric, absmax scale along the contraction axis, float32
accumulation): the control that ``correct`` has to fail.  The router,
float32 in the program, is not rounded.
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
BIAS_STD = 0.01
F32 = jnp.float32


def make_weights(key, sizes: Dict) -> Dict:
    """Seeded bfloat16 weights: matrices normal(0, 0.02), output
    projections (attention o, feed-forward down, every expert's down)
    scaled by 1/sqrt(2 L), gains one, the router's selection bias
    normal(0, 0.01) in float32.  One small program a leaf."""
    D, V = sizes["hidden_size"], sizes["vocab_size"]
    F, Fe = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    Fs = sizes["n_shared_experts"] * Fe
    H, E = sizes["num_attention_heads"], sizes["n_routed_experts"]
    Rq, Rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope, vd = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    L = sizes["num_hidden_layers"]
    Ld = sizes["first_k_dense_replace"]
    Lm = L - Ld
    out_std = INIT_STD / math.sqrt(2 * L)
    keys = iter(jax.random.split(key, 40))
    bf16 = jnp.bfloat16

    def normal(shape, std, dtype=bf16):
        return jax.jit(lambda k: (jax.random.normal(k, shape, F32) * std
                                  ).astype(dtype))(next(keys))

    def attention(n):
        return {"ln1": jnp.ones((n, D), bf16),
                "wq_a": normal((n, D, Rq), INIT_STD),
                "q_norm": jnp.ones((n, Rq), bf16),
                "wq_b": normal((n, Rq, H, nope + rope), INIT_STD),
                "wkv_a": normal((n, D, Rkv + rope), INIT_STD),
                "kv_norm": jnp.ones((n, Rkv), bf16),
                "w_uk": normal((n, Rkv, H, nope), INIT_STD),
                "w_uv": normal((n, Rkv, H, vd), INIT_STD),
                "wo": normal((n, H, vd, D), out_std),
                "ln2": jnp.ones((n, D), bf16)}

    dense = {**attention(Ld),
             "w_in": normal((Ld, D, F), INIT_STD),
             "w_gate": normal((Ld, D, F), INIT_STD),
             "w_out": normal((Ld, F, D), out_std)}
    moe = {**attention(Lm),
           "router": normal((Lm, D, E), INIT_STD),
           "router_bias": normal((Lm, E), BIAS_STD, F32),
           "w_in": normal((Lm, E, D, Fe), INIT_STD),
           "w_gate": normal((Lm, E, D, Fe), INIT_STD),
           "w_out": normal((Lm, E, Fe, D), out_std),
           "shared_in": normal((Lm, D, Fs), INIT_STD),
           "shared_gate": normal((Lm, D, Fs), INIT_STD),
           "shared_out": normal((Lm, Fs, D), out_std)}
    return {"embed": normal((V, D), INIT_STD), "dense": dense, "moe": moe,
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
    """x: [S, ..., R], position = row.  Dim ``i`` turns with dim ``i +
    R/2`` by the angle ``position * theta^(-i / (R/2))``."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freqs
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _upcast(lp: Dict, but=()) -> Dict:
    return {k: v if k in but else v.astype(F32) for k, v in lp.items()}


def attention(lp: Dict, x, *, eps: float, theta: float, quant: bool):
    """x: [S, D] float32; ``lp`` float32.  The expanded form: every key
    and value is made from its latent, head by head."""
    S = x.shape[0]
    Rkv = lp["kv_norm"].shape[0]
    nope = lp["w_uk"].shape[-1]
    u = _rmsnorm(x, lp["ln1"], eps)
    c_q = _rmsnorm(_mm("sd,dr->sr", u, lp["wq_a"], (1,), (0,), quant),
                   lp["q_norm"], eps)
    q = _mm("sr,rhk->shk", c_q, lp["wq_b"], (1,), (0,), quant)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    ckr = _mm("sd,dr->sr", u, lp["wkv_a"], (1,), (0,), quant)
    c = _rmsnorm(ckr[:, :Rkv], lp["kv_norm"], eps)
    k_r = _rope(ckr[:, Rkv:], theta)
    k_n = _mm("sc,chk->shk", c, lp["w_uk"], (1,), (0,), quant)
    v = _mm("sc,chk->shk", c, lp["w_uv"], (1,), (0,), quant)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r[:, None], k_n.shape[:2] + k_r.shape[1:])],
        -1)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scale = 1.0 / math.sqrt(q.shape[-1])

    def one_head(qkv):
        q_h, k_h, v_h = qkv                                  # [S, .]
        scores = _mm("sk,tk->st", q_h, k_h, (1,), (1,), quant) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        return _mm("st,tk->sk", probs, v_h, (1,), (0,), quant)

    # One head's [S, S] scores at a time: 4608 positions fit beside the
    # weights.
    ctx = jax.lax.map(one_head, tuple(
        jnp.swapaxes(t, 0, 1) for t in (q, k, v)))           # [H, S, vd]
    return x + _mm("hsk,hkd->sd", ctx, lp["wo"], (0, 2), (0, 1), quant)


def _gated(u, w_in, w_gate, w_out, quant: bool):
    up = _mm("sd,df->sf", u, w_in, (1,), (0,), quant)
    gate = _mm("sd,df->sf", u, w_gate, (1,), (0,), quant)
    return _mm("sf,fd->sd", up * jax.nn.silu(gate), w_out, (1,), (0,), quant)


def routing(u, router, bias, *, top_k: int, scale: float):
    """The weight of every expert for every row, [S, E] float32: zero
    where the row did not choose the expert."""
    s = jax.nn.sigmoid(jnp.einsum("sd,de->se", u, router, precision=HI,
                                  preferred_element_type=F32))
    _, chosen = jax.lax.top_k(s + bias, top_k)               # [S, k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = scale * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(w)


def dense_layer(lp: Dict, x, *, eps, theta, quant=False):
    lp = _upcast(lp)
    x = attention(lp, x, eps=eps, theta=theta, quant=quant)
    u = _rmsnorm(x, lp["ln2"], eps)
    return x + _gated(u, lp["w_in"], lp["w_gate"], lp["w_out"], quant)


def moe_layer(lp: Dict, x, *, eps, theta, top_k, scale, quant=False):
    experts = ("w_in", "w_gate", "w_out")
    lp = _upcast(lp, but=experts)
    x = attention(lp, x, eps=eps, theta=theta, quant=quant)
    u = _rmsnorm(x, lp["ln2"], eps)
    w = routing(u, lp["router"], lp["router_bias"], top_k=top_k, scale=scale)

    def one_expert(e, y):
        w_in, w_gate, w_out = (
            jax.lax.dynamic_index_in_dim(lp[k], e, 0, keepdims=False
                                         ).astype(F32) for k in experts)
        w_e = jax.lax.dynamic_slice_in_dim(w, e, 1, axis=1)  # [S, 1]
        return y + w_e * _gated(u, w_in, w_gate, w_out, quant)

    y = jax.lax.fori_loop(0, w.shape[1], one_expert, jnp.zeros_like(x))
    y = y + _gated(u, lp["shared_in"], lp["shared_gate"], lp["shared_out"],
                   quant)
    return x + y


def head_logits(head, ln_f, x, *, eps: float, quant: bool = False):
    return _mm("sd,vd->sv", _rmsnorm(x, ln_f.astype(F32), eps),
               head.astype(F32), (1,), (1,), quant)


class Forward:
    """Full forward passes over one padded token row, one compile a
    shape.  Padding at the end of a row never reaches an earlier position
    (causal attention; the feed-forward is row by row)."""

    def __init__(self, sizes: Dict, *, quant: bool = False):
        kw = dict(eps=float(sizes["rms_norm_eps"]),
                  theta=float(sizes["rope_theta"]), quant=quant)
        self._embed = jax.jit(lambda e, t: e[t].astype(F32))
        self._dense = jax.jit(partial(dense_layer, **kw))
        self._moe = jax.jit(partial(
            moe_layer, top_k=int(sizes["num_experts_per_tok"]),
            scale=float(sizes["routed_scaling_factor"]), **kw))
        self._head = jax.jit(partial(head_logits, eps=kw["eps"], quant=quant))
        self._rows = jax.jit(jax.lax.dynamic_slice_in_dim,
                             static_argnums=(2,))

    def hidden(self, weights: Dict, tokens) -> jax.Array:
        """tokens [S] int32 -> the last layer's output [S, D] float32."""
        x = self._embed(weights["embed"], tokens)
        for kind, layer in (("dense", self._dense), ("moe", self._moe)):
            stacked = weights[kind]
            for i in range(stacked["ln1"].shape[0]):
                x = layer({k: v[i] for k, v in stacked.items()}, x)
        return x

    def logits(self, weights: Dict, tokens, first: Optional[int] = None,
               count: Optional[int] = None) -> jax.Array:
        """tokens [S] int32 -> logits [S, V] float32; of the ``count`` rows
        from row ``first`` on, where given (the head over 4608 rows of
        154 880 is 2.9 GB that nobody reads)."""
        x = self.hidden(weights, tokens)
        if first is not None:
            x = self._rows(x, first, count)
        return self._head(weights["head"], weights["ln_f"], x)
