"""Plain float32 reference of the latent-attention LM with a learned
indexer and group-limited routed experts that the ``deepseek-v3.2`` cell
serves (``deepseek-ai/DeepSeek-V3.2``'s ``config.json``, ``model_type``
``deepseek_v32``; the layer as the inference code of ``DeepSeek-V3.2-Exp``
computes it), for ONE chip's share of its deployment.

Independent of ``horovod_tpu``: no cache, no batching, no grouped product,
no absorbed form, no kernel, no threshold search.  One sequence at a time,
rows and heads in blocks so that 18 432 positions at the published widths
fit beside the weights; every matmul is float32 at ``precision="highest"``.
What ``reference/moe_lm.py`` says of its model holds here (pre-norm
residual blocks, RMSNorm with a gain, no biases, an untied head, the first
``first_k_dense_replace`` layers dense), with, in every layer and with
``x`` a normalised row, ``c_q`` and ``c`` the query's and the keys'
latents as there:

* **the indexer**: ``q_I = c_q W_Iq`` ``[HI, DI]`` with RoPE on its first
  ``rope`` dims; ``k_I = LayerNorm(x W_Ik)`` ``[DI]`` with RoPE on its
  first ``rope`` dims (eps 1e-6, gain and bias); ``w = x W_Iw / sqrt(HI
  DI)``; ``I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s])`` for ``s <=
  t``; ``S_t`` the ``min(index_topk, t + 1)`` positions of largest
  ``I[t, .]`` (``lax.top_k``: equal scores by position, lowest first);
* **attention over ``S_t`` alone**: ``softmax_{s in S_t}(q_h . k_h,s
  scale) v_h,s`` with ``scale = (nope + rope)^-1/2 m^2``, ``m = 0.1
  mscale_all_dim ln(factor) + 1`` (YaRN);
* **YaRN frequencies**: ``f_i = theta^(-2i/d)``; ``low, high`` = floor,
  ceil of ``d ln(original / (beta 2 pi)) / (2 ln theta)`` for ``beta_fast``,
  ``beta_slow``; ``r_i = clip((i - low) / (high - low), 0, 1)``; the
  frequency ``f_i ((1 - r_i) + r_i / factor)``, for the attention's and
  the indexer's rotary parts alike;
* **the router**: ``s = sigmoid(x W_r)`` over all ``n_routed_experts``;
  ``n_group`` groups, a group's score the sum of its two largest ``s +
  b``, the ``topk_group`` best groups kept; top-k of ``s + b`` inside
  them; weights ``routed_scaling_factor s_i / sum of the chosen s``;
* **this chip's part**: ``y = shared(x) + sum over the chosen experts e
  with expert_first <= e < expert_first + experts_held of w_e E_e(x)``.
  What the other experts would add is left out and that partial sum goes
  on to the next layer (the ``model-configs`` guide's cut).  The
  vocabulary slice is a smaller vocabulary.

Departures from the published model, all under ``assumed`` in the
configuration file: seeded weights; the rotary pairing (dim ``i`` with
``i + rope/2``); no Hadamard rotation of ``q_I`` and ``k_I`` (orthogonal:
it changes no score, and serves an FP8 cast that is not made); a seeded
selection bias; the multi-token-prediction layer neither held nor run.

``quant`` rounds every matmul operand that the program holds in bfloat16
to int8, the indexer's among them: the control that ``correct`` has to
fail.  The router, float32 in the program, is not rounded.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.moe_lm import (BIAS_STD, F32, HI, INIT_STD, _gated,
                                        _mm, _rmsnorm, head_logits)

ROWS = 2048             # rows of a sequence a block holds, at most


def make_weights(key, sizes: Dict) -> Dict:
    """Seeded bfloat16 weights in the layout the program serves: as
    ``reference/moe_lm.py`` makes them, the routed experts' stacks holding
    ``experts_held`` of the ``n_routed_experts`` the router scores, and
    every layer with the indexer's three projections and its key's
    LayerNorm (gain one, bias zero)."""
    D, V = sizes["hidden_size"], sizes["vocab_size"]
    F, Fe = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    Fs = sizes["n_shared_experts"] * Fe
    H, E, Eh = (sizes["num_attention_heads"], sizes["n_routed_experts"],
                sizes["experts_held"])
    Rq, Rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope, vd = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    HIx, DI = sizes["index_n_heads"], sizes["index_head_dim"]
    L = sizes["num_hidden_layers"]
    Ld = sizes["first_k_dense_replace"]
    Lm = L - Ld
    out_std = INIT_STD / math.sqrt(2 * L)
    keys = iter(jax.random.split(key, 48))
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
                "ln2": jnp.ones((n, D), bf16),
                "wi_q": normal((n, Rq, HIx, DI), INIT_STD),
                "wi_k": normal((n, D, DI), INIT_STD),
                "wi_k_gain": jnp.ones((n, DI), bf16),
                "wi_k_bias": jnp.zeros((n, DI), bf16),
                "wi_w": normal((n, D, HIx), INIT_STD)}

    dense = {**attention(Ld),
             "w_in": normal((Ld, D, F), INIT_STD),
             "w_gate": normal((Ld, D, F), INIT_STD),
             "w_out": normal((Ld, F, D), out_std)}
    moe = {**attention(Lm),
           "router": normal((Lm, D, E), INIT_STD),
           "router_bias": normal((Lm, E), BIAS_STD, F32),
           "w_in": normal((Lm, Eh, D, Fe), INIT_STD),
           "w_gate": normal((Lm, Eh, D, Fe), INIT_STD),
           "w_out": normal((Lm, Eh, Fe, D), out_std),
           "shared_in": normal((Lm, D, Fs), INIT_STD),
           "shared_gate": normal((Lm, D, Fs), INIT_STD),
           "shared_out": normal((Lm, Fs, D), out_std)}
    return {"embed": normal((V, D), INIT_STD), "dense": dense, "moe": moe,
            "ln_f": jnp.ones((D,), bf16), "head": normal((V, D), INIT_STD)}


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------


def yarn_frequencies(dim: int, theta: float, scaling: Optional[Dict]):
    """The ``dim / 2`` rotary frequencies, float64 (module docstring)."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    if scaling is None:
        return f

    def dim_of(beta):
        return dim * math.log(scaling["original_max_position_embeddings"]
                              / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim_of(scaling["beta_slow"])), dim - 1)
    r = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * ((1 - r) + r / scaling["factor"])


def softmax_scale(width: int, scaling: Optional[Dict]) -> float:
    m = 1.0
    if scaling is not None and scaling.get("mscale_all_dim"):
        m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1
    return m * m / math.sqrt(width)


def _rope(x, freqs):
    """x: [S, ..., R], position = row.  Dim ``i`` turns with dim ``i +
    R/2`` by the angle ``position * freqs[i]``."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freqs
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(n: int) -> int:
    """Rows a block: the largest divisor of ``n`` not over ``ROWS``."""
    return max(b for b in range(1, min(n, ROWS) + 1) if n % b == 0)


def _by_rows(fn, *arrays):
    """``fn`` over blocks of the arrays' rows (their first axis), the
    results joined: what exists at once is a block's."""
    n = arrays[0].shape[0]
    b = _blocks(n)
    if b == n:
        return fn(*arrays)
    out = jax.lax.map(lambda xs: fn(*xs), tuple(
        a.reshape((n // b, b) + a.shape[1:]) for a in arrays))
    return out.reshape((n,) + out.shape[2:])


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------


def selection(lp: Dict, u, c_q, *, freqs, rope: int, top: int, quant: bool):
    """Which positions each position attends: [S, S] bool, row t true at
    the ``min(top, t + 1)`` positions ``s <= t`` of largest index score."""
    S = u.shape[0]
    heads, width = lp["wi_q"].shape[1:]
    q = _mm("sr,rhk->shk", c_q, lp["wi_q"].astype(F32), (1,), (0,), quant)
    q = jnp.concatenate([_rope(q[..., :rope], freqs), q[..., rope:]], -1)
    k = _mm("sd,dk->sk", u, lp["wi_k"].astype(F32), (1,), (0,), quant)
    k = k - jnp.mean(k, -1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True) + 1e-6)
    k = k * lp["wi_k_gain"].astype(F32) + lp["wi_k_bias"].astype(F32)
    k = jnp.concatenate([_rope(k[:, :rope], freqs), k[:, rope:]], -1)
    w = _mm("sd,dh->sh", u, lp["wi_w"].astype(F32), (1,), (0,), quant
            ) / math.sqrt(heads * width)

    def rows(q_b, w_b, t_b):        # a block of query rows against all keys
        def one_head(h, total):
            s = _mm("tk,sk->ts", q_b[:, h], k, (1,), (1,), quant)
            return total + w_b[:, h, None] * jax.nn.relu(s)

        scores = jax.lax.fori_loop(
            0, heads, one_head, jnp.zeros((q_b.shape[0], S), F32))
        causal = jnp.arange(S)[None, :] <= t_b[:, None]
        _, best = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                                min(top, S))
        picked = jnp.zeros_like(causal).at[
            jnp.arange(q_b.shape[0])[:, None], best].set(True)
        return picked & causal

    return _by_rows(rows, q, w, jnp.arange(S))


def attention(lp: Dict, x, *, eps, freqs, scaling, top, quant: bool):
    """x: [S, D] float32.  The expanded form, a head at a time: every key
    and value is made from its latent, and a row attends the positions
    the indexer selected for it."""
    S = x.shape[0]
    Rkv = lp["kv_norm"].shape[0]
    H, nope = lp["w_uk"].shape[1:]
    rope = lp["wq_b"].shape[-1] - nope
    up = {k: lp[k].astype(F32) for k in ("ln1", "q_norm", "kv_norm")}
    u = _rmsnorm(x, up["ln1"], eps)
    c_q = _rmsnorm(_mm("sd,dr->sr", u, lp["wq_a"].astype(F32), (1,), (0,),
                       quant), up["q_norm"], eps)
    ckr = _mm("sd,dr->sr", u, lp["wkv_a"].astype(F32), (1,), (0,), quant)
    c = _rmsnorm(ckr[:, :Rkv], up["kv_norm"], eps)
    k_r = _rope(ckr[:, Rkv:], freqs)
    seen = selection(lp, u, c_q, freqs=freqs, rope=rope, top=top,
                     quant=quant)
    scale = softmax_scale(nope + rope, scaling)

    def one_head(h, out):
        def at(name):
            return jax.lax.dynamic_index_in_dim(
                lp[name], h, lp[name].ndim - 2, keepdims=False).astype(F32)

        q = _mm("sr,rk->sk", c_q, at("wq_b"), (1,), (0,), quant)
        q = jnp.concatenate([q[:, :nope], _rope(q[:, nope:], freqs)], -1)
        k = jnp.concatenate(
            [_mm("sc,ck->sk", c, at("w_uk"), (1,), (0,), quant), k_r], -1)
        v = _mm("sc,ck->sk", c, at("w_uv"), (1,), (0,), quant)

        def rows(q_b, seen_b):
            scores = _mm("tk,sk->ts", q_b, k, (1,), (1,), quant) * scale
            probs = jax.nn.softmax(jnp.where(seen_b, scores, -1e30), axis=-1)
            return _mm("ts,sk->tk", probs, v, (1,), (0,), quant)

        ctx = _by_rows(rows, q, seen)                           # [S, v]
        wo = jax.lax.dynamic_index_in_dim(lp["wo"], h, 0, keepdims=False)
        return out + _mm("sk,kd->sd", ctx, wo.astype(F32), (1,), (0,), quant)

    return x + jax.lax.fori_loop(0, H, one_head, jnp.zeros_like(x))


def routing(u, router, bias, *, top_k: int, scale: float, n_group: int,
            topk_group: int):
    """The weight of every routed expert for every row, [S, E] float32:
    zero where the row did not choose the expert."""
    s = jax.nn.sigmoid(jnp.einsum("sd,de->se", u, router, precision=HI,
                                  preferred_element_type=F32))
    biased = s + bias
    S, E = s.shape
    groups = biased.reshape(S, n_group, E // n_group)
    two_best, _ = jax.lax.top_k(groups, min(2, E // n_group))
    _, kept = jax.lax.top_k(jnp.sum(two_best, -1), topk_group)
    keep = jnp.zeros((S, n_group), bool).at[
        jnp.arange(S)[:, None], kept].set(True)
    allowed = jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(S, E)
    _, chosen = jax.lax.top_k(allowed, top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = scale * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return jnp.zeros_like(s).at[jnp.arange(S)[:, None], chosen].set(w)


def _ffn(u, w_in, w_gate, w_out, quant: bool):
    return _by_rows(lambda rows: _gated(rows, w_in.astype(F32),
                                        w_gate.astype(F32),
                                        w_out.astype(F32), quant), u)


def dense_layer(lp: Dict, x, *, eps, quant=False, **attn):
    x = attention(lp, x, eps=eps, quant=quant, **attn)
    u = _rmsnorm(x, lp["ln2"].astype(F32), eps)
    return x + _ffn(u, lp["w_in"], lp["w_gate"], lp["w_out"], quant)


def moe_layer(lp: Dict, x, *, eps, top_k, scale, n_group, topk_group,
              expert_first, quant=False, **attn):
    x = attention(lp, x, eps=eps, quant=quant, **attn)
    u = _rmsnorm(x, lp["ln2"].astype(F32), eps)
    w = routing(u, lp["router"].astype(F32), lp["router_bias"], top_k=top_k,
                scale=scale, n_group=n_group, topk_group=topk_group)

    def one_expert(e, y):       # the e-th expert held here
        w_in, w_gate, w_out = (
            jax.lax.dynamic_index_in_dim(lp[k], e, 0, keepdims=False)
            for k in ("w_in", "w_gate", "w_out"))
        w_e = jax.lax.dynamic_slice_in_dim(w, expert_first + e, 1, axis=1)
        return y + w_e * _ffn(u, w_in, w_gate, w_out, quant)

    y = jax.lax.fori_loop(0, lp["w_in"].shape[0], one_expert,
                          jnp.zeros_like(x))
    return x + y + _ffn(u, lp["shared_in"], lp["shared_gate"],
                        lp["shared_out"], quant)


class Forward:
    """Full forward passes over one padded token row, one compile a
    shape.  Padding at the end of a row never reaches an earlier position
    (causal attention and selection; the feed-forward is row by row)."""

    def __init__(self, sizes: Dict, *, quant: bool = False):
        scaling = sizes.get("rope_scaling")
        freqs = jnp.asarray(yarn_frequencies(
            sizes["qk_rope_head_dim"], float(sizes["rope_theta"]), scaling),
            F32)
        kw = dict(eps=float(sizes["rms_norm_eps"]), freqs=freqs,
                  scaling=scaling, top=int(sizes["index_topk"]), quant=quant)
        self._embed = jax.jit(lambda e, t: e[t].astype(F32))
        self._dense = jax.jit(partial(dense_layer, **kw))
        self._moe = jax.jit(partial(
            moe_layer, top_k=int(sizes["num_experts_per_tok"]),
            scale=float(sizes["routed_scaling_factor"]),
            n_group=int(sizes["n_group"]),
            topk_group=int(sizes["topk_group"]),
            expert_first=int(sizes["expert_first"]), **kw))
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
        from row ``first`` on, where given."""
        x = self.hidden(weights, tokens)
        if first is not None:
            x = self._rows(x, first, count)
        return self._head(weights["head"], weights["ln_f"], x)
