"""Plain float32 reference of the short-convolution / attention /
routed-expert LM that the ``lfm2-8b-a1b`` cell serves
(``LiquidAI/LFM2-8B-A1B``'s ``config.json``, ``model_type`` ``lfm2_moe``;
the layer as Hugging Face's ``lfm2_moe`` computes it).

Independent of ``horovod_tpu``: no cache, **no kept window**, no batching,
no grouped product, no kernel.  One sequence at a time; every matmul is
float32 at ``precision="highest"``.  The model, from the configuration's
keys (RMSNorm has a gain, eps ``norm_eps``; no bias anywhere):

* block ``l``: ``h = h + op_l(RMSNorm(h))``, then ``h = h +
  ff_l(RMSNorm(h))``; ``op_l`` by ``layer_types[l]``, ``ff_l`` dense
  (width ``intermediate_size``) for ``l < num_dense_layers``, routed from
  there on; a final RMSNorm and the head, the embedding transposed;
* ``conv``: ``[B | C | x] = u W_in`` (three parts of ``hidden_size``);
  ``z = B x``; ``c_t = sum_j w_j z_{t - (K - 1) + j}`` with ``K =
  conv_L_cache``, zeros before the start, no activation, over the WHOLE
  sequence by shifted sums; out ``= (C c) W_out``;
* ``full_attention``: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` key/value heads of ``hidden_size /
  num_attention_heads``; an RMSNorm over each head's values of q and of k
  (one gain ``[head_dim]`` each, shared by the heads); the rotation of q
  and k (``moe_lm._rope``: dim ``i`` with ``i + head_dim / 2``, base
  ``rope_theta``, position = row); the full masked softmax at
  1/sqrt(head_dim);
* dense feed-forward ``W_2(silu(W_1 x) W_3 x)`` (``w_gate`` is ``W_1``,
  ``w_in`` ``W_3``, ``w_out`` ``W_2``);
* routed feed-forward: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok``
  largest of ``s + b``; ``w_i = routed_scaling_factor s_i / (sum of the
  chosen s + 1e-20)`` (``moe_lm.routing``: a dense ``[S, num_experts]``
  matrix of weights, zero where the row did not choose the expert); EVERY
  expert applied to every row in a loop and weighted by its column; no
  shared expert.

Departures from the published model, all under ``assumed`` in the
configuration file: seeded weights and how each leaf is seeded; the
selection bias a seeded constant; the tied head; the normaliser's epsilon
(1e-20, the program's).

Weights are made here from the seed, leaf by leaf, in **bfloat16** (never
whole in float32) and in the layout the program serves (layers of a kind
stacked on a leading axis, the experts' stacks ``padded_width`` wide
with zeros past the published width), and handed to both sides.  The reference reads the published corner and upcasts one
layer at a time, an expert layer's experts one at a time.

``quant`` rounds every matmul operand that the program holds in bfloat16
to int8 (symmetric, absmax scale along the contraction axis, float32
accumulation): the control that ``correct`` has to fail.  The router and
the convolution's sum, float32 in the program, are not rounded.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from perfbench.reference.moe_lm import (BIAS_STD, F32, INIT_STD, _gated, _mm,
                                        _rmsnorm, _rope, _upcast,
                                        head_logits, routing)
from perfbench.reference.ssd_moe_lm import padded_width

OPERATORS = {"conv": "conv", "full_attention": "attn"}
ROWS = 1024             # rows of a sequence an attention block holds
_EXPERTS = ("w_gate", "w_in", "w_out")


def layer_kinds(sizes: Dict) -> List[Tuple[str, str]]:
    """(operator, feed-forward) of each layer, as the stacks' names."""
    return [(OPERATORS[t], "dense" if l < sizes["num_dense_layers"]
             else "moe") for l, t in enumerate(sizes["layer_types"])]


def make_weights(key, sizes: Dict) -> Dict:
    """Seeded bfloat16 weights: matrices normal(0, 0.02), the output
    projections (the convolution's, attention o, every feed-forward's and
    expert's down) scaled by 1/sqrt(2 L); the convolution uniform in
    +-1/sqrt(conv_L_cache); gains one; the router's selection bias
    normal(0, 0.01) in float32.  One small program a leaf."""
    D, V = sizes["hidden_size"], sizes["vocab_size"]
    H, KVH = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    HD = D // H
    F, Fe = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    E, K = sizes["num_experts"], sizes["conv_L_cache"]
    kinds = layer_kinds(sizes)
    Lc, La, Ld, Le = (sum(k in pair for pair in kinds)
                      for k in ("conv", "attn", "dense", "moe"))
    out_std = INIT_STD / math.sqrt(2 * len(kinds))
    keys = iter(jax.random.split(key, 24))
    bf16 = jnp.bfloat16

    def normal(shape, std, dtype=bf16):
        return jax.jit(lambda k: (jax.random.normal(k, shape, F32) * std
                                  ).astype(dtype))(next(keys))

    def held(w, axis):      # the program's layout: zeros past the width
        pad = [(0, 0)] * w.ndim
        pad[axis] = (0, padded_width(Fe) - Fe)
        return jax.jit(lambda a: jnp.pad(a, pad))(w)

    bound = 1.0 / math.sqrt(K)
    conv = {
        "ln": jnp.ones((Lc, D), bf16),
        "in_proj": normal((Lc, D, 3 * D), INIT_STD),
        "conv_w": jax.random.uniform(next(keys), (Lc, K, D), F32, -bound,
                                     bound).astype(bf16),
        "out_proj": normal((Lc, D, D), out_std)}
    attn = {
        "ln": jnp.ones((La, D), bf16),
        "wq": normal((La, D, H, HD), INIT_STD),
        "wk": normal((La, D, KVH, HD), INIT_STD),
        "wv": normal((La, D, KVH, HD), INIT_STD),
        "q_norm": jnp.ones((La, HD), bf16),
        "k_norm": jnp.ones((La, HD), bf16),
        "wo": normal((La, H, HD, D), out_std)}
    dense = {
        "ln": jnp.ones((Ld, D), bf16),
        "w_gate": normal((Ld, D, F), INIT_STD),
        "w_in": normal((Ld, D, F), INIT_STD),
        "w_out": normal((Ld, F, D), out_std)}
    moe = {
        "ln": jnp.ones((Le, D), bf16),
        "router": normal((Le, D, E), INIT_STD),
        "router_bias": normal((Le, E), BIAS_STD, F32),
        "w_gate": held(normal((Le, E, D, Fe), INIT_STD), 3),
        "w_in": held(normal((Le, E, D, Fe), INIT_STD), 3),
        "w_out": held(normal((Le, E, Fe, D), out_std), 2)}
    return {"embed": normal((V, D), INIT_STD), "conv": conv, "attn": attn,
            "dense": dense, "moe": moe, "ln_f": jnp.ones((D,), bf16)}


# ---------------------------------------------------------------------------
# the model, one sequence, one layer's operator or feed-forward at a time
# ---------------------------------------------------------------------------


def conv_operator(lp: Dict, x, *, eps: float, quant: bool = False):
    """x: [S, D] float32.  The convolution's sum is float32 whatever
    ``quant`` says: only matmul operands are rounded."""
    lp = _upcast(lp)
    S, D = x.shape
    K = lp["conv_w"].shape[0]
    u = _rmsnorm(x, lp["ln"], eps)
    bcx = _mm("sd,de->se", u, lp["in_proj"], (1,), (0,), quant)
    gate_in, gate_out, xs = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
    z = jnp.pad(gate_in * xs, [(K - 1, 0), (0, 0)])
    c = sum(lp["conv_w"][j] * z[j:j + S] for j in range(K))
    return x + _mm("se,ed->sd", gate_out * c, lp["out_proj"], (1,), (0,),
                   quant)


def attention_operator(lp: Dict, x, *, eps: float, theta: float,
                       quant: bool = False):
    """x: [S, D] float32.  Query head ``h`` reads key/value head ``h //
    (H / KVH)``; a head at a time, query rows in blocks of ``ROWS``."""
    lp = _upcast(lp)
    S = x.shape[0]
    H, HD = lp["wq"].shape[1:]
    KVH = lp["wk"].shape[1]
    u = _rmsnorm(x, lp["ln"], eps)
    q = _mm("sd,dhk->shk", u, lp["wq"], (1,), (0,), quant)
    k = _mm("sd,dhk->shk", u, lp["wk"], (1,), (0,), quant)
    v = _mm("sd,dhk->hsk", u, lp["wv"], (1,), (0,), quant)
    q = _rope(_rmsnorm(q, lp["q_norm"], eps), theta).swapaxes(0, 1)
    k = _rope(_rmsnorm(k, lp["k_norm"], eps), theta).swapaxes(0, 1)
    block = max(b for b in range(1, min(S, ROWS) + 1) if S % b == 0)

    def one_head(h, out):
        q_h = jax.lax.dynamic_index_in_dim(q, h, 0, keepdims=False)
        k_h = jax.lax.dynamic_index_in_dim(k, h // (H // KVH), 0, False)
        v_h = jax.lax.dynamic_index_in_dim(v, h // (H // KVH), 0, False)

        def rows(t):
            q_b, first = t
            scores = _mm("tk,sk->ts", q_b, k_h, (1,), (1,), quant
                         ) / math.sqrt(HD)
            seen = jnp.arange(S)[None, :] \
                <= (first + jnp.arange(block))[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
            return _mm("ts,sk->tk", probs, v_h, (1,), (0,), quant)

        ctx = jax.lax.map(rows, (q_h.reshape(S // block, block, HD),
                                 jnp.arange(0, S, block))).reshape(S, HD)
        wo = jax.lax.dynamic_index_in_dim(lp["wo"], h, 0, keepdims=False)
        return out + _mm("sk,kd->sd", ctx, wo, (1,), (0,), quant)

    return x + jax.lax.fori_loop(0, H, one_head, jnp.zeros_like(x))


def dense_ffn(lp: Dict, x, *, eps: float, quant: bool = False):
    lp = _upcast(lp)
    u = _rmsnorm(x, lp["ln"], eps)
    return x + _gated(u, lp["w_in"], lp["w_gate"], lp["w_out"], quant)


def moe_ffn(lp: Dict, x, *, eps: float, top_k: int, scale: float,
            width: int, quant: bool = False):
    """x: [S, D] float32.  Every expert over every row, weighted by the
    row's weight for it; an expert is the published ``[D, width]`` and
    ``[width, D]`` corner of its held stack."""
    u = _rmsnorm(x, lp["ln"].astype(F32), eps)
    w = routing(u, lp["router"].astype(F32), lp["router_bias"], top_k=top_k,
                scale=scale)

    def one_expert(e, y):
        w_gate, w_in, w_out = (
            jax.lax.dynamic_index_in_dim(lp[k], e, 0, keepdims=False
                                         ).astype(F32) for k in _EXPERTS)
        w_e = jax.lax.dynamic_slice_in_dim(w, e, 1, axis=1)  # [S, 1]
        return y + w_e * _gated(u, w_in[:, :width], w_gate[:, :width],
                                w_out[:width], quant)

    return x + jax.lax.fori_loop(0, w.shape[1], one_expert,
                                 jnp.zeros_like(x))


class Forward:
    """Full forward passes over one padded token row, one compile a
    shape.  Padding at the end of a row never reaches an earlier position
    (causal attention, a causal convolution; the feed-forward is row by
    row)."""

    def __init__(self, sizes: Dict, *, quant: bool = False):
        self.kinds = layer_kinds(sizes)
        eps = float(sizes["norm_eps"])
        self._embed = jax.jit(lambda e, t: e[t].astype(F32))
        self._part = {
            "conv": jax.jit(partial(conv_operator, eps=eps, quant=quant)),
            "attn": jax.jit(partial(
                attention_operator, eps=eps,
                theta=float(sizes["rope_theta"]), quant=quant)),
            "dense": jax.jit(partial(dense_ffn, eps=eps, quant=quant)),
            "moe": jax.jit(partial(
                moe_ffn, eps=eps, top_k=int(sizes["num_experts_per_tok"]),
                scale=float(sizes["routed_scaling_factor"]),
                width=int(sizes["moe_intermediate_size"]), quant=quant))}
        self._head = jax.jit(partial(head_logits, eps=eps, quant=quant))
        self._rows = jax.jit(jax.lax.dynamic_slice_in_dim,
                             static_argnums=(2,))

    def hidden(self, weights: Dict, tokens) -> jax.Array:
        """tokens [S] int32 -> the last layer's output [S, D] float32."""
        x = self._embed(weights["embed"], tokens)
        seen = dict.fromkeys(self._part, 0)
        for pair in self.kinds:
            for kind in pair:
                lp = {k: v[seen[kind]] for k, v in weights[kind].items()}
                x = self._part[kind](lp, x)
                seen[kind] += 1
        return x

    def logits(self, weights: Dict, tokens, first: Optional[int] = None,
               count: Optional[int] = None) -> jax.Array:
        """tokens [S] int32 -> logits [S, V] float32; of the ``count`` rows
        from row ``first`` on, where given."""
        x = self.hidden(weights, tokens)
        if first is not None:
            x = self._rows(x, first, count)
        return self._head(weights["embed"], weights["ln_f"], x)
