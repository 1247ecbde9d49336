"""Plain float32 reference of the Mamba-2 / routed-expert / attention LM
that the ``nemotron-3-nano-30b-a3b`` cell serves
(``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``'s ``config.json``,
``model_type`` ``nemotron_h``; the layer as Hugging Face's ``nemotron_h``
computes it), for ONE chip's share of its deployment.

Independent of ``horovod_tpu``: no cache, no batching, **no chunks**, no
grouped product, no kernel.  One sequence at a time; every matmul is
float32 at ``precision="highest"``; the Mamba-2 recurrence runs token by
token (``lax.scan`` over time), so the program's chunked form is checked
against something it is not.  The model, from the configuration's keys
(RMSNorm has a gain, eps ``layer_norm_epsilon``; no bias unless said):

* layer ``l`` is letter ``l`` of ``hybrid_override_pattern``: ``M``
  Mamba-2, ``E`` experts, ``*`` attention; a block is ``h = h +
  mixer_l(RMSNorm_l(h))``: ONE mixer or ONE feed-forward a layer; a final
  RMSNorm and an **untied** head;
* ``M``: ``d_inner = mamba_num_heads x mamba_head_dim`` (not ``expand x
  hidden_size``); ``[z | xBC | dt] = u W_in`` of widths ``d_inner |
  d_inner + 2 n_groups ssm_state_size | mamba_num_heads``; ``xBC_t =
  silu(b + sum_j w[j] xBC_{t-(K-1)+j})`` (depthwise, causal, ``K =
  conv_kernel``, zeros before the start); ``x [H, P]``, ``B [G, N]``, ``C
  [G, N]`` its split, head ``h`` reading group ``h // (H / G)``; ``D_t =
  softplus(dt_t + dt_bias)`` a head; ``A = -exp(A_log)`` a head; ``S_t =
  exp(D_t A) S_{t-1} + (D_t x_t) (x) B_t``; ``y_t = S_t C_t + D x_t``;
  ``y = RMSNorm_grouped(y silu(z))``: the gate first, then a norm over
  each of the ``n_groups`` groups of channels apart, one gain ``[d_inner]``;
  out ``= y W_out``;
* ``*``: ``num_attention_heads`` query heads on ``num_key_value_heads``
  key/value heads of ``head_dim``, causal softmax at 1/sqrt(head_dim),
  **no positional encoding**;
* ``E``: ``s = sigmoid(x W_r)`` over all ``n_routed_experts`` outputs;
  the ``num_experts_per_tok`` largest of ``s + b``; ``w_i =
  routed_scaling_factor s_i / (sum of the chosen s + 1e-20)``; ``E(x) =
  relu(x W_up)^2 W_down``; **this chip's part**: ``y = E_shared(x) + sum
  over the chosen e with expert_first <= e < expert_first + experts_held
  of w_e E_e(x)``, every held expert applied in a loop and masked by its
  weight.  What the other experts would add is left out and that partial
  sum goes on (the ``model-configs`` guide's cut).  The vocabulary slice
  is a smaller vocabulary.

Departures from the published model, all under ``assumed`` in the
configuration file: seeded weights and how each leaf is seeded; no
positional encoding in attention (``rope_theta`` and
``partial_rotary_factor`` are inert keys of ``nemotron_h``); ``D_t`` not
clipped (the published limit is ``(0, inf)``).

Weights are made here from the seed, leaf by leaf, in **bfloat16** (never
whole in float32) and in the layout the program serves (layers of a kind
stacked on a leading axis), and handed to both sides.  The reference
upcasts one layer at a time, an expert layer's experts one at a time.

``quant`` rounds every matmul operand that the program holds in bfloat16
to int8 (symmetric, absmax scale along the contraction axis, float32
accumulation): the control that ``correct`` has to fail.  The router and
the recurrence, float32 in the program, are not rounded.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from perfbench.reference.moe_lm import (BIAS_STD, F32, INIT_STD, _mm,
                                        _rmsnorm, _upcast, head_logits,
                                        routing)

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
ROWS = 1024             # rows of a sequence an attention block holds
TILE = 512              # the program's grouped product's tile


def padded_width(n: int) -> int:
    """The width the program holds a routed expert's stack axis of
    published width ``n`` at: whole tiles, where ``n`` is more than one
    (zeros past ``n``; the reference cuts them off)."""
    return -(-n // TILE) * TILE if n > TILE else n


def layer_kinds(sizes: Dict) -> List[str]:
    return [KINDS[c] for c in sizes["hybrid_override_pattern"]]


def make_weights(key, sizes: Dict) -> Dict:
    """Seeded bfloat16 weights: matrices normal(0, 0.02), the three output
    projections (Mamba-2 out, attention o, every expert's down) scaled by
    1/sqrt(2 L); ``A_log = log(uniform(1, 16))`` a head, ``D = 1``,
    ``dt_bias`` the inverse softplus of a log-uniform step in
    [time_step_min, time_step_max] floored at time_step_floor, the
    convolution uniform in +-1/sqrt(conv_kernel); gains one; the router's
    selection bias normal(0, 0.01) in float32.  The routed experts' stacks
    hold ``experts_held`` of the ``n_routed_experts`` the router scores,
    in the program's layout: their two width axes padded with zeros to
    :func:`padded_width`.  One small program a leaf."""
    D, V = sizes["hidden_size"], sizes["vocab_size"]
    H, KVH, HD = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    Hm, K = sizes["mamba_num_heads"], sizes["conv_kernel"]
    Di = Hm * sizes["mamba_head_dim"]
    C = Di + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    Fe = sizes["moe_intermediate_size"]
    Fs = sizes["moe_shared_expert_intermediate_size"]
    E, Eh = sizes["n_routed_experts"], sizes["experts_held"]
    kinds = layer_kinds(sizes)
    Lm, Le, La = (kinds.count(k) for k in ("mamba", "moe", "attn"))
    out_std = INIT_STD / math.sqrt(2 * len(kinds))
    keys = iter(jax.random.split(key, 24))
    bf16 = jnp.bfloat16

    def normal(shape, std, dtype=bf16):
        return jax.jit(lambda k: (jax.random.normal(k, shape, F32) * std
                                  ).astype(dtype))(next(keys))

    def uniform(shape, lo, hi):
        return jax.jit(lambda k: jax.random.uniform(k, shape, F32, lo, hi)
                       )(next(keys))

    def held(w):        # the program's layout: zeros up to whole tiles
        pad = [(0, padded_width(n) - n) for n in w.shape[2:]]
        return jax.jit(lambda a: jnp.pad(a, [(0, 0), (0, 0)] + pad))(w)

    step = jnp.maximum(
        jnp.exp(uniform((Lm, Hm), math.log(sizes["time_step_min"]),
                        math.log(sizes["time_step_max"]))),
        sizes["time_step_floor"])
    bound = 1.0 / math.sqrt(K)
    mamba = {
        "ln": jnp.ones((Lm, D), bf16),
        "in_proj": normal((Lm, D, Di + C + Hm), INIT_STD),
        "conv_w": uniform((Lm, K, C), -bound, bound).astype(bf16),
        "conv_b": uniform((Lm, C), -bound, bound).astype(bf16),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(bf16),
        "a_log": jnp.log(uniform((Lm, Hm), 1.0, 16.0)).astype(bf16),
        "d": jnp.ones((Lm, Hm), bf16),
        "norm": jnp.ones((Lm, Di), bf16),
        "out_proj": normal((Lm, Di, D), out_std)}
    moe = {
        "ln": jnp.ones((Le, D), bf16),
        "router": normal((Le, D, E), INIT_STD),
        "router_bias": normal((Le, E), BIAS_STD, F32),
        "w_in": held(normal((Le, Eh, D, Fe), INIT_STD)),
        "w_out": held(normal((Le, Eh, Fe, D), out_std)),
        "shared_in": normal((Le, D, Fs), INIT_STD),
        "shared_out": normal((Le, Fs, D), out_std)}
    attn = {
        "ln": jnp.ones((La, D), bf16),
        "wq": normal((La, D, H, HD), INIT_STD),
        "wk": normal((La, D, KVH, HD), INIT_STD),
        "wv": normal((La, D, KVH, HD), INIT_STD),
        "wo": normal((La, H, HD, D), out_std)}
    return {"embed": normal((V, D), INIT_STD), "mamba": mamba, "moe": moe,
            "attn": attn, "ln_f": jnp.ones((D,), bf16),
            "head": normal((V, D), INIT_STD)}


# ---------------------------------------------------------------------------
# the model, one sequence, one layer at a time
# ---------------------------------------------------------------------------


def mamba_layer(lp: Dict, x, *, eps: float, heads: int, groups: int,
                state: int, quant: bool = False):
    """x: [S, D] float32.  The recurrence is float32 whatever ``quant``
    says: only matmul operands are rounded."""
    lp = _upcast(lp)
    S = x.shape[0]
    K, C = lp["conv_w"].shape
    Di = lp["norm"].shape[0]
    P, GN = Di // heads, groups * state
    u = _rmsnorm(x, lp["ln"], eps)
    zxd = _mm("sd,de->se", u, lp["in_proj"], (1,), (0,), quant)
    z, xbc, dt = zxd[:, :Di], zxd[:, Di:Di + C], zxd[:, Di + C:]
    padded = jnp.pad(xbc, [(K - 1, 0), (0, 0)])
    xbc = jax.nn.silu(lp["conv_b"] + sum(
        lp["conv_w"][j] * padded[j:j + S] for j in range(K)))
    xs = xbc[:, :Di].reshape(S, heads, P)
    # head h reads group h // (heads / groups)
    b_in = jnp.repeat(xbc[:, Di:Di + GN].reshape(S, groups, state),
                      heads // groups, axis=1)                  # [S, H, N]
    c_out = jnp.repeat(xbc[:, Di + GN:].reshape(S, groups, state),
                       heads // groups, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                    # [S, H]
    a = -jnp.exp(lp["a_log"])                                   # [H]

    def step(s, t):
        dt_t, x_t, b_t, c_t = t
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, P, state), F32),
                        (dt, xs, b_in, c_out))
    y = (y + lp["d"][:, None] * xs).reshape(S, Di) * jax.nn.silu(z)
    y = y.reshape(S, groups, Di // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = y.reshape(S, Di) * lp["norm"]
    return x + _mm("se,ed->sd", y, lp["out_proj"], (1,), (0,), quant)


def attention_layer(lp: Dict, x, *, eps: float, quant: bool = False):
    """x: [S, D] float32.  Query head ``h`` reads key/value head ``h //
    (H / KVH)``; a head at a time, query rows in blocks of ``ROWS``."""
    lp = _upcast(lp)
    S = x.shape[0]
    H, HD = lp["wq"].shape[1:]
    KVH = lp["wk"].shape[1]
    u = _rmsnorm(x, lp["ln"], eps)
    q = _mm("sd,dhk->hsk", u, lp["wq"], (1,), (0,), quant)
    k = _mm("sd,dhk->hsk", u, lp["wk"], (1,), (0,), quant)
    v = _mm("sd,dhk->hsk", u, lp["wv"], (1,), (0,), quant)
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


def _relu2(u, w_in, w_out, quant: bool):
    h = jax.nn.relu(_mm("sd,df->sf", u, w_in.astype(F32), (1,), (0,), quant))
    return _mm("sf,fd->sd", h * h, w_out.astype(F32), (1,), (0,), quant)


def moe_layer(lp: Dict, x, *, eps: float, top_k: int, scale: float,
              expert_first: int, width: int, quant: bool = False):
    """x: [S, D] float32.  Every held expert over every row, masked by the
    row's weight for it; an expert is the published ``[D, width]`` and
    ``[width, D]`` corner of its held stack."""
    D = x.shape[1]
    u = _rmsnorm(x, lp["ln"].astype(F32), eps)
    w = routing(u, lp["router"].astype(F32), lp["router_bias"], top_k=top_k,
                scale=scale)

    def one_expert(e, y):       # the e-th expert held here
        w_in, w_out = (jax.lax.dynamic_index_in_dim(lp[k], e, 0,
                                                    keepdims=False)
                       for k in ("w_in", "w_out"))
        w_in, w_out = w_in[:D, :width], w_out[:width, :D]
        w_e = jax.lax.dynamic_slice_in_dim(w, expert_first + e, 1, axis=1)
        return y + w_e * _relu2(u, w_in, w_out, quant)

    y = jax.lax.fori_loop(0, lp["w_in"].shape[0], one_expert,
                          jnp.zeros_like(x))
    return x + y + _relu2(u, lp["shared_in"], lp["shared_out"], quant)


class Forward:
    """Full forward passes over one padded token row, one compile a
    shape.  Padding at the end of a row never reaches an earlier position
    (causal attention, a causal convolution, a forward recurrence; the
    feed-forward is row by row)."""

    def __init__(self, sizes: Dict, *, quant: bool = False):
        self.kinds = layer_kinds(sizes)
        eps = float(sizes["layer_norm_epsilon"])
        self._embed = jax.jit(lambda e, t: e[t].astype(F32))
        self._layer = {
            "mamba": jax.jit(partial(
                mamba_layer, eps=eps, heads=int(sizes["mamba_num_heads"]),
                groups=int(sizes["n_groups"]),
                state=int(sizes["ssm_state_size"]), quant=quant)),
            "attn": jax.jit(partial(attention_layer, eps=eps, quant=quant)),
            "moe": jax.jit(partial(
                moe_layer, eps=eps, top_k=int(sizes["num_experts_per_tok"]),
                scale=float(sizes["routed_scaling_factor"]),
                expert_first=int(sizes["expert_first"]),
                width=int(sizes["moe_intermediate_size"]), quant=quant))}
        self._head = jax.jit(partial(head_logits, eps=eps, quant=quant))
        self._rows = jax.jit(jax.lax.dynamic_slice_in_dim,
                             static_argnums=(2,))

    def hidden(self, weights: Dict, tokens) -> jax.Array:
        """tokens [S] int32 -> the last layer's output [S, D] float32."""
        x = self._embed(weights["embed"], tokens)
        seen = dict.fromkeys(self._layer, 0)
        for kind in self.kinds:
            lp = {k: v[seen[kind]] for k, v in weights[kind].items()}
            x = self._layer[kind](lp, x)
            seen[kind] += 1
        return x

    def logits(self, weights: Dict, tokens, first: Optional[int] = None,
               count: Optional[int] = None) -> jax.Array:
        """tokens [S] int32 -> logits [S, V] float32; of the ``count`` rows
        from row ``first`` on, where given."""
        x = self.hidden(weights, tokens)
        if first is not None:
            x = self._rows(x, first, count)
        return self._head(weights["head"], weights["ln_f"], x)
