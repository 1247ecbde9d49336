"""Plain float32 reference of the hybrid state-space LM the ``jamba2-3b``
cell serves (``ai21labs/AI21-Jamba2-3B``'s ``config.json``).

Independent of ``horovod_tpu``: no cache, no batching, no chunked scan, no
kernel.  One sequence at a time; every matmul is float32 at
``precision="highest"``; the state-space recurrence runs token by token
(``lax.scan`` over time).  The model, from the configuration's keys
(RMSNorm has a gain, eps ``rms_norm_eps``; no bias unless said):

* layer ``i`` is attention if ``i % attn_layer_period ==
  attn_layer_offset``, else Mamba (Hugging Face
  ``JambaConfig.layers_block_type``; the catalog does not give the order:
  ``assumed`` in the configuration file).  ``num_experts`` is 1: every
  layer's feed-forward is the dense gated one;
* block: ``h = h + Mixer(RMSNorm(h))``; ``h = h + W_down(silu(W_gate u) *
  W_up u)``, ``u = RMSNorm(h)``; a final RMSNorm; logits ``= h E^T`` with
  the tied embedding;
* attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``hidden_size /
  num_attention_heads``, causal softmax at 1/sqrt(head size), **no
  positional encoding**;
* Mamba-1 with Jamba's inner norms: ``(x, z) = split(u W_in)``; ``c_t =
  silu(b + sum_j w[j] x_{t-(K-1)+j})`` (depthwise, causal, zeros before
  the start); ``(delta, B_t, C_t) = split(c_t W_x)``, each RMS-normed;
  ``Delta_t = softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t =
  exp(Delta_t A) h_{t-1} + (Delta_t c_t) B_t``; ``y_t = h_t C_t + D c_t``;
  out ``= (y_t silu(z_t)) W_out``.

Departures from the published model, all under ``assumed`` in the
configuration file: the weights are seeded, not the checkpoint's; the
layer order rule above.

Weights are made here from the seed, leaf by leaf, in **bfloat16** (the
published ``torch_dtype``; 12 GB of float32 copies fit beside nothing) and
in the layout the program serves (layers of a kind stacked on a leading
axis; ``A_log`` and the convolution with ``d_inner`` last), and handed to
both sides.  The reference takes the same bfloat16-rounded values and
upcasts one layer at a time.

``quant`` rounds every matmul operand to int8 (symmetric, absmax scale
along the contraction axis, float32 accumulation): the control that
``correct`` has to fail (the configuration states bfloat16).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp

from perfbench.reference.int8 import fake_quant as _fq

HI = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
DT_MIN, DT_MAX = 1e-3, 1e-1
F32 = jnp.float32


def layer_kinds(sizes: Dict) -> List[str]:
    return ["attn" if i % sizes["attn_layer_period"]
            == sizes["attn_layer_offset"] else "mamba"
            for i in range(sizes["num_hidden_layers"])]


def make_weights(key, sizes: Dict) -> Dict:
    """Seeded bfloat16 weights: matrices normal(0, 0.02), output
    projections scaled by 1/sqrt(2 L), gains one; the mixer's own leaves
    by Mamba's rule: ``A_log = log(1..N)`` a channel, ``D = 1``, ``b_dt``
    the inverse softplus of a log-uniform step in [1e-3, 1e-1], the
    convolution uniform in +-1/sqrt(d_conv).  One small program a leaf,
    so that no float32 copy of more than one leaf exists at a time."""
    D, F, V = (sizes["hidden_size"], sizes["intermediate_size"],
               sizes["vocab_size"])
    H, KVH = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    HD = D // H
    Di = sizes["mamba_expand"] * D
    N, K, R = (sizes["mamba_d_state"], sizes["mamba_d_conv"],
               sizes["mamba_dt_rank"])
    kinds = layer_kinds(sizes)
    Lm, La = kinds.count("mamba"), kinds.count("attn")
    out_std = INIT_STD / math.sqrt(2 * len(kinds))
    keys = iter(jax.random.split(key, 20))
    bf16 = jnp.bfloat16

    def normal(shape, std):
        return jax.jit(lambda k: (jax.random.normal(k, shape, F32) * std
                                  ).astype(bf16))(next(keys))

    def uniform(shape, lo, hi):
        return jax.jit(lambda k: jax.random.uniform(k, shape, F32, lo, hi)
                       )(next(keys))

    def ffn(L):
        return {"ln2": jnp.ones((L, D), bf16),
                "w_in": normal((L, D, F), INIT_STD),
                "w_gate": normal((L, D, F), INIT_STD),
                "w_out": normal((L, F, D), out_std)}

    step = jnp.exp(uniform((Lm, Di), math.log(DT_MIN), math.log(DT_MAX)))
    bound = 1.0 / math.sqrt(K)
    a_log = jnp.log(jnp.arange(1, N + 1, dtype=F32))
    mamba = {
        "ln1": jnp.ones((Lm, D), bf16),
        "in_proj": normal((Lm, D, 2 * Di), INIT_STD),
        "conv_w": uniform((Lm, K, Di), -bound, bound).astype(bf16),
        "conv_b": uniform((Lm, Di), -bound, bound).astype(bf16),
        "x_proj": normal((Lm, Di, R + 2 * N), INIT_STD),
        "dt_norm": jnp.ones((Lm, R), bf16),
        "b_norm": jnp.ones((Lm, N), bf16),
        "c_norm": jnp.ones((Lm, N), bf16),
        "dt_proj": normal((Lm, R, Di), INIT_STD),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(bf16),
        "a_log": jnp.broadcast_to(a_log[None, :, None],
                                  (Lm, N, Di)).astype(bf16),
        "d": jnp.ones((Lm, Di), bf16),
        "out_proj": normal((Lm, Di, D), out_std),
        **ffn(Lm)}
    attn = {
        "ln1": jnp.ones((La, D), bf16),
        "wq": normal((La, D, H, HD), INIT_STD),
        "wk": normal((La, D, KVH, HD), INIT_STD),
        "wv": normal((La, D, KVH, HD), INIT_STD),
        "wo": normal((La, H, HD, D), out_std),
        **ffn(La)}
    return {"embed": normal((V, D), INIT_STD), "mamba": mamba,
            "attn": attn, "ln_f": jnp.ones((D,), bf16)}


# ---------------------------------------------------------------------------
# the model, one sequence, one layer at a time
# ---------------------------------------------------------------------------


def _mm(spec: str, a, b, a_axes, b_axes, quant: bool):
    return jnp.einsum(spec, _fq(a, a_axes, quant), _fq(b, b_axes, quant),
                      precision=HI, preferred_element_type=F32)


def _rmsnorm(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _upcast(lp: Dict) -> Dict:
    return {k: v.astype(F32) for k, v in lp.items()}


def _ffn(lp: Dict, x, eps: float, quant: bool):
    u = _rmsnorm(x, lp["ln2"], eps)
    up = _mm("sd,df->sf", u, lp["w_in"], (1,), (0,), quant)
    gate = _mm("sd,df->sf", u, lp["w_gate"], (1,), (0,), quant)
    return x + _mm("sf,fd->sd", up * jax.nn.silu(gate), lp["w_out"],
                   (1,), (0,), quant)


def attention_layer(lp: Dict, x, *, eps: float, quant: bool = False):
    """x: [S, D] float32.  Query head ``h`` reads key/value head ``h //
    (H / KVH)``."""
    lp = _upcast(lp)
    S = x.shape[0]
    H, HD = lp["wq"].shape[1:]
    KVH = lp["wk"].shape[1]
    u = _rmsnorm(x, lp["ln1"], eps)
    q = _mm("sd,dhk->shk", u, lp["wq"], (1,), (0,), quant)
    k = _mm("sd,dhk->shk", u, lp["wk"], (1,), (0,), quant)
    v = _mm("sd,dhk->shk", u, lp["wv"], (1,), (0,), quant)
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    scores = _mm("shk,thk->hst", q, k, (2,), (2,), quant) / math.sqrt(HD)
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], scores,
                       -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _mm("hst,thk->shk", probs, v, (2,), (0,), quant)
    x = x + _mm("shk,hkd->sd", ctx, lp["wo"], (1, 2), (0, 1), quant)
    return _ffn(lp, x, eps, quant)


def mamba_layer(lp: Dict, x, *, eps: float, quant: bool = False):
    """x: [S, D] float32.  The recurrence is float32 whatever ``quant``
    says: only matmul operands are rounded."""
    lp = _upcast(lp)
    S = x.shape[0]
    K, Di = lp["conv_w"].shape
    N = lp["a_log"].shape[0]
    R = lp["dt_proj"].shape[0]
    u = _rmsnorm(x, lp["ln1"], eps)
    xz = _mm("sd,de->se", u, lp["in_proj"], (1,), (0,), quant)
    xs, z = xz[:, :Di], xz[:, Di:]
    padded = jnp.pad(xs, [(K - 1, 0), (0, 0)])
    c = lp["conv_b"] + sum(lp["conv_w"][j] * padded[j:j + S]
                           for j in range(K))
    c = jax.nn.silu(c)
    dbc = _mm("se,er->sr", c, lp["x_proj"], (1,), (0,), quant)
    delta = _rmsnorm(dbc[:, :R], lp["dt_norm"], eps)
    b_in = _rmsnorm(dbc[:, R:R + N], lp["b_norm"], eps)
    c_out = _rmsnorm(dbc[:, R + N:], lp["c_norm"], eps)
    delta = jax.nn.softplus(
        _mm("sr,re->se", delta, lp["dt_proj"], (1,), (0,), quant)
        + lp["dt_bias"])
    a = -jnp.exp(lp["a_log"])                                   # [N, Di]

    def step(h, t):
        delta_t, c_t, b_t, c_out_t = t
        h = jnp.exp(delta_t[None, :] * a) * h \
            + (delta_t * c_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_out_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((N, Di), F32),
                        (delta, c, b_in, c_out))
    y = (y + lp["d"] * c) * jax.nn.silu(z)
    x = x + _mm("se,ed->sd", y, lp["out_proj"], (1,), (0,), quant)
    return _ffn(lp, x, eps, quant)


def head_logits(embed, ln_f, x, *, eps: float, quant: bool = False):
    return _mm("sd,vd->sv", _rmsnorm(x, ln_f.astype(F32), eps),
               embed.astype(F32), (1,), (1,), quant)


class Forward:
    """Full forward passes over one padded token row, one compile a
    shape.  Padding at the end of a row never reaches an earlier position
    (causal attention, a causal convolution, a forward recurrence)."""

    def __init__(self, sizes: Dict, *, quant: bool = False):
        self.kinds = layer_kinds(sizes)
        eps = float(sizes["rms_norm_eps"])
        self._embed = jax.jit(lambda e, t: e[t].astype(F32))
        self._layer = {
            "attn": jax.jit(partial(attention_layer, eps=eps, quant=quant)),
            "mamba": jax.jit(partial(mamba_layer, eps=eps, quant=quant))}
        self._head = jax.jit(partial(head_logits, eps=eps, quant=quant))

    def logits(self, weights: Dict, tokens) -> jax.Array:
        """tokens [S] int32 -> logits [S, V] float32."""
        x = self._embed(weights["embed"], tokens)
        seen = {"attn": 0, "mamba": 0}
        for kind in self.kinds:
            lp = {k: v[seen[kind]] for k, v in weights[kind].items()}
            x = self._layer[kind](lp, x)
            seen[kind] += 1
        return self._head(weights["embed"], weights["ln_f"], x)
