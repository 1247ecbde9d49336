"""Plain float32 reference of the decoder-only LM the ``olmo-1b`` cells run.

Independent of ``horovod_tpu``: no kernel, no cache, no scan, no remat.
Every matmul is float32 at ``precision="highest"`` (six bf16 passes on the
MXU).  It follows the model the configuration file describes: pre-norm
blocks, RMSNorm with a gain (eps 1e-6), rotary positions over the two
halves of each head, causal softmax attention, a gated SiLU feed-forward,
tied input/output embeddings, no biases.

Weights are made here from the seed, in the layout the program's step and
server take (layers stacked on a leading axis), and handed to both sides.
The reference walks the stack layer by layer and the batch in blocks of
rows, so that three training steps of it fit beside nothing else on one
16 GB chip.

``quant`` switches every matmul operand to int8 (symmetric, absmax scale
along the contraction axis, float32 accumulation): the control that
``correct`` has to fail (the configuration states bfloat16 compute).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from perfbench.compare import leaf_norms
from perfbench.reference.int8 import fake_quant as _fq

HI = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
RMS_EPS = 1e-6
LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_in", "w_gate",
              "w_out")


def make_weights(key, sizes: Dict) -> Dict:
    """Seeded float32 weights: normal(0, 0.02), output projections scaled
    by 1/sqrt(2 L), gains one.  ``sizes``: vocab_size, d_model, n_layers,
    n_heads, d_ff."""
    L, D, H, F, V = (sizes["n_layers"], sizes["d_model"], sizes["n_heads"],
                     sizes["d_ff"], sizes["vocab_size"])
    HD = D // H
    k = jax.random.split(key, 8)
    out_std = INIT_STD / math.sqrt(2 * L)

    def normal(kk, shape, std):
        return jax.random.normal(kk, shape, jnp.float32) * std

    return {
        "embed": normal(k[0], (V, D), INIT_STD),
        "layers": {
            "ln1": jnp.ones((L, D), jnp.float32),
            "ln2": jnp.ones((L, D), jnp.float32),
            "wq": normal(k[1], (L, D, H, HD), INIT_STD),
            "wk": normal(k[2], (L, D, H, HD), INIT_STD),
            "wv": normal(k[3], (L, D, H, HD), INIT_STD),
            "wo": normal(k[4], (L, H, HD, D), out_std),
            "w_in": normal(k[5], (L, D, F), INIT_STD),
            "w_gate": normal(k[6], (L, D, F), INIT_STD),
            "w_out": normal(k[7], (L, F, D), out_std),
        },
        "ln_f": jnp.ones((D,), jnp.float32),
    }


# ---------------------------------------------------------------------------
# the model, one layer at a time
# ---------------------------------------------------------------------------


def _mm(spec: str, a, b, a_axes, b_axes, quant: bool):
    return jnp.einsum(spec, _fq(a, a_axes, quant), _fq(b, b_axes, quant),
                      precision=HI, preferred_element_type=jnp.float32)


def _rmsnorm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + RMS_EPS) * g


def _rope(x, theta: float):
    """x: [B, S, H, HD]; rotates the (first half, second half) pairs."""
    S, HD = x.shape[1], x.shape[3]
    half = HD // 2
    freqs = jnp.exp(-math.log(theta)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(lp: Dict, x, *, theta: float, quant: bool = False):
    """One block.  x: [B, S, D] float32."""
    S = x.shape[1]
    HD = lp["wq"].shape[-1]
    y = _rmsnorm(x, lp["ln1"])
    q = _rope(_mm("bsd,dhk->bshk", y, lp["wq"], (2,), (0,), quant), theta)
    k = _rope(_mm("bsd,dhk->bshk", y, lp["wk"], (2,), (0,), quant), theta)
    v = _mm("bsd,dhk->bshk", y, lp["wv"], (2,), (0,), quant)
    logits = _mm("bshk,bthk->bhst", q, k, (3,), (3,), quant) / math.sqrt(HD)
    mask = jnp.tril(jnp.ones((S, S), bool))
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = _mm("bhst,bthk->bshk", probs, v, (3,), (1,), quant)
    x = x + _mm("bshk,hkd->bsd", ctx, lp["wo"], (2, 3), (0, 1), quant)
    y = _rmsnorm(x, lp["ln2"])
    h = _mm("bsd,df->bsf", y, lp["w_in"], (2,), (0,), quant)
    g = _mm("bsd,df->bsf", y, lp["w_gate"], (2,), (0,), quant)
    return x + _mm("bsf,fd->bsd", h * jax.nn.silu(g), lp["w_out"],
                   (2,), (0,), quant)


def head_logits(embed, ln_f, x, *, quant: bool = False):
    return _mm("bsd,vd->bsv", _rmsnorm(x, ln_f), embed, (2,), (1,), quant)


def _head_loss_sum(embed, ln_f, x, targets, quant):
    logits = head_logits(embed, ln_f, x, quant=quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)


def _layer_params(weights: Dict, l: int) -> Dict:
    return {k: weights["layers"][k][l] for k in LAYER_KEYS}


# ---------------------------------------------------------------------------
# serving: logits of whole sequences
# ---------------------------------------------------------------------------


class Forward:
    """Full forward passes over padded token rows, one compile per shape."""

    def __init__(self, sizes: Dict, *, quant: bool = False):
        self.L = sizes["n_layers"]
        theta = float(sizes["rope_theta"])
        self._embed = jax.jit(lambda e, t: e[t])
        self._layer = jax.jit(partial(layer, theta=theta, quant=quant))
        self._head = jax.jit(partial(head_logits, quant=quant))

    def logits(self, weights: Dict, tokens) -> jax.Array:
        """tokens [B, S] int32 -> logits [B, S, V] float32.  Padding at
        the end of a row never reaches an earlier position (causal)."""
        x = self._embed(weights["embed"], tokens)
        for l in range(self.L):
            x = self._layer(_layer_params(weights, l), x)
        return self._head(weights["embed"], weights["ln_f"], x)


# ---------------------------------------------------------------------------
# training: three AdamW steps, layer by layer, rows in blocks
# ---------------------------------------------------------------------------


class Trainer:
    """The reference's train step: mean cross-entropy over all tokens of
    the batch, exact gradients by a hand-ordered backward pass (each
    layer's vjp with its input kept from the forward pass), AdamW."""

    def __init__(self, sizes: Dict, opt: Dict, *, quant: bool = False,
                 row_block: int = 1):
        self.L = sizes["n_layers"]
        self.opt = opt
        self.row_block = row_block
        theta = float(sizes["rope_theta"])
        fwd = partial(layer, theta=theta, quant=quant)
        self._embed = jax.jit(lambda e, t: e[t])
        self._layer = jax.jit(fwd)

        def layer_vjp(lp, x, g):
            _, pull = jax.vjp(fwd, lp, x)
            return pull(g)

        self._layer_vjp = jax.jit(layer_vjp)
        self._head = jax.jit(jax.value_and_grad(
            partial(_head_loss_sum, quant=quant), argnums=(0, 1, 2)))
        self._scatter = jax.jit(
            lambda ge, tok, gx: ge.at[tok.reshape(-1)].add(
                gx.reshape(-1, gx.shape[-1])))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                            donate_argnums=(0,))
        self._adamw = jax.jit(self._adamw_impl, donate_argnums=(0, 1, 2))
        self._scale = jax.jit(
            lambda t, d: jax.tree.map(lambda a: a / d, t),
            donate_argnums=(0,))

    def _adamw_impl(self, p, mu, nu, g, t):
        o = self.opt
        b1, b2 = o["b1"], o["b2"]
        mu = jax.tree.map(lambda m, gg: b1 * m + (1 - b1) * gg, mu, g)
        nu = jax.tree.map(lambda n, gg: b2 * n + (1 - b2) * gg * gg, nu, g)
        c1 = 1 - b1 ** t
        c2 = 1 - b2 ** t

        def upd(pp, m, n):
            step = (m / c1) / (jnp.sqrt(n / c2) + o["eps"])
            return pp - o["learning_rate"] * (step + o["weight_decay"] * pp)

        return jax.tree.map(upd, p, mu, nu), mu, nu

    def _blocks(self, n: int):
        return [slice(i, min(i + self.row_block, n))
                for i in range(0, n, self.row_block)]

    def grads(self, w: Dict, tokens, targets) -> Tuple[float, Dict]:
        B, S = tokens.shape
        blocks = self._blocks(B)
        denom = float(B * S)
        xs = [self._embed(w["embed"], tokens)]
        for l in range(self.L):
            lp = _layer_params(w, l)
            xs.append(jnp.concatenate(
                [self._layer(lp, xs[-1][b]) for b in blocks], 0))
        loss = 0.0
        g_embed = g_lnf = None
        gx_parts = []
        for b in blocks:
            ls, (ge, gl, gx) = self._head(w["embed"], w["ln_f"], xs[-1][b],
                                          targets[b])
            loss += float(ls)
            g_embed = ge if g_embed is None else self._add(g_embed, ge)
            g_lnf = gl if g_lnf is None else self._add(g_lnf, gl)
            gx_parts.append(gx)
        gx = jnp.concatenate(gx_parts, 0)
        del gx_parts
        layer_grads: List[Optional[Dict]] = [None] * self.L
        for l in reversed(range(self.L)):
            lp = _layer_params(w, l)
            acc = None
            nxt = []
            for b in blocks:
                glp, gxb = self._layer_vjp(lp, xs[l][b], gx[b])
                acc = glp if acc is None else self._add(acc, glp)
                nxt.append(gxb)
            gx = jnp.concatenate(nxt, 0)
            layer_grads[l] = acc
            xs[l + 1] = None
        g_embed = self._scatter(g_embed, tokens, gx)
        g = {"embed": g_embed, "ln_f": g_lnf,
             "layers": {k: jnp.stack([lg[k] for lg in layer_grads])
                        for k in LAYER_KEYS}}
        del layer_grads
        return loss / denom, self._scale(g, denom)

    def run(self, make_w, tokens, targets, n_steps: int) -> Dict:
        """``n_steps`` steps on one batch from ``make_w()``.  Returns the
        losses, the first gradient's norm per leaf and the norm of each
        leaf's change after the last step."""
        w = make_w()
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        mu, nu = zeros(w), zeros(w)
        losses, first = [], None
        for t in range(1, n_steps + 1):
            loss, g = self.grads(w, tokens, targets)
            losses.append(loss)
            if first is None:
                first = leaf_norms(g)
            w, mu, nu = self._adamw(w, mu, nu, g, float(t))
            del g
        del mu, nu
        delta = leaf_norms(jax.jit(lambda a, b: jax.tree.map(
            jnp.subtract, a, b), donate_argnums=(0,))(w, make_w()))
        return {"losses": losses, "first_grad_norm": first,
                "delta_norm": delta}
