"""Plain float32 reference of the ResNet v1.5 the ``resnet50`` cells train.

Independent of ``horovod_tpu``.  Bottleneck blocks (1x1, 3x3 carrying the
stride, 1x1), a plain 7x7 stride-2 stem (the program runs an equivalent
space-to-depth form), 3x3 stride-2 max-pool, batch normalisation on the
batch's own statistics (biased variance, eps 1e-5), global mean pool, a
linear classifier, mean softmax cross-entropy, SGD with momentum.  All
float32, every convolution and matmul at ``precision="highest"``.

The only memory device used is ``jax.checkpoint`` around each block (the
same arithmetic, recomputed in the backward pass), so that a batch of 128
at 224 x 224 fits in float32 on one chip.  With several shards (the
data-parallel cell) each shard is a batch of its own, with its own batch
statistics; losses and gradients are averaged over the shards, as the
Horovod contract's all-reduce does.

``quant`` holds in int8 what the program holds in bfloat16: every
convolution and matmul operand, every normalised activation and every
block's output, and what flows back through each of them (symmetric,
255 levels; activations by tensor, weights by output channel; float32
accumulation and statistics): the control that ``correct`` has to fail.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.compare import leaf_norms
from perfbench.reference.int8 import fake_quant as _fq

HI = lax.Precision.HIGHEST
BN_EPS = 1e-5


def block_plan(cfg: Dict):
    """(name, c_in, c_mid, c_out, stride, has_projection) per block."""
    cin = cfg["width"]
    for si, nblocks in enumerate(cfg["blocks"]):
        cmid = cfg["width"] * 2 ** si
        cout = 4 * cmid
        for bi in range(nblocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            yield (f"stage{si}_block{bi}", cin, cmid, cout, stride,
                   bi == 0)
            cin = cout


def make_weights(key, cfg: Dict):
    """Seeded float32 ``(params, batch_stats)`` in the layout the program's
    step takes: He-normal (fan-out) convolutions, unit scales, zero biases,
    a uniform classifier."""
    keys = iter(jax.random.split(key, 64))

    def conv(kh, kw, cin, cout):
        std = math.sqrt(2.0 / (kh * kw * cout))
        return jax.random.normal(next(keys), (kh, kw, cin, cout),
                                 jnp.float32) * std

    def bn(c):
        return {"scale": jnp.ones((c,), jnp.float32),
                "bias": jnp.zeros((c,), jnp.float32)}

    def bn_state(c):
        return {"mean": jnp.zeros((c,), jnp.float32),
                "var": jnp.ones((c,), jnp.float32)}

    w = cfg["width"]
    params = {"stem_conv": conv(7, 7, 3, w), "stem_bn": bn(w)}
    stats = {"stem_bn": bn_state(w)}
    for name, cin, cmid, cout, _, proj in block_plan(cfg):
        blk = {"conv1": conv(1, 1, cin, cmid), "bn1": bn(cmid),
               "conv2": conv(3, 3, cmid, cmid), "bn2": bn(cmid),
               "conv3": conv(1, 1, cmid, cout), "bn3": bn(cout)}
        bst = {"bn1": bn_state(cmid), "bn2": bn_state(cmid),
               "bn3": bn_state(cout)}
        if proj:
            blk["proj_conv"] = conv(1, 1, cin, cout)
            blk["proj_bn"] = bn(cout)
            bst["proj_bn"] = bn_state(cout)
        params[name], stats[name] = blk, bst
        cin = cout
    lim = 1.0 / math.sqrt(cin)
    params["head_w"] = jax.random.uniform(
        next(keys), (cin, cfg["num_classes"]), jnp.float32, -lim, lim)
    params["head_b"] = jnp.zeros((cfg["num_classes"],), jnp.float32)
    return params, stats


def _conv(x, w, stride, quant):
    pad = (w.shape[0] - 1) // 2
    return lax.conv_general_dilated(
        _fq(x, None, quant), _fq(w, (0, 1, 2), quant),
        window_strides=(stride, stride), padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _bn(x, p, quant=False):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean((x - mean) ** 2, axis=(0, 1, 2))
    y = (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    return _fq(y, None, quant)      # the program holds this in bfloat16


def _block(blk, x, stride, quant):
    shortcut = x
    if "proj_conv" in blk:
        shortcut = _bn(_conv(x, blk["proj_conv"], stride, quant),
                       blk["proj_bn"], quant)
    y = jax.nn.relu(_bn(_conv(x, blk["conv1"], 1, quant), blk["bn1"],
                        quant))
    y = jax.nn.relu(_bn(_conv(y, blk["conv2"], stride, quant), blk["bn2"],
                        quant))
    y = _bn(_conv(y, blk["conv3"], 1, quant), blk["bn3"], quant)
    return _fq(jax.nn.relu(y + shortcut), None, quant)


def loss(params, images, labels, cfg: Dict, quant: bool = False):
    x = jax.nn.relu(_bn(_conv(images, params["stem_conv"], 2, quant),
                        params["stem_bn"], quant))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for name, _, _, _, stride, _ in block_plan(cfg):
        x = jax.checkpoint(partial(_block, stride=stride, quant=quant))(
            params[name], x)
    x = jnp.mean(x, axis=(1, 2))
    logits = jnp.matmul(_fq(x, (1,), quant), _fq(params["head_w"], (0,),
                                                 quant),
                        precision=HI) + params["head_b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


class Trainer:
    def __init__(self, cfg: Dict, opt: Dict, *, quant: bool = False):
        self._vg = jax.jit(jax.value_and_grad(
            partial(loss, cfg=cfg, quant=quant)))
        lr, mom = opt["learning_rate"], opt["momentum"]

        def sgd(p, trace, g):
            trace = jax.tree.map(lambda t, gg: gg + mom * t, trace, g)
            return jax.tree.map(lambda pp, t: pp - lr * t, p, trace), trace

        self._sgd = jax.jit(sgd, donate_argnums=(0, 1))
        self._mean = jax.jit(lambda gs: jax.tree.map(
            lambda *a: sum(a) / len(a), *gs))

    def run(self, make_params, images, labels, n_shards: int,
            n_steps: int) -> Dict:
        """``n_steps`` steps on one batch, split into ``n_shards`` equal
        shards with batch statistics of their own."""
        p = make_params()
        trace = jax.tree.map(jnp.zeros_like, p)
        per = images.shape[0] // n_shards
        losses: List[float] = []
        first = None
        for _ in range(n_steps):
            ls, gs = [], []
            for s in range(n_shards):
                l, g = self._vg(p, images[s * per:(s + 1) * per],
                                labels[s * per:(s + 1) * per])
                ls.append(float(l))
                gs.append(g)
            g = gs[0] if n_shards == 1 else self._mean(gs)
            losses.append(sum(ls) / len(ls))
            if first is None:
                first = leaf_norms(g)
            p, trace = self._sgd(p, trace, g)
        delta = leaf_norms(jax.jit(lambda a, b: jax.tree.map(
            jnp.subtract, a, b))(p, make_params()))
        return {"losses": losses, "first_grad_norm": first,
                "delta_norm": delta}
