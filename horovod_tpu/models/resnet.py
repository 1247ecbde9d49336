"""ResNet v1.5 family in pure functional JAX — the benchmark-parity model.

The reference's headline numbers are ResNet-50 synthetic-benchmark
images/sec (``examples/tensorflow2_synthetic_benchmark.py:30-45``, batch 32,
``applications.ResNet50``) and ResNet-101 scaling efficiency
(``docs/benchmarks.rst:8-13``).  This module provides the same model family,
built TPU-first:

* NHWC layout with channel counts that are multiples of 128 in the deep
  stages — convs lower to MXU matmuls with full tiles.
* bf16 compute / fp32 params + fp32 batch-norm statistics: the standard
  TPU mixed-precision recipe (params stay fp32 so allreduce numerics can
  hit the 1e-6 gate against the CPU oracle in fp32).
* No Python objects in the forward path: params are a pytree of arrays,
  ``apply`` is a pure function — jit/pjit/grad compose freely.
* Batch norm is folded into functional form with state threaded explicitly
  (training mode returns updated running stats), so the whole train step is
  one compiled XLA program.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, Any]


@dataclass(frozen=True)
class ResNetConfig:
    """Stage layout per the classic v1 family.  ``basic=True`` selects the
    two-conv basic block (ResNet-18/34); False the 1-3-1 bottleneck."""

    blocks: Tuple[int, ...] = (3, 4, 6, 3)  # ResNet-50
    width: int = 64
    num_classes: int = 1000
    basic: bool = False
    compute_dtype: Any = jnp.bfloat16
    # Run the stem as a 4x4 conv over a 2x2 space-to-depth transform of
    # the input (12 channels instead of 3) — mathematically equivalent to
    # the 7x7 stride-2 conv (weights are rearranged at apply time; the
    # parameter stays the canonical [7,7,3,w] tensor so checkpoints are
    # layout-independent), but it feeds the MXU 4x the input channels.
    # A 3-in-channel conv wastes most of each 128-lane contraction tile;
    # this is the standard TPU ResNet stem rewrite.
    stem_s2d: bool = True
    # Rematerialize each residual block in the backward pass
    # (jax.checkpoint): stores only block inputs instead of every
    # intermediate activation — the standard HBM-for-FLOPs trade that
    # unlocks large batches (e.g. 256x224x224) on one chip.
    remat: bool = False

    @property
    def bottleneck(self) -> bool:
        return not self.basic


def resnet50_config(num_classes: int = 1000, **kw) -> ResNetConfig:
    return ResNetConfig(blocks=(3, 4, 6, 3), num_classes=num_classes, **kw)


def resnet101_config(num_classes: int = 1000, **kw) -> ResNetConfig:
    return ResNetConfig(blocks=(3, 4, 23, 3), num_classes=num_classes, **kw)


def resnet152_config(num_classes: int = 1000, **kw) -> ResNetConfig:
    return ResNetConfig(blocks=(3, 8, 36, 3), num_classes=num_classes, **kw)


def resnet18_config(num_classes: int = 1000, **kw) -> ResNetConfig:
    return ResNetConfig(blocks=(2, 2, 2, 2), num_classes=num_classes,
                        basic=True, **kw)


def _is_basic(cfg: ResNetConfig) -> bool:
    return cfg.basic


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _conv_init(key, kh, kw, cin, cout):
    # He-normal fan-out, the torchvision/Keras ResNet default.
    fan_out = kh * kw * cout
    std = math.sqrt(2.0 / fan_out)
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * std


def _bn_init(c):
    return {
        "scale": jnp.ones((c,), jnp.float32),
        "bias": jnp.zeros((c,), jnp.float32),
    }


def _bn_state(c):
    return {
        "mean": jnp.zeros((c,), jnp.float32),
        "var": jnp.ones((c,), jnp.float32),
    }


def init(rng, config: ResNetConfig) -> Tuple[Params, Params]:
    """Returns ``(params, batch_stats)`` pytrees."""
    # Indexed on demand, not iter(array): iterating slices out all 512
    # keys up front (512 ops traced or dispatched for the ~50 used).
    all_keys = jax.random.split(rng, 512)
    keys = (all_keys[i] for i in range(512))
    params: Params = {}
    stats: Params = {}

    params["stem_conv"] = _conv_init(next(keys), 7, 7, 3, config.width)
    params["stem_bn"] = _bn_init(config.width)
    stats["stem_bn"] = _bn_state(config.width)

    cin = config.width
    expansion = 1 if _is_basic(config) else 4
    for si, nblocks in enumerate(config.blocks):
        cmid = config.width * (2 ** si)
        cout = cmid * expansion
        for bi in range(nblocks):
            name = f"stage{si}_block{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            blk: Params = {}
            bst: Params = {}
            if _is_basic(config):
                blk["conv1"] = _conv_init(next(keys), 3, 3, cin, cmid)
                blk["bn1"] = _bn_init(cmid)
                bst["bn1"] = _bn_state(cmid)
                blk["conv2"] = _conv_init(next(keys), 3, 3, cmid, cout)
                blk["bn2"] = _bn_init(cout)
                bst["bn2"] = _bn_state(cout)
            else:
                blk["conv1"] = _conv_init(next(keys), 1, 1, cin, cmid)
                blk["bn1"] = _bn_init(cmid)
                bst["bn1"] = _bn_state(cmid)
                blk["conv2"] = _conv_init(next(keys), 3, 3, cmid, cmid)
                blk["bn2"] = _bn_init(cmid)
                bst["bn2"] = _bn_state(cmid)
                blk["conv3"] = _conv_init(next(keys), 1, 1, cmid, cout)
                blk["bn3"] = _bn_init(cout)
                bst["bn3"] = _bn_state(cout)
            if bi == 0 and (cin != cout or stride != 1):
                blk["proj_conv"] = _conv_init(next(keys), 1, 1, cin, cout)
                blk["proj_bn"] = _bn_init(cout)
                bst["proj_bn"] = _bn_state(cout)
            params[name] = blk
            stats[name] = bst
            cin = cout

    head_std = 1.0 / math.sqrt(cin)
    params["head_w"] = jax.random.uniform(
        next(keys), (cin, config.num_classes), jnp.float32,
        -head_std, head_std)
    params["head_b"] = jnp.zeros((config.num_classes,), jnp.float32)
    return params, stats


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

_BN_MOMENTUM = 0.9
_BN_EPS = 1e-5


def _conv(x, w, stride=1, dtype=jnp.bfloat16):
    kh = w.shape[0]
    pad = (kh - 1) // 2
    return lax.conv_general_dilated(
        x.astype(dtype), w.astype(dtype),
        window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _stem_s2d_conv(images, w, dtype):
    """The 7x7 stride-2 stem as an equivalent 4x4 stride-1 conv on a 2x2
    space-to-depth input.

    Derivation: with the input padded by 4 (not the usual 3) on every
    spatial edge and the kernel zero-padded to 8x8 at the top-left, the
    stride-2 conv output is ``out[p] = sum_u xpad[2p+u] * w8[u]``
    (u = 0..7, w8[0] = 0, w8[u] = w[u-1]).  Splitting u = 2k + d maps
    every tap onto the space-to-depth grid ``x2[p+k, d-block]`` — a 4x4
    stride-1 VALID conv over 4x the channels.  The output is sliced to
    ceil(H/2) (the VALID conv yields one extra row/col from the pad-4).
    """
    n, h, wd, c = images.shape
    x = jnp.pad(images, ((0, 0), (4, 4), (4, 4), (0, 0)))
    hp, wp = h + 8, wd + 8
    # s2d: x2[n, i, j, (dy*2+dx)*c + ch] = x[n, 2i+dy, 2j+dx, ch]
    x = x.reshape(n, hp // 2, 2, wp // 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, hp // 2, wp // 2, 4 * c)
    # kernel: w8[2k+d, 2l+e, ch, o] -> ws[k, l, (d*2+e)*c + ch, o]
    w8 = jnp.pad(w, ((1, 0), (1, 0), (0, 0), (0, 0)))
    cout = w.shape[-1]
    ws = w8.reshape(4, 2, 4, 2, c, cout)
    ws = ws.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c, cout)
    y = lax.conv_general_dilated(
        x.astype(dtype), ws.astype(dtype),
        window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y[:, : (h + 1) // 2, : (wd + 1) // 2, :]


def _bn(x, p, s, train: bool):
    """Functional batch-norm; statistics in fp32, normalization applied in
    the activation dtype.  Returns (y, new_state).

    The mean/var reductions stay fp32 (bf16 accumulation of squared sums
    is unusable), but the per-element normalization is a single fused
    multiply-add ``x * inv + shift`` with the fp32 scalars folded and cast
    once — in bf16 this halves the HBM bytes of every BN in the network
    versus upcasting the whole activation tensor to fp32.

    The batch statistics are two sums over ``x`` that do not wait for
    each other: ``E[x]``, and the biased variance as ``E[x^2] - E[x]^2``.
    XLA puts both in the epilogue of the convolution that wrote ``x``, so
    no pass reads the activation for statistics alone; ``jnp.var`` needs
    the mean first and costs such a pass a layer forward and, for the
    mean's cotangent, another backward (``tools/resnet_passes.py`` counts
    them).  The difference cancels where a channel's mean is large
    against its deviation: float32 sums hold the variance to 1e-3 up to
    10 deviations and to 1e-2 at 30, ResNet-50's own channels stay under
    10 (PERF.md section 6, PR 48), and the clamp keeps rounding from
    handing ``rsqrt`` a negative number (``tests/test_models.py`` holds
    all of it to a float64 reference)."""
    with jax.named_scope("norm"):
        if train:
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=(0, 1, 2))
            var = jnp.maximum(
                jnp.mean(xf * xf, axis=(0, 1, 2)) - mean * mean, 0.0)
            new_s = {
                "mean": _BN_MOMENTUM * s["mean"] + (1 - _BN_MOMENTUM) * mean,
                "var": _BN_MOMENTUM * s["var"] + (1 - _BN_MOMENTUM) * var,
            }
        else:
            mean, var = s["mean"], s["var"]
            new_s = s
        inv = lax.rsqrt(var + _BN_EPS) * p["scale"]
        shift = p["bias"] - mean * inv
        y = x * inv.astype(x.dtype) + shift.astype(x.dtype)
    return y, new_s


def _block(x, blk, bst, stride, basic, train, dtype):
    out_stats = {}
    shortcut = x
    if "proj_conv" in blk:
        shortcut = _conv(x, blk["proj_conv"], stride, dtype)
        shortcut, out_stats["proj_bn"] = _bn(
            shortcut, blk["proj_bn"], bst["proj_bn"], train)
    if basic:
        y = _conv(x, blk["conv1"], stride, dtype)
        y, out_stats["bn1"] = _bn(y, blk["bn1"], bst["bn1"], train)
        y = jax.nn.relu(y)
        y = _conv(y, blk["conv2"], 1, dtype)
        y, out_stats["bn2"] = _bn(y, blk["bn2"], bst["bn2"], train)
    else:
        y = _conv(x, blk["conv1"], 1, dtype)
        y, out_stats["bn1"] = _bn(y, blk["bn1"], bst["bn1"], train)
        y = jax.nn.relu(y)
        # v1.5: stride lives on the 3x3, not the 1x1.
        y = _conv(y, blk["conv2"], stride, dtype)
        y, out_stats["bn2"] = _bn(y, blk["bn2"], bst["bn2"], train)
        y = jax.nn.relu(y)
        y = _conv(y, blk["conv3"], 1, dtype)
        y, out_stats["bn3"] = _bn(y, blk["bn3"], bst["bn3"], train)
    return jax.nn.relu(y + shortcut), out_stats


def apply(params: Params, batch_stats: Params, images,
          config: ResNetConfig, train: bool = False):
    """Forward pass.  ``images``: [N, H, W, 3] float.  Returns
    ``(logits_fp32, new_batch_stats)``."""
    dtype = config.compute_dtype
    basic = _is_basic(config)
    new_stats: Params = {}

    with jax.named_scope("stem"):
        if (config.stem_s2d and images.shape[1] % 2 == 0
                and images.shape[2] % 2 == 0):
            x = _stem_s2d_conv(images, params["stem_conv"], dtype)
        else:
            x = _conv(images, params["stem_conv"], 2, dtype)
        x, new_stats["stem_bn"] = _bn(
            x, params["stem_bn"], batch_stats["stem_bn"], train)
        x = jax.nn.relu(x)
        x = lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            [(0, 0), (1, 1), (1, 1), (0, 0)])

    block_fn = _block
    if config.remat:
        # Static args (stride/basic/train/dtype) stay python-level;
        # only the array args are checkpointed.
        block_fn = jax.checkpoint(_block, static_argnums=(3, 4, 5, 6))

    cin = config.width
    expansion = 1 if basic else 4
    for si, nblocks in enumerate(config.blocks):
        for bi in range(nblocks):
            name = f"stage{si}_block{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            # The parameters count stages from 0, the paper from 1.
            with jax.named_scope(f"stage{si + 1}"):
                x, new_stats[name] = block_fn(
                    x, params[name], batch_stats[name], stride, basic,
                    train, dtype)

    with jax.named_scope("head_loss"):
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
        logits = x @ params["head_w"] + params["head_b"]
    return logits, new_stats


def loss_fn(params, batch_stats, images, labels, config: ResNetConfig):
    """Softmax cross-entropy; the synthetic-benchmark objective."""
    logits, new_stats = apply(params, batch_stats, images, config,
                              train=True)
    with jax.named_scope("head_loss"):
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    return loss, new_stats
