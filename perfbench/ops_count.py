"""Operations and bytes the timed work *needs*, counted from shapes.

A multiply-add is two operations.  A training step needs a forward pass
and twice that for the backward pass; recomputed work (remat, the
attention kernel's second forward) is not needed work and is not counted.
Attention is causal: half of the S x S score matrix.  The hand counts that
check these functions are in PERF.md, section 3.
"""

from __future__ import annotations

from typing import Dict, List, Tuple


# ---------------------------------------------------------------------------
# ResNet (v1.5 bottleneck): from the convolution shapes
# ---------------------------------------------------------------------------


def resnet_conv_shapes(cfg: Dict) -> List[Tuple[int, int, int, int, int]]:
    """(out_h, out_w, kernel_area, c_in, c_out) of every convolution and of
    the classifier, for ``image_size`` square inputs."""
    size = cfg["image_size"]
    width = cfg["width"]
    shapes = []
    h = size // 2                                   # 7x7 stride-2 stem
    shapes.append((h, h, 49, 3, width))
    h = h // 2                                      # 3x3 stride-2 max-pool
    cin = width
    for si, nblocks in enumerate(cfg["blocks"]):
        cmid = width * 2 ** si
        cout = 4 * cmid
        for bi in range(nblocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            h_out = h // stride
            shapes.append((h, h, 1, cin, cmid))             # 1x1
            shapes.append((h_out, h_out, 9, cmid, cmid))    # 3x3, strided
            shapes.append((h_out, h_out, 1, cmid, cout))    # 1x1
            if bi == 0:
                shapes.append((h_out, h_out, 1, cin, cout))  # projection
            cin, h = cout, h_out
    shapes.append((1, 1, 1, cin, cfg["num_classes"]))       # classifier
    return shapes


def resnet_train_flops_per_image(cfg: Dict) -> float:
    macs = sum(oh * ow * k * ci * co
               for oh, ow, k, ci, co in resnet_conv_shapes(cfg))
    return 3.0 * 2.0 * macs


# ---------------------------------------------------------------------------
# decoder LM
# ---------------------------------------------------------------------------


def lm_train_flops_per_token(sizes: Dict, seq_len: int) -> float:
    """6 x (matmul parameters a token passes through) + the causal
    attention core: per layer QK^T and PV are 2 x 2 x S x D operations a
    token uncausal, half that causal, three times that with the backward
    pass."""
    D, F, V, L = (sizes["d_model"], sizes["d_ff"], sizes["vocab_size"],
                  sizes["n_layers"])
    matmul_params = L * (4 * D * D + 3 * D * F) + V * D
    attn_core = L * 3 * (2 * 2 * seq_len * D) / 2
    return 6.0 * matmul_params + attn_core


def flash_attention_needed(batch: int, heads: int, seq_len: int,
                           head_dim: int, layers: int,
                           itemsize: int = 2) -> Dict[str, float]:
    """Operations and HBM bytes one training step's causal attention needs
    over all layers: forward QK^T and PV (2 matmuls), backward the score
    recompute, dP, dV, dK and dQ (5); each 2 x S^2 x D / 2 operations a
    head.  Bytes: q, k, v read and o written forward; q, k, v, o, do read
    and dq, dk, dv written backward (the row statistics are small)."""
    per_matmul = 2.0 * seq_len * seq_len * head_dim / 2.0
    ops = layers * batch * heads * 7.0 * per_matmul
    tensor = batch * heads * seq_len * head_dim * itemsize
    return {"ops": ops, "bytes": layers * 12.0 * tensor}
