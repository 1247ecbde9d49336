"""Bytes the timed work *needs* to move through the chip's memory, counted
from shapes (``ops_count.py`` counts operations).  The hand count that
checks this function is in ``perfbench/tests/test_bytes_count.py``.
"""

from __future__ import annotations

from typing import Dict


def ssm_lm_params(sizes: Dict) -> Dict[str, int]:
    """Parameters of the hybrid state-space LM (``reference/ssm_lm.py``),
    by part: one Mamba mixer, one attention mixer, one feed-forward (with
    the block's two norms), the tied embedding with the final norm, and
    how many layers of each kind there are."""
    D, F, V = (sizes["hidden_size"], sizes["intermediate_size"],
               sizes["vocab_size"])
    H, KVH = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    HD = D // H
    Di = sizes["mamba_expand"] * D
    N, K, R = (sizes["mamba_d_state"], sizes["mamba_d_conv"],
               sizes["mamba_dt_rank"])
    attn_layers = sum(
        i % sizes["attn_layer_period"] == sizes["attn_layer_offset"]
        for i in range(sizes["num_hidden_layers"]))
    return {
        "mamba_mixer": (D * 2 * Di + K * Di + Di + Di * (R + 2 * N)
                        + R + 2 * N + R * Di + Di + N * Di + Di + Di * D),
        "attn_mixer": D * H * HD + 2 * D * KVH * HD + H * HD * D,
        "ffn": 3 * D * F + 2 * D,
        "embed": V * D + D,
        "attn_layers": attn_layers,
        "mamba_layers": sizes["num_hidden_layers"] - attn_layers}


def ssm_lm_decode_turn_bytes(sizes: Dict, max_batch: int,
                             weight_itemsize: int = 2,
                             conv_itemsize: int = 2) -> float:
    """What one decode turn of ``max_batch`` slots must move: every weight
    once in the type it is held in, and the recurrent state of ALL slots
    (a free slot's is computed too) once in and once out: ``d_state x
    d_inner`` float32 and the convolution's ``d_conv - 1`` last inputs a
    slot a Mamba layer.  The attention layers' key/value reads are left
    out: they grow with the positions held and are under 2 % of this at
    the cell's sizes (0.1 GB for 64 slots x 1536)."""
    p = ssm_lm_params(sizes)
    n_params = (p["mamba_layers"] * (p["mamba_mixer"] + p["ffn"])
                + p["attn_layers"] * (p["attn_mixer"] + p["ffn"])
                + p["embed"])
    Di = sizes["mamba_expand"] * sizes["hidden_size"]
    state = p["mamba_layers"] * max_batch * Di * (
        4 * sizes["mamba_d_state"]
        + conv_itemsize * (sizes["mamba_d_conv"] - 1))
    return float(weight_itemsize * n_params + 2 * state)
