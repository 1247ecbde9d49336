"""Parameters, bytes and operations of the power-retention LM
(``reference/retention_lm.py``), counted from shapes: what a decode turn
*needs* to move and what a prefill *needs* to compute.  The hand counts
that check these functions are in ``perfbench/tests/test_retention_lm.py``.
(``bytes_count.py`` and ``ops_count.py`` hold the older families'; a PR
that adds a configuration may edit neither, so this family's live here.)
"""

from __future__ import annotations

from typing import Dict

STATE_ITEMSIZE = 4      # the recurrent state is float32


def retention_lm_params(sizes: Dict) -> Dict[str, int]:
    """Matmul parameters by part (norm gains and the gate's bias are a few
    thousand and left out): one layer's retention (q, k, v, o and the
    gate), one feed-forward, the embedding (the untied head is as large),
    and the layers."""
    D, H, KVH, HD = (sizes["hidden_size"], sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    return {"retention": 2 * D * H * HD + 2 * D * KVH * HD + D * KVH,
            "ffn": 3 * D * sizes["intermediate_size"],
            "embed": sizes["vocab_size"] * D,
            "layers": sizes["num_hidden_layers"]}


def state_rows(sizes: Dict, pad_to: int = 1) -> int:
    """Rows of the symmetric square of a key, ``head_dim (head_dim + 1) /
    2``: the rows a state NEEDS; up to a multiple of ``pad_to`` (128: the
    chip's lanes) the rows the program holds."""
    HD = sizes["head_dim"]
    return -(-(HD * (HD + 1) // 2) // pad_to) * pad_to


def state_bytes(sizes: Dict, slots: int, pad_to: int = 1) -> int:
    """The recurrent state of ``slots`` slots: a ``[head_dim, rows]``
    matrix and a ``[rows]`` normaliser a slot a key/value head a layer.
    Needed bytes; with ``pad_to`` 128 what the program holds in memory
    (padding is memory, not work: no roofline counts it)."""
    return (sizes["num_hidden_layers"] * slots * sizes["num_key_value_heads"]
            * state_rows(sizes, pad_to) * (sizes["head_dim"] + 1)
            * STATE_ITEMSIZE)


def state_pass_bytes(sizes: Dict, slots: int) -> int:
    """What the step's passes over the state matrices must move a turn:
    the needed rows of every slot's ``S`` (a free slot's is stepped too)
    once in and once out.  The normaliser, 1/129 of the state, is not the
    kernel's."""
    return (2 * sizes["num_hidden_layers"] * slots
            * sizes["num_key_value_heads"] * state_rows(sizes)
            * sizes["head_dim"] * STATE_ITEMSIZE)


def retention_lm_decode_turn_bytes(sizes: Dict, max_batch: int,
                                   weight_itemsize: int = 2) -> float:
    """What one decode turn of ``max_batch`` slots must move: every layer's
    weights and the head once in the type they are held in (the embedding
    is a lookup of a row a slot), and the needed rows of the recurrent
    state of ALL slots once in and once out.  Nothing in it grows with a
    slot's position."""
    p = retention_lm_params(sizes)
    weights = p["layers"] * (p["retention"] + p["ffn"]) + p["embed"]
    return float(weight_itemsize * weights
                 + 2 * state_bytes(sizes, max_batch))


def retention_lm_prefill_flops(sizes: Dict, prompt_len: int) -> float:
    """Operations one prompt of ``prompt_len`` tokens NEEDS: twice the
    matmul parameters a token passes through, the head for the one row
    that is sampled, and the retention at the cheaper of its two forms
    for this length: the quadratic form (q . k and w v over half of the
    S x S matrix, then the state the prompt ends in: one outer product a
    key) or the recurrence (a product with the state a query and an
    outer product a key, a position)."""
    p = retention_lm_params(sizes)
    L, H, KVH, HD = (p["layers"], sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    rows = HD * (HD + 1) // 2           # the needed rows, not the padding
    n = float(prompt_len)
    end_state = 2.0 * KVH * rows * HD * n
    quadratic = H * 4.0 * HD * n * n / 2.0 + end_state
    recurrent = 2.0 * H * rows * HD * n + end_state
    return (2.0 * L * (p["retention"] + p["ffn"]) * n
            + L * min(quadratic, recurrent) + 2.0 * p["embed"])
