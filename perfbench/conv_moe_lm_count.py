"""Parameters, bytes and operations of the short-convolution / attention /
routed-expert LM (``reference/conv_moe_lm.py``), counted from shapes: what
a decode turn *needs* to move and what a prefill *needs* to compute.
``sizes`` are the configuration's published keys.  The hand counts that
check these functions are in ``perfbench/tests/test_conv_moe_lm.py``.
"""

from __future__ import annotations

from typing import Dict, Sequence

ITEM = 2        # bytes of a bfloat16 weight, cached value or window row


def params(sizes: Dict) -> Dict[str, int]:
    """Parameters by part: one short-convolution operator (in, out, the
    convolution), one attention (its two per-head gains with it), one
    dense feed-forward, the router, ONE routed expert, the embedding (the
    head is the same matrix), and how many layers hold each.  The layers'
    own norm gains (``hidden_size`` each) are left out."""
    D = sizes["hidden_size"]
    H, KVH = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    HD = D // H
    kinds = list(sizes["layer_types"])
    dense = sizes["num_dense_layers"]
    return {
        "conv": D * 3 * D + D * D + sizes["conv_L_cache"] * D,
        "attention": 2 * D * H * HD + 2 * D * KVH * HD + 2 * HD,
        "dense": 3 * D * sizes["intermediate_size"],
        "router": D * sizes["num_experts"],
        "expert": 3 * D * sizes["moe_intermediate_size"],
        "embed": sizes["vocab_size"] * D,
        "conv_layers": kinds.count("conv"),
        "attn_layers": kinds.count("full_attention"),
        "dense_layers": dense,
        "moe_layers": len(kinds) - dense}


def outside_experts(sizes: Dict) -> int:
    """Parameters of all layers outside their routed experts."""
    p = params(sizes)
    return (p["conv_layers"] * p["conv"] + p["attn_layers"] * p["attention"]
            + p["dense_layers"] * p["dense"] + p["moe_layers"] * p["router"])


def held_params(sizes: Dict) -> int:
    """Everything the chip holds: its layers with every routed expert, and
    the embedding, which is the head."""
    p = params(sizes)
    return (outside_experts(sizes)
            + p["moe_layers"] * sizes["num_experts"] * p["expert"]
            + p["embed"])


def held_weight_bytes(sizes: Dict) -> int:
    """The bfloat16 bytes the chip really holds: ``held_params`` with each
    routed expert's three matrices ``padded_width`` wide, as the program
    holds them (zeros past the published width): what fills the memory,
    not what a turn needs."""
    from perfbench.reference.ssd_moe_lm import padded_width

    p = params(sizes)
    width = padded_width(sizes["moe_intermediate_size"])
    stacks = (p["moe_layers"] * sizes["num_experts"] * 3
              * sizes["hidden_size"] * width)
    return ITEM * (outside_experts(sizes) + p["embed"] + stacks)


def window_bytes_per_slot(sizes: Dict) -> int:
    """A slot's kept windows over all short-convolution layers: the last
    ``conv_L_cache - 1`` rows of the gate's product."""
    return (params(sizes)["conv_layers"] * ITEM
            * (sizes["conv_L_cache"] - 1) * sizes["hidden_size"])


def kv_bytes_per_position(sizes: Dict) -> int:
    """What a position keeps over all attention layers: a key and a value
    a key/value head."""
    D, H = sizes["hidden_size"], sizes["num_attention_heads"]
    return (params(sizes)["attn_layers"] * 2 * ITEM
            * sizes["num_key_value_heads"] * (D // H))


def routed_product_bytes(sizes: Dict, experts_touched: float) -> float:
    """What the grouped products of ONE decode turn must read: the
    published bytes of the experts that had a row, ``experts_touched`` the
    MEASURED mean a layer a turn (the program's counters), over all
    expert layers."""
    p = params(sizes)
    return float(ITEM * p["moe_layers"] * experts_touched * p["expert"])


def decode_turn_bytes(sizes: Dict, experts_touched: float,
                      slots_busy: float, mean_position: float) -> float:
    """What one decode turn must move: every weight outside the routed
    experts once and the head (the embedding's lookup is a few rows), of
    each expert layer the ``experts_touched`` that had a row
    (:func:`routed_product_bytes`), and of the slots' state what the
    ``slots_busy`` requests hold: their windows once in and once out, and
    the keys and values of the positions they have WRITTEN
    (``mean_position`` a busy slot), not the lanes' length.  A program
    that reads every lane whole moves more than this and is credited no
    more, so a share of the memory roofline computed from this is a lower
    bound."""
    p = params(sizes)
    return float(ITEM * (outside_experts(sizes) + p["embed"])
                 + routed_product_bytes(sizes, experts_touched)
                 + slots_busy * (2 * window_bytes_per_slot(sizes)
                                 + mean_position
                                 * kv_bytes_per_position(sizes)))


def prefill_flops(sizes: Dict, prompt_len: int) -> float:
    """Operations one prompt of ``prompt_len`` tokens NEEDS: twice the
    matmul parameters a token passes through (the operators, the dense
    layers, the routers and ``num_experts_per_tok`` experts a layer), the
    convolution's own sum, causal attention (q.k and p.v over the head's
    values, half of the S x S matrix), and the head for the one row
    sampled."""
    p = params(sizes)
    D, H = sizes["hidden_size"], sizes["num_attention_heads"]
    active = (outside_experts(sizes)
              + p["moe_layers"] * sizes["num_experts_per_tok"] * p["expert"])
    n = prompt_len
    attention = p["attn_layers"] * H * 2.0 * 2 * (D // H) * n * n / 2.0
    return 2.0 * active * n + attention + 2.0 * p["embed"]


def mean_prefill_flops_per_token(sizes: Dict, prompts: Sequence[int]
                                 ) -> float:
    return sum(prefill_flops(sizes, n) for n in prompts) / sum(prompts)
