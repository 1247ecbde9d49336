"""Parameters, bytes and operations of the Mamba-2 / routed-expert /
attention LM (``reference/ssd_moe_lm.py``), counted from shapes: what a
decode turn *needs* to move and what a prefill *needs* to compute.
``sizes`` are the program's keys: ``n_routed_experts`` the router's
outputs, ``experts_held`` the experts on this chip.  The hand counts that
check these functions are in ``perfbench/tests/test_ssd_moe_lm.py``.
"""

from __future__ import annotations

from typing import Dict, Sequence

ITEM = 2        # bytes of a bfloat16 weight, cached value or window row
STATE_ITEM = 4  # bytes of a float32 state value


def params(sizes: Dict) -> Dict[str, int]:
    """Parameters by part: one Mamba-2 mixer (in, out, the convolution and
    its per-head and per-channel leaves), one attention, the router, ONE
    routed expert, the shared expert, the embedding (the untied head is
    as large), and how many layers of each kind the pattern has.  The
    layers' own norm gains (``hidden_size`` each) are left out."""
    D = sizes["hidden_size"]
    H, KVH, HD = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    Hm = sizes["mamba_num_heads"]
    Di = Hm * sizes["mamba_head_dim"]
    C = Di + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    pattern = sizes["hybrid_override_pattern"]
    return {
        "mamba": (D * (Di + C + Hm) + Di * D
                  + (sizes["conv_kernel"] + 1) * C + 3 * Hm + Di),
        "attention": D * H * HD + 2 * D * KVH * HD + H * HD * D,
        "router": D * sizes["n_routed_experts"],
        "expert": 2 * D * sizes["moe_intermediate_size"],
        "shared": 2 * D * sizes["moe_shared_expert_intermediate_size"],
        "embed": sizes["vocab_size"] * D,
        "mamba_layers": pattern.count("M"),
        "moe_layers": pattern.count("E"),
        "attn_layers": pattern.count("*")}


def outside_experts(sizes: Dict) -> int:
    """Parameters of all layers outside their routed experts."""
    p = params(sizes)
    return (p["mamba_layers"] * p["mamba"] + p["attn_layers"] * p["attention"]
            + p["moe_layers"] * (p["router"] + p["shared"]))


def held_params(sizes: Dict) -> int:
    """Everything the chip holds: its layers with ``experts_held`` routed
    experts each, the embedding and the head."""
    p = params(sizes)
    return (outside_experts(sizes)
            + p["moe_layers"] * sizes["experts_held"] * p["expert"]
            + 2 * p["embed"])


def held_weight_bytes(sizes: Dict) -> int:
    """The bfloat16 bytes the chip really holds: ``held_params`` with each
    routed expert's two matrices at the widths the program holds them at,
    zeros up to whole tiles (2688 x 1856 as 3072 x 2048): what fills the
    memory, not what a turn needs."""
    from perfbench.reference.ssd_moe_lm import padded_width

    p = params(sizes)
    stacks = (p["moe_layers"] * sizes["experts_held"] * 2
              * padded_width(sizes["hidden_size"])
              * padded_width(sizes["moe_intermediate_size"]))
    return ITEM * (outside_experts(sizes) + 2 * p["embed"] + stacks)


def state_bytes_per_slot(sizes: Dict) -> int:
    """A slot's recurrent state over all Mamba-2 layers: the float32
    ``[heads, head_dim, ssm_state_size]`` state and the convolution's
    ``conv_kernel - 1`` last inputs."""
    Hm, P, N = (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
                sizes["ssm_state_size"])
    C = Hm * P + 2 * sizes["n_groups"] * N
    return params(sizes)["mamba_layers"] * (
        STATE_ITEM * Hm * P * N + ITEM * (sizes["conv_kernel"] - 1) * C)


def kv_bytes_per_position(sizes: Dict) -> int:
    """What a position keeps over all attention layers: a key and a value
    a key/value head."""
    return (params(sizes)["attn_layers"] * 2 * ITEM
            * sizes["num_key_value_heads"] * sizes["head_dim"])


def decode_turn_bytes(sizes: Dict, max_batch: int, experts_touched: float
                      ) -> float:
    """What one decode turn of ``max_batch`` slots must move: every weight
    outside the routed experts once (the embedding is a lookup; the head
    counts), of each expert layer's held experts the ``experts_touched``
    that had a row (the MEASURED mean a layer a turn, from the program's
    counters), and the recurrent state of ALL slots (a free slot's is
    stepped too) once in and once out.  The attention layers' key/value
    reads are left out (they grow with the positions held, and are under
    5 % of this at the cell's sizes), so a share of the memory roofline
    computed from this is a lower bound."""
    p = params(sizes)
    weights = (outside_experts(sizes) + p["embed"]
               + p["moe_layers"] * experts_touched * p["expert"])
    return float(ITEM * weights
                 + 2 * max_batch * state_bytes_per_slot(sizes))


def prefill_flops(sizes: Dict, prompt_len: int) -> float:
    """Operations one prompt of ``prompt_len`` tokens NEEDS: twice the
    matmul parameters a token passes through (of the routed experts what
    this chip holds of the ``num_experts_per_tok`` chosen: ``experts_held
    / n_routed_experts`` of them under an even router), the recurrence's
    own update and read-out of the state a token a Mamba-2 layer (4 H P N:
    what the step-by-step form makes; the chunked form makes more and is
    credited no more), causal attention (q.k and p.v over ``head_dim``,
    half of the S x S matrix), and the head for the one row sampled."""
    p = params(sizes)
    here = (sizes["num_experts_per_tok"] * sizes["experts_held"]
            / sizes["n_routed_experts"])
    active = outside_experts(sizes) + p["moe_layers"] * here * p["expert"]
    n = prompt_len
    recurrence = (p["mamba_layers"] * 4.0 * sizes["mamba_num_heads"]
                  * sizes["mamba_head_dim"] * sizes["ssm_state_size"] * n)
    attention = (p["attn_layers"] * sizes["num_attention_heads"] * 2.0
                 * 2 * sizes["head_dim"] * n * n / 2.0)
    return 2.0 * active * n + recurrence + attention + 2.0 * p["embed"]


def mean_prefill_flops_per_token(sizes: Dict, prompts: Sequence[int]
                                 ) -> float:
    return sum(prefill_flops(sizes, n) for n in prompts) / sum(prompts)
