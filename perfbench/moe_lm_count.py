"""Parameters, bytes and operations of the routed-expert, latent-attention
LM (``reference/moe_lm.py``), counted from shapes: what a decode turn
*needs* to move and what a prefill *needs* to compute.  The hand counts
that check these functions are in ``perfbench/tests/test_moe_lm.py``.
(``bytes_count.py`` and ``ops_count.py`` hold the other families'; a PR
that adds a configuration may edit neither, so this family's live here.)
"""

from __future__ import annotations

from typing import Dict


def moe_lm_params(sizes: Dict) -> Dict[str, int]:
    """Matmul parameters by part (norm gains and the router's bias are a
    few thousand and left out): one attention, the dense feed-forward,
    the router, ONE expert (routed or shared), the embedding (the untied
    head is as large), and how many layers of each kind there are."""
    D, H = sizes["hidden_size"], sizes["num_attention_heads"]
    Rq, Rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope, vd = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    dense = sizes["first_k_dense_replace"]
    return {
        "attention": (D * Rq + Rq * H * (nope + rope) + D * (Rkv + rope)
                      + Rkv * H * (nope + vd) + H * vd * D),
        "dense_ffn": 3 * D * sizes["intermediate_size"],
        "router": D * sizes["n_routed_experts"],
        "expert": 3 * D * sizes["moe_intermediate_size"],
        "embed": sizes["vocab_size"] * D,
        "dense_layers": dense,
        "moe_layers": sizes["num_hidden_layers"] - dense}


def moe_lm_decode_turn_bytes(sizes: Dict, experts_touched: float,
                             weight_itemsize: int = 2) -> float:
    """What one decode turn must move: every weight outside the routed
    experts once (the attention of every layer, the dense feed-forward,
    the routers, the shared experts, the head; the embedding is a lookup
    of a row a slot), and of each expert layer's routed experts the
    ``experts_touched`` that had a row: the MEASURED mean, a layer a
    turn, from the program's counters.  The latent cache's reads are left
    out (they grow with the positions held), so a share of the memory
    roofline computed from this is a lower bound."""
    p = moe_lm_params(sizes)
    shared = sizes["n_shared_experts"] * p["expert"]
    outside = (p["dense_layers"] * (p["attention"] + p["dense_ffn"])
               + p["moe_layers"] * (p["attention"] + p["router"] + shared)
               + p["embed"])
    routed = p["moe_layers"] * experts_touched * p["expert"]
    return float(weight_itemsize * (outside + routed))


def moe_lm_prefill_flops(sizes: Dict, prompt_len: int) -> float:
    """Operations one prompt of ``prompt_len`` tokens NEEDS: twice the
    matmul parameters a token passes through (of the routed experts the
    ``num_experts_per_tok`` it chose, not all of them), causal attention
    at the expanded width (q.k over nope + rope, p.v over v, half of the
    S x S matrix), and the head for the one row that is sampled."""
    p = moe_lm_params(sizes)
    active = (p["dense_layers"] * (p["attention"] + p["dense_ffn"])
              + p["moe_layers"] * (
                  p["attention"] + p["router"]
                  + (sizes["n_shared_experts"]
                     + sizes["num_experts_per_tok"]) * p["expert"]))
    layers = p["dense_layers"] + p["moe_layers"]
    width = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
             + sizes["v_head_dim"])
    attention = (layers * sizes["num_attention_heads"] * 2.0 * width
                 * prompt_len * prompt_len / 2.0)
    return 2.0 * active * prompt_len + attention + 2.0 * p["embed"]
