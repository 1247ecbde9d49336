"""Parameters, bytes and operations of the latent-attention LM with a
learned indexer and a share of its routed experts
(``reference/sparse_moe_lm.py``), counted from shapes: what a decode turn
*needs* to move and what a prefill *needs* to compute.  ``sizes`` are the
program's keys: ``n_routed_experts`` the router's outputs,
``experts_held`` the experts on this chip.  The hand counts that check
these functions are in ``perfbench/tests/test_sparse_moe_lm.py``.
"""

from __future__ import annotations

from typing import Dict, Sequence

ITEM = 2        # bytes of a bfloat16 weight or cached value


def params(sizes: Dict) -> Dict[str, int]:
    """Matmul parameters by part (norm gains and biases are a few
    thousand and left out): one attention, one indexer, the dense
    feed-forward, the router, ONE expert (routed or shared), the embedding
    (the untied head is as large), and how many layers of each kind."""
    D, H = sizes["hidden_size"], sizes["num_attention_heads"]
    Rq, Rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope, vd = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    HI, DI = sizes["index_n_heads"], sizes["index_head_dim"]
    dense = sizes["first_k_dense_replace"]
    return {
        "attention": (D * Rq + Rq * H * (nope + rope) + D * (Rkv + rope)
                      + Rkv * H * (nope + vd) + H * vd * D),
        "indexer": Rq * HI * DI + D * DI + D * HI,
        "dense_ffn": 3 * D * sizes["intermediate_size"],
        "router": D * sizes["n_routed_experts"],
        "expert": 3 * D * sizes["moe_intermediate_size"],
        "embed": sizes["vocab_size"] * D,
        "dense_layers": dense,
        "moe_layers": sizes["num_hidden_layers"] - dense}


def outside_experts(sizes: Dict) -> Dict[str, int]:
    """Parameters of a layer of each kind outside its routed experts."""
    p = params(sizes)
    mixer = p["attention"] + p["indexer"]
    return {"moe": (mixer + p["router"]
                    + sizes["n_shared_experts"] * p["expert"]),
            "dense": mixer + p["dense_ffn"]}


def held_params(sizes: Dict) -> int:
    """Everything the chip holds: its layers with ``experts_held`` routed
    experts each, the embedding and the head."""
    p, o = params(sizes), outside_experts(sizes)
    return (p["dense_layers"] * o["dense"]
            + p["moe_layers"] * (o["moe"]
                                 + sizes["experts_held"] * p["expert"])
            + 2 * p["embed"])


def cache_bytes_per_position(sizes: Dict) -> int:
    """What a position keeps over all layers: a latent, a rotary key and
    an index key a layer."""
    return ITEM * sizes["num_hidden_layers"] * (
        sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]
        + sizes["index_head_dim"])


def selected_read(sizes: Dict, selected: float) -> Dict[str, float]:
    """What the attention over ``selected`` (slot, position) pairs of ONE
    layer needs: each pair's latent and rotary key once, and the absorbed
    form's two products over them for every head (q . [c | k_r] and
    p . c)."""
    H = sizes["num_attention_heads"]
    Rkv, rope = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    return {"bytes": ITEM * selected * (Rkv + rope),
            "ops": 2.0 * H * selected * (2 * Rkv + rope)}


def decode_turn_bytes(sizes: Dict, experts_touched: float, scored: float,
                      selected: float) -> float:
    """What one decode turn must move: every weight outside the routed
    experts once (the embedding is a lookup; the head counts), of each
    expert layer's held experts the ``experts_touched`` that had a row
    (the MEASURED mean a layer a turn), the index keys of the ``scored``
    positions and the latents and rotary keys of the ``selected`` ones (a
    layer a turn, MEASURED from the program's counters).  The program
    reads every scored position's latent, not the selected alone: what
    the selected read needs is the lower bound that is counted."""
    p, o = params(sizes), outside_experts(sizes)
    weights = (p["dense_layers"] * o["dense"] + p["moe_layers"] * o["moe"]
               + p["embed"]
               + p["moe_layers"] * experts_touched * p["expert"])
    L = sizes["num_hidden_layers"]
    return float(ITEM * weights + L * (
        ITEM * scored * sizes["index_head_dim"]
        + selected_read(sizes, selected)["bytes"]))


def prefill_flops(sizes: Dict, prompt_len: int) -> float:
    """Operations one prompt of ``prompt_len`` tokens NEEDS: twice the
    matmul parameters a token passes through (of the routed experts what
    this chip holds of the ``num_experts_per_tok`` chosen: ``experts_held
    / n_routed_experts`` of them under an even router), the indexer's
    scores over the causal triangle, attention at the expanded width over
    the SELECTED keys of each query (``min(index_topk, t + 1)``, not the
    dense triangle), and the head for the one row that is sampled."""
    p, o = params(sizes), outside_experts(sizes)
    here = (sizes["num_experts_per_tok"] * sizes["experts_held"]
            / sizes["n_routed_experts"])
    active = (p["dense_layers"] * o["dense"]
              + p["moe_layers"] * (o["moe"] + here * p["expert"]))
    layers = sizes["num_hidden_layers"]
    top = sizes["index_topk"]
    n = prompt_len
    attended = (n * (n + 1) / 2 if n <= top
                else top * (top + 1) / 2 + (n - top) * top)
    width = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
             + sizes["v_head_dim"])
    attention = layers * sizes["num_attention_heads"] * 2.0 * width * attended
    index = (layers * sizes["index_n_heads"] * 2.0 * sizes["index_head_dim"]
             * n * (n + 1) / 2)
    return 2.0 * active * n + attention + index + 2.0 * p["embed"]


def mean_prefill_flops_per_token(sizes: Dict, prompts: Sequence[int]
                                 ) -> float:
    return sum(prefill_flops(sizes, n) for n in prompts) / sum(prompts)
