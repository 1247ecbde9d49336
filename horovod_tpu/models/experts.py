"""A routed feed-forward that drops no row: top-k of many experts, grouped
matrix products over the experts that have rows.

One function, :func:`routed_ffn`, for a prompt's thousands of rows and
for a serving turn's few dozen:

1. ``s = sigmoid(x W_r)`` in float32; the ``k`` experts of a row are the
   top-k of ``s + b`` (``b``: a bias that *selects only*), their weights
   ``scale * s_i / (sum of the chosen s + 1e-20)`` (the ``noaux_tc``
   router of the DeepSeek-V3 line).  With ``n_group`` > 1 the choice is
   limited to groups: the experts lie in ``n_group`` groups of equal
   size, a group's score is the sum of its two largest ``s + b``, and
   only the ``topk_group`` best groups' experts stand for the top-k;
2. the ``rows x k`` (row, expert) pairs are sorted by expert, the rows
   gathered in that order, and each expert's rows go through its
   matrices, in the expert's own form: with a ``w_gate`` stack three
   (``w_out (w_in x * silu(w_gate x))``), without one two
   (``w_out relu(w_in x)^2``).  An expert with no row costs nothing, one
   with many rows gets them all.  **No capacity, no dropped row, no
   auxiliary loss**, so a row's output depends on that row alone: what a
   served slot returns never depends on its neighbours.  Two forms of
   the same products, chosen from shapes alone (:func:`one_kernel`):

   * ``jax.lax.ragged_dot`` over the groups, a call a matrix, the
     product between them through main memory: a few rows an expert (a
     walk over ALL experts would fetch blocks for the many that have no
     row), a prompt's thousands of rows, a share of the experts, experts
     without a gate;
   * ONE kernel a layer, ``ops/pallas_routed_ffn.py:routed_ffn_rows``:
     gated experts all held (``first`` None), ``MANY_ROWS`` (8) pairs an
     expert or more, so that a turn touches every expert, and at most
     ``RESIDENT_ROWS`` (1024) pairs, which stay in fast memory with their
     float32 result while each expert's three matrices stream past once,
     up to the published ``width``.  ``lfm2-8b-a1b``'s step (192 slots x 4
     over 32 experts) and its prompts of 128 and 256 tokens; not
     ``glm-4.7-flash`` (4 pairs an expert), ``deepseek-v3.2`` (a share) or
     ``nemotron-3-nano-30b-a3b`` (a share, no gate);
3. the results go back to their rows with their weights.

The experts' weights arrive stacked over LAYERS as well, ``[L, E, D, F]``,
with the layer a traced index: the groups are laid over the ``L x E``
leading axis (a free reshape) and only layer ``l``'s are non-empty, so a
layer loop with a dynamic index never cuts a layer's 1.2 GB of experts
out of their stack (a grouped product is a custom call and a slice in
front of it is a copy); the kernel addresses layer and expert in its
index maps.

Rows marked not ``live`` (a serving batch's free slots) are routed
nowhere: they sort behind the last group, no expert's weights are read
for them, and their output is zero.

**A share of the experts.**  The stack may hold fewer experts than the
router has outputs: experts ``first`` to ``first + E`` of a layer divided
over chips (``E`` is the stack's own second axis).  The routing is over
all of them; a pair whose expert lies on another chip goes the way of a
free slot's (behind the last group, no product, zero), and what comes back
is this chip's part of the sum.  Over all the shares of a layer the parts
add up to the whole layer's output (tests/test_sparse_latent_moe.py);
nothing here stands in for the other chips or for the exchange with
them.

``stats`` counts what was really routed, as ``[3]`` int32: (row, expert)
pairs, experts with at least one row, the fullest expert's rows.

models/transformer.py's ``_moe_ffn`` (top-1, a capacity that drops, a
``[T, E, C]`` one-hot dispatch, the ``ep`` exchange) is the trained path
and is not replaced here; this module imports nothing of it so that it
can take this one later.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops.pallas_routed_ffn import routed_ffn_rows


# What a model's decode step adds to its state's ``"counters"`` from
# ``routed_ffn``'s stats, over its expert layers: (row, expert) pairs
# routed, experts with at least one row, the fullest expert's rows, and the
# expert layers stepped; with a share of the experts, also the live pairs
# whose expert lies on another chip.
MOE_COUNTERS = ("hvd_moe_rows_routed_total", "hvd_moe_experts_touched_total",
                "hvd_moe_max_expert_rows_total", "hvd_moe_layer_turns_total")
ABSENT_COUNTER = "hvd_moe_rows_absent_total"
# Of the expert layers stepped, those whose products ran as the one kernel
# (:func:`one_kernel`): a model that can take it counts them.
FUSED_COUNTER = "hvd_moe_fused_layer_turns_total"


# The grouped product's tiles: a stack axis that is not whole tiles makes
# it walk the stack in pieces of 128 or 384 (on the chip, 576 rows against
# 63 experts, bytes of the touched experts a second: [2688, 1856] 118 GB/s,
# [3072, 2048] 561; 6144 rows: 179 and 269; PR 46, TPU v5 lite).
TILE = 512

# Step 2 runs as the one kernel (``ops/pallas_routed_ffn.py``) from
# ``MANY_ROWS`` pairs an expert of the stack on, where a turn touches every
# expert, up to the ``RESIDENT_ROWS`` pairs whose rows [pairs, D] and
# float32 result the kernel keeps in fast memory beside its weight tiles
# (at D 2048: 4 + 8 of v5e's 128 MiB).
MANY_ROWS = 8
RESIDENT_ROWS = 1024


def padded_width(n: int) -> int:
    """The width a routed expert's stack is held at for a published width
    ``n``: whole tiles, where ``n`` is more than one."""
    return -(-n // TILE) * TILE if n > TILE else n


def route(x, router, bias, top_k: int, scale: float, n_group: int = 1,
          topk_group: int = 1) -> Tuple[jax.Array, jax.Array]:
    """x: [T, D] -> (chosen [T, k] int32, weights [T, k] float32).
    Selection by the biased score (inside the ``topk_group`` best of
    ``n_group`` groups where there are groups), weight from the unbiased
    one."""
    s = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x.astype(jnp.float32), router.astype(jnp.float32),
        preferred_element_type=jnp.float32))
    biased = s + bias.astype(jnp.float32)
    if n_group > 1:
        T, E = biased.shape
        grouped = biased.reshape(T, n_group, E // n_group)
        best_two, _ = lax.top_k(grouped, 2)
        _, kept = lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
        keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
        biased = jnp.where(keep[:, :, None], grouped, -jnp.inf
                           ).reshape(T, E)
    _, chosen = lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = scale * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return chosen, weights


def one_kernel(experts, pairs: int, first: Optional[int] = None) -> bool:
    """Whether :func:`routed_ffn` runs step 2 of ``pairs`` (row, expert)
    pairs as the one kernel: from shapes alone (see the module
    docstring)."""
    return ("w_gate" in experts and first is None
            and MANY_ROWS * experts["w_in"].shape[1] <= pairs
            <= RESIDENT_ROWS)


def routed_ffn(x, experts, layer, chosen, weights, dtype,
               live: Optional[jax.Array] = None,
               first: Optional[int] = None, width: Optional[int] = None):
    """x: [T, D]; ``experts``: ``w_in`` [L, E, D, F], ``w_out`` [L, E, F,
    D] and, for gated experts, ``w_gate`` [L, E, D, F] (without it an
    expert is ``w_out relu(w_in x)^2``), ``D`` and ``F`` as published or
    padded with zeros (:func:`padded_width`); ``layer``: which of the L
    (may be traced); ``chosen``, ``weights``: [T, k] from :func:`route`;
    ``live``: [T] bool or None (all); ``first``: None where the stack holds
    every expert the router chooses among, else the router's output that the
    stack's expert 0 answers to (a share: ``first`` to ``first + E``);
    ``width`` (static): the published ``F`` where the stacks are held
    wider, which the kernel does not read past (None: as held).
    Returns (y [T, D] in ``dtype``, stats [3])."""
    T, k = chosen.shape
    L, E = experts["w_in"].shape[:2]
    flat = chosen.reshape(T * k)
    if first is not None:       # a share: the others' pairs go behind too
        flat = flat - first
        flat = jnp.where((flat >= 0) & (flat < E), flat, E)
    if live is not None:
        flat = jnp.where(jnp.repeat(live, k), flat, E)     # behind every group
    order = jnp.argsort(flat, stable=True)                 # pairs by expert
    counts = jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E]
    fused = one_kernel(experts, T * k, first)
    # (made here, ahead of the gather, as before the kernel: the order is
    # part of the lowered text that the other callers' digests pin)
    groups = None if fused else lax.dynamic_update_slice(
        jnp.zeros((L * E,), jnp.int32), counts, (layer * E,))
    xs = x.astype(dtype)[order // k]                       # [T k, D]
    # A stack may be held with zero rows and columns up to whole tiles of
    # the grouped product (``padded_width``): the rows take zero columns
    # to match and lose them again, and a zero column of ``w_in`` is a
    # zero of ``h`` against a zero row of ``w_out``.
    D, held = x.shape[1], experts["w_in"].shape[2]
    if held != D:
        xs = jnp.pad(xs, ((0, 0), (0, held - D)))

    if fused:
        ys = routed_ffn_rows(xs, counts, layer, *(
            experts[w].astype(dtype) for w in ("w_in", "w_gate", "w_out")),
            width=width)
    else:
        def grouped(rows, w):
            return lax.ragged_dot(
                rows, w.reshape((L * E,) + w.shape[2:]).astype(dtype),
                groups)

        if "w_gate" in experts:
            h = grouped(xs, experts["w_in"]) * jax.nn.silu(
                grouped(xs, experts["w_gate"]))
        else:
            h = jnp.square(jax.nn.relu(grouped(xs, experts["w_in"])))
        ys = grouped(h, experts["w_out"])
    if held != D:
        ys = ys[:, :D]
    # Back to (row, choice) order.  Pairs behind the last group were in
    # no product: what the rows hold there is not a number to weigh.
    in_a_group = jnp.arange(T * k) < jnp.sum(counts)
    ys = jnp.where(in_a_group[:, None], ys, 0)[jnp.argsort(order)]
    y = jnp.sum(ys.reshape(T, k, -1).astype(jnp.float32)
                * weights[..., None], axis=1)
    stats = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0),
                       jnp.max(counts)])
    return y.astype(dtype), stats
