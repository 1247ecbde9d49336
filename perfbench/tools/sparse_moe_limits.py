"""``limits.py`` for a cell of the ``sparse_moe_lm`` family: one run of the
cell at its own load with the reference's int8 pass IN THE PROGRAM'S PLACE:
the tokens that pass puts first go through the cell's own checks against
the cell's own limits (``compare.Checks``), so the result line reads
``correct`` false where the limits hold the precision, and true where they
do not.  The program's own numbers are printed before it
(``sound_widest_gap``, ``sound_mean_gap``, ``sound_resident_mean_gap``).
One process a seed.  Not run by the benchmark's own runs.

    python3 perfbench/tools/sparse_moe_limits.py \
        --workload deepseek-v3.2_serve_resident --seed 7 [--seconds 15]

``--fault`` serves from a program whose SELECTION is wrong, and puts ITS
tokens through the checks (no int8 pass), to show once that the cell's
limits catch it (the fault is laid over the model's two selecting
functions here, in the tool; the program has no switch for it):

* ``select_recent``: a position attends the newest ``index_topk``
  positions before it, whatever the indexer scored;
* ``no_selection``: a position attends every position before it.
"""

import sys
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import harness  # noqa: E402

FAULTS = ("select_recent", "no_selection")


def lay_fault(fault: str) -> None:
    """Replace ``models/latent_moe.py``'s selection of a step
    (``index_select``) and of a prompt's rows (``_selected_rows``)."""
    import jax.numpy as jnp

    from horovod_tpu.models import latent_moe as X
    from horovod_tpu.ops.pallas_decode_attention import ordered

    select, low = X.index_select, -(1 << 31)

    def faulty_step(q, w, keys, layer, pos, *, top, work=None):
        scores, cut, tie = select(q, w, keys, layer, pos, top=top, work=work)
        if fault == "no_selection":
            return scores, jnp.full_like(cut, low), jnp.full_like(tie, -1)
        # Scored by position: above the cut are the newest ``top``.
        smax = keys.shape[2]
        by_position = jnp.broadcast_to(
            jnp.arange(smax, dtype=jnp.float32), scores.shape)
        edge = ordered((pos - top).astype(jnp.float32) + 0.5)
        return (by_position,
                jnp.broadcast_to(edge[:, None, None], cut.shape),
                jnp.full_like(tie, -1))

    def faulty_rows(q_i, k_i, w, lo, hi, top):
        rows, cols = jnp.arange(lo, hi)[:, None], jnp.arange(hi)[None, :]
        seen = cols <= rows
        if fault == "select_recent":
            seen = seen & (cols > rows - top)
        return jnp.broadcast_to(seen, (q_i.shape[0],) + seen.shape)

    X.index_select, X._selected_rows = faulty_step, faulty_rows


def main() -> None:
    import argparse

    from perfbench.jobs import sparse_moe_lm_serve

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--no-control", action="store_true",
                    help="the program's own tokens through the checks")
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    if a.fault:
        lay_fault(a.fault)
    argv = ["--workload", a.workload, "--seed", str(a.seed), "--seconds",
            str(a.seconds), "--trace", "0"] \
        + (["--rehearsal"] if a.rehearsal else [])
    sys.exit(harness.main(argv, job=partial(
        sparse_moe_lm_serve.run, control=not (a.no_control or a.fault))))


if __name__ == "__main__":
    main()
