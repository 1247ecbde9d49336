"""``limits.py`` for a cell of the ``retention_lm`` family: one run of the
cell at its own load that also prints, before the result line, the number
``correct`` compares with the reference's int8 path in the program's place
(``control_widest_gap``) beside the program's own (``sound_widest_gap``).
The cell's two limits on the slots' state (``slot_state_drift``,
``step_state_drift``) are on the ``check`` lines of every run.  One process
a seed.  Not run by the benchmark's own runs.

    python3 perfbench/tools/retention_limits.py \
        --workload brumby-14b_serve_longform --seed 7 [--seconds 15]

``--fault`` serves from a program with a fault that the cell's limit has
to catch, to show once that it does (the fault is laid over the model's
seam here, in the tool; the program has no switch for it):

* ``state_bf16``: the recurrent state is rounded to bfloat16 after the
  prefill and after every step, the nearest precision below the float32
  the configuration states: the logit gap does not see it, the state's
  drift does;
* ``no_normaliser``: the step form forgets ``z``, the sum of the weights
  of the positions before it, so an answer is normalised by its newest
  position's weight alone.
"""

import sys
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import harness  # noqa: E402

FAULTS = ("state_bf16", "no_normaliser")


def lay_fault(fault: str) -> None:
    """Wrap ``models/retention.py``'s side of the serving seam."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import retention as R

    prefill, step = R.prefill_request, R.decode_step

    def rounded(rec):
        # Not ``astype`` there and back: XLA for the TPU drops that pair
        # as excess precision it may keep, and the fault with it.
        return tuple(jax.lax.reduce_precision(a, exponent_bits=8,
                                              mantissa_bits=7) for a in rec)

    def faulty_prefill(params, prompt, cfg, cache_len):
        logits, request = prefill(params, prompt, cfg, cache_len)
        if fault == "state_bf16":
            request = {"recurrent": rounded(request["recurrent"])}
        return logits, request

    def faulty_step(params, tok, pos, state, cfg):
        S, z = state["recurrent"]
        if fault == "no_normaliser":
            z = jnp.zeros_like(z)
        logits, new = step(params, tok, pos,
                           {**state, "recurrent": (S, z)}, cfg)
        if fault == "state_bf16":
            new = {**new, "recurrent": rounded(new["recurrent"])}
        return logits, new

    R.prefill_request, R.decode_step = faulty_prefill, faulty_step


def main() -> None:
    import argparse

    from perfbench.jobs import retention_lm_serve

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--no-control", action="store_true",
                    help="the program's own number alone")
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    if a.fault:
        lay_fault(a.fault)
    argv = ["--workload", a.workload, "--seed", str(a.seed), "--seconds",
            str(a.seconds), "--trace", "0"] \
        + (["--rehearsal"] if a.rehearsal else [])
    sys.exit(harness.main(argv, job=partial(
        retention_lm_serve.run, control=not a.no_control)))


if __name__ == "__main__":
    main()
