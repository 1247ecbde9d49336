"""``limits.py`` for a cell of the ``conv_moe_lm`` family: one run of the
cell at its own load with the reference's int8 pass IN THE PROGRAM'S PLACE:
the tokens that pass puts first go through the cell's own checks against
the cell's own limits (``compare.Checks``), so the result line reads
``correct`` false where the limits hold the precision, and true where they
do not.  The program's own numbers are printed before it
(``sound_widest_gap``, ``sound_mean_gap``).  One process a seed.  Not run
by the benchmark's own runs.

    python3 perfbench/tools/conv_moe_limits.py \
        --workload lfm2-8b-a1b_serve_assistants --seed 7 [--seconds 15]
"""

import sys
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import harness  # noqa: E402


def main() -> None:
    import argparse

    from perfbench.jobs import conv_moe_lm_serve

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    argv = ["--workload", a.workload, "--seed", str(a.seed), "--seconds",
            str(a.seconds), "--trace", "0"] \
        + (["--rehearsal"] if a.rehearsal else [])
    sys.exit(harness.main(argv, job=partial(conv_moe_lm_serve.run,
                                             control=True)))


if __name__ == "__main__":
    main()
