"""``limits.py`` for a cell of the ``moe_lm`` family: one run of the cell
at its own load that also prints, before the result line, the two numbers
``correct`` compares with the reference's int8 path in the program's place
(``control_widest_gap``, ``control_mean_gap``) beside the program's own
(``sound_widest_gap``, ``sound_mean_gap``).
One process a seed.  Not run by the benchmark's own runs.

    python3 perfbench/tools/moe_limits.py --workload glm-4.7-flash_serve_context \
        --seed 7 [--seconds 15]
"""

import sys
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import harness  # noqa: E402


def main() -> None:
    import argparse

    from perfbench.jobs import moe_lm_serve

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    argv = ["--workload", a.workload, "--seed", str(a.seed), "--seconds",
            str(a.seconds), "--trace", "0"] \
        + (["--rehearsal"] if a.rehearsal else [])
    sys.exit(harness.main(argv, job=partial(moe_lm_serve.run, control=True)))


if __name__ == "__main__":
    main()
