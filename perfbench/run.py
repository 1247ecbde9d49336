"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object.  Needs
a TPU; ``--rehearsal`` with ``JAX_PLATFORMS=cpu`` runs the same code at the
tiny sizes of the configuration's rehearsal block and prefixes every
metric ``rehearsal_``.
"""

import sys
import time

_T_START = time.perf_counter()      # set-up is counted from here

if __name__ == "__main__":
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from perfbench import harness

    sys.exit(harness.main(t_start=_T_START))
