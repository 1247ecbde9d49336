"""Stop a run's whole process in the middle of its window, as the shared
host does now and then, and see what the stall costs the cell's numbers.

    python3 perfbench/tools/stall_probe.py --stall 2.5 --after 5 12 -- \\
        --workload resnet50_b128_1chip --seed 7 --seconds 20 --trace 0

Starts ``perfbench/run.py`` with the arguments after ``--``, waits for its
"window open" line, and at each ``--after`` second of the window sends
SIGSTOP, then SIGCONT ``--stall`` seconds later.  This process never
touches JAX, so the chip is the child's.  Prints the child's result line.
"""

import argparse
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

RUN = Path(__file__).resolve().parents[1] / "run.py"


def main() -> int:
    argv = sys.argv[1:]
    cut = argv.index("--")
    ap = argparse.ArgumentParser()
    ap.add_argument("--stall", type=float, default=2.5)
    ap.add_argument("--after", type=float, nargs="*", default=[5.0])
    a = ap.parse_args(argv[:cut])
    child = subprocess.Popen([sys.executable, str(RUN)] + argv[cut + 1:],
                             stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                             text=True)
    opened = threading.Event()

    def errors():
        for line in child.stderr:
            sys.stderr.write(line)
            if line.startswith("window open"):
                opened.set()

    th = threading.Thread(target=errors, daemon=True)
    th.start()
    try:
        while not opened.wait(0.5):
            if child.poll() is not None:
                break
        t0 = time.monotonic()
        for at in sorted(a.after) if a.stall > 0 and opened.is_set() else []:
            time.sleep(max(0.0, at - (time.monotonic() - t0)))
            os.kill(child.pid, signal.SIGSTOP)
            time.sleep(a.stall)
            os.kill(child.pid, signal.SIGCONT)
            print(f"stalled the run for {a.stall} s at {at} s of its window",
                  file=sys.stderr, flush=True)
        out = child.stdout.read()
        child.wait()
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    th.join(5)
    sys.stdout.write(out)
    return child.returncode


if __name__ == "__main__":
    sys.exit(main())
