"""Medians and spreads of a cell's runs, as the bound is set from them.

    python3 perfbench/tools/spread.py <result files of one set> [-- <second set>]

A spread is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.  Reads
the last line of each file (a run's result line).
"""

import json
import statistics
import sys


def load(paths):
    runs = []
    for p in paths:
        with open(p) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        runs.append(json.loads(lines[-1]))
    return runs


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> None:
    args = sys.argv[1:]
    sets = [[]]
    for a in args:
        if a == "--":
            sets.append([])
        else:
            sets[-1].append(a)
    table = {}
    for i, paths in enumerate(sets):
        runs = load(paths)
        print(f"set {i + 1}: {len(runs)} runs, correct "
              f"{[r['correct'] for r in runs]}")
        for name in runs[0]["metrics"]:
            vals = [r["metrics"][name]["value"] for r in runs
                    if name in r["metrics"]]
            if name == "setup_s":
                vals = vals[1:]         # the first run compiles
            if len(vals) < 2:
                continue
            table.setdefault(name, []).append(
                (statistics.median(vals), spread(vals), vals))
    for name, rows in table.items():
        for i, (med, sp, vals) in enumerate(rows):
            print(f"{name} set {i + 1}: median {med:.6g} spread "
                  f"{100 * sp:.3f}%  values {[round(v, 4) for v in vals]}")
        if len(rows) == 2:
            print(f"{name}: second median / first = "
                  f"{rows[1][0] / rows[0][0]:.5f}; widest spread "
                  f"{100 * max(r[1] for r in rows):.3f}% -> five times is "
                  f"{500 * max(r[1] for r in rows):.2f}%")


if __name__ == "__main__":
    main()
