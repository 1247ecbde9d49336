"""One traced run of a training cell with, in its result line, the step's
device time by phase and by the program's own scopes: every op the first
chip ran inside a ``jit_train_step*`` execution of the traced window, its
self time joined by instruction name to the ``op_name`` the compiled step
carries (``horovod_tpu.telemetry.programs``), summed by phase x scope.

    python3 perfbench/tools/scope_table.py --workload <cell> --seed <n> \\
        --seconds <s> [--depth 2] [--ops] [--out FILE]

``scope_table``: ``[phase, scope, ms a step, of it in fusions that hold
more than one phase, ops]``, largest first; ``scope`` at ``--depth`` named
scopes (``stage3/norm``), ``""`` for an op outside every scope.  ``--ops``
adds every op: instruction name, shape, calls and self ms a step beside
its phase, its scope and whether it is ``mixed`` (what the ``breakdown``
of a result line holds ten of).  ``step_busy_ms`` is what the rows add up
to, ``map_parse_s`` what reading the map out of the compiled text took,
``traced_end_to_end`` the end-to-end numbers of this traced run.
``--out`` also writes the line to a file (the chip tool shows the last
24 000 bytes of the output: ``--out chiprun_out/<name>.json``).
"""

import argparse
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

_T_START = time.perf_counter()


def table(trace, window, steps, depth, with_ops):
    from horovod_tpu.telemetry import programs
    from perfbench import trace as tr
    from perfbench.readers import step_phase

    t0 = time.perf_counter()
    maps = {m: programs.scopes(m) for m in programs.remembered()}
    out = {"map_parse_s": time.perf_counter() - t0,
           "map_instructions": {m: len(v) for m, v in maps.items()}}
    rows_in = step_phase.joined(step_phase.step_ops(trace, window),
                                lambda module: maps.get(module, {}))
    rows = defaultdict(lambda: [0.0, 0.0, 0])
    per_op = defaultdict(lambda: [0.0, 0])
    for name, d, s in rows_in:
        mixed = bool(s and s.mixed)
        key = (s.phase if s else "other",
               programs.scope(s.op_name, depth) if s else "")
        row = rows[key]
        row[0] += d
        row[1] += d if mixed else 0.0
        row[2] += 1
        op = per_op[(name, *key, mixed)]
        op[0] += d
        op[1] += 1
    per = 1e-6 / steps
    out["step_busy_ms"] = per * sum(r[0] for r in rows.values())
    out["scope_table"] = sorted(
        ([*k, per * r[0], per * r[1], r[2] / steps] for k, r in rows.items()),
        key=lambda r: -r[2])
    if with_ops:
        out["ops"] = sorted(
            ([tr.short_name(name, 96), phase, scope, mixed, n / steps,
              per * d] for (name, phase, scope, mixed), (d, n)
             in per_op.items()), key=lambda r: -r[-1])
    return out


def main() -> int:
    from perfbench import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--out", default=None)
    own, rest = ap.parse_known_args(sys.argv[1:])
    result = harness.Run.result

    def result_with_table(self):
        out = result(self)
        out["traced_end_to_end"] = dict(self.end_to_end)
        t, steps = self.facts.get("trace"), self.facts.get("steps")
        if t is not None and steps:
            out.update(table(t, self.facts.get("trace_window"), steps,
                             own.depth, own.ops))
        if own.out:
            Path(own.out).parent.mkdir(parents=True, exist_ok=True)
            Path(own.out).write_text(json.dumps(out))
        return out

    harness.Run.result = result_with_table
    return harness.main(rest + ["--trace", "1"], t_start=_T_START)


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    sys.exit(main())
