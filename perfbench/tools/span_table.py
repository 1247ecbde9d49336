"""One traced run of a cell with, in its result line, a table of the
program's own names in the trace: every ``hvd:*`` span (how many ended in
the traced window, their mean, median and longest, the share of the window
the chip was idle under them) and every Mosaic kernel (calls and ms per traced step).

    python3 perfbench/tools/span_table.py --workload <cell> --seed <n> --seconds <s>

What the per-layer metrics do not carry and ``PERF.md`` quotes: the mean
``hvd:serve.apply`` beside ``server_step_ms_mean.serve``, how many calls
of each attention kernel a step makes, and (``traced_end_to_end``) the
end-to-end numbers of this traced run, which a ``--trace 1`` result line
leaves out: what tracing costs when it is on.
"""

import sys
import time
from pathlib import Path
from types import SimpleNamespace

_T_START = time.perf_counter()


def table(trace, window, steps):
    from perfbench import trace as tr
    from perfbench.readers import idle_under, span_stat

    run = SimpleNamespace(facts={"trace": trace, "trace_window": window})
    names = sorted({e.name for evs in trace.host.values() for e in evs
                    if e.name.startswith("hvd:")})
    spans = {}
    for name in names:
        found = span_stat.spans(trace, (name,), window)
        lengths = sorted(1e-6 * (e - s) for s, e in found)
        spans[name] = {
            "count": len(found),
            "mean_ms": span_stat.read(run, name, "mean_ms"),
            "p50_ms": lengths[len(lengths) // 2] if lengths else None,
            "max_ms": lengths[-1] if lengths else None,
            "idle_under_pct": idle_under.read(run, [name])}
    kernels = {}
    if trace.ops:
        chip = sorted(trace.ops)[0]
        for name, d in tr.self_times(tr.clip(trace.ops[chip], window)):
            if tr.is_mosaic_call(name):
                k = kernels.setdefault(
                    name.split("=", 1)[0].strip(), {"calls": 0, "ms": 0.0})
                k["calls"] += 1
                k["ms"] += d * 1e-6
        for k in kernels.values() if steps else ():
            k["calls_per_step"] = k["calls"] / steps
            k["ms_per_step"] = k["ms"] / steps
    return {"spans": spans, "kernels": kernels}


def main() -> int:
    from perfbench import harness

    result = harness.Run.result

    def result_with_table(self):
        out = result(self)
        out["traced_end_to_end"] = dict(self.end_to_end)
        t = self.facts.get("trace")
        if t is not None:
            out["names"] = table(t, self.facts.get("trace_window"),
                                 self.facts.get("steps"))
        return out

    harness.Run.result = result_with_table
    return harness.main(sys.argv[1:] + ["--trace", "1"], t_start=_T_START)


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    sys.exit(main())
