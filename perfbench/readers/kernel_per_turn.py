"""Device time a decode turn of one of the step's Mosaic kernels that a
prompt's program calls too: self time of the first chip's Mosaic custom
calls whose instruction name (the event name up to its ``=``:
``%routed_ffn_rows.3``) contains ``match`` and whose result has
``run.facts[rows_key]`` rows (a decode step's ``max_batch x top_k``; a
prompt's calls have the prompt's), counted only where they start inside an
execution of the decode step's program (``readers/module_ms.py:runs``: the
``XLA Modules`` events named ``jit_serve_step(...)`` that end inside the
traced window), over the number of those executions.

``what`` ``"ms_per_turn"``: that time in ms.  ``"hbm_roofline_pct"``: the
bytes one turn's calls must read (``run.facts[bytes_key]``, counted from
the program's counters and the published shapes) over the chip's memory
peak x that time, in percent; it cannot pass 100 unless the bytes are
counted too high.  None where no such call ran in such an execution, or a
fact is missing (a program without the kernel, or one that does not
count)."""

import bisect
import re

from perfbench import trace as tr
from perfbench.peaks import peak
from perfbench.readers import module_ms

_ROWS = re.compile(r"=\s*\(?[a-z0-9]+\[(\d+),")
STEP = "serve_step"


def read(run, what, match, rows_key, bytes_key=None):
    t, rows = run.facts.get("trace"), run.facts.get(rows_key)
    if t is None or not t.ops or not rows:
        return None
    window = run.facts.get("trace_window")
    turns = sorted(module_ms.runs(t, STEP, window))
    if not turns:
        return None
    starts = [s for s, _ in turns]

    def in_a_turn(at):
        i = bisect.bisect_right(starts, at) - 1
        return i >= 0 and at < turns[i][1]

    def ours(name):
        if not (tr.is_mosaic_call(name) and match in name.split("=", 1)[0]):
            return False
        m = _ROWS.search(name)
        return m is not None and int(m.group(1)) == rows

    events = tr.clip(t.ops[sorted(t.ops)[0]], window)
    picked = [d for ev, (name, d) in zip(events, tr.self_times(events))
              if ours(name) and in_a_turn(ev.start)]
    if not picked:
        return None
    ms = 1e-6 * sum(picked) / len(turns)
    if what == "ms_per_turn":
        return ms
    if what == "hbm_roofline_pct":
        need = run.facts.get(bytes_key)
        if need is None or run.devices[0].platform != "tpu":
            return None
        bw = peak(run.devices[0].device_kind).hbm_bytes_per_s
        return 100.0 * need / (bw * ms * 1e-3)
    raise ValueError(what)
