"""Device time of a decode turn's grouped matrix products
(``jax.lax.ragged_dot``: on the chip Mosaic custom calls named
``%ragged-dot-<n>``): self time of the first chip's calls whose
instruction name contains ``match`` and whose result has
``run.facts[rows_key]`` rows (a decode step's ``max_batch x top_k``; a
prompt's products have the prompt's), over the turns traced: the calls
counted, over ``run.facts[calls_key]`` of them a turn.

``what`` ``"ms_per_turn"``: that time in ms.  ``"hbm_roofline_pct"``: the
bytes one turn's products must read (``run.facts[bytes_key]``, counted
from the program's counters and the published shapes) over the chip's
memory peak x that time, in percent; it cannot pass 100 unless the bytes
are counted too high.  None where no such call ran, or a fact is missing
(a program that does not count)."""

import re

from perfbench import trace as tr
from perfbench.peaks import peak

_ROWS = re.compile(r"=\s*\(?[a-z0-9]+\[(\d+),")


def read(run, what, match, rows_key, calls_key, bytes_key=None):
    t = run.facts.get("trace")
    rows, per_turn = run.facts.get(rows_key), run.facts.get(calls_key)
    if t is None or not t.ops or not rows or not per_turn:
        return None

    def a_turns(name):
        m = _ROWS.search(name)
        return (tr.is_mosaic_call(name) and match in name.split("=", 1)[0]
                and m is not None and int(m.group(1)) == rows)

    seconds, calls = tr.op_seconds(t, sorted(t.ops)[0], a_turns,
                                   run.facts.get("trace_window"))
    if not calls:
        return None
    ms = 1e3 * seconds * per_turn / calls
    if what == "ms_per_turn":
        return ms
    if what == "hbm_roofline_pct":
        need = run.facts.get(bytes_key)
        if need is None or run.devices[0].platform != "tpu":
            return None
        bw = peak(run.devices[0].device_kind).hbm_bytes_per_s
        return 100.0 * need / (bw * ms * 1e-3)
    raise ValueError(what)
