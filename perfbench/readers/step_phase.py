"""Device time a training step by phase: the first chip's ``XLA Ops``
events of the traced window that start inside an ``XLA Modules`` execution
whose name begins ``jit_train_step`` (the step makers' programs; a fence's
little programs have instruction names of their own that would collide),
their SELF times (``trace.self_times``: a ``while`` holds its body), each
event's instruction name (the event name up to its ``=``) looked up in the
map the program keeps of what it compiled
(``horovod_tpu.telemetry.programs.scopes(<that module>)``: instruction ->
phase, from the ``op_name`` jax wrote into the compiled text).

``phase`` one of ``forward``, ``recompute``, ``backward``, ``reduce``,
``optimizer``, ``other``: the sum of that phase's self time in ms over
``run.facts["steps"]``; the six add up to the step's busy time on the
chip.  ``other`` is every op without an ``op_name`` or not in the map (the
async copies between the two memory spaces).  ``mixed``: the fusions that
hold more than one phase (they count under their own phase too).  0.0
where a phase has no op.  None where the map is empty (a program that
does not remember what it compiled, a trace without a device plane) or
the tree has no ``telemetry/programs.py``: it does not raise."""

import bisect
from collections import defaultdict

from perfbench import trace as tr

PREFIX = "jit_train_step"


def instruction(name):
    """``%fusion.116 = f32[...] fusion(...)`` -> ``fusion.116``."""
    return name.split("=", 1)[0].strip().lstrip("%")


def step_ops(trace, window):
    """``[(module name, event name, self ns)]`` of the first chip's ops
    that start inside a step program's execution ending in ``window``."""
    if not trace.ops or not trace.modules:
        return []
    runs = sorted(
        (e.start, e.end, e.name.split("(", 1)[0])
        for e in trace.modules[sorted(trace.modules)[0]]
        if e.name.startswith(PREFIX)
        and (window is None or window[0] <= e.end <= window[1]))
    starts = [r[0] for r in runs]
    events = tr.clip(trace.ops[sorted(trace.ops)[0]], window)
    out = []
    for ev, (name, d) in zip(events, tr.self_times(events)):
        i = bisect.bisect_right(starts, ev.start) - 1
        if i >= 0 and ev.start < runs[i][1]:
            out.append((runs[i][2], name, d))
    return out


def joined(ops, scopes_of):
    """:func:`step_ops`' rows beside what the program's map says of each:
    ``[(event name, self ns, Scope or None)]``; ``scopes_of(module name)``
    gives that module's map.  ``[]`` where every module's map is empty."""
    maps = {m: scopes_of(m) for m in {m for m, _, _ in ops}}
    if not any(maps.values()):
        return []
    return [(name, d, maps[module].get(instruction(name)))
            for module, name, d in ops]


def by_phase(ops, scopes_of, steps):
    """``{phase: ms a step}`` (with ``mixed``) of :func:`step_ops`' rows.
    None where there is nothing to join."""
    rows = joined(ops, scopes_of)
    if not steps or not rows:
        return None
    out = defaultdict(float)
    for _, d, s in rows:
        out[s.phase if s else "other"] += 1e-6 * d / steps
        if s and s.mixed:
            out["mixed"] += 1e-6 * d / steps
    return out


def read(run, phase):
    if "step_phase_ms" not in run.facts:
        t = run.facts.get("trace")
        try:
            from horovod_tpu.telemetry import programs
        except ImportError:
            t = None
        run.facts["step_phase_ms"] = None if t is None else by_phase(
            step_ops(t, run.facts.get("trace_window")), programs.scopes,
            run.facts.get("steps"))
    table = run.facts["step_phase_ms"]
    return None if table is None else table[phase]
