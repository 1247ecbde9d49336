"""Device time of one of the step's Mosaic kernels: self time of the first
chip's Mosaic custom calls whose instruction name (the event name up to its
``=``: ``%flash_fwd.3``) contains ``match``, in ms per traced step.  None
where no such call ran (a program that does not name its kernels)."""

from perfbench import trace as tr


def read(run, match):
    t, steps = run.facts.get("trace"), run.facts.get("steps")
    if t is None or not steps or not t.ops:
        return None
    seconds, calls = tr.op_seconds(
        t, sorted(t.ops)[0],
        lambda n: tr.is_mosaic_call(n) and match in n.split("=", 1)[0],
        run.facts.get("trace_window"))
    return 1e3 * seconds / steps if calls else None
