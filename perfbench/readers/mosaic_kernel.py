"""The Mosaic custom calls of the traced step (the attention kernel's
three ``pallas_call``s; the program gives them no stable name yet).
``share_pct``: their device time over the device's busy time.
``roofline_pct``: the least time the chip could take for the operations
and bytes the step's attention needs (``ops_count.flash_attention_needed``:
the larger of operations / peak FLOP/s and bytes / peak bytes/s) over the
kernels' measured time."""

from perfbench import trace as tr
from perfbench.peaks import peak


def read(run, what):
    t, steps = run.facts.get("trace"), run.facts.get("steps")
    need = run.facts.get("flash_needed")
    if t is None or not steps or need is None or not t.ops:
        return None
    w = run.facts.get("trace_window")
    chip = sorted(t.ops)[0]
    kernel_s, calls = tr.op_seconds(t, chip, tr.is_mosaic_call, w)
    if not calls:
        return None
    if what == "share_pct":
        return 100.0 * kernel_s / tr.busy(t, w)["busy_s"][chip]
    if what == "roofline_pct":
        pk = peak(run.devices[0].device_kind)
        least = max(need["ops"] / pk.bf16_flops,
                    need["bytes"] / pk.hbm_bytes_per_s)
        run.facts["flash_bound"] = (
            "compute" if need["ops"] / pk.bf16_flops
            >= need["bytes"] / pk.hbm_bytes_per_s else "memory")
        return 100.0 * least * steps / kernel_s
    raise ValueError(what)
