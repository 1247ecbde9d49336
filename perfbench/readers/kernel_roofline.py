"""A Mosaic kernel's share of its roofline: the least time the chip could
take for the operations and bytes its calls need a traced step (two facts
the job counted from shapes: the larger of operations / peak FLOP/s and
bytes / peak bytes/s) over the kernel's device time a step
(``readers/kernel_ms.py``: self time of the calls whose instruction name
contains ``match``), in percent.  It cannot pass 100 unless the needs are
counted too high.  None off the chip, or where no such call ran."""

from perfbench.peaks import peak
from perfbench.readers import kernel_ms


def read(run, match, bytes_key, ops_key):
    need_bytes, need_ops = run.facts.get(bytes_key), run.facts.get(ops_key)
    if need_bytes is None or need_ops is None or not run.devices \
            or run.devices[0].platform != "tpu":
        return None
    ms = kernel_ms.read(run, match)
    if not ms:
        return None
    pk = peak(run.devices[0].device_kind)
    least = max(need_ops / pk.bf16_flops, need_bytes / pk.hbm_bytes_per_s)
    return 100.0 * least / (ms * 1e-3)
