"""A Mosaic kernel's share of the memory roofline: the bytes its calls
must move a traced step (a fact the job counted from shapes) over the
chip's memory peak x the kernel's device time a step
(``readers/kernel_ms.py``: self time of the calls whose instruction name
contains ``match``), in percent.  It cannot pass 100 unless the bytes are
counted too high.  None off the chip, or where no such call ran."""

from perfbench.peaks import peak
from perfbench.readers import kernel_ms


def read(run, match, bytes_key):
    need = run.facts.get(bytes_key)
    if need is None or not run.devices or run.devices[0].platform != "tpu":
        return None
    ms = kernel_ms.read(run, match)
    if not ms:
        return None
    bw = peak(run.devices[0].device_kind).hbm_bytes_per_s
    return 100.0 * need / (bw * ms * 1e-3)
