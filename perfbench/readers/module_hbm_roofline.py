"""A compiled program's share of the memory roofline: the bytes one
execution of it must move (a fact the job counted from shapes) over the
chip's memory peak x the program's mean device time
(``readers/module_ms.py``: the ``XLA Modules`` events whose name contains
``match``), in percent.  The time is the device's whole execution of the
program, so with needed bytes above it the share cannot pass 100 whatever
the host does.  None off the chip, or where no such program ran."""

from perfbench.peaks import peak
from perfbench.readers import module_ms


def read(run, match, bytes_key):
    need = run.facts.get(bytes_key)
    if need is None or not run.devices or run.devices[0].platform != "tpu":
        return None
    ms = module_ms.read(run, match)
    if not ms:
        return None
    bw = peak(run.devices[0].device_kind).hbm_bytes_per_s
    return 100.0 * need / (bw * ms * 1e-3)
