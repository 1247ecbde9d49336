"""A memory roofline share of one of the program's spans: the bytes the
work under the span must move (a fact the job counted from shapes,
``perfbench/bytes_count.py``) over the chip's memory peak x the span's
mean length in the traced window, in percent.  The span holds host time
too (dispatch, readback), so the share is a lower bound of the device's
and cannot pass 100 unless the bytes are counted too high.  None off the
chip, or where the trace holds no such span."""

from perfbench.peaks import peak
from perfbench.readers import span_stat


def read(run, bytes_key, span):
    need = run.facts.get(bytes_key)
    if need is None or run.devices[0].platform != "tpu":
        return None
    mean_ms = span_stat.read(run, span, "mean_ms")
    if not mean_ms:
        return None
    bw = peak(run.devices[0].device_kind).hbm_bytes_per_s
    return 100.0 * need / (bw * mean_ms * 1e-3)
