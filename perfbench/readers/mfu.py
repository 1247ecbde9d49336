"""Model FLOP/s utilisation: the operations the forward and backward
passes need (``ops_count``; no recomputation, causal attention) times the
traced run's rate, over chips x the chip's bf16 peak; in percent."""

from perfbench.peaks import peak


def read(run):
    f = run.facts
    if "rate" not in f or run.devices[0].platform != "tpu":
        return None
    pk = peak(run.devices[0].device_kind).bf16_flops
    return 100.0 * f["rate"] * f["flops_per_item"] / (len(run.devices) * pk)
