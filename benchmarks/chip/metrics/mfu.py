"""Model FLOPs of the useful tokens served in the window (``flops``),
over its length times the chip's bf16 peak (``peaks``), in %."""

from benchmarks.chip.flops import window_flops
from benchmarks.chip.peaks import peak


def read(rec):
    f = window_flops(rec.run.served, rec.seconds, rec.model)
    if f <= 0:
        return None
    return 100.0 * f / (rec.seconds * peak(rec.device_kind)["bf16_flops"])
