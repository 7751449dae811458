"""Mean host time of ``AutoScaler.target`` (the governor's Δ), per tick,
over the ticks that began in the window."""

from benchmarks.chip.record import mean


def read(rec):
    v = mean([s.end - s.start for s in rec.spans("governor")])
    return None if v is None else v * 1e3
