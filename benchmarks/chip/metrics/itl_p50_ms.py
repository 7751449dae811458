"""Median gap between consecutive tokens of a request, over every gap of
every request due in the window."""

from benchmarks.chip.record import pct


def read(rec):
    v = pct(rec.gaps(), 50)
    return None if v is None else v * 1e3
