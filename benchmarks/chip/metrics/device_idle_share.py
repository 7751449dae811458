"""Share of the traced stretch in which no operation ran on the device,
in %, from the profiler trace (``trace.reduce``)."""


def read(rec):
    if rec.trace is None or rec.trace["idle_share"] is None:
        return None
    return 100.0 * rec.trace["idle_share"]
