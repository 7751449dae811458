"""Mean device time of an execution of the decode step
(``jit_decode_step`` on the device trace's "XLA Modules" line) in the
traced stretch (``spans``).  None where the trace holds no device."""

from benchmarks.chip.record import mean

PROGRAM = "jit_decode_step"


def read(rec):
    if rec.engine is None:
        return None
    v = mean(rec.engine.programs.get(PROGRAM, []))
    return None if v is None else v * 1e3
