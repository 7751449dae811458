"""Mean time of one admission's prefill, as the engine publishes it on
its bus (``TASK_COMPLETED`` of type ``prefill``: from before the prefill
to after its first token and the cache scatter were dispatched), over
the prefills that ended in the window."""

from benchmarks.chip.record import mean


def read(rec):
    v = mean([e for t, e in rec.prefills if 0.0 <= t < rec.seconds])
    return None if v is None else v * 1e3
