"""Share of the chip's memory bandwidth (``peaks``) that the decode step
would need to move its bytes in its mean device time
(``decode_device_ms.mean``), in %.

The bytes are the configuration's counts module's, whatever the program
does to move them: ``weight_bytes`` once a step, and the cache bytes
(``kv_bytes``) of the positions that the decoded tokens stamped in the
stretch see, over the ``engine.decode`` spans there.  Output token j >= 1
of an n-token prompt sees n + j positions, as ``flops.window_flops``
counts them.  The bytes are what a step cannot do without, so the share
is at most 100%.  None where the trace holds no decode step."""

from benchmarks.chip.peaks import peak
from benchmarks.chip.spec import metric_reader

device_ms = metric_reader("decode_device_ms.mean")


def read(rec):
    ms = device_ms(rec)
    steps = len(rec.engine.named("engine.decode")) if rec.engine else 0
    if ms is None or not steps:
        return None
    kv = sum(rec.counts.kv_bytes(rec.model, len(s.plan.prompt) + j)
             for s in rec.run.served
             for j, t in enumerate(s.stamps) if j and t < rec.seconds)
    need = rec.counts.weight_bytes(rec.model) + kv / steps
    return 100.0 * need / (ms * 1e-3 * peak(rec.device_kind)
                           ["hbm_bytes_per_s"])
