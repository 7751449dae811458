"""The code cell's 99th percentile gap between tokens: a decode held up
by the prefills of an admitting tick.

Read as ``itl_p99_ms`` reads it, under a name of its own: in the code cell the
host's stalls make it too unsteady to bound end to end, and there it
moves ``tokens_per_s``, since prefill is most of that cell's device time
and slower prefills take the cell past its knee."""

from benchmarks.chip.spec import metric_reader

read = metric_reader("itl_p99_ms")
