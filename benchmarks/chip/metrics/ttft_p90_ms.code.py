"""The code cell's 90th percentile of time to first token.

Read as ``ttft_p90_ms`` reads it, under a name of its own: in the code cell the
host's stalls make it too unsteady to bound end to end, and there it
moves ``tokens_per_s``, since prefill is most of that cell's device time
and slower prefills take the cell past its knee."""

from benchmarks.chip.spec import metric_reader

read = metric_reader("ttft_p90_ms")
