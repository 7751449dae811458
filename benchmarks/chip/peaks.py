"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A kind that is not here is an error:
a share of a peak is never taken against a guessed one."""

from __future__ import annotations

__all__ = ["PEAKS", "peak"]

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}


def peak(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
