"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind missing from the table is an error:
a utilization or roofline share is never computed against a guessed peak.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,   # FLOP/s per chip, dense bf16
        "hbm_bw": 819e9,        # bytes/s per chip
        "hbm_bytes": 16e9,      # bytes per chip
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks_for(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device_kind {kind!r}; add a "
                       f"row with its source to bench/peaks.py") from None
