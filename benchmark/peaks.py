"""Published peaks of the devices the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A copy of hydragnn_tpu/telemetry/flops.py:
DEVICE_PEAKS (PERF.md, Open questions: one of the two should go).  A device
that is not in the table is an error, never a default."""

from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks_for(device_kind: str) -> dict:
    row = DEVICE_PEAKS.get(device_kind)
    if row is None:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} "
            "(benchmark/peaks.py:DEVICE_PEAKS)")
    return row
