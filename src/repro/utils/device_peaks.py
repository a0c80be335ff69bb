"""Published peak rates per accelerator, keyed by JAX's ``device_kind``.

Roofline shares divide a measured kernel time into the least time these
peaks allow, so every benchmark reads them from this one table.  A device
that is not listed is an error, never a default: a roofline against the
wrong chip's peaks is a wrong number, not an approximate one.

Source: Google Cloud documentation, "TPU v5e" (system architecture, per
chip): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""

from __future__ import annotations

# device_kind -> per-chip peaks
PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}

# the chip the analytic roofline rows of the benchmarks model
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict[str, float]:
    """Peak rates of ``device_kind``; raises for a kind not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} — add the device with its source") from None
