"""Published peak rates per accelerator, keyed by JAX's ``device_kind``.

The yardstick's own copy: roofline shares and ``round_mfu`` divide by these.
A device that is not listed is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture, per
chip): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""

from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "ops_int8": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict[str, float]:
    """Peak rates of ``device_kind``; raises for a kind not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def least_time_s(ops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take for ``ops`` operations and
    ``nbytes`` bytes of HBM traffic: the larger of the two bounds."""
    p = peaks(device_kind)
    return max(ops / p["flops"], nbytes / p["hbm_bytes_per_s"])
