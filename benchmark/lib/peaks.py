"""Published per-chip peaks, keyed by `jax.devices()[0].device_kind`.

The benchmark's own copy of `paddle_tpu/obs/cost.py: DEVICE_PEAKS`, so
that a change to the program cannot move the yardstick.  A device that
is not in the table is an error, never a default."""

from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,      # bf16 FLOP/s
        "hbm_bps": 819e9,     # bytes/s
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak row of `device_kind`; KeyError naming the table when
    the kind is unknown."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak numbers for device_kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}): add a row with its source to "
            "benchmark/lib/peaks.py") from None
