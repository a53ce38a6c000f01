"""Published hardware peaks, keyed by ``jax.Device.device_kind``.

The yardstick's own copy of ``deepspeed_tpu/utils/peaks.py`` (the program's
copy may change; this one may not). Every utilization the benchmark prints
divides by a number in this table, and a device that is not in it is an
error, never a default.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float        # dense bf16 matmul FLOP/s per chip
    hbm_bytes_per_s: float   # HBM bandwidth per chip
    hbm_bytes: int           # HBM capacity per chip


# Source: Google Cloud documentation, "TPU v5e" (system architecture table):
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip. The key is the string
# jax 0.9.0 / libtpu 0.0.34 reports as ``device_kind`` for that chip.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                               hbm_bytes=16 * 10 ** 9),
}


class UnknownDevice(LookupError):
    """The device has no entry in :data:`DEVICE_PEAKS`."""


def peaks_for(device_kind: str) -> DevicePeaks:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add it to benchmark/harness/peaks.py "
            f"with its source") from None
