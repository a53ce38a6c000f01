"""Published hardware peaks, keyed by ``jax.Device.device_kind``.

The ONE table every utilization number in the repo divides by (``bench.py``
MFU, ``telemetry/compile.py`` per-program MFU gauges, the autotuner's memory
pruning). A device that is not in the table raises :class:`UnknownDevice`:
a utilization against an assumed peak is not a measurement, so callers
either propagate the error or leave the gauge absent.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax


@dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float        # dense bf16 matmul FLOP/s per chip
    hbm_bytes_per_s: float   # HBM bandwidth per chip
    hbm_bytes: int           # HBM capacity per chip


# Source: Google Cloud documentation, "TPU v5e" (system architecture table):
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip. ``device_kind`` is the
# string the installed runtime (jax 0.9.0 / libtpu 0.0.34) reports for it.
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                               hbm_bytes=16 * 10 ** 9),
}


class UnknownDevice(LookupError):
    """The local accelerator has no entry in :data:`DEVICE_PEAKS`."""


def device_peaks(device=None) -> DevicePeaks:
    """Peaks of ``device`` (default: the first local device)."""
    kind = (device if device is not None else jax.devices()[0]).device_kind
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add it to utils/peaks.py with its "
            f"source") from None
