"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

A kind that is not here is an error: a roofline or utilisation is never
worked out with another chip's peaks.
"""

from __future__ import annotations

PEAKS: dict[str, dict] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
    # 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
    "TPU v5 lite": {"name": "tpu-v5e", "flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


class UnknownDevice(LookupError):
    """The device kind has no entry in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}") from None
