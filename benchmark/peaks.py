"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it. Source: Google Cloud documentation, "TPU v5e" system architecture
(197 TFLOP/s bf16, 16 GB HBM at 819 GB/s). A device that is not here is
an error, never a default (copied from ``bench.py:PEAK_FLOPS``, which a
later PR can delete)."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks known for device_kind={device_kind!r}; this "
            f"benchmark measures one of {sorted(PEAKS)}") from None
