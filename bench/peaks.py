"""Published peaks of each chip the benchmark runs on, keyed by
``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect over 4 ICI links (50 GB/s each).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(
        peak_flops=197e12,  # bf16
        peak_int8_ops=393e12,
        hbm_bytes=16e9,
        hbm_bw=819e9,  # B/s
        link_bw=50e9,  # B/s per ICI link
    ),
}


def for_kind(device_kind: str) -> dict:
    """Peaks of ``device_kind``; a kind not in :data:`PEAKS` is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
