"""Published per-chip peaks, keyed by ``device_kind``.

The benchmark's own table: a device that is not in it is an error, and a
device that is not a TPU has no entry at all.
"""
from __future__ import annotations

SOURCE = 'Google Cloud documentation, "TPU v5e"'

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bw": 819e9, "hbm_bytes": 16e9, "source": SOURCE},
}


def peaks_for(platform: str, device_kind: str) -> dict:
    """Peaks of one device; raises for a non-TPU or an unknown kind."""
    if platform != "tpu":
        raise KeyError(f"no peaks off a TPU (platform {platform!r})")
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for TPU kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
