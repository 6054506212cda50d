"""Published per-chip peaks, keyed by ``device_kind``.

The one table of hardware peaks.  Measurement code asks
:func:`chip_peaks` for the chip it runs on; analytic models (the roofline
decomposition, the assist controller's trigger) read
:data:`MODEL_TARGET`, the chip this repository models when no chip is
attached.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    device_kind: str       # as JAX reports it (``Device.device_kind``)
    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bw: float          # bytes/s
    hbm_bytes: float
    ici_link_bw: float     # bytes/s per chip-to-chip link
    source: str


PEAKS = {p.device_kind: p for p in (
    # 1,600 Gbit/s of interconnect per chip over its four links
    ChipPeaks("TPU v5 lite", bf16_flops=197e12, int8_ops=393e12,
              hbm_bw=819e9, hbm_bytes=16e9, ici_link_bw=1600e9 / 8 / 4,
              source='Google Cloud documentation, "TPU v5e"'),
)}

#: the chip analytic models assume when none is attached (CPU runs)
MODEL_TARGET = PEAKS["TPU v5 lite"]


def chip_peaks(device=None) -> ChipPeaks:
    """Peaks of ``device`` (default: the first JAX device).

    A TPU whose kind is not in :data:`PEAKS` is an error, never a default.
    Off a TPU there is no chip to measure, and the model target is
    returned by name."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform != "tpu":
        return MODEL_TARGET
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for TPU kind "
                       f"{device.device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
