"""Published peak rates per accelerator chip, keyed by JAX's ``device_kind``.

Single source of truth for the compute/bandwidth numbers used by the roofline
analysis (roofline.py), the opportunistic-protocol latency model
(core/protocol.py) and the chip smoke run. A device kind missing from the
table is an error, never a default: a roofline share computed against another
chip's peaks is a wrong number that looks right.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class DevicePeaks:
    """Per-chip peaks of one accelerator generation."""

    kind: str  # jax.Device.device_kind
    bf16_flops: float  # FLOP/s
    int8_ops: float  # OP/s
    hbm_bw: float  # bytes/s
    hbm_bytes: float  # bytes
    ici_bw: float  # bytes/s per inter-chip link
    source: str


PEAKS: Dict[str, DevicePeaks] = {
    p.kind: p for p in (
        DevicePeaks(
            kind="TPU v5 lite",  # TPU v5e
            bf16_flops=197e12,
            int8_ops=393e12,
            hbm_bw=819e9,
            hbm_bytes=16e9,
            # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
            ici_bw=50e9,
            source="Google Cloud documentation, 'TPU v5e'",
        ),
    )
}

#: The chip the analytic models assume (roofline.py, core/protocol.py).
V5E = PEAKS["TPU v5 lite"]


def peaks(device_kind: str) -> DevicePeaks:
    """Peak rates of ``device_kind``; raises KeyError for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak rates for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


__all__ = ["DevicePeaks", "PEAKS", "V5E", "peaks"]
