"""Published per-chip peaks for roofline terms, keyed by JAX's
``device_kind``.  A device missing from the table is an error: a
roofline against another chip's peaks is a wrong number, not a rough
one."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float         # FLOP/s
    hbm_bw: float             # B/s
    ici_bw_per_link: float    # B/s per link
    hbm_bytes: int
    source: str


V5E = "TPU v5 lite"           # what JAX reports for a TPU v5e chip

PEAKS = {
    V5E: ChipPeaks(
        flops_bf16=197e12,
        hbm_bw=819e9,
        # 1,600 Gbit/s of interchip interconnect over four links
        ici_bw_per_link=1600e9 / 8 / 4,
        hbm_bytes=16 * 1024**3,
        source='Google Cloud documentation, "TPU v5e"'),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; ``ValueError`` when it has none."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})") from None
