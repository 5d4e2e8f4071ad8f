"""Published peaks of the chips a run may land on, keyed by the
``device_kind`` JAX reports.  A device that is not here has nothing to
be measured against: looking it up is an error, never a default."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float       # FLOP/s
    hbm_bytes_per_s: float  # decimal bytes/s
    hbm_bytes: float        # capacity, decimal bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9, 16e9,
                         "Google Cloud documentation, TPU v5e"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None
