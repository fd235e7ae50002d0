"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.  A chip that is not here is an error: a
roofline share against a guessed peak would be a guess."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float    # FLOP/s
    hbm_bw: float        # bytes/s
    hbm_bytes: float     # bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for any other."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
