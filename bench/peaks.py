"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.

Copied from the program's ``repro.launch.roofline.PEAKS`` so that no
change to the program can move the yardstick.  A device kind that is not
in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float    # bf16 FLOP/s
    hbm_bw: float   # HBM bytes/s
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s'),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py with "
                       f"their source") from None
