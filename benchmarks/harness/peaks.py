"""Published peaks of the chips the benchmark may run on.

Keyed by a substring of ``device_kind``. A device that is not in the
table is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" system page: 197 TFLOP/s
in bf16, 16 GB of HBM at 819 GB/s, per chip.
"""

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float  # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "v5 lite": Peak(197e12, 819e9, 16e9),
    "v5e": Peak(197e12, 819e9, 16e9),
}


def peak_for(device_kind: str) -> Peak:
    kind = device_kind.lower()
    for key, peak in PEAKS.items():
        if key in kind:
            return peak
    raise ValueError(
        f"no peaks recorded for device kind {device_kind!r}; add it to "
        "benchmarks/harness/peaks.py with its source"
    )
