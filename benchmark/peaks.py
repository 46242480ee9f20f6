"""Per-chip peaks the benchmark divides by, keyed by ``device_kind``.

Kept apart from the program's own table so that no change to the program
can move the yardstick.  A kind that is not listed is an error.
"""

from __future__ import annotations

SOURCE = ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
          "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI")

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
