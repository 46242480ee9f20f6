"""Per-chip hardware peaks, keyed by ``jax.Device.device_kind``.

One table for every consumer: the roofline cost models (``repro.roofline``,
``launch/dryrun.py``), the kernels' VMEM guard and on-chip reports.  A kind
that is not in the table is an error, never a default.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float          # FLOP/s, dense bf16 matmul
    int8_ops: float            # OP/s
    hbm_bytes: int             # device memory
    hbm_bw: float              # B/s
    ici_bw: float              # B/s, all chip-to-chip links together
    ici_links: int
    vmem_bytes: int            # on-core vector memory
    source: str

    @property
    def ici_bw_per_link(self) -> float:
        return self.ici_bw / self.ici_links


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16 * 1024 ** 3,
        hbm_bw=819e9, ici_bw=1600e9 / 8, ici_links=4,
        vmem_bytes=128 * 1024 ** 2,
        source=("Google Cloud documentation, 'TPU v5e' (compute, HBM, "
                "1,600 Gbit/s interconnect over 4 links); VMEM as the TPU "
                "compiler reports it for v5e")),
}

#: the chip the kernels and the cost models are written for (TPU v5e).
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str = TARGET_KIND) -> ChipPeaks:
    """Peaks of ``device_kind``; raises ``KeyError`` for a kind not in
    :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no hardware peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


#: cross-node (data-center network) bandwidth per host, B/s.  A modeled
#: deployment assumption for the two-tier collective cost model, not a chip
#: peak: no single-host run measures it.
DCN_BW = 12.5e9
