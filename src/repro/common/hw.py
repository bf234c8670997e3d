"""Hardware constants for the target platform (TPU v5e) and the host pool.

These numbers parameterize the roofline model (core/roofline.py), the tier
topology (core/tiers.py) and the interference link model (core/interference.py).
The container we *run* in is CPU-only; v5e is the *target* the dry-run and
roofline analysis are computed for.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """One accelerator chip."""

    name: str
    peak_flops_bf16: float      # flop/s
    hbm_bytes: float            # bytes of fast-tier memory per chip
    hbm_bw: float               # bytes/s fast-tier bandwidth per chip
    ici_link_bw: float          # bytes/s per ICI link (one direction)
    ici_num_links: int          # links per chip (2D torus on v5e -> 4)
    vmem_bytes: float           # on-chip vector memory (Pallas tile budget)
    mxu_dim: int                # systolic array native dim


@dataclasses.dataclass(frozen=True)
class HostSpec:
    """The host that a group of chips shares — our 'rack-scale memory pool'.

    In the paper the pool is a CXL box shared by the nodes of a rack; here it
    is the host DRAM shared by `chips_per_host` TPU chips, reached over PCIe.
    """

    dram_bytes: float           # pool capacity per host
    pcie_bw: float              # bytes/s per chip to host (the 'remote link')
    pcie_shared_bw: float       # bytes/s total host<->chips (contention domain)
    chips_per_host: int
    dcn_bw: float               # bytes/s per host across pods


# TPU v5e (brief-specified constants: 197 TFLOP/s bf16, 819 GB/s HBM,
# ~50 GB/s/link ICI).
V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bytes=16 * 2**30,
    hbm_bw=819e9,
    ici_link_bw=50e9,
    ici_num_links=4,
    vmem_bytes=128 * 2**20,
    mxu_dim=128,
)

# JAX's `device_kind` -> the chip it names. A device that is not listed
# is an error, never a silent v5e default: every constant above would be
# wrong for it.
CHIPS = {
    "TPU v5 lite": V5E,
    "TPU v5e": V5E,
}


def chip_for(device_kind: str) -> ChipSpec:
    """The described chip for a JAX `device_kind`; raises if unknown."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            f"device_kind {device_kind!r} is not described in common/hw.py "
            f"(known: {sorted(CHIPS)})") from None


# v5e hosts carry 8 chips (4x2) with PCIe gen3 x16 per 2 chips in practice;
# we model a per-chip effective 16 GB/s and a shared 64 GB/s domain, which is
# deliberately *slower relative to HBM* than the paper's UPI (34 vs 73 GB/s):
# the TPU pool link ratio (~2%) is harsher than the paper's (~47%), which is
# why placement policy matters more here, not less.
V5E_HOST = HostSpec(
    dram_bytes=512 * 2**30,
    pcie_bw=16e9,
    pcie_shared_bw=64e9,
    chips_per_host=8,
    dcn_bw=25e9,
)

DTYPE_BYTES = {
    "float32": 4,
    "bfloat16": 2,
    "float16": 2,
    "int8": 1,
    "uint8": 1,
    "int32": 4,
    "uint32": 4,
    "int64": 8,
    "bool": 1,
    "float64": 8,
}


def dtype_size(dtype) -> int:
    return DTYPE_BYTES[str(getattr(dtype, "name", dtype))]


def bidir_ici_bw(chip: ChipSpec = V5E) -> float:
    """Aggregate ICI bandwidth per chip (all links, one direction each)."""
    return chip.ici_link_bw * chip.ici_num_links
