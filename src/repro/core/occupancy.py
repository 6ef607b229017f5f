"""Occupancy models: the paper's Fermi arithmetic (Tables 1/2, Section 2 &
6.1) and its TPU-residency analogue (Section 2 of DESIGN.md).

The Fermi model reproduces, bit-exactly, the numbers the paper derives:
IMGVF at 52 regs x 32 threads x 10 warps = 16,640 regs/block -> 1 block ->
10/48 = 20.8% occupancy; compressed to 29 regs -> 3 blocks -> 62.5%; and
the shared-memory cap discussed for the 24-reg high-quality point.

The TPU model translates the same resource arithmetic to serving: how many
sequences' KV state fits in HBM next to the (packed) weights, which sets
decode batch size and therefore arithmetic intensity.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class GPUCoreConfig:
    """Per-SM limits (Table 2, Fermi GTX 480)."""

    registers_per_sm: int = 32768
    max_warps: int = 48
    threads_per_warp: int = 32
    shared_mem_per_sm: int = 48 * 1024
    max_blocks: int = 8                 # Fermi CC 2.0 resident-block limit


FERMI = GPUCoreConfig()


@dataclasses.dataclass(frozen=True)
class OccupancyResult:
    blocks: int
    warps: int
    occupancy: float
    limiter: str                        # "registers" | "shared" | "blocks" | "warps"


def occupancy(
    regs_per_thread: int,
    warps_per_block: int,
    shared_bytes_per_block: int = 0,
    core: GPUCoreConfig = FERMI,
) -> OccupancyResult:
    """Resident blocks/warps for a kernel on one SM (CUDA occupancy math)."""
    regs_per_block = regs_per_thread * core.threads_per_warp * warps_per_block
    by_regs = core.registers_per_sm // regs_per_block if regs_per_block else 10**9
    by_smem = (
        core.shared_mem_per_sm // shared_bytes_per_block
        if shared_bytes_per_block
        else 10**9
    )
    by_warps = core.max_warps // warps_per_block
    blocks = min(by_regs, by_smem, by_warps, core.max_blocks)
    limiter = {
        by_regs: "registers",
        by_smem: "shared",
        by_warps: "warps",
        core.max_blocks: "blocks",
    }[blocks]
    warps = blocks * warps_per_block
    return OccupancyResult(
        blocks=blocks,
        warps=warps,
        occupancy=warps / core.max_warps,
        limiter=limiter,
    )


# ---------------------------------------------------------------------------
# TPU residency analogue: occupancy == resident decode sequences
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TPUChipConfig:
    """One TPU chip's published constants (the roofline and residency
    inputs). Defaults are TPU v5e's."""

    hbm_bytes: int = 16 * 1024**3
    peak_flops_bf16: float = 197e12
    hbm_bw: float = 819e9
    ici_bw_per_link: float = 50e9
    source: str = ("Google Cloud TPU docs, 'TPU v5e': 197 TFLOP/s bf16, "
                   "16 GiB HBM at 819 GB/s, 1600 Gbit/s ICI per chip")


TPU_V5E = TPUChipConfig()

# Chip constants keyed by ``jax.Device.device_kind``. The one table the
# serving planner and the benchmarks read; a TPU kind missing here is an
# error, never a silent default.
CHIPS = {
    "TPU v5 lite": TPU_V5E,
}


def chip_for_device(device) -> TPUChipConfig:
    """The constants of ``device``'s chip. A TPU whose ``device_kind`` is
    not in :data:`CHIPS` raises. Off-TPU (CPU tests, dry-runs) there is
    no chip to read, and the planner sizes for the reference TPU v5e."""
    if device.platform != "tpu":
        return TPU_V5E
    try:
        return CHIPS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no chip constants for TPU kind {device.device_kind!r}; add "
            f"it to repro.core.occupancy.CHIPS (known: {sorted(CHIPS)})"
        ) from None


@dataclasses.dataclass(frozen=True)
class ResidencyResult:
    max_sequences: int
    kv_bytes_per_seq: int
    weight_bytes: int
    occupancy: float                    # vs. a reference capacity
    arithmetic_intensity: float         # decode flops / byte moved


def decode_residency(
    weight_bytes: int,
    kv_bytes_per_token: int,
    seq_len: int,
    chip: TPUChipConfig = TPU_V5E,
    reserve_fraction: float = 0.10,
    reference_sequences: int | None = None,
    flops_per_token: float | None = None,
) -> ResidencyResult:
    """How many sequences fit beside the weights — the TPU 'occupancy'.

    Mirrors the paper's Section 2 chain: packed state -> more resident
    contexts -> better latency hiding. In decode, more resident sequences
    raise the batch size over which each weight read is amortized, lifting
    arithmetic intensity toward the compute roof.
    """
    usable = int(chip.hbm_bytes * (1 - reserve_fraction)) - weight_bytes
    kv_per_seq = kv_bytes_per_token * seq_len
    max_seqs = max(usable // max(kv_per_seq, 1), 0)
    ref = reference_sequences or max_seqs or 1
    fpt = flops_per_token if flops_per_token is not None else 2.0 * weight_bytes
    bytes_per_step = weight_bytes + max_seqs * kv_per_seq
    flops_per_step = max_seqs * fpt
    return ResidencyResult(
        max_sequences=max_seqs,
        kv_bytes_per_seq=kv_per_seq,
        weight_bytes=weight_bytes,
        occupancy=max_seqs / ref,
        arithmetic_intensity=flops_per_step / max(bytes_per_step, 1),
    )


def ipc_uplift_table1(core: GPUCoreConfig = FERMI) -> dict:
    """Reproduce Table 1's occupancy rows for IMGVF (52 -> 29 registers)."""
    orig = occupancy(52, 10, core=core)
    packed = occupancy(29, 10, core=core)
    return {
        "original": {"pressure": 52, "occupancy": orig.occupancy,
                     "blocks": orig.blocks},
        "packed": {"pressure": 29, "occupancy": packed.occupancy,
                   "blocks": packed.blocks},
    }
