"""TPU chip specification database.

The paper demonstrates portability across GPU *vendors* (A100 vs MI250).
The TPU-native analogue is portability across TPU *generations*: each
generation changes VMEM capacity, MXU throughput, HBM bandwidth and
interconnect — exactly the parameters that decide which kernel block
configuration is optimal (and even *valid*: a block that fits v5p VMEM can
exceed v5e VMEM, mirroring the paper's "configs invalid on the other
platform" finding).

All numbers are per-chip, from public TPU documentation. ``CPU_HOST`` is the
degenerate "platform" used when wall-clock measuring on this container.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    # Compute.
    peak_bf16_flops: float      # FLOP/s
    peak_int8_ops: float        # OP/s
    mxu_shape: tuple            # systolic array tile (rows, cols)
    # Memory hierarchy.
    hbm_bytes: int
    hbm_bandwidth: float        # B/s
    # Usable per-core VMEM budget for one kernel's working set. Approximate
    # public numbers; what matters for tuning is the per-generation *ratio*
    # (it decides which block configs are valid on which chip — the TPU
    # analogue of paper Fig. 4's configs being invalid on the other GPU).
    vmem_bytes: int
    # Interconnect.
    ici_bandwidth: float        # B/s per link
    ici_links: int
    # TensorCores per chip ("megacore" on v4/v5p). Parallel grid dimensions
    # of a Pallas kernel can be split across cores; HBM bandwidth is shared.
    cores: int = 1
    # Lane/sublane tiling granularity for f32 (sublane, lane).
    min_tile: tuple = (8, 128)
    # Fixed per-grid-step overhead (s): dispatch + pipeline fill. Calibrated
    # coarse constant; only relative config ordering matters for tuning.
    grid_overhead_s: float = 1.2e-6

    @property
    def peak_fp32_flops(self) -> float:
        return self.peak_bf16_flops / 4.0

    def flops_for_dtype(self, dtype_name: str) -> float:
        """THE dtype → peak-throughput lookup. Every cost-model term
        (``estimate_seconds``, ``roofline_terms``) routes through here;
        nothing else may pick a peak, or a dtype policy silently prices
        int8 work at the bf16 rate (the pre-quant bug: ``peak_int8_ops``
        was defined for every chip but no matmul-family workload ever
        declared an int8 stream, so the int8 roofline was dead code).

        The name keys the MXU *operand* stream: int8 → the double-rate
        int8 path (v5e/v6e; 1× on v4), f32 → the quarter-rate fp32 path,
        everything half-precision (bf16/f16) → the bf16 peak.
        """
        name = _canonical_dtype(dtype_name)
        if name == "int8":
            return self.peak_int8_ops
        if name == "float32":
            return self.peak_fp32_flops
        return self.peak_bf16_flops


_DTYPE_ALIASES = {
    "int8": "int8", "uint8": "int8",
    "float32": "float32", "f32": "float32", "fp32": "float32",
    "bfloat16": "bfloat16", "bf16": "bfloat16",
    "float16": "bfloat16", "f16": "bfloat16",
}


def _canonical_dtype(name: str) -> str:
    try:
        return _DTYPE_ALIASES[name]
    except KeyError:
        raise KeyError(
            f"unknown stream dtype {name!r} for peak lookup; known: "
            f"{sorted(set(_DTYPE_ALIASES))}") from None


# Public-spec numbers. VMEM: usable per-core scratch for one Pallas kernel.
CHIPS: Dict[str, ChipSpec] = {
    "tpu_v4": ChipSpec(
        name="tpu_v4",
        peak_bf16_flops=275e12,
        peak_int8_ops=275e12,
        mxu_shape=(128, 128),
        hbm_bytes=32 * 2**30,
        hbm_bandwidth=1228e9,
        vmem_bytes=16 * 2**20,
        ici_bandwidth=50e9,
        ici_links=6,
        cores=2,
    ),
    "tpu_v5e": ChipSpec(
        name="tpu_v5e",
        peak_bf16_flops=197e12,
        peak_int8_ops=394e12,
        mxu_shape=(128, 128),
        hbm_bytes=16 * 2**30,
        hbm_bandwidth=819e9,
        vmem_bytes=32 * 2**20,
        ici_bandwidth=50e9,
        ici_links=4,
    ),
    "tpu_v5p": ChipSpec(
        name="tpu_v5p",
        peak_bf16_flops=459e12,
        peak_int8_ops=918e12,
        mxu_shape=(128, 128),
        hbm_bytes=95 * 2**30,
        hbm_bandwidth=2765e9,
        vmem_bytes=32 * 2**20,
        ici_bandwidth=100e9,
        ici_links=6,
        cores=2,
    ),
    "tpu_v6e": ChipSpec(
        name="tpu_v6e",
        peak_bf16_flops=918e12,
        peak_int8_ops=1836e12,
        mxu_shape=(256, 256),
        hbm_bytes=32 * 2**30,
        hbm_bandwidth=1640e9,
        vmem_bytes=64 * 2**20,
        ici_bandwidth=90e9,
        ici_links=4,
    ),
    # Wall-clock measurement platform for this container (used by timers,
    # never by the analytical model).
    "cpu_host": ChipSpec(
        name="cpu_host",
        peak_bf16_flops=5e10,
        peak_int8_ops=1e11,
        mxu_shape=(1, 1),
        hbm_bytes=32 * 2**30,
        hbm_bandwidth=20e9,
        vmem_bytes=8 * 2**20,
        ici_bandwidth=1e9,
        ici_links=1,
        grid_overhead_s=5e-6,
    ),
}

# The production fleet target used for roofline terms in EXPERIMENTS.md.
PRODUCTION_CHIP = "tpu_v5e"


def get_chip(name: str) -> ChipSpec:
    try:
        return CHIPS[name]
    except KeyError:
        raise KeyError(f"unknown chip {name!r}; known: {sorted(CHIPS)}") from None


# ``jax.devices()[0].device_kind`` of each generation -> its CHIPS entry.
DEVICE_KINDS: Dict[str, str] = {
    "TPU v4": "tpu_v4",
    "TPU v5 lite": "tpu_v5e",
    "TPU v5": "tpu_v5p",
    "TPU v6 lite": "tpu_v6e",
}


def chip_for_device_kind(kind: str) -> ChipSpec:
    """The ChipSpec of an attached TPU; a kind missing here is an error,
    never a silent fallback to another generation's numbers."""
    try:
        return CHIPS[DEVICE_KINDS[kind]]
    except KeyError:
        raise KeyError(f"no ChipSpec for device_kind {kind!r}; known: "
                       f"{sorted(DEVICE_KINDS)}") from None


def default_chip() -> ChipSpec:
    """The chip the process tunes for: on a TPU backend the attached
    device's, otherwise ``$REPRO_TARGET_CHIP`` (default tpu_v5e) — the
    modelled target of CPU-host tuning."""
    import jax
    if jax.default_backend() == "tpu":
        return chip_for_device_kind(jax.devices()[0].device_kind)
    return get_chip(os.environ.get("REPRO_TARGET_CHIP", PRODUCTION_CHIP))
