"""Pallas TPU kernels for the paper's performance-critical LLM hot spots.

Layout per the repo convention:
    flash_attention.py / flash_attention_bwd.py / decode_attention.py /
    gqa_decode.py / mla_decode.py / paged_decode.py / rms_norm.py /
    matmul.py
        — pl.pallas_call + BlockSpec kernel bodies
    ops.py      — autotuned jit'd public wrappers: per-kernel ConfigSpaces,
                  analytical workloads, runner factories, heuristics, and
                  the ``register()`` calls that publish each kernel
    registry.py — the declarative kernel registry (KernelSpec: tunable +
                  scenario tags + oracle + entry point + bench cases);
                  every consumer enumerates kernels through it
    ref.py      — pure-jnp oracles

Adding a kernel is a drop-in: write the kernel body module, declare its
ConfigSpace/workload/runner in ops.py, and ``register()`` it — the tuner,
tests, benchmarks, and serving pick it up with no further wiring
(DESIGN.md §1).

Every kernel takes ``interpret=None`` and resolves it in ``mode.py``:
compiled through Mosaic on a TPU backend, the Pallas interpreter elsewhere
(the CPU tests validate them against ref.py that way). An explicit
``interpret=`` wins.
"""

from repro.kernels import ops, ref, registry  # noqa: F401
from repro.kernels.ops import (  # noqa: F401
    DECODE_ATTENTION, FLASH_ATTENTION, FLASH_ATTENTION_BWD,
    GQA_DECODE_RAGGED, MATMUL, MLA_DECODE, PAGED_DECODE, PAGED_VERIFY,
    RMS_NORM, attention, decode, latent_decode, matmul, paged_decode,
    paged_verify, ragged_decode, rmsnorm,
)
from repro.kernels.registry import (  # noqa: F401
    BenchCase, KernelSpec, get_kernel, kernel_names, list_kernels, register,
    unregister,
)
