"""Pallas TPU kernels for the paper's compute hot spots.

<name>.py          pl.pallas_call + BlockSpec implementation (TPU target)
ref.py             pure-jnp oracles (CPU + dry-run execution path)
ops.py             jit'd dispatch wrappers (backend auto-detect)

Semantics checked in interpret mode against ref.py (tests/test_kernels.py);
the IM kernels compiled for a TPU v5e by tests/test_tpu_compile.py.
"""
from repro.kernels import ops, ref
from repro.kernels.ops import (
    coverage_matvec,
    ic_frontier_step,
    fm_interaction,
    flash_attention,
)

__all__ = [
    "ops", "ref", "coverage_matvec", "ic_frontier_step",
    "fm_interaction", "flash_attention",
]
