"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (EXPERIMENTS §Roofline):

    compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device / HBM_bw_per_chip
    collective = wire_bytes_per_device / ICI_link_bw

``cost_analysis`` of the compiled executable is already per-device (the
SPMD-partitioned program), so dividing by per-chip peaks is equivalent to
the global form HLO_FLOPs / (chips x peak).

collective bytes are NOT in cost_analysis: we parse the optimized HLO and
sum wire bytes of every collective op with ring-algorithm conventions:
  all-reduce X bytes      -> 2X on the wire per device (reduce-scatter +
                             all-gather phases, (G-1)/G ~ 1)
  all-gather out X        -> X   (each device receives X(G-1)/G)
  reduce-scatter in X     -> X
  all-to-all X            -> X
  collective-permute X    -> X
``-start`` async forms are counted; ``-done`` forms are skipped.
"""
from __future__ import annotations

import dataclasses
import re

from repro.launch.mesh import TPU_V5E


# ------------------------------------------------- device peak table ----
#
# Peaks keyed by ``jax.devices()[0].device_kind``.  The one row is the
# TPU v5e this repository targets, with its published peaks (Google Cloud
# documentation, "TPU v5e"; `repro.launch.mesh.TPU_V5E`).  A device that
# is not in the table is an error: no device metric is ever computed
# against a guessed peak, and a CPU run computes none.

HW_PEAKS = {
    "TPU v5 lite": TPU_V5E,
}


def peaks_for(device_kind: str | None = None) -> dict:
    """The `HW_PEAKS` row for ``device_kind`` (default: the
    ``device_kind`` of ``jax.devices()[0]``); raises ``KeyError`` for a
    device the table does not know."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    try:
        return HW_PEAKS[str(device_kind)]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(HW_PEAKS)}") from None


# --------------------------------------------- per-kernel cost models ----
#
# Analytic (flops, bytes) estimates for the Pallas kernels in
# ``repro.kernels`` — the *useful* work, not what a given impl happens
# to execute, so ``achieved_frac`` compares impls against the same
# yardstick.  Shapes are the kwargs each entry names; counts assume f32
# accumulation (2 flops per MAC) and one HBM touch per logical input and
# output byte.

KERNEL_COST_MODELS = {
    # masked counter rebuild: (theta,) x (theta, n) mat-vec
    "coverage_matvec": lambda theta, n: (
        2.0 * theta * n, theta * n + 4.0 * theta + 4.0 * n),
    # one probabilistic-BFS step: frontier @ logq + activation test
    "ic_frontier_step": lambda B, n: (
        2.0 * B * n * n + 4.0 * B * n,
        4.0 * n * n + 3.0 * B * n),
    # encode + column-count over one sampled batch (the commit tail of
    # the fused chain): bitmap stores B*n bytes back, packed B*n/8
    "arena_commit": lambda B, n, kind="bitmap": (
        (2.0 if kind == "packed" else 1.0) * B * n,
        B * n + (B * n / 8.0 if kind == "packed" else B * n) + 4.0 * n),
    # decode-and-count over a bit-packed arena
    "packed_count": lambda theta, n: (
        3.0 * theta * n, theta * n / 8.0 + 4.0 * theta + 4.0 * n),
    # decode-and-count over token rows (s_pad int32 tokens per row)
    "token_count": lambda theta, n, s_pad=8: (
        3.0 * theta * n, 4.0 * theta * s_pad + 4.0 * theta + 4.0 * n),
    # the full fused sample->write->count chain: `steps` frontier
    # passes + the commit (BENCH_10's kernel row)
    "sample_write_count": lambda B, n, steps=4, kind="bitmap": tuple(
        a + b for a, b in zip(
            tuple(x * steps for x in
                  KERNEL_COST_MODELS["ic_frontier_step"](B=B, n=n)),
            KERNEL_COST_MODELS["arena_commit"](B=B, n=n, kind=kind))),
}


def kernel_cost(kernel: str, **shape) -> tuple[float, float]:
    """(flops, bytes) of ``kernel`` at ``shape`` per
    `KERNEL_COST_MODELS`; raises KeyError for an unmodeled kernel so a
    bench cannot silently report a cost of zero."""
    return KERNEL_COST_MODELS[kernel](**shape)


def achieved_frac(kernel: str, wall_s: float, *,
                  device_kind: str | None = None, **shape) -> float:
    """Achieved fraction of the roofline bound: the kernel's analytic
    best-case time on ``device_kind`` (max of its compute and memory
    terms against `peaks_for`) divided by the measured ``wall_s``,
    clamped to [0, 1].  This is an *estimate* keyed by the cost model —
    its job in BENCH_10 is comparing fused vs unfused on the same
    yardstick, not absolute attainment."""
    if wall_s <= 0.0:
        return 0.0
    flops, bytes_acc = kernel_cost(kernel, **shape)
    hw = peaks_for(device_kind)
    t_bound = max(flops / hw["peak_flops_bf16"],
                  bytes_acc / hw["hbm_bytes_per_s"])
    return min(t_bound / wall_s, 1.0)


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")

_COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_bytes(type_str: str) -> int:
    """Total bytes of all arrays in an HLO type string like
    'f32[128,1024]{1,0}' or '(f32[8], bf16[4,4])'."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    bytes_by_kind: dict
    wire_bytes: float

    def as_dict(self):
        return {"counts": self.counts, "bytes_by_kind": self.bytes_by_kind,
                "wire_bytes": self.wire_bytes}


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts = {k: 0 for k in _COLLECTIVE_KINDS}
    bytes_by_kind = {k: 0 for k in _COLLECTIVE_KINDS}
    wire = 0.0
    for line in hlo_text.splitlines():
        s = line.strip()
        # "%name = TYPE kind(" — the op kind follows the '=' and type
        m = re.search(r"=\s+(\S.*?)\s+([a-z0-9-]+)\(", s)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        base = op
        if base.endswith("-start"):
            base = base[:-6]
        elif base.endswith("-done") or base.endswith("-update"):
            continue
        if base not in _COLLECTIVE_KINDS:
            continue
        nbytes = _shape_bytes(type_str)
        counts[base] += 1
        bytes_by_kind[base] += nbytes
        if base == "all-reduce":
            wire += 2.0 * nbytes
        else:
            wire += float(nbytes)
    return CollectiveStats(counts, bytes_by_kind, wire)


def roofline_terms(flops: float, bytes_acc: float, wire_bytes: float,
                   model_flops_global: float, n_devices: int,
                   hw: dict = TPU_V5E, extra: dict | None = None) -> dict:
    """All inputs are PER-DEVICE (the compiled module is the per-device
    program); model_flops_global is the whole-step analytic count."""
    t_compute = flops / hw["peak_flops_bf16"]
    t_memory = bytes_acc / hw["hbm_bytes_per_s"]
    t_collective = wire_bytes / hw["ici_bytes_per_s"]
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    dominant = max(terms, key=terms.get)
    hlo_flops_global = flops * n_devices
    return {
        **terms,
        "dominant": dominant,
        "bound_s": terms[dominant],
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "wire_bytes_per_device": wire_bytes,
        "model_flops_global": model_flops_global,
        "useful_flops_ratio": (model_flops_global / hlo_flops_global
                               if hlo_flops_global else 0.0),
        "roofline_fraction": (
            (model_flops_global / n_devices / hw["peak_flops_bf16"])
            / terms[dominant] if terms[dominant] > 0 else 0.0),
        **(extra or {}),
    }
