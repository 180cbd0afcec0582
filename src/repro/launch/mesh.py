"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run must set XLA_FLAGS before the first jax device query, while smoke
tests/benches must keep seeing 1 CPU device.

Mesh shapes (TPU v5e):
  single-pod : (16, 16)    axes ("data", "model")       — 256 chips
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") — 512 chips

IMM shards the RRRset (theta) axis over ("pod","data") and the vertex axis
over "model" (DESIGN §2); LMs put batch on ("pod","data") and TP/experts on
"model".
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """The one mesh constructor of the repo.  Every axis is ``Auto``:
    the stores, samplers and selection place data with
    ``with_sharding_constraint`` and ``shard_map`` and leave the rest to
    GSPMD propagation, which ``jax.make_mesh``'s default Explicit axes
    refuse."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests/benchmarks on CPU)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1)
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a production mesh ('pod' included)."""
    names = mesh.axis_names
    return tuple(a for a in names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"


# published peaks of one TPU v5e chip (Google Cloud documentation,
# "TPU v5e"): 197 TFLOP/s bf16, 16 GiB of HBM at 819 GB/s, and
# 1,600 Gbit/s of chip-to-chip interconnect over four links
TPU_V5E = {
    "name": "TPU v5e",
    "peak_flops_bf16": 197e12,      # per chip
    "hbm_bytes_per_s": 819e9,       # per chip
    "ici_bytes_per_s": 50e9,        # per link (~4 links/chip usable)
    "hbm_bytes": 16 * 2**30,
}
