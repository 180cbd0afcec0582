"""The one ``shard_map`` spelling this repo uses.

Every sharded kernel (stores, selection, the fused chain, the MoE/GNN
paths) calls `shard_map` from here, so the replication-check policy is
set in one place: off, because the per-tile bodies psum and all-gather
by hand and their outputs' replication is established by construction.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
