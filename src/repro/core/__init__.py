"""IMM (Influence Maximization via Martingales) — EfficientIMM edition.

The paper's primary contribution, as a composable JAX module:
  * martingale.py  — Tang'15 sampling bounds (theta estimation, OPT LB)
  * sampler.py     — batched RRR-set generation composed from orthogonal
                     axes: `DiffusionModel` (IC / WC / GT coin models, LT
                     walk) x `TraversalBackend` (dense log-semiring,
                     sparse edge-list, Pallas MXU kernel, walk) x a
                     delta-stability flag, with fused in-place counter
                     accumulation (paper C3) and the sampler registry
                     (`make_sampler` compositions + legacy aliases) the
                     engine resolves by name
  * selection.py   — greedy max-coverage: EfficientIMM RRR-partitioned
                     rebuild (C1+C5), Ripples-style decremental baseline,
                     and the `SelectionStrategy` registry
  * adaptive.py    — bitmap vs index-list representation choice (C4)
  * store.py       — preallocated RRR arenas (BitmapStore / IndexStore /
                     mesh-sharded ShardedStore, paper C1 end-to-end)
  * engine.py      — `InfluenceEngine`: Algorithm 1 + incremental
                     extend/select/influence multi-query serving and
                     snapshot/restore resumability
  * imm.py         — one-shot ``imm(graph, cfg)`` back-compat wrapper
"""
from repro.core.martingale import IMMBounds, compute_bounds, theta_from_lb
from repro.core.sampler import (
    sample_ic_dense,
    sample_ic_sparse,
    sample_lt,
    CoinModel,
    WalkModel,
    TraversalBackend,
    make_sampler,
    sampler_matrix,
    composed_name,
    stable_variant,
    register_model,
    get_model,
    registered_models,
    register_backend,
    get_backend,
    registered_backends,
    register_sampler,
    get_sampler,
    registered_samplers,
    default_sampler_name,
)
from repro.core.selection import (
    select_dense,
    select_sparse,
    select_dense_sharded,
    register_selection,
    get_selection,
)
from repro.core.adaptive import (
    choose_representation, bitmap_to_indices, indices_to_bitmap, l_pad_for,
)
from repro.core.store import (
    RRRStore, StoreView, BitmapStore, IndexStore, ShardedStore, make_store,
    store_from_state,
)
from repro.core.engine import (
    InfluenceEngine, Selection, IMMResult, IMMConfig,
)
from repro.core.imm import imm

__all__ = [
    "IMMBounds", "compute_bounds", "theta_from_lb",
    "sample_ic_dense", "sample_ic_sparse", "sample_lt",
    "CoinModel", "WalkModel", "TraversalBackend",
    "make_sampler", "sampler_matrix", "composed_name", "stable_variant",
    "register_model", "get_model", "registered_models",
    "register_backend", "get_backend", "registered_backends",
    "register_sampler", "get_sampler", "registered_samplers",
    "default_sampler_name",
    "select_dense", "select_sparse", "select_dense_sharded",
    "register_selection", "get_selection",
    "choose_representation", "bitmap_to_indices", "indices_to_bitmap",
    "l_pad_for",
    "RRRStore", "StoreView", "BitmapStore", "IndexStore", "ShardedStore",
    "make_store", "store_from_state",
    "InfluenceEngine", "Selection",
    "imm", "IMMResult", "IMMConfig",
]
