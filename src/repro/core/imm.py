"""``imm(graph, cfg)`` — the one-shot IMM entry point (back-compat wrapper).

Historically this module owned the whole Algorithm-1 driver: a grow-only
list-of-batches store, if/elif sampler dispatch, and selection wired inline.
That machinery now lives in the stateful engine:

  * ``repro.core.engine.InfluenceEngine`` — Algorithm 1 plus incremental
    ``extend``/``select``/``influence`` multi-query serving and
    ``snapshot``/``restore`` resumability;
  * ``repro.core.store``   — preallocated bitmap/index RRR arenas (C3/C4);
  * ``repro.core.sampler`` — the DiffusionModel x TraversalBackend
    sampler matrix ("IC/dense", "WC/sparse", "GT/pallas", "LT/walk",
    ... composed by ``make_sampler``; legacy monolithic names resolve
    as deprecated aliases);
  * ``repro.core.selection`` — the `SelectionStrategy` registry
    (rebuild/decrement x dense/sparse/sharded, C5/C1).

``imm()`` constructs a fresh engine and runs it once; for a fixed
``cfg.seed`` it returns seeds identical to the historical implementation.
Callers that issue more than one query per sampled store should hold an
`InfluenceEngine` instead:

    engine = InfluenceEngine(graph, IMMConfig(model="IC"))
    result = engine.run()          # == imm(graph, cfg)
    more   = engine.select(10)     # extra queries, no re-sampling

The paper-faithful baseline and the optimized path both remain first-class:

    IMMConfig(selection_method="decrement",
              adaptive_representation=False)   # Ripples-style baseline
    IMMConfig()                                # EfficientIMM defaults
"""
from __future__ import annotations

from repro.graphs.csr import Graph
from repro.core.engine import (          # noqa: F401  (re-exported API)
    IMMConfig, IMMResult, InfluenceEngine, Selection,
)


def imm(graph: Graph, cfg: IMMConfig = None) -> IMMResult:
    """Run IMM Algorithm 1 end-to-end and return the seed set.

    Thin wrapper over ``InfluenceEngine(graph, cfg).run()``; the engine
    (and its sampled store) is discarded afterwards.
    """
    return InfluenceEngine(graph, cfg if cfg is not None else IMMConfig()).run()
