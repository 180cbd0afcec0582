"""IMM end-to-end driver (the paper's workload), on the InfluenceEngine.

    PYTHONPATH=src python -m repro.launch.im_run --graph com-Amazon \
        --scale 0.01 --model IC --k 50

Runs Algorithm 1 with EfficientIMM defaults (rebuild selection + fused
counters + adaptive representation) or the Ripples-style baseline
(--baseline), on a synthetic SNAP stand-in (hermetic container: see
graphs/datasets.py).  Because the engine keeps its sampled RRR store,
``--select-k`` answers extra campaign queries from the same store for free,
and ``--snapshot-dir`` persists the store for later resumption.

``--mesh N`` (or ``--mesh auto``) shards the RRR store's theta axis across
N devices; ``--mesh RxC`` (e.g. ``--mesh 2x4``) makes the mesh genuinely
2D — R theta shards x C vertex shards, so theta *and* the graph's vertex
dimension scale with device count (paper C1 end-to-end: device-local
sampling writes over both axes, sharded selection).  Results are
seed-for-seed identical to the single-device default; on one device any
flag degrades gracefully to a 1-tile mesh.
"""
from __future__ import annotations

import argparse
import json
import time

from repro import obs
from repro.launch.compile_cache import init_compile_cache
from repro.configs.imm_snap import (
    IMM_EXPERIMENTS, make_im_mesh, mesh_engine_kwargs,
)
from repro.core.engine import InfluenceEngine, IMMConfig
from repro.graphs.datasets import scaled_snap, synthetic_snap


def run(graph: str, *, scale: float = None, model: str = "IC", k: int = 50,
        eps: float = 0.5, baseline: bool = False, seed: int = 0,
        max_theta: int = 1 << 14, select_ks=(), snapshot_dir: str = None,
        mesh=None, backend: str = None, sampler: str = None,
        store: str = "auto", metrics_out: str = None, trace_out: str = None,
        log=print):
    """Build the graph and engine, run IMM, answer ``select_ks`` from the
    same store, and log one JSON line.  Returns ``(out, engine)``: the
    logged record and the engine, whose store stays resident for more
    ``select``/``influences`` queries."""
    if metrics_out or trace_out:
        obs.enable()
    exp = IMM_EXPERIMENTS[graph]
    scale = exp.bench_scale if scale is None else scale
    t0 = time.time()
    g = scaled_snap(graph, scale, seed=seed) if scale < 1.0 else \
        synthetic_snap(graph, seed=seed)
    t_graph = time.time() - t0

    cfg = IMMConfig(
        k=k, eps=eps, model=model, backend=backend, sampler=sampler,
        max_theta=max_theta, seed=seed, store=store,
        selection_method="decrement" if baseline else "rebuild",
        adaptive_representation=not baseline,
    )
    mesh = make_im_mesh(mesh)
    engine = InfluenceEngine(g, cfg, **mesh_engine_kwargs(mesh))
    if snapshot_dir:
        engine.restore(snapshot_dir)       # resume if a snapshot exists
    t0 = time.time()
    res = engine.run()
    t_imm = time.time() - t0

    # extra (k, influence) campaign queries — same store, no re-sampling
    t0 = time.time()
    queries = {
        int(q): {"influence": engine.select(int(q)).influence,
                 "seeds": [int(s) for s in engine.select(int(q)).seeds[:10]]}
        for q in select_ks
    }
    t_queries = time.time() - t0

    if snapshot_dir:
        engine.snapshot(snapshot_dir)

    out = {
        "graph": graph, "scale": scale, "n": g.n, "m": g.m, "model": model,
        "sampler": engine.sampler_name,
        "k": k, "mode": "ripples-style" if baseline else "efficientimm",
        "mesh_shards": None if mesh is None else int(
            engine.store.D if hasattr(engine.store, "D") else 1),
        "vertex_shards": None if mesh is None else int(
            getattr(engine.store, "Dv", 1)),
        "influence": res.influence, "covered_frac": res.covered_frac,
        "theta": res.theta, "representation": res.representation,
        "graph_s": round(t_graph, 3), "imm_s": round(t_imm, 3),
        "seeds": [int(s) for s in res.seeds[:10]],
    }
    if queries:
        out["queries"] = queries
        out["queries_s"] = round(t_queries, 3)
    if metrics_out:
        out["metrics_out"] = obs.write_metrics(metrics_out)
    if trace_out:
        out["trace_out"] = obs.write_trace(trace_out)
    log(json.dumps(out))
    return out, engine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="com-Amazon",
                    choices=sorted(IMM_EXPERIMENTS))
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--model", default="IC",
                    choices=("IC", "WC", "GT", "LT"),
                    help="diffusion model: IC (per-edge probs), WC "
                         "(weighted cascade), GT (generalized triggering),"
                         " LT (linear threshold walk)")
    ap.add_argument("--backend", default=None,
                    choices=("dense", "sparse", "pallas", "walk"),
                    help="traversal backend (default: auto by model/n; "
                         "'pallas' drives the fused MXU ic_frontier "
                         "kernel, falling back to the jnp oracle off-TPU)")
    ap.add_argument("--sampler", default=None,
                    help="full sampler-name override, e.g. "
                         "'WC/pallas+stable' (wins over --model/--backend)")
    ap.add_argument("--k", type=int, default=50)
    ap.add_argument("--eps", type=float, default=0.5)
    ap.add_argument("--baseline", action="store_true")
    ap.add_argument("--max-theta", type=int, default=1 << 14)
    ap.add_argument("--select-k", type=int, action="append", default=[],
                    help="extra seed-set sizes to answer from the same "
                         "sampled store (repeatable)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="resume from / persist the engine store here")
    ap.add_argument("--store", default="auto",
                    choices=("auto", "bitmap", "indices", "packed",
                             "compressed", "sharded"),
                    help="RRR arena at-rest representation: 'packed' "
                         "(bit-packed, 8x smaller) and 'compressed' "
                         "(token lists) are the IMPack formats; all are "
                         "seed-for-seed identical to 'bitmap'")
    ap.add_argument("--mesh", default=None,
                    help="RRR store mesh: an int or 'auto' (1D theta "
                         "sharding), 'RxC' e.g. '2x4' (2D theta x vertex "
                         "sharding), or omit for single-device")
    ap.add_argument("--metrics-out", default=None,
                    help="enable repro.obs and write the metrics-registry "
                         "JSON snapshot here at exit")
    ap.add_argument("--trace-out", default=None,
                    help="enable repro.obs and write the Chrome "
                         "trace-event JSON (Perfetto-loadable) here")
    args = ap.parse_args(argv)
    init_compile_cache()
    run(args.graph, scale=args.scale, model=args.model, k=args.k,
        eps=args.eps, baseline=args.baseline, max_theta=args.max_theta,
        select_ks=args.select_k, snapshot_dir=args.snapshot_dir,
        mesh=args.mesh, backend=args.backend, sampler=args.sampler,
        store=args.store, metrics_out=args.metrics_out,
        trace_out=args.trace_out)


if __name__ == "__main__":
    main()
