"""The four-chip cell, ``youtube_ic.sample``, at a size a CPU holds on a
forced 4-device host platform: its run is correct and its control is
not, a planted fault in the meshed commit turns it false, a traced run
reports the sampler's window, and the meshed engine's rows, counts and
spans are those of the single-device engine.

Each test runs this file as a subprocess under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the flag has to
be set before JAX starts), as ``tests/force_mesh_check.py`` is run;
the case to check is its first argument and the CPU-sized overrides
its second.  The graph has an odd n, so the vertex blocks of a 2x2 mesh
end in one pad column."""
import contextlib
import io
import json
import os
import re
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "youtube_ic.sample"
SEED = 2**31 + 4242


def run_case(case: str, tiny: dict) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    inherited = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + inherited).strip()
    over = {**tiny, "graph": {**tiny["graph"], "n": 4999,
                              "undirected_edges": 15000}}
    r = subprocess.run([sys.executable, os.path.abspath(__file__), case,
                        json.dumps(over)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cell_is_correct_and_its_control_is_not(tiny):
    r = run_case("cell", tiny)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["count"] == 4
    assert set(r["checks"]) == {"rows_wrong", "counter_wrong",
                                "sizes_wrong", "pad_wrong", "empty_wrong"}
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert any(c["value"] > c["limit"] for c in r["control"].values())
    assert set(r["metrics"]) == {"setup_s", "rrr_sets_per_s"}
    assert r["capacity_max"] == 1024 and r["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", ["half_a_shard", "pad_column"])
def test_a_planted_fault_is_not_correct(fault, tiny):
    r = run_case(fault, tiny)
    assert r["correct"] is False
    checks = {k: v["value"] for k, v in r["checks"].items()}
    if fault == "pad_column":
        assert checks["pad_wrong"] > 0 and checks["sizes_wrong"] > 0
        assert checks["rows_wrong"] == 0      # read-back strips the pad
    else:
        assert checks["empty_wrong"] > 0


def test_a_traced_run_reads_the_window(tiny):
    r = run_case("traced", tiny)
    assert r["correct"] is True
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert {"traversal_steps_per_batch", "coins_per_live_edge",
            "store_bytes_per_set", "host_ms_per_batch",
            "window_edges_per_owned"} <= set(m)
    assert m["window_edges_per_owned"] == pytest.approx(r["walked"] / r["m"])
    assert 1 <= m["window_edges_per_owned"] <= 2


def test_rows_equal_the_single_device_engine(tiny):
    r = run_case("rows", tiny)
    assert r["layouts"] == ["4", "2x2", "2x2 balanced"]
    assert r["equal"] == [True, True, True]


def test_sample_span_counts_the_window(tiny):
    r = run_case("spans", tiny)
    B, m = r["batch"], r["m"]
    on, off = r["mesh"], r["none"]
    assert on["walked"] == 2 * r["slab"] and on["owned"] == m
    assert on["coins"] == on["steps"] * B * 2 * r["slab"]
    assert r["slab"] < m
    assert "walked" not in off and "owned" not in off
    assert off["coins"] == off["steps"] * B * m


# ----------------------------------------------- the subprocess body ---

def _engines(over: dict, layouts, seed: int, batches: int):
    """For each mesh layout (None for one device, or (mesh, partition)),
    the first ``batches`` batches' rows in set order."""
    import dataclasses
    import numpy as np
    from bench import data, relabel
    from bench.harness import engine_config, load_cell
    from repro.configs.imm_snap import make_im_mesh, mesh_engine_kwargs
    from repro.core.engine import InfluenceEngine

    cfg = load_cell(CELL, overrides=over).config
    graph = data.program_graph(relabel.make_edges(cfg))
    base = engine_config(cfg, seed)
    out = []
    for lay in layouts:
        if lay is None:
            eng = InfluenceEngine(graph, base)
        else:
            mesh, part = lay
            eng = InfluenceEngine(
                graph, dataclasses.replace(base, partition=part),
                **mesh_engine_kwargs(make_im_mesh(mesh)))
        eng.extend(batches * base.batch)
        s = eng.store
        out.append(np.asarray(s.R[:s.count]) if lay is None
                   else s.read_sets(np.arange(s.count)))
    return out


def _spans(over: dict, mesh):
    """The ``sample`` span arguments of two batches on a theta x vertex
    mesh of shape ``mesh`` (None: one device) and the sampler's slab and
    edge count."""
    import jax
    import numpy as np
    from bench import data, relabel
    from bench.harness import engine_config, load_cell
    from repro import obs
    from repro.configs.imm_snap import mesh_engine_kwargs
    from repro.core.engine import InfluenceEngine
    from repro.core.sampler import _sparse_slab
    from repro.launch.mesh import make_mesh

    cfg = load_cell(CELL, overrides=over).config
    graph = data.program_graph(relabel.make_edges(cfg))
    m = mesh and make_mesh(mesh, ("data", "vertex"),
                           devices=jax.devices()[:int(np.prod(mesh))])
    eng = InfluenceEngine(graph, engine_config(cfg, SEED),
                          **mesh_engine_kwargs(m))
    obs.reset()
    obs.enable()
    eng.extend(2 * eng.cfg.batch)
    ev = [e["args"] for e in obs.chrome_trace()["traceEvents"]
          if e.get("name") == "sample"]
    obs.reset()
    placement = getattr(eng.store, "batch_sharding", None)
    return ev, _sparse_slab(graph.edge_src, graph.n, placement), graph.m


def _main(case: str, over: dict) -> dict:
    import jax
    import numpy as np
    from bench import harness
    from repro.core.store import ShardedStore

    assert jax.device_count() == 4, jax.devices()
    if case == "rows":
        want, *got = _engines(over, [None, ("4", "equal"),
                                     ("2x2", "equal"),
                                     ("2x2", "balanced")], SEED, 4)
        return {"layouts": ["4", "2x2", "2x2 balanced"],
                "equal": [bool(np.array_equal(want, g)) for g in got]}
    if case == "spans":
        mesh_ev, slab, m = _spans(over, (1, 2))
        none_ev, _, _ = _spans(over, None)
        assert len(mesh_ev) == len(none_ev) == 2
        return {"mesh": mesh_ev[0], "none": none_ev[0], "slab": slab,
                "m": m, "batch": over["batch"]}
    real = ShardedStore._layout_cols
    if case == "half_a_shard":
        # theta shard 0 commits the first half of its rows and no more
        def layout(self, rows):
            b = -(-rows.shape[0] // self.D)
            keep = ~((np.arange(rows.shape[0]) >= b // 2)
                     & (np.arange(rows.shape[0]) < b))
            return real(self, rows) * keep[:, None].astype(rows.dtype)
        ShardedStore._layout_cols = layout
    elif case == "pad_column":
        def layout(self, rows):
            return real(self, rows).at[:, -1].set(1)
        ShardedStore._layout_cols = layout
    cell = harness.load_cell(CELL, overrides=over)
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        r = harness.run(cell, seed=SEED, seconds=3.0 if case == "cell" else 0.5,
                        trace=case == "traced", t_start=time.perf_counter(),
                        control=case == "cell")
    info = json.loads(re.search(r"^window: (.*)$", err.getvalue(),
                                re.M).group(1))
    out = {**r, "capacity_max": info["capacity_max"],
           "compiles_in_window": int(re.search(
               r"compiles_in_window=(\d+)", err.getvalue()).group(1))}
    if case == "traced":
        from jax.sharding import NamedSharding, PartitionSpec as P
        from bench import relabel
        from repro.configs.imm_snap import make_im_mesh
        from repro.core.sampler import _sparse_slab
        edges = relabel.make_edges(cell.config)
        mesh = make_im_mesh(cell.config["mesh"])
        out.update(walked=2 * _sparse_slab(
            edges.src, edges.n, NamedSharding(mesh, P("data", "vertex"))),
            m=int(edges.src.size))
    return out


if __name__ == "__main__":
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    log = io.StringIO()
    with contextlib.redirect_stdout(log):
        res = _main(sys.argv[1], json.loads(sys.argv[2]))
    print(json.dumps(res, default=int))
