"""The program's own counts, read from its obs spans: the clock they
share with a profile, the sampler's loop steps, coins and consulted
edges against the plain reference, the store's bytes against the shape
arithmetic, obs on against off, and the four span readers, on a
hand-made trace and through a traced run at a size a CPU holds."""
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, harness, reference, spans
from bench.trace import Trace, load
from repro import obs
from repro.core.engine import IMMConfig, InfluenceEngine

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2**31 + 777
READERS = ["traversal_steps_per_batch", "coins_per_live_edge",
           "store_bytes_per_set", "host_ms_per_batch"]


@pytest.fixture(autouse=True)
def _isolated_obs():
    obs.reset()
    yield
    obs.reset()


def reader(name):
    return harness.load_module(
        os.path.join(ROOT, "bench", "metrics", name + ".py"), name)


def x_spans(name=None):
    return [e for e in obs.chrome_trace()["traceEvents"]
            if e["ph"] == "X" and (name is None or e["name"] == name)]


# ------------------------------------------------------------ clock ---


def test_spans_share_the_profilers_clock(tmp_path):
    """Every bridged span, placed by the profile's start time, starts
    within 1 ms of its annotation in the .xplane.pb, and the readers'
    offset recovers that start time from the trace alone."""
    from jax.profiler import ProfileData, TraceAnnotation
    obs.enable(jax_annotations=True)
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.traced"):
        for i in range(6):
            with obs.span("extend", tier="engine"):
                with obs.span("sample", tier="engine"):
                    jnp.arange(8).sum().block_until_ready()
            time.sleep(0.001 * (i % 3))
    jax.profiler.stop_trace()
    evs = x_spans()
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
                for f in fs if f.endswith(".xplane.pb"))
    pd = ProfileData.from_file(path)
    start, marks = None, {"extend": [], "sample": []}
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = int(dict(plane.stats)["profile_start_time"])
        for line in plane.lines:
            for e in line.events:
                if e.name in marks:
                    marks[e.name].append(int(e.start_ns))
    assert start and len(marks["extend"]) == len(marks["sample"]) == 6
    for name, at in marks.items():
        placed = sorted(e["ts"] * 1e3 - start for e in evs
                        if e["name"] == name)
        assert np.abs(np.array(placed) - np.sort(at)).max() < 1e6
    run = types.SimpleNamespace(trace=load(str(tmp_path)), spans=evs)
    assert abs(spans.offset_ns(run) - start) < 1e6


# ----------------------------------------------------- the readers ---

E = 1_792_000_000_000_000_000      # the profile's start, ns since 1970


def fake_run(host_names=("extend",), args=True):
    """A 1 ms to 5 ms traced window of a fake trace: six profiled
    batches at uneven gaps, two before the window and one after it,
    plus two set-up batches before the profile began.  One span reads
    the clock 2 ms after its annotation opened (a collection between)."""
    starts = [500_000, 1_200_000, 2_000_000, 3_500_000, 4_800_000,
              5_500_000]
    host, evs = [], []

    def ev(name, at, dur, **kw):
        evs.append({"name": name, "ph": "X", "ts": (E + at) / 1e3,
                    "dur": dur / 1e3, "args": kw if args else {}})

    for i, s in enumerate([-9_000_000, -4_000_000] + starts):
        if s >= 0 and "extend" in host_names:
            host.append(["extend", s, 90_000])
        late = 2_000_000 if i == 4 else 0
        ev("extend", s + 1_000 + 300 * i + late, 80_000, batches=1)
        ev("sample", s + 2_000, 30_000, steps=10 + i,
           coins=1000 * (10 + i), consulted=100 * (i + 1), sets=4)
        if i == 5:
            ev("store.grow", s + 40_000, 10_000, bytes=7_000)
        ev("store.write", s + 50_000, 20_000, sets=4, bytes=1_000)
    evs.append({"name": "process_name", "ph": "M", "args": {}})
    return types.SimpleNamespace(
        trace=Trace(1_000_000, 5_000_000, {}, {}, host), spans=evs)


def test_readers_keep_the_spans_inside_the_window():
    """Batches 3 to 6 (i = 3..6) start inside the window."""
    r = fake_run()
    assert spans.offset_ns(r) == pytest.approx(E + 1_000 + 300 * 2,
                                               abs=512)
    got = {m: reader(m).read(r) for m in READERS}
    assert got["traversal_steps_per_batch"] == pytest.approx(
        (13 + 14 + 15 + 16) / 4)
    assert got["coins_per_live_edge"] == pytest.approx(
        1000 * (13 + 14 + 15 + 16) / (100 * (4 + 5 + 6 + 7)))
    assert got["store_bytes_per_set"] == pytest.approx(
        (4 * 1_000 + 7_000) / 16)
    assert got["host_ms_per_batch"] == pytest.approx(0.08)


@pytest.mark.parametrize("case", ["no_anchor", "no_args", "no_spans"])
def test_readers_return_none_where_they_find_nothing(case):
    r = (fake_run(host_names=()) if case == "no_anchor" else
         fake_run(args=False) if case == "no_args" else fake_run())
    if case == "no_spans":
        r.spans = []
    for m in READERS:
        assert reader(m).read(r) is None, m


# ------------------------------------------------ the program's counts ---

def tiny_edges(tiny):
    return data.make_edges({**tiny, "graph": {**tiny["graph"],
                                              "undirected_edges": 15000}})


def one_batch(edges, model, batch, **cfg):
    obs.enable()
    eng = InfluenceEngine(data.program_graph(edges),
                          IMMConfig(model=model, batch=batch, seed=SEED,
                                    store="bitmap", **cfg))
    eng.extend(batch)
    sample, = x_spans("sample")
    rows = np.asarray(eng.store.R[:batch])
    return sample["args"], rows, eng


def ris_levels(sub, g, batch):
    """RIS on the engine's coins with each member's BFS level, and the
    loop's trip count (the last trip finds every frontier empty)."""
    kroot, kstep = jax.random.split(sub)
    roots = np.asarray(jax.random.randint(kroot, (batch,), 0, g.n))
    dst = np.repeat(np.arange(g.n), np.diff(g.off))
    level = np.full((batch, g.n), -1)
    level[np.arange(batch), roots] = 0
    front, trips = level == 0, 0
    while front.any():
        kstep, s = jax.random.split(kstep)
        coins = np.asarray(jax.random.uniform(s, (batch, g.m)))
        trips += 1
        live = front[:, dst] & (coins < g.prob) & (level[:, g.src] < 0)
        front = np.zeros_like(front)
        for b in range(batch):
            front[b, g.src[live[b]]] = True
        level[front] = trips
    return level, trips


def test_ic_counts_match_the_reference_bfs(tiny):
    edges = tiny_edges(tiny)
    args, rows, eng = one_batch(edges, "IC", 64)
    assert eng.sampler_name == "IC/sparse"
    g = data.csc(edges)
    sub = reference.batch_keys(SEED, [0])[0]
    level, trips = ris_levels(sub, g, 64)
    want = reference.ic_rows(sub, range(64), g, 64)
    assert all(np.array_equal(np.flatnonzero(level[b] >= 0), want[b])
               for b in range(64))
    assert (rows.astype(bool) == (level >= 0)).all()
    assert args["steps"] == trips == level.max() + 1
    in_deg = np.diff(g.off)
    assert args["consulted"] == int((rows * in_deg).sum())
    assert args["coins"] == args["steps"] * 64 * g.m
    assert args["consulted"] <= 64 * g.m
    assert args["sets"] == 64


def test_lt_steps_is_the_largest_row(tiny):
    args, rows, eng = one_batch(tiny_edges(tiny), "LT", 64)
    assert eng.sampler_name == "LT/walk"
    assert args["steps"] == rows.sum(axis=1).max()
    assert args["coins"] == args["steps"] * 64
    assert "consulted" not in args


def grow_bytes(old, new, row):
    return (new + 2 * old) * (row + 4) + new + old


def test_store_bytes_follow_the_shapes():
    """A 16 -> 256 -> 512 growth under two 256-set batches."""
    from repro.graphs import rmat_graph
    obs.enable()
    g = rmat_graph(96, 512, seed=2)
    eng = InfluenceEngine(g, IMMConfig(batch=256, seed=3, store="bitmap"))
    eng.extend(512)
    grows = x_spans("store.grow")
    assert [(e["args"]["old_capacity"], e["args"]["new_capacity"])
            for e in grows] == [(16, 256), (256, 512)]
    assert [e["args"]["bytes"] for e in grows] == [
        grow_bytes(16, 256, 96), grow_bytes(256, 512, 96)]
    assert all(e["cat"] == "store" and e["args"]["parent"] == "extend"
               for e in grows)
    writes = x_spans("store.write")
    assert [e["args"]["sets"] for e in writes] == [256, 256]
    assert all(e["args"]["bytes"] == 256 * 96 + 4 * 256 + 8 * 96
               for e in writes)
    ext, = x_spans("extend")
    assert ext["args"]["batches"] == 2


def test_sharded_store_bytes_and_counts():
    """The meshed fused chain (one device here) counts the same way."""
    from repro.graphs import rmat_graph
    from repro.launch.mesh import make_mesh
    obs.enable()
    g = rmat_graph(96, 512, seed=2)
    mesh = make_mesh((1,), ("data",))
    eng = InfluenceEngine(g, IMMConfig(batch=64, seed=3), mesh=mesh)
    eng.extend(128)
    s = eng.store
    grows = x_spans("store.grow")
    assert [(e["args"]["old_capacity"], e["args"]["new_capacity"])
            for e in grows] == [(16, 64), (64, 128)]
    assert grows[0]["args"]["bytes"] == (64 + 16) * (s._row_bytes() + 5)
    wr = x_spans("store.write")
    assert [e["args"]["sets"] for e in wr] == [64, 64]
    samples = x_spans("sample")
    assert all(e["args"]["steps"] >= 1 and e["args"]["coins"] > 0
               for e in samples)


@pytest.mark.parametrize("model", ["IC", "LT"])
def test_obs_on_and_off_commit_the_same_bits(tiny, model):
    edges = tiny_edges(tiny)

    def arena(on):
        obs.reset()
        if on:
            obs.enable(jax_annotations=True)
        eng = InfluenceEngine(data.program_graph(edges),
                              IMMConfig(model=model, batch=64, seed=SEED,
                                        store="bitmap", k=5))
        eng.extend(192)
        sel = eng.select(5)
        out = [np.asarray(a) for a in (eng.store.R, eng.store.sizes,
                                       eng.store.counter)]
        if on:
            assert len(x_spans("sample")) == 3
        return out + [np.asarray(sel.seeds)]

    for a, b in zip(arena(False), arena(True)):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------- a traced run ---

@pytest.mark.parametrize("cell", ["amazon_ic.sample", "amazon_lt.sample"])
def test_a_traced_run_reports_the_span_metrics(cell, tiny):
    c = harness.load_cell(cell, overrides=tiny)
    r = harness.run(c, seed=SEED, seconds=0.5, trace=True,
                    t_start=time.perf_counter())
    assert r["correct"] is True
    m = {k: v["value"] for k, v in r["metrics"].items()}
    want = {"traversal_steps_per_batch", "store_bytes_per_set",
            "host_ms_per_batch"} | ({"coins_per_live_edge"}
                                    if cell.startswith("amazon_ic") else set())
    assert want <= set(m)
    assert m["traversal_steps_per_batch"] >= 1
    assert m["store_bytes_per_set"] >= tiny["graph"]["n"]
    assert m["host_ms_per_batch"] > 0
    if "coins_per_live_edge" in m:
        assert m["coins_per_live_edge"] >= m["traversal_steps_per_batch"]
