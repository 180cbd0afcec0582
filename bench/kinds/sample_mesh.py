"""``sample_mesh``: IMM's sampling phase, repeated, on a mesh.

As ``sample``, with the engine built as the launch scripts build it on a
mesh:
``InfluenceEngine(graph, cfg, **mesh_engine_kwargs(make_im_mesh(mesh)))``
with the configuration's ``mesh`` and equal vertex blocks, so that
``store="auto"`` is a `ShardedStore` of uint8 tiles, one
(theta shard, vertex block) tile a device.  The graph is the stand-in
with its vertex ids relabelled by a seeded permutation
(`bench.relabel`), so that R-MAT's numbering does not decide which
block holds the edges; the program and the reference get the same
relabelled edges.  The window fills the arena
from empty to the configuration's theta through
`InfluenceEngine.extend`, one batch per call, each shard's tile growing
along its pow2 capacity ladder, then empties it in place
(`ShardedStore.reset`) and starts again, until the window ends.  Set-up
warms every program the window runs: the fused sample-and-commit chain
and each rung of the per-shard capacity ladder (one batch per rung,
each tile grown to the next rung ahead of it by `ShardedStore.reserve`,
the program a write's growth runs), then empties the arena.

Metric: ``rrr_sets_per_s``, the sets committed over the whole window;
the device is waited on every ``sync_every`` batches and at the end.
The window's details also give the largest arena capacity it reached
and the device memory in use, read at the first wait after each new
largest capacity.

Check, on the last arena: ``check_batches`` batches, ``check_rows``
rows of each, drawn from the seed and read back through
`ShardedStore.read_sets`, against RIS on the same coins
(`bench.reference`); and from the arena's tiles as the devices hold
them (equal vertex blocks, each padded to ``ceil(n / Dv)`` columns):
the per-vertex counter and the set sizes against the rows, no byte in
a pad column, and no empty set (every set holds its root).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import data, reference, relabel
from bench.harness import Window, engine_config


@dataclasses.dataclass
class State:
    cell: object
    seed: int
    edges: object
    engine: object
    theta: int
    batch: int
    batches: int = 0        # batches the engine has sampled so far
    arena_first: int = 0    # batch index of the current arena's set 0


def fresh_arena(st: State) -> None:
    """Empty the engine's arena in place; the engine keeps its compiled
    chain and its key stream."""
    st.engine.store.reset()
    st.arena_first = st.batches


def one_batch(st: State) -> None:
    eng = st.engine
    eng.extend(eng.store.count + st.batch)
    st.batches += 1


def bytes_in_use() -> int:
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.local_devices())


def setup(cell, seed: int, log) -> State:
    from repro.configs.imm_snap import make_im_mesh, mesh_engine_kwargs
    from repro.core.engine import InfluenceEngine
    cfg = cell.config
    # `tile_sums` reads tile column j of vertex block c0 as vertex c0 + j
    assert cfg["partition"] == "equal", cfg["partition"]
    mesh = make_im_mesh(cfg["mesh"])
    edges = relabel.make_edges(cfg)
    graph = data.program_graph(edges)
    eng = InfluenceEngine(
        graph, dataclasses.replace(engine_config(cfg, seed),
                                   partition=cfg["partition"]),
        **mesh_engine_kwargs(mesh))
    st = State(cell, seed, edges, eng, int(cfg["theta_per_arena"]),
               int(cfg["batch"]))
    fresh_arena(st)
    one_batch(st)
    while eng.store.capacity < st.theta:
        eng.store.reserve(eng.store.capacity - eng.store.count + 1)
        one_batch(st)
    eng.store.sizes.block_until_ready()
    fresh_arena(st)
    log(f"setup: n={graph.n} m={graph.m} sampler={eng.sampler_name} "
        f"mesh={dict(mesh.shape)} store={type(eng.store).__name__} "
        f"walked={getattr(eng._sample, 'walked', None)} "
        f"warm_batches={st.batches}")
    return st


def window(st: State, clock) -> Window:
    sync_every = int(st.cell.traffic["sync_every"])
    fresh_arena(st)
    store = st.engine.store
    first, arenas, inflight = st.batches, 1, 0
    cap_max, in_use = 0, 0
    clock.start()
    while True:
        if store.count >= st.theta:
            fresh_arena(st)
            arenas += 1
        one_batch(st)
        inflight += 1
        if inflight >= sync_every:
            with TraceAnnotation("bench.sync"):
                store.sizes.block_until_ready()
            inflight = 0
            if store.capacity > cap_max:
                cap_max, in_use = store.capacity, max(in_use, bytes_in_use())
            if clock.done():
                break
    elapsed = clock.stop()
    sets = (st.batches - first) * st.batch
    return Window({"rrr_sets_per_s": sets / elapsed}, attempted=sets,
                  info={"sets": sets, "arenas": arenas,
                        "elapsed_s": elapsed, "capacity_max": cap_max,
                        "bytes_in_use_max": in_use})


def tile_sums(R, counts, n: int) -> tuple:
    """From the arena ``R`` as its devices hold it (rows ``P(theta,
    vertex)``, equal vertex blocks of ``n_local`` columns, the last
    padded) and the per-shard filled counts: the per-vertex column sums
    and the per-slot row sums over the filled rows, counting vertex
    columns only, and the bytes set in pad columns."""
    colsum = np.zeros(R.shape[1], np.int64)
    rowsum = np.zeros(R.shape[0], np.int64)
    filled = np.zeros(R.shape[0], bool)
    cap_local = R.shape[0] // len(counts)
    for t, c in enumerate(counts):
        filled[t * cap_local:t * cap_local + int(c)] = True
    pad = 0
    for sh in R.addressable_shards:
        rows, cols = sh.index
        lo, c0 = rows.start or 0, cols.start or 0
        tile = sh.data
        mask = filled[lo:lo + tile.shape[0]]
        real = max(0, min(tile.shape[1], n - c0))
        colsum[c0:c0 + tile.shape[1]] += np.asarray(
            reference.colsum(tile, mask))
        rowsum[lo:lo + tile.shape[0]] += np.where(mask, np.asarray(
            reference.rowsum(tile if real == tile.shape[1]
                             else tile[:, :real])), 0)
        if real < tile.shape[1]:
            pad += int(np.asarray(reference.colsum(tile[:, real:], mask)
                                  ).sum())
    return colsum[:n], rowsum, filled, pad


def check(st: State, win: Window, *, control: bool = False):
    """Returns (checks, wrong, control_checks): each number compared
    with its limit, the rows and sets found wrong, and with ``control``
    the same row comparison with the bfloat16 reference in the
    program's place."""
    tr = st.cell.traffic
    store = st.engine.store
    count = int(store.count)
    rng = data.streams(st.seed, 4)[3]
    slots = rng.choice(count // st.batch,
                       size=min(int(tr["check_batches"]), count // st.batch),
                       replace=False)
    rows_in = {int(b): np.sort(rng.choice(st.batch, size=int(
        tr["check_rows"]), replace=False)) for b in slots}
    ids = np.concatenate([b * st.batch + r for b, r in rows_in.items()])
    got = [np.flatnonzero(r) for r in store.read_sets(ids)]
    colsum, rowsum, filled, pad = tile_sums(store.R, store.counts,
                                            st.edges.n)
    counter_wrong = int((colsum != np.asarray(store.counter)).sum())
    sizes_wrong = int((np.where(filled, rowsum, 0)
                       != np.asarray(store.sizes)).sum())
    empty_wrong = int((filled & (rowsum == 0)).sum())
    st.engine = store = None

    g = data.csc(st.edges)
    keys = reference.batch_keys(st.seed, [st.arena_first + b
                                          for b in rows_in])

    def rows(ctrl):
        out = []
        for b, r in rows_in.items():
            out += reference.ic_rows(keys[st.arena_first + b], r, g,
                                     st.batch, control=ctrl)
        return out

    want = rows(False)

    def wrong(answers):
        return sum(not np.array_equal(a, w) for a, w in zip(answers, want))

    rows_wrong = wrong(got)
    checks = {"rows_wrong": (rows_wrong, 0),
              "counter_wrong": (counter_wrong, 0),
              "sizes_wrong": (sizes_wrong, 0),
              "pad_wrong": (pad, 0),
              "empty_wrong": (empty_wrong, 0)}
    ctrl = {"rows_wrong": (wrong(rows(True)), 0)} if control else None
    return checks, rows_wrong + empty_wrong, ctrl
