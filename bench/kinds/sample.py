"""``sample``: IMM's sampling phase, repeated.

The window fills an arena from empty to the configuration's theta
through `InfluenceEngine.extend`, one batch per call, then starts a
fresh arena, until the window ends.  Growth along the arena's pow2
capacity ladder is part of the work, as in a run of IMM.  Set-up warms
every program the window uses: the fused sample-and-commit chain and
each capacity of the ladder (one batch per capacity, with the fill
count stepped to the top of each capacity).

Metric: ``rrr_sets_per_s``, the sets committed over the whole window;
the device is waited on every ``sync_every`` batches and at the end.
The window's details also give the largest arena capacity it reached
and the device memory in use, read at the first wait after each new
largest capacity.

Check: ``check_batches`` batches of the last arena, ``check_rows``
rows of each, drawn from the seed, against RIS on the same coins
(`bench.reference`); the arena's per-vertex counter and set sizes
against its rows.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import data, reference
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
    arena_first: int = 0    # batch index of the current arena's row 0


def fresh_arena(st: State) -> None:
    """Empty the engine's arena in place (capacity back to the floor);
    the engine keeps its compiled chain and its key stream."""
    store = st.engine.store
    store.__init__(store.n)
    st.arena_first = st.batches


def one_batch(st: State) -> None:
    eng = st.engine
    eng.extend(eng.store.count + st.batch)
    st.batches += 1


def bytes_in_use() -> int:
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.local_devices())


def setup(cell, seed: int, log) -> State:
    from repro.core.engine import InfluenceEngine
    cfg = cell.config
    edges = data.make_edges(cfg)
    graph = data.program_graph(edges)
    eng = InfluenceEngine(graph, engine_config(cfg, seed))
    st = State(cell, seed, edges, eng, int(cfg["theta_per_arena"]),
               int(cfg["batch"]))
    one_batch(st)
    while eng.store.capacity < st.theta:
        eng.store.count = eng.store.capacity
        one_batch(st)
    eng.store.counter.block_until_ready()
    fresh_arena(st)
    log(f"setup: n={graph.n} m={graph.m} sampler={eng.sampler_name} "
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
                store.counter.block_until_ready()
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


def check(st: State, win: Window, *, control: bool = False):
    """Returns (checks, wrong, control_checks): each number compared
    with its limit, the rows found wrong, and with ``control`` the same
    row comparison with the bfloat16 reference in the program's place."""
    import jax.numpy as jnp
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
    got = [np.flatnonzero(r) for r in np.asarray(store.R[jnp.asarray(ids)])]
    valid = jnp.arange(store.R.shape[0]) < count
    counter_wrong = int((reference.colsum(store.R, valid)
                         != store.counter).sum())
    sizes_wrong = int((jnp.where(valid, reference.rowsum(store.R), 0)
                       != store.sizes).sum())
    st.engine = store = None

    g = data.csc(st.edges)
    sample = reference.ic_rows if st.cell.config["model"] == "IC" \
        else reference.lt_rows
    keys = reference.batch_keys(st.seed, [st.arena_first + b
                                          for b in rows_in])

    def rows(ctrl):
        out = []
        for b, r in rows_in.items():
            out += sample(keys[st.arena_first + b], r, g, st.batch,
                          control=ctrl)
        return out

    want = rows(False)

    def wrong(answers):
        return sum(not np.array_equal(a, w) for a, w in zip(answers, want))

    rows_wrong = wrong(got)
    checks = {"rows_wrong": (rows_wrong, 0),
              "counter_wrong": (counter_wrong, 0),
              "sizes_wrong": (sizes_wrong, 0)}
    ctrl = {"rows_wrong": (wrong(rows(True)), 0)} if control else None
    return checks, rows_wrong, ctrl
