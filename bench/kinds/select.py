"""``select``: greedy seed selection, repeated over one arena.

Set-up samples the configuration's theta into the engine's arena (the
arena IMM's ``run()`` leaves at this theta: the same key stream, batch
by batch) and warms ``select(k)``.  The window repeats ``select(k)``
through the engine's own strategy dispatch, with its memo emptied
before each call so that every call does its work; a call that returns
the memoized object stops the run.

Metric: ``select_s``, the window over the number of completed calls.

Check: the seeds and gains of every call in the window against a plain
greedy over the same arena rows (`bench.reference.greedy`), after the
engine is freed.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import data, reference
from bench.harness import Window, engine_config


@dataclasses.dataclass
class State:
    cell: object
    seed: int
    engine: object
    k: int
    answers: list = dataclasses.field(default_factory=list)


def select(eng, k: int, prev=None):
    eng._select_cache.clear()
    sel = eng.select(k)
    if sel is prev:
        raise RuntimeError("select(k) returned its memo: the window would "
                           "time no work")
    return sel


def setup(cell, seed: int, log) -> State:
    from repro.core.engine import InfluenceEngine
    cfg = cell.config
    edges = data.make_edges(cfg)
    eng = InfluenceEngine(data.program_graph(edges),
                          engine_config(cfg, seed))
    eng.extend(int(cfg["theta_per_arena"]))
    k = int(cfg["k"])
    select(eng, k)
    log(f"setup: n={eng.graph.n} m={eng.graph.m} theta={eng.theta} "
        f"sampler={eng.sampler_name}")
    return State(cell, seed, eng, k)


def window(st: State, clock) -> Window:
    sel = None
    clock.start()
    while True:
        sel = select(st.engine, st.k, sel)
        st.answers.append((sel.seeds, sel.gains))
        if clock.done():
            break
    elapsed = clock.stop()
    n = len(st.answers)
    return Window({"select_s": elapsed / n}, attempted=n,
                  info={"selects": n, "elapsed_s": elapsed,
                        "covered_frac": sel.covered_frac,
                        "representation": sel.representation})


def check(st: State, win: Window, *, control: bool = False):
    """Seeds and gains of every call against the plain greedy."""
    R, count = st.engine.store.R, int(st.engine.store.count)
    st.engine = None
    want_s, want_g = reference.greedy(R, count, st.k)

    def wrong(answers):
        bad_s = max(int((np.asarray(s) != want_s).sum()) for s, _ in answers)
        bad_g = max(int((np.asarray(g) != want_g).sum()) for _, g in answers)
        return bad_s, bad_g, sum(
            not (np.array_equal(s, want_s) and np.array_equal(g, want_g))
            for s, g in answers)

    bad_s, bad_g, n_wrong = wrong(st.answers)
    checks = {"seeds_wrong": (bad_s, 0), "gains_wrong": (bad_g, 0)}
    ctrl = None
    if control:
        c_s, c_g, _ = wrong([reference.greedy(R, count, st.k, control=True)])
        ctrl = {"seeds_wrong": (c_s, 0), "gains_wrong": (c_g, 0)}
    return checks, n_wrong, ctrl
