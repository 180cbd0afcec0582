"""``serve``: an open loop of sigma(S) queries through `IMServe`.

Set-up samples the configuration's theta into one engine shared by
``tenants`` tenants (``share_engine_with``), and warms every shape of
the store's ``hits`` program that the window's batches can take (up to
the DRR quantum of queries, seed sets up to ``set_max``).

Traffic (generated before the window, from the seed): per-tenant
Poisson arrivals whose rates follow a Zipf profile of exponent ``skew``
and sum to ``rate_qps``; each query is a fresh seed set of a size
uniform in [set_min, set_max], so no set is re-asked and every query
reaches ``hits``.  The arithmetic is a copy of the program's
``repro.serve.trace`` (``zipf_rates``, ``_poisson_times``).

The driver submits each query when it is due (``try_submit``) and
pumps the tier (``pump``) whenever queries are pending.  A query's
latency runs from its due time to the end of the batch that answered
it; a refused query, or one still unanswered ``drain_s`` after the
window, counts as missing at ``drain_s``.

Metric: ``query_p95_ms`` over every query due in the window.

Check: ``check_queries`` answered sigma(S), drawn from the seed (all of
them when fewer were answered), each as a hit count against a plain
count over the same arena rows (`bench.reference.hit_counts`).
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

from repro import obs

from bench import data, reference
from bench.harness import Window, engine_config


def zipf_rates(names, total_qps: float, skew: float, rng) -> dict:
    order = list(names)
    rng.shuffle(order)
    raw = np.array([1.0 / (i + 1) ** skew for i in range(len(order))])
    raw = raw / raw.sum() * total_qps
    return {t: float(r) for t, r in zip(order, raw)}


def poisson_times(rate: float, duration: float, rng) -> np.ndarray:
    if rate <= 0:
        return np.zeros((0,))
    gaps = rng.exponential(1.0 / rate, size=max(int(rate * duration * 2), 16))
    times = np.cumsum(gaps)
    while times.size and times[-1] < duration:
        more = np.cumsum(rng.exponential(1.0 / rate, size=16)) + times[-1]
        times = np.concatenate([times, more])
    return times[times < duration]


def make_queries(tr: dict, n: int, duration: float, rate: float, rng):
    """(due times, tenant names, seed sets), sorted by due time."""
    names = [f"t{i}" for i in range(int(tr["tenants"]))]
    rates = zipf_rates(names, rate, float(tr["skew"]), rng)
    due, who, sets = [], [], []
    lo, hi = int(tr["set_min"]), int(tr["set_max"])
    for name in names:
        for t in poisson_times(rates[name], duration, rng):
            due.append(float(t))
            who.append(name)
            sets.append(rng.choice(n, size=int(rng.integers(lo, hi + 1)),
                                   replace=False).astype(np.int32))
    order = np.argsort(np.asarray(due), kind="stable")
    return (np.asarray(due)[order], [who[i] for i in order],
            [sets[i] for i in order])


@dataclasses.dataclass
class State:
    cell: object
    seed: int
    tier: object
    engine: object
    rate: float
    answered: dict = dataclasses.field(default_factory=dict)
    # traced runs: (begin, end, {tenant: [query]}) per pump, on the
    # window's clock, and that clock's time at the bench.clock span
    pumps: list = dataclasses.field(default_factory=list)
    clock_mark: float = 0.0


def setup(cell, seed: int, log) -> State:
    from repro.serve.tenant import TenantSpec
    from repro.serve.tier import IMServe
    cfg, tr = cell.config, cell.traffic
    edges = data.make_edges(cfg)
    graph = data.program_graph(edges)
    icfg = engine_config(cfg, seed)
    tier = IMServe(quantum=int(tr["quantum"]))
    names = [f"t{i}" for i in range(int(tr["tenants"]))]
    for i, name in enumerate(names):
        tier.register(TenantSpec(
            name=name, graph=graph if i == 0 else None,
            cfg=icfg if i == 0 else None,
            theta=int(cfg["theta_per_arena"]),
            max_pending=int(tr["max_pending"]),
            share_engine_with=None if i == 0 else names[0]))
    eng = tier.tenants[names[0]].engine
    rng = np.random.default_rng(0)
    q = 1
    while q <= int(tr["quantum"]):
        ell = 1
        while ell <= int(tr["set_max"]):
            eng.influences([rng.choice(graph.n, size=ell, replace=False)
                            for _ in range(q)])
            ell *= 2
        q *= 2
    for name in names:
        tier.try_submit(name, [0])
    tier.flush()
    tier.cache.__init__(tier.cache.max_entries)     # forget the warm-up
    log(f"setup: n={graph.n} m={graph.m} theta={eng.theta} "
        f"tenants={len(names)} rate_qps={tr['rate_qps']}")
    return State(cell, seed, tier, eng, float(tr["rate_qps"]))


def window(st: State, clock) -> Window:
    tr = st.cell.traffic
    tier, drain_s = st.tier, float(tr["drain_s"])
    rng = data.streams(st.seed, 5)[4]
    due, who, sets = make_queries(tr, st.engine.graph.n, clock.seconds,
                                  st.rate, rng)
    lat = np.full(due.size, drain_s)
    late = np.zeros(due.size)
    sent = {}                   # ticket -> (query index, submit time)
    i, refused, closed = 0, 0, None
    traced = obs.enabled()
    clock.start()
    if traced:
        with obs.span("bench.clock"):
            st.clock_mark = clock.now()
    while True:
        now = clock.now()
        if closed is None and clock.done():
            closed = now
        while closed is None and i < due.size and due[i] <= now:
            t_sub = clock.now()
            with TraceAnnotation("bench.submit"):
                tid = tier.try_submit(who[i], sets[i])
            late[i] = t_sub - due[i]
            if tid is None:
                refused += 1
            else:
                sent[tid] = (i, t_sub)
            i += 1
        if tier.pending:
            begin = clock.now()
            served = tier.pump()
            by_tenant = {}
            for tid, v in served.items():
                q, t_sub = sent[tid]
                done_at = t_sub + tier.result(tid).latency_s
                lat[q] = done_at - due[q]
                st.answered[q] = (sets[q], v)
                by_tenant.setdefault(who[q], []).append(q)
            if traced:
                st.pumps.append((begin, clock.now(), by_tenant))
        elif closed is None and i < due.size:
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(due[i] - clock.now(), 0.01)))
        elif closed is not None or i >= due.size:
            if closed is None:
                closed = now
            if not tier.pending or clock.now() > closed + drain_s:
                break
    clock.stop()
    n = i
    lat_ms = np.minimum(lat[:n], drain_s) * 1e3
    unanswered = n - refused - len(st.answered)
    info = {"queries": n, "answered": len(st.answered), "refused": refused,
            "unanswered": unanswered, "window_s": closed,
            "offered_qps": n / max(closed, 1e-9),
            "p50_ms": float(np.percentile(lat_ms, 50)) if n else None,
            "p99_ms": float(np.percentile(lat_ms, 99)) if n else None,
            "late_p95_ms": float(np.percentile(late[:n], 95) * 1e3)
            if n else None,
            "late_max_ms": float(late[:n].max() * 1e3) if n else None}
    st.due, st.lat_ms = due[:n], lat_ms
    st.traced = (clock.traced_from, clock.traced_to)
    return Window({"query_p95_ms": float(np.percentile(lat_ms, 95))},
                  attempted=n, failed=refused + unanswered, info=info)


def check(st: State, win: Window, *, control: bool = False):
    """Answered sigma(S), as hit counts, against the plain count."""
    store = st.engine.store
    R, count, n = store.R, int(store.count), st.engine.graph.n
    st.tier = st.engine = store = None
    qs = sorted(st.answered)
    want_n = int(st.cell.traffic["check_queries"])
    if len(qs) > want_n:
        rng = data.streams(st.seed, 6)[5]
        qs = sorted(rng.choice(qs, size=want_n, replace=False).tolist())
    sets = [st.answered[q][0] for q in qs]
    got = np.rint(np.asarray([st.answered[q][1] for q in qs]) / n * count)
    want = reference.hit_counts(R, count, sets)
    n_wrong = int((got != want).sum())
    checks = {"answers_wrong": (n_wrong, 0)}
    ctrl = None
    if control:
        c = reference.hit_counts(R, count, sets, control=True)
        ctrl = {"answers_wrong": (int((c != want).sum()), 0)}
    return checks, n_wrong, ctrl
