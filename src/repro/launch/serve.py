"""Serving drivers.

Two workloads share this module:
  * ``LMServer`` — batched prefill + decode with a KV cache (CPU-runnable
    smoke serving; the production decode cells in launch/steps.py lower the
    same decode_step onto the 256/512-chip meshes).
  * ``IMServer`` — influence-query serving over one shared
    `InfluenceEngine`: clients submit sigma(S) queries for arbitrary seed
    sets, the server coalesces everything pending into a single fused
    membership kernel over the resident RRR store (no re-sampling per
    query), and seed-selection queries hit the engine's memoized
    ``select``.  This is the multi-query regime the store redesign exists
    for: sampling once amortizes across an entire campaign of queries.
    ``--mesh N`` serves the same workload from a mesh-sharded RRR store
    (paper C1): the resident arena is partitioned across devices, so the
    served theta scales with device count — answers are seed-for-seed
    identical to the single-device store.  ``--deltas N`` switches to the
    dynamic-graph regime: the server runs a `StreamEngine`, random edge
    deltas land between query bursts, and up to ``--refresh-budget`` rows
    of stale-RRR repair run between flushes while every flush stays
    epoch-consistent (see docs/streaming.md).  ``--async-refresh`` moves
    the repair onto a background worker thread that drains the backlog
    continuously between flushes instead of only inside them.
    ``--mesh RxC`` (e.g. ``2x4``) serves from a 2D theta x vertex store:
    per-device memory is ``theta/R x n/C``, so resident theta *and* graph
    size scale with the mesh.

    PYTHONPATH=src python -m repro.launch.serve --workload im \
        --graph com-Amazon --queries 64 --mesh auto --deltas 4

A third workload, ``--workload tier``, is a thin CLI over the
**multi-tenant serving tier** (`repro.serve.IMServe` — engine pools,
admission control + DRR fairness, the epoch-keyed sigma(S) cache,
replica read scaling, SLO-aware refresh scheduling; docs/serving.md):
it registers ``--tenants`` campaigns (static and streaming, one
relaxed-SLO tenant with ``--replicas``), generates a Zipf-skewed
arrival-process trace interleaved with GraphDeltas
(`repro.serve.trace`), and replays it with the refresh worker running:

    PYTHONPATH=src python -m repro.launch.serve --workload tier \
        --tenants 4 --qps 256 --duration 1.0 --mesh auto
"""
from __future__ import annotations

import argparse
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.launch.compile_cache import init_compile_cache
from repro.configs import get_arch
from repro.models.transformer import (
    LMConfig, init_lm, prefill, decode_step, init_kv_cache,
)


class LMServer:
    """Minimal batched server: submit token prompts, get continuations."""

    def __init__(self, cfg: LMConfig, params=None, *, max_len: int = 256,
                 seed: int = 0):
        self.cfg = cfg
        self.params = params if params is not None else init_lm(
            jax.random.PRNGKey(seed), cfg)
        self.max_len = max_len
        self._prefill = jax.jit(lambda p, t: prefill(p, cfg, t))
        self._decode = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t))

    def generate(self, prompts, n_tokens: int = 16):
        """prompts: (B, S) int32 -> (B, n_tokens) greedy continuation."""
        prompts = jnp.asarray(prompts)
        B, S = prompts.shape
        cache_len = self.cfg.window if self.cfg.window > 0 else self.max_len
        logits, pcache = self._prefill(self.params, prompts)
        # seed the decode cache by replaying the prompt (simple + correct
        # ring-buffer handling for SWA archs)
        cache = init_kv_cache(self.cfg, B, cache_len)
        for i in range(S):
            _, cache = self._decode(self.params, cache, prompts[:, i:i + 1])
        out = []
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(prompts.dtype)
        for _ in range(n_tokens):
            out.append(tok)
            tok, cache = self._decode(self.params, cache, tok)
        return jnp.concatenate(out, axis=1)


class IMServer:
    """Batches concurrent influence queries against a shared engine.

    ``submit`` enqueues a sigma(S) query and returns a ticket; ``flush``
    answers every pending ticket with one fused store pass (seed sets are
    padded to shared power-of-two shapes inside the engine, so mixed query
    sizes don't fragment compilation).  ``select`` serves top-k queries
    from the engine's memoized selection — repeated k values are free.

    **Background-refresh mode** (dynamic graphs): construct with a
    `repro.stream.StreamEngine` and a ``refresh_budget``.  ``apply_delta``
    forwards graph mutations to the stream (stale RRR rows leave serving
    immediately), and every ``flush`` first answers *all* pending tickets
    against one consistent store state — the epoch recorded in
    ``served_epoch`` — and only then spends up to ``refresh_budget`` rows
    of repair between flushes (cooperative backgrounding: the refresh
    never interleaves with answering, so a flush can never mix rows from
    two epochs — no torn reads across ``apply_delta``).

    **Async-refresh mode** (``async_refresh=True``) upgrades the
    cooperative scheme to a real worker thread: the worker drains the
    staleness backlog in ``refresh_budget``-row slices *continuously*,
    not just once per flush — repair overlaps the server's host-side
    work (request intake, batch assembly, idle gaps between bursts)
    instead of waiting for it.  Engine access stays serialized by one
    lock: stores donate their arena buffers on every repair write, so a
    query racing a refresh would read a deleted buffer — the lock is the
    epoch-consistency guarantee (every flush answers against exactly one
    store state; tested in tests/test_stream.py).  ``close`` (or the
    context manager) stops the worker.
    """

    def __init__(self, engine, *, max_batch: int = 256,
                 refresh_budget: int | None = None,
                 async_refresh: bool = False):
        self.engine = engine
        self.max_batch = max_batch
        self.refresh_budget = refresh_budget
        if refresh_budget is not None and not hasattr(engine, "refresh"):
            raise ValueError(
                "refresh_budget needs a StreamEngine (got a static "
                "engine with nothing to refresh)")
        if refresh_budget is not None and refresh_budget < 1:
            raise ValueError(
                f"refresh_budget must be >= 1 row (got {refresh_budget})")
        if async_refresh and refresh_budget is None:
            raise ValueError(
                "async_refresh needs a refresh_budget (the worker "
                "repairs in budget-row slices)")
        self._pending = []          # list[(ticket, seed_set)]
        self._next_ticket = 0
        self.queries_served = 0
        self.served_epoch = getattr(engine, "epoch", None)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._worker: threading.Thread | None = None
        self.refreshes_run = 0      # worker repair slices completed
        if async_refresh:
            self.start_refresh_worker()

    # ------------------------------------------------- async refresh ----

    def start_refresh_worker(self) -> None:
        """Start the background repair worker.  Idempotent: a second
        call while the worker is alive is a no-op, and a stopped server
        (``stop_refresh_worker``/``close``/``__exit__``) can be
        restarted by calling this again."""
        if self.refresh_budget is None:
            raise ValueError(
                "the refresh worker needs a refresh_budget (it repairs "
                "in budget-row slices)")
        if self._worker is not None and self._worker.is_alive():
            return
        self._stop.clear()
        self._worker = threading.Thread(
            target=self._refresh_loop, name="im-refresh", daemon=True)
        self._worker.start()

    def stop_refresh_worker(self) -> None:
        """Stop the worker and join it.  Safe to call any number of
        times, in any state — twice, after ``close``, after the context
        manager has already exited, or with no worker ever started —
        and safe from the worker thread itself (no self-join)."""
        self._stop.set()
        worker, self._worker = self._worker, None
        if worker is not None and worker is not threading.current_thread():
            worker.join()

    close = stop_refresh_worker

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop_refresh_worker()

    @property
    def async_refreshing(self) -> bool:
        return self._worker is not None and self._worker.is_alive()

    def _refresh_loop(self):
        while not self._stop.is_set():
            did = False
            with self._lock:
                if getattr(self.engine, "stale", 0) > 0:
                    self.engine.refresh(self.refresh_budget)
                    self.refreshes_run += 1
                    did = True
            if did:
                # Python locks are not fair: without an explicit yield
                # between slices the worker can win the lock re-acquire
                # race repeatedly and starve a blocked flush()/submit()
                # for the whole drain — give waiters a real window
                time.sleep(1e-4)
            else:
                # backlog drained: sleep until the next delta (re-checked
                # on a short tick; apply_delta wakes work implicitly)
                self._stop.wait(0.002)

    # ------------------------------------------------------- queries ----

    @property
    def pending(self) -> int:
        return len(self._pending)

    def submit(self, seed_set) -> int:
        """Enqueue one sigma(S) query; returns its ticket id."""
        with self._lock:
            ticket = self._next_ticket
            self._next_ticket += 1
            self._pending.append((ticket, np.asarray(seed_set, np.int32)))
        return ticket

    def apply_delta(self, delta) -> int:
        """Forward a `GraphDelta` to the underlying stream engine; the
        next flush answers from the new epoch (the async worker starts
        repairing it immediately).  Returns the number of resident rows
        that went stale."""
        if not hasattr(self.engine, "apply_delta"):
            raise ValueError("apply_delta needs a StreamEngine")
        with self._lock:
            return self.engine.apply_delta(delta)

    def flush(self) -> dict:
        """Answer all pending queries; returns {ticket: influence}.

        Every ticket in one flush is answered against the same store
        state (the engine lock is held across the whole flush, so
        neither ``apply_delta`` nor any refresh slice can interleave) —
        the results are epoch-consistent even when deltas land between
        submits.  In cooperative background-refresh mode (no worker),
        repair work runs *after* the answers, bounded by
        ``refresh_budget`` rows; in async mode the worker owns repair
        and the flush does none.
        """
        results = {}
        with obs.span("flush", tier="serve"), self._lock:
            while self._pending:
                chunk = self._pending[:self.max_batch]
                self._pending = self._pending[self.max_batch:]
                vals = self.engine.influences([s for _, s in chunk])
                results.update(
                    {t: float(v) for (t, _), v in zip(chunk, vals)})
            self.queries_served += len(results)
            self.served_epoch = getattr(self.engine, "epoch", None)
            if self.refresh_budget is not None and not self.async_refreshing:
                self.engine.refresh(self.refresh_budget)
        return results

    def influence(self, seed_set) -> float:
        """Convenience single-query path (submit + flush)."""
        ticket = self.submit(seed_set)
        return self.flush()[ticket]

    def select(self, k: int):
        """Top-k seed-selection query (memoized by the engine)."""
        with self._lock:
            return self.engine.select(k)

    def metrics(self) -> dict:
        """The obs metrics-registry snapshot (empty maps unless
        ``repro.obs`` is enabled — see docs/observability.md)."""
        return obs.snapshot()

    def drain(self, timeout: float | None = 30.0) -> bool:
        """Block until the staleness backlog is fully repaired (True) or
        ``timeout`` seconds elapse (False); ``timeout=None`` waits
        forever.  With a live async worker this waits on it; otherwise
        it refreshes inline in budget-row slices, re-checking the
        deadline between slices so a finite timeout is honored on the
        inline path too (a backlog bigger than the time allows returns
        False with partial progress kept)."""
        deadline = (None if timeout is None
                    else time.monotonic() + float(timeout))
        while True:
            with self._lock:
                if getattr(self.engine, "stale", 0) == 0:
                    return True
                if not self.async_refreshing:
                    self.engine.refresh(self.refresh_budget)
                    continue_inline = True
                else:
                    continue_inline = False
            if deadline is not None and time.monotonic() > deadline:
                with self._lock:
                    if getattr(self.engine, "stale", 0) == 0:
                        return True
                return False
            if not continue_inline:
                time.sleep(0.002)


def _main_lm(args):
    arch = get_arch(args.arch)
    cfg = arch.smoke_config
    server = LMServer(cfg)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab)
    t0 = time.time()
    out = server.generate(prompts, args.gen)
    dt = time.time() - t0
    print(f"[serve] {args.arch}: generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print(out[0])


def _main_im(args):
    from repro.configs.imm_snap import (
        IMM_EXPERIMENTS, make_im_mesh, mesh_engine_kwargs,
    )
    from repro.core.engine import InfluenceEngine, IMMConfig
    from repro.graphs.datasets import scaled_snap

    exp = IMM_EXPERIMENTS[args.graph]
    scale = exp.bench_scale if args.scale is None else args.scale
    g = scaled_snap(args.graph, scale, seed=0)
    mesh = make_im_mesh(args.mesh)
    mesh_kw = mesh_engine_kwargs(mesh)
    cfg = IMMConfig(k=args.k, model=args.model, backend=args.backend,
                    sampler=args.sampler, max_theta=args.max_theta,
                    store=args.store)
    if args.deltas:
        from repro.stream import StreamEngine
        engine = StreamEngine(g, cfg, **mesh_kw)
    else:
        engine = InfluenceEngine(g, cfg, **mesh_kw)
    t0 = time.time()
    engine.extend(args.max_theta)
    t_sample = time.time() - t0
    server = IMServer(
        engine,
        refresh_budget=args.refresh_budget if args.deltas else None,
        async_refresh=bool(args.deltas and args.async_refresh))
    if mesh is not None:
        print(f"[serve-im] sharded store: theta axis over "
              f"{engine.store.D} shard(s) x vertex axis over "
              f"{getattr(engine.store, 'Dv', 1)} shard(s), "
              f"cap_local={engine.store.cap_local}, "
              f"n_local={getattr(engine.store, 'n_local', g.n)}")

    # a realistic mixed workload: top-k selections of several sizes plus a
    # burst of random candidate-set influence queries, all from one store
    t0 = time.time()
    sels = {kk: server.select(kk) for kk in (5, args.k // 2 or 1, args.k)}
    rng = np.random.default_rng(0)
    tickets = [server.submit(rng.choice(g.n, size=rng.integers(1, 9),
                                        replace=False))
               for _ in range(args.queries)]
    answers = server.flush()
    dt = time.time() - t0
    n_q = len(sels) + len(tickets)
    print(f"[serve-im] {args.graph} n={g.n:,} theta={engine.theta}: "
          f"sampled in {t_sample:.2f}s, answered {n_q} queries in {dt:.2f}s "
          f"({n_q / max(dt, 1e-9):.1f} q/s)")
    for kk, s in sorted(sels.items()):
        print(f"  select(k={kk}): influence={s.influence:.1f} "
              f"seeds={[int(v) for v in s.seeds[:5]]}...")
    vals = [answers[t] for t in tickets[:4]]
    print(f"  sample influence answers: {[round(v, 1) for v in vals]}")

    if args.deltas:
        from repro.stream import random_delta
        drng = np.random.default_rng(7)
        probe = engine.select(args.k).seeds
        for i in range(args.deltas):
            d = random_delta(engine.graph, drng, inserts=4, deletes=4,
                             reweights=4)
            stale = server.apply_delta(d)
            tickets = [server.submit(probe) for _ in range(8)]
            ans = server.flush()      # consistent answers + budgeted repair
            sig = ans[tickets[0]]
            print(f"  delta {i}: {len(d)} edge ops, {stale} rows stale, "
                  f"epoch {server.served_epoch}, sigma(probe)={sig:.1f}, "
                  f"backlog {engine.stale}")
        if server.async_refreshing:
            if not server.drain(timeout=120.0):
                print(f"  WARNING: async drain timed out with "
                      f"{engine.stale} rows still stale; finishing "
                      f"inline")
                while engine.stale:
                    engine.refresh(args.refresh_budget)
            server.stop_refresh_worker()
            print(f"  async worker ran {server.refreshes_run} repair "
                  f"slice(s)")
        else:
            while engine.stale:
                engine.refresh(args.refresh_budget)
        final = engine.select(args.k)
        print(f"  drained: epoch {engine.epoch} consistent, "
              f"select(k={args.k}) influence={final.influence:.1f}")


def _main_tier(args):
    """Thin CLI over the `repro.serve.IMServe` tier: N tenants (static
    and streaming alternating, one relaxed-SLO tenant with replicas when
    ``--replicas`` > 0), a Zipf-skewed Poisson query trace interleaved
    with GraphDeltas, replayed in arrival order with the SLO-aware
    refresh worker running in the background."""
    import numpy as np
    from repro.configs.imm_snap import make_im_mesh, mesh_engine_kwargs
    from repro.core.engine import IMMConfig
    from repro.graphs import rmat_graph
    from repro.serve import (
        IMServe, TenantSpec, make_trace, replay, trace_summary, zipf_rates,
    )

    mesh_kw = mesh_engine_kwargs(make_im_mesh(args.mesh))
    cfg = IMMConfig(k=args.k, batch=min(args.max_theta, 256),
                    max_theta=max(args.max_theta, 1 << 20), seed=0,
                    store=args.store)
    tier = IMServe(quantum=args.quantum, refresh_budget=args.refresh_budget,
                   mesh_kwargs=mesh_kw)
    graphs, stream_map = {}, {}
    for i in range(args.tenants):
        name = f"tenant{i}"
        streaming = i % 2 == 1
        relaxed = args.replicas > 0 and i == 2 % max(args.tenants, 1)
        g = rmat_graph(args.tier_n, args.tier_n * 8, seed=10 + i,
                       weighted_ic="wc")
        tier.register(TenantSpec(
            name, graph=g, cfg=cfg, theta=args.max_theta,
            streaming=streaming,
            slo="relaxed" if relaxed else "strict",
            replicas=args.replicas if relaxed else 0,
            max_pending=args.max_pending))
        graphs[name], stream_map[name] = g, streaming
    print(f"[serve-tier] {args.tenants} tenants x n={args.tier_n} "
          f"(theta={args.max_theta}, mesh={args.mesh or 1}) registered")

    events = make_trace(
        graphs, duration=args.duration,
        qps=zipf_rates(sorted(graphs), args.qps, args.skew,
                       np.random.default_rng(1)),
        streaming=stream_map, delta_period=args.duration / 4,
        seed=2)
    print(f"[serve-tier] trace: {len(events)} events "
          f"{trace_summary(events)}")
    tier.start_refresh_worker()
    t0 = time.time()
    answered, rejected = replay(tier, events, pump_every=args.quantum * 2)
    wall = time.time() - t0
    drained = tier.drain(timeout=60.0)
    tier.close()
    lat = sorted(tier.result(t).latency_s for t in answered)
    stats = tier.stats()
    print(f"[serve-tier] {len(answered)} answered / {rejected} rejected "
          f"in {wall:.2f}s ({len(answered) / max(wall, 1e-9):.1f} q/s), "
          f"p50={lat[len(lat) // 2] * 1e3:.1f}ms "
          f"p99={lat[int(len(lat) * 0.99)] * 1e3:.1f}ms")
    print(f"[serve-tier] cache {stats['cache']}, "
          f"refresh {stats.get('refresh')}, drained={drained}")
    for name, ts in sorted(stats["tenants"].items()):
        print(f"  {name}: served={ts['served']} rejected={ts['rejected']} "
              f"cache_hits={ts['cache_hits']} epoch={ts['epoch']} "
              f"refreshes={ts['refreshes']}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lm", choices=("lm", "im", "tier"))
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--graph", default="com-Amazon")
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--model", default="IC",
                    choices=("IC", "WC", "GT", "LT"))
    ap.add_argument("--backend", default=None,
                    choices=("dense", "sparse", "pallas", "walk"),
                    help="traversal backend (default: auto by model/n)")
    ap.add_argument("--sampler", default=None,
                    help="full sampler-name override, e.g. 'WC/pallas'")
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--max-theta", type=int, default=4096)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--deltas", type=int, default=0,
                    help="IM workload: apply N random graph deltas and "
                         "serve through them (StreamEngine)")
    ap.add_argument("--refresh-budget", type=int, default=1024,
                    help="stale rows repaired between flushes in "
                         "--deltas mode")
    ap.add_argument("--async-refresh", action="store_true",
                    help="--deltas mode: repair on a background worker "
                         "thread instead of cooperatively inside flush")
    ap.add_argument("--store", default="auto",
                    choices=("auto", "bitmap", "indices", "packed",
                             "compressed", "sharded"),
                    help="IM arena at-rest representation ('packed'/"
                         "'compressed' = IMPack encoded tiles; results "
                         "are bitwise-identical to 'bitmap')")
    ap.add_argument("--mesh", default=None,
                    help="IM store mesh: int or 'auto' (1D theta "
                         "sharding), 'RxC' e.g. '2x4' (2D theta x "
                         "vertex), or omit for single-device")
    ap.add_argument("--tenants", type=int, default=4,
                    help="tier workload: campaigns to register")
    ap.add_argument("--tier-n", type=int, default=512,
                    help="tier workload: vertices per tenant graph")
    ap.add_argument("--duration", type=float, default=1.0,
                    help="tier workload: trace length (virtual seconds)")
    ap.add_argument("--qps", type=float, default=256.0,
                    help="tier workload: total query arrival rate")
    ap.add_argument("--skew", type=float, default=1.0,
                    help="tier workload: Zipf exponent of per-tenant "
                         "traffic shares")
    ap.add_argument("--quantum", type=int, default=8,
                    help="tier workload: DRR quantum per round")
    ap.add_argument("--replicas", type=int, default=1,
                    help="tier workload: read replicas for the "
                         "relaxed-SLO tenant (0 disables)")
    ap.add_argument("--max-pending", type=int, default=1024,
                    help="tier workload: per-tenant admission queue cap")
    ap.add_argument("--metrics-out", default=None,
                    help="enable repro.obs and write the metrics-registry "
                         "JSON snapshot here at exit")
    ap.add_argument("--trace-out", default=None,
                    help="enable repro.obs and write the Chrome "
                         "trace-event JSON (Perfetto-loadable) here")
    args = ap.parse_args(argv)
    init_compile_cache()
    if args.metrics_out or args.trace_out:
        obs.enable()
    if args.workload == "tier":
        _main_tier(args)
    elif args.workload == "im":
        _main_im(args)
    else:
        _main_lm(args)
    if args.metrics_out:
        print(f"[obs] metrics -> {obs.write_metrics(args.metrics_out)}")
    if args.trace_out:
        print(f"[obs] trace -> {obs.write_trace(args.trace_out)}")


if __name__ == "__main__":
    main()
