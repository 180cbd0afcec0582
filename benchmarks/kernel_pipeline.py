"""Fused RRR pipeline: what does the sample->write->count chain buy?

Times `InfluenceEngine.extend(theta)` twice per arena cell — once with
``fused_pipeline="off"`` (the legacy sample-jit -> add_batch-jit path,
where every batch exists as a separate ``(B, n)`` device array between
the two calls) and once with ``"auto"`` (one jit per batch: the bound
sampler inlined ahead of the ``kernels/commit.py`` arena-commit kernel,
buffers donated, no intermediate handoff).  Both engines are built from
the *same* ``IMMConfig.seed``, so the PRNG streams are identical by
construction; the emitter then **asserts** — not just reports — that the
per-vertex counters, the selected seed sets, ``covered_frac``, and
``influence`` are bitwise identical before any row is written.  A BENCH
file from this emitter is therefore a pure execution-strategy diff.

Emits machine-readable ``BENCH_10.json`` rows

    {name, mesh, n, theta, wall_s, kernel, fused, store, impl,
     achieved_frac[, speedup]}

where ``impl`` is the ``kernels/ops.py`` dispatch outcome
(``pallas``/``interpret``/``oracle``; sharded cells always report
``oracle`` — the mesh write body is the jnp oracle inside ``shard_map``,
never the single-device Pallas kernel) and ``achieved_frac`` is the
per-batch roofline fraction from ``repro.launch.roofline`` for the
``sample_write_count`` cost model on this ``device_kind``.

The real-hardware section (raw ``arena_commit`` kernel, pallas vs
oracle) runs only when the default backend is an accelerator; on CPU it
skips with a message rather than timing the interpreter.

    PYTHONPATH=src python -m benchmarks.kernel_pipeline [--tiny]
        [--mesh RxC] [--out F] [--require-speedup X]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks._emit import bench_row, device_kind, write_bench
from benchmarks._util import block, print_table, timeit
from repro.core.engine import IMMConfig, InfluenceEngine
from repro.graphs import rmat_graph
from repro.kernels import ops as kops
from repro.launch.roofline import HW_PEAKS, achieved_frac

# small n + many batches on purpose: the fused chain removes per-batch
# dispatch + the (B, n) handoff, which is exactly the regime where that
# fixed cost dominates the arithmetic
CELLS = {
    "default": dict(n=256, m=2048, theta=16384, batch=64, seed=0, k=4),
    "tiny": dict(n=128, m=1024, theta=512, batch=64, seed=0, k=4),
}
STORES = ("auto", "packed")  # bitmap arena + bit-packed arena


def _engine(g, cfg, mesh):
    if mesh is None:
        return InfluenceEngine(g, cfg)
    from repro.configs.imm_snap import mesh_engine_kwargs
    return InfluenceEngine(g, cfg, **mesh_engine_kwargs(mesh))


def _timed_extend(g, cfg, theta, mesh):
    """(engine, wall_s) for extend(theta) after warming the engine's own
    first batch.  The warmup is the engine itself (not a throwaway, as
    in sampler_matrix): the fused chain jit closes over the per-engine
    bound sampler, so only a same-engine batch pre-compiles it — and
    running the identical warmup on the unfused engine keeps the two
    PRNG streams aligned batch-for-batch for the bitwise asserts."""
    engine = _engine(g, cfg, mesh)
    engine.extend(cfg.batch)
    block(engine.store.counter)
    t0 = time.perf_counter()
    engine.extend(theta)
    block(engine.store.counter)
    return engine, time.perf_counter() - t0


def _assert_bitwise(off, on, k):
    """Fused and legacy engines must agree bitwise before a row is
    emitted — counters, then the full selection answer."""
    assert off.cfg.seed == on.cfg.seed, "emitter bug: seeds differ"
    np.testing.assert_array_equal(
        np.asarray(off.store.counter), np.asarray(on.store.counter),
        err_msg="fused vs unfused per-vertex counters diverged")
    s_off, s_on = off.select(k), on.select(k)
    np.testing.assert_array_equal(
        np.asarray(s_off.seeds), np.asarray(s_on.seeds),
        err_msg="fused vs unfused seed sets diverged")
    assert float(s_off.covered_frac) == float(s_on.covered_frac), (
        f"covered_frac diverged: {s_off.covered_frac} vs "
        f"{s_on.covered_frac}")
    assert float(s_off.influence) == float(s_on.influence), (
        f"influence diverged: {s_off.influence} vs {s_on.influence}")
    return s_on


def run(n, m, theta, batch, seed, k, mesh=None, log=print):
    g = rmat_graph(n, m, seed=seed)
    batches = -(-theta // batch)
    # what the dispatch layer would pick for the single-device commit
    # kernel here; sharded cells use the jnp oracle inside shard_map
    impl = "oracle" if mesh is not None else kops.resolve_impl()
    # a device metric only where the device's peaks are published: a CPU
    # run writes none
    on_device = device_kind() in HW_PEAKS
    rows, bench = [], []
    for store in STORES:
        kind = "packed" if store == "packed" else "bitmap"
        base = dict(model="IC", batch=batch, max_theta=max(theta, 1 << 20),
                    seed=seed, k=k, store=store)
        off, w_off = _timed_extend(
            g, IMMConfig(fused_pipeline="off", **base), theta, mesh)
        on, w_on = _timed_extend(
            g, IMMConfig(fused_pipeline="auto", **base), theta, mesh)
        sel = _assert_bitwise(off, on, k)
        speedup = w_off / w_on if w_on > 0 else 0.0
        for fused, wall in ((False, w_off), (True, w_on)):
            extra = dict(kernel="sample_write_count", fused=fused,
                         store=store, impl=impl)
            af = None
            if on_device:
                af = achieved_frac("sample_write_count", wall / batches,
                                   B=batch, n=n, kind=kind)
                extra["achieved_frac"] = round(af, 6)
            if fused:
                extra["speedup"] = round(speedup, 3)
            bench.append(bench_row(
                f"kernel_pipeline/{store}/"
                f"{'fused' if fused else 'unfused'}",
                n=n, theta=theta, wall_s=wall, mesh=mesh, **extra))
            rows.append([store, fused, f"{wall:.3f}", impl,
                         "not measured" if af is None else f"{af:.4f}",
                         f"{speedup:.2f}x" if fused else "-"])
        log(f"[kernel-pipeline] store={store}: unfused {w_off:.3f}s, "
            f"fused {w_on:.3f}s ({speedup:.2f}x), influence "
            f"{sel.influence:.1f} bitwise-equal")
    print_table(
        f"Fused RRR pipeline (n={n}, m={m}, theta={theta}, batch={batch},"
        f" mesh={'1' if mesh is None else 'x'.join(map(str, mesh.devices.shape))})",
        ["store", "fused", "wall_s", "impl", "achieved_frac", "speedup"],
        rows)
    return bench


def run_hw(n, batch, seed, log=print):
    """Raw arena-commit kernel on a device with published peaks only.

    The interpreter is not hardware — timing it says nothing about the
    MXU path — so off-accelerator this section skips cleanly."""
    dk = device_kind()
    if dk not in HW_PEAKS:
        log(f"[kernel-pipeline] device_kind={dk}: skipping the raw "
            "arena_commit hardware section (needs a device in HW_PEAKS)")
        return []
    import jax
    rng = np.random.default_rng(seed)
    rows_np = (rng.random((batch, n)) < 0.25).astype(np.uint8)
    bench = []
    for kind in ("bitmap", "packed"):
        fn = jax.jit(lambda r, kd=kind: kops.arena_commit(r, kind=kd))
        wall = timeit(fn, jax.numpy.asarray(rows_np))
        bench.append(bench_row(
            f"arena_commit/{kind}/pallas", n=n, theta=batch,
            wall_s=wall, kernel="arena_commit", fused=False,
            store=kind, impl="pallas",
            achieved_frac=round(achieved_frac(
                "arena_commit", wall, B=batch, n=n, kind=kind), 6)))
        log(f"[kernel-pipeline] arena_commit {kind}/pallas: "
            f"{wall * 1e3:.3f}ms")
    return bench


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: small cell, same asserts")
    ap.add_argument("--mesh", default=None,
                    help="run the cells on a device mesh (e.g. 2x2)")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--theta", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--out", default="BENCH_10.json",
                    help="machine-readable output path")
    ap.add_argument("--require-speedup", type=float, default=None,
                    help="fail unless some fused cell hits this speedup")
    args = ap.parse_args(argv)
    cell = dict(CELLS["tiny" if args.tiny else "default"])
    for key in ("n", "theta", "batch"):
        if getattr(args, key) is not None:
            cell[key] = getattr(args, key)
    mesh = None
    if args.mesh is not None:
        from repro.configs.imm_snap import make_im_mesh
        mesh = make_im_mesh(args.mesh)
    bench = run(mesh=mesh, **cell)
    bench += run_hw(cell["n"], cell["batch"], cell["seed"])
    if args.require_speedup is not None:
        best = max((r.get("speedup", 0.0) for r in bench), default=0.0)
        assert best >= args.require_speedup, (
            f"best fused speedup {best:.2f}x < required "
            f"{args.require_speedup:.2f}x")
    write_bench(args.out, bench)


if __name__ == "__main__":
    main()
