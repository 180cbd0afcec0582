"""Chip smoke test: the IMM engine's main path on a TPU v5e.

    python chip_smoke.py             # one chip: phases a and b
    python chip_smoke.py --chips 4   # four chips: the meshed phase only

Every phase drives the entry point a user calls,
`repro.launch.im_run.run`, on com-Amazon at its full SNAP size (n =
334,863 vertices, m = 925,872 undirected edges, seeded R-MAT stand-in),
IC, k = 50, eps = 0.5, theta capped at 16,384, then queries the engine
that call built.

* Phase a: the default store (a uint8 bitmap arena at rest), then
  ``select(k)`` for k in 5, 10, 20 and ``influences`` for 32 seed sets.
  The seeds, the greedy gains and the 32 influences are checked against
  a plain numpy greedy max-coverage over the same arena, copied to the
  host.
* Phase b: the same run with ``store="packed"`` (bit-packed arena, the
  packed ``arena_commit`` and decode-and-count selection); seeds, theta,
  coverage and influences must equal phase a's.
* ``--chips 4``: the same run with no mesh on device 0, on a 1D mesh
  (``make_im_mesh("4")``) and on a 2D theta x vertex mesh
  (``make_im_mesh("2x2")``); seeds, coverage and theta must be identical
  across the three, and each meshed arena must sit as one
  ``(cap_local, n_local)`` tile per device with no device holding the
  whole arena.

`repro.obs` is on throughout: every ``kernels.dispatch`` the run
compiled must have resolved to the compiled Pallas kernel.  Compile and
run times are printed as set-up information, not as benchmark numbers.
The script exits non-zero, before printing any result, when JAX finds
no TPU or when any check fails; its last line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

GRAPH = "com-Amazon"
N_VERTICES = 334_863
K = 50
MAX_THETA = 1 << 14
QUERY_KS = (5, 10, 20)
N_SETS = 32


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(f"check failed: {what}")


class CompileClock:
    """Sums backend compile seconds as JAX reports them."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def setup_line(phase: str, **fields) -> None:
    """Set-up information (wall-clock on the host, compiles included):
    never a benchmark number."""
    print(f"setup[{phase}]: " + json.dumps(fields), flush=True)


def run_im(phase, clock, **kw):
    from repro.launch.im_run import run
    c0, t0 = clock.total, time.time()
    out, engine = run(GRAPH, scale=1.0, model="IC", k=K, eps=0.5,
                      max_theta=MAX_THETA, log=lambda *a: None, **kw)
    setup_line(phase, wall_s=time.time() - t0,
               compile_s=clock.total - c0, graph_s=out["graph_s"],
               imm_s=out["imm_s"], theta=out["theta"],
               representation=out["representation"])
    return out, engine


def seed_sets(seeds, n: int) -> list:
    """The 32 influence queries: greedy prefixes of 1..8 seeds plus 24
    seeded random sets of 1..50 vertices."""
    rng = np.random.default_rng(0)
    sets = [seeds[:i] for i in range(1, 9)]
    sets += [rng.choice(n, size=int(rng.integers(1, K + 1)), replace=False)
             for _ in range(N_SETS - len(sets))]
    return sets


def check_result(out, engine, n: int) -> np.ndarray:
    res = engine.select(K)
    seeds = np.asarray(res.seeds)
    check(out["n"] == n, f"n = {out['n']}")
    check(0 < out["theta"] <= MAX_THETA and engine.theta == out["theta"],
          f"theta = {out['theta']}")
    check(seeds.shape == (K,) and len(set(seeds.tolist())) == K
          and seeds.min() >= 0 and seeds.max() < n, "k distinct seeds")
    check(0.0 < res.covered_frac <= 1.0
          and np.isfinite(res.influence), f"coverage {res.covered_frac}")
    for q in QUERY_KS:
        np.testing.assert_array_equal(
            engine.select(q).seeds, seeds[:q],
            err_msg=f"select({q}) is not the greedy prefix")
    return seeds


def reference_greedy(R, k: int):
    """Plain numpy greedy max-coverage over host rows ``R (theta, n)``:
    first-index argmax, exact integer counts."""
    step = 512
    counts = np.zeros(R.shape[1], np.int64)
    for i in range(0, R.shape[0], step):
        counts += R[i:i + step].sum(axis=0, dtype=np.int64)
    alive = np.ones(R.shape[0], bool)
    seeds, gains = [], []
    for _ in range(k):
        v = int(np.argmax(counts))
        hit = alive & (R[:, v] > 0)
        for i in range(0, R.shape[0], step):
            h = hit[i:i + step]
            if h.any():
                counts -= R[i:i + step][h].sum(axis=0, dtype=np.int64)
        alive &= ~hit
        seeds.append(v)
        gains.append(int(hit.sum()))
    return np.asarray(seeds), np.asarray(gains)


def phase_single(clock) -> None:
    out_a, eng = run_im("a", clock)
    n = out_a["n"]
    seeds = check_result(out_a, eng, n)
    sets = seed_sets(seeds, n)
    t0 = time.time()
    infl_a = eng.influences(sets)
    setup_line("a", influences_s=time.time() - t0, queries=len(sets))
    check(infl_a.shape == (N_SETS,) and np.isfinite(infl_a).all()
          and (infl_a >= 0).all() and (infl_a <= n).all(), "influences")
    res = eng.select(K)
    theta = eng.theta
    check(round(infl_a[7] / n * theta) == int(res.gains[:8].sum()),
          "sigma(top 8) from hits equals the greedy gains")

    # the plain reference over the same rows, on the host
    t0 = time.time()
    R = np.asarray(eng.store.R)[:theta]
    ref_seeds, ref_gains = reference_greedy(R, K)
    np.testing.assert_array_equal(seeds, ref_seeds,
                                  err_msg="seeds differ from the reference")
    np.testing.assert_array_equal(np.asarray(res.gains), ref_gains,
                                  err_msg="gains differ from the reference")
    ref_hits = np.asarray([(R[:, np.asarray(s)] > 0).any(axis=1).sum()
                           for s in sets])
    np.testing.assert_array_equal(np.rint(infl_a / n * theta), ref_hits,
                                  err_msg="influences differ from the "
                                          "reference")
    setup_line("a", reference_s=time.time() - t0)
    frac_a = res.covered_frac
    del R, eng, res
    gc.collect()

    out_b, eng_b = run_im("b", clock, store="packed")
    check(eng_b.store.representation == "packed", "packed store")
    check(out_b["theta"] == theta, "phase b theta equals phase a")
    seeds_b = check_result(out_b, eng_b, n)
    np.testing.assert_array_equal(seeds_b, seeds,
                                  err_msg="packed seeds differ from bitmap")
    check(eng_b.select(K).covered_frac == frac_a, "packed coverage")
    np.testing.assert_array_equal(eng_b.influences(sets), infl_a,
                                  err_msg="packed influences differ")
    print(json.dumps({"phase": "a+b", "n": n, "m": out_a["m"],
                      "theta": theta, "covered_frac": frac_a,
                      "influence": out_a["influence"],
                      "seeds": seeds[:10].tolist()}), flush=True)


def tile_check(engine, jax) -> dict:
    """Each device holds one (cap_local, n_local) tile; none holds the
    whole arena."""
    s = engine.store
    shards = s.R.addressable_shards
    devices = {sh.device for sh in shards}
    check(len(devices) == len(shards) == s.D * s.Dv, "one tile per device")
    for sh in shards:
        check(sh.data.shape == (s.cap_local, s.n_local),
              f"tile {sh.data.shape} on {sh.device}")
    check(s.R.shape != (s.cap_local, s.n_local), "no device holds it all")
    return {"tile": [s.cap_local, s.n_local], "arena": list(s.R.shape),
            "devices": len(devices)}


def phase_mesh(clock, jax) -> None:
    results = {}
    for spec in (None, "4", "2x2"):
        name = spec or "none"
        out, eng = run_im(f"mesh={name}", clock, mesh=spec)
        seeds = check_result(out, eng, out["n"])
        res = eng.select(K)
        info = {}
        if spec is None:
            check(eng.store.R.devices() == {jax.devices()[0]},
                  "unmeshed arena on device 0")
        else:
            info = tile_check(eng, jax)
        results[name] = (seeds, res.covered_frac, eng.theta)
        setup_line(f"mesh={name}", **info)
        del eng, res
        gc.collect()
    seeds0, frac0, theta0 = results["none"]
    for name in ("4", "2x2"):
        seeds, frac, theta = results[name]
        np.testing.assert_array_equal(seeds, seeds0,
                                      err_msg=f"mesh {name} seeds differ")
        check(frac == frac0, f"mesh {name} coverage {frac} != {frac0}")
        check(theta == theta0, f"mesh {name} theta {theta} != {theta0}")
    print(json.dumps({"phase": "mesh", "theta": theta0,
                      "covered_frac": frac0,
                      "seeds": seeds0[:10].tolist()}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()
    import jax
    from repro import obs

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: jax.devices()[0].platform = "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    obs.enable()
    clock = CompileClock(jax)
    if args.chips == 4:
        phase_mesh(clock, jax)
    else:
        phase_single(clock)

    dispatch = {k: v for k, v in obs.snapshot()["counters"].items()
                if k.startswith("kernels.dispatch")}
    print("kernels.dispatch: " + json.dumps(dispatch), flush=True)
    check(dispatch, "no kernel was dispatched")
    bad = [k for k in dispatch if "impl=pallas" not in k]
    check(not bad, f"kernels not compiled as Pallas: {bad}")
    if args.chips == 1:
        for kernel in ("arena_commit", "coverage_matvec", "packed_count"):
            check(any(f"kernel={kernel}" in k for k in dispatch),
                  f"{kernel} not on the path")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
