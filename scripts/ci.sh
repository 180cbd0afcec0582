#!/usr/bin/env bash
# Tier-1 CI gate: bytecode-compile everything under src, run the fast test
# suite (slow production cells are deselected; run them explicitly with
# `pytest -m slow`), re-run the mesh-touching tests on a forced 4-device
# host platform so the sharded code paths execute with real multi-device
# buffers on CPU-only runners, and check that docs references resolve.
# Extra args pass through to the main pytest invocation.
set -euo pipefail
cd "$(dirname "$0")/.."

python -m compileall -q src

PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m pytest -q -m "not slow" "$@"

# mesh code paths under a forced 4-device host mesh (paper C1 layouts):
# ShardedStore (1D and 2x2 theta x vertex), sharded selection (dense and
# sharded-sparse), the engine equivalence tests, the streaming subsystem
# (per-shard invalidation/eviction/compaction, refresh-equivalence and
# cross-layout snapshot-provenance cells incl. 2D), the sampler
# model x backend x stable matrix (legacy goldens + per-cell mesh
# equivalence), and the IMPack suite (codec round-trips, encoded mesh
# tiles, the compress-before-evict ladder, snapshot elasticity) all run
# with the theta axis physically split 4 ways
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
    python -m pytest -q -m "not slow" \
        tests/test_sharded_store.py \
        tests/test_stream.py \
        tests/test_sampler_matrix.py \
        tests/test_pack.py \
        tests/test_fused_pipeline.py \
        "tests/test_engine_store.py::test_sharded_strategy_through_engine_matches_local" \
        "tests/test_sharded_and_integration.py::test_select_dense_sharded_equals_local"

# the 2D acceptance cell on a forced-8-device 2x4 mesh: theta over 2
# shards x vertices over 4 — per-device arena buffers are (cap_local,
# n/4), the full (theta, n) arena never exists on one device,
# select/influence answers are bitwise identical to the single-device
# engine, and both selection rules over the tiles and over tile-local
# index lists give the plain greedy's seeds, gains and covered_frac
# (tests/force_mesh_check.py asserts all of it); the packed and
# compressed cells re-prove it with IMPack-encoded tiles, whose
# per-device buffers are (cap_local, w_local) at the codec width
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
    python tests/force_mesh_check.py --mesh 2x4
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
    python tests/force_mesh_check.py --mesh 2x4 --store packed
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
    python tests/force_mesh_check.py --mesh 2x4 --store compressed

# sharding-scaling benchmark smoke (BENCH_5): every mesh factorization of
# 8 forced devices (1, 8, 8x1, 4x2, 2x4, 1x8) runs the same workload —
# vertex-sharded layouts in both equal and edge-balanced (+bal) column
# layouts — with identical seeds asserted, reporting wall time, arena
# bytes per device, per-tile edge imbalance, and the per-step
# collective/compute breakdown; the run itself asserts balanced <= equal
# imbalance on the rmat graph
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
    python -m benchmarks.sharding_scaling --tiny \
        --out "${TMPDIR:-/tmp}/BENCH_5.json"

# step-time-breakdown schema gate: every BENCH_5 row must carry the
# imbalance + collective_s/compute_s fields the overlap work reports
python - "${TMPDIR:-/tmp}/BENCH_5.json" <<'PY'
import json, sys
rows = json.load(open(sys.argv[1]))
assert rows, "BENCH_5.json has no rows"
for row in rows:
    missing = [k for k in ("imbalance", "collective_s", "compute_s")
               if k not in row]
    assert not missing, f"row {row.get('mesh')} missing {missing}"
print(f"BENCH_5 schema OK: {len(rows)} rows carry "
      f"imbalance/collective_s/compute_s")
PY

# IMPack memory benchmark smoke (BENCH_9): bitmap vs packed vs
# compressed arenas on every layout of the 8 forced devices (1, 1D 8,
# 2D 2x4) — identical seeds asserted per cell, packed >= 4x fewer
# bytes_per_device than bitmap asserted per layout, plus the
# quality-per-byte curve rows
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
    python -m benchmarks.pack_memory --tiny \
        --out "${TMPDIR:-/tmp}/BENCH_9.json"

# fused RRR pipeline smoke (BENCH_10): the one-chain sample->write->count
# path vs the legacy two-call path at identical seeds — the emitter itself
# asserts bitwise-equal counters, seed sets, covered_frac and influence
# before writing a row — first single-device, then on a forced 4-device
# 2x2 theta x vertex mesh
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.kernel_pipeline --tiny \
        --out "${TMPDIR:-/tmp}/BENCH_10.json"
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
    python -m benchmarks.kernel_pipeline --tiny --mesh 2x2 \
        --out "${TMPDIR:-/tmp}/BENCH_10.json"

# fused-pipeline schema gate: every BENCH_10 row must carry the kernel /
# fused / impl fields, and achieved_frac only where the device has
# published peaks (a CPU run writes none)
python - "${TMPDIR:-/tmp}/BENCH_10.json" <<'PY'
import json, sys
rows = json.load(open(sys.argv[1]))
assert rows, "BENCH_10.json has no rows"
for row in rows:
    missing = [k for k in ("kernel", "fused", "impl") if k not in row]
    assert not missing, f"row {row.get('name')} missing {missing}"
    assert row["impl"] in ("pallas", "interpret", "oracle"), row
    assert ("achieved_frac" in row) == (row["device_kind"] != "cpu"), row
    assert 0.0 <= row.get("achieved_frac", 0.0) <= 1.0, row
fused = [r for r in rows if r.get("fused")]
assert fused and all("speedup" in r for r in fused), \
    "fused rows must report speedup vs the unfused twin"
print(f"BENCH_10 schema OK: {len(rows)} rows carry kernel/fused/impl")
PY

# streaming benchmark smoke (tiny evolving graph; the non-slow analogue of
# the full benchmarks/stream_runtime.py run) — exercises delta apply,
# row-granular refresh, and the bounded-memory mode end-to-end
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.stream_runtime --tiny \
        --out "${TMPDIR:-/tmp}/BENCH_3.json"

# sampler-matrix benchmark smoke: every coin model across the dense /
# sparse / pallas backends (plus the LT walk) through the engine —
# exercises the Pallas ic_frontier dispatch end-to-end off-TPU
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.sampler_matrix --tiny \
        --out "${TMPDIR:-/tmp}/BENCH_4.json"

# serve-tier smoke (IMServe): a tiny multi-tenant trace — static +
# streaming tenants, interleaved deltas, a relaxed-SLO replica tenant,
# background SLO-aware refresh — through the launch CLI and the BENCH_6
# emitter, first on the default single-device engines...
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --workload tier \
        --tenants 3 --tier-n 128 --max-theta 256 --duration 0.25 \
        --qps 64 --refresh-budget 128 --replicas 1
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.serve_tier --tiny \
        --out "${TMPDIR:-/tmp}/BENCH_6.json"

# ...then with every tenant engine (and its replica fan-out) on a forced
# 4-device 2x2 theta x vertex mesh — the serving tier is layout-agnostic
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
    python -m repro.launch.serve --workload tier \
        --tenants 3 --tier-n 128 --max-theta 256 --duration 0.25 \
        --qps 64 --refresh-budget 128 --replicas 1 --mesh 2x2
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
    python -m benchmarks.serve_tier --tiny --mesh 2x2 \
        --out "${TMPDIR:-/tmp}/BENCH_6.json"

# IMTrace (repro.obs) export path: a small IMM campaign with
# --metrics-out/--trace-out, then the artifact gate — the metrics
# snapshot must match the registry schema and the trace must parse as
# Chrome trace-event JSON with spans from the engine and store tiers
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.im_run --graph com-Amazon --scale 0.002 \
        --k 4 --max-theta 256 \
        --metrics-out "${TMPDIR:-/tmp}/obs_metrics.json" \
        --trace-out "${TMPDIR:-/tmp}/obs_trace.json"
python scripts/check_obs.py \
    --metrics "${TMPDIR:-/tmp}/obs_metrics.json" \
    --trace "${TMPDIR:-/tmp}/obs_trace.json" --tiers engine,store \
    --require-counter kernels.dispatch

# ...and the serving tier under the same flags: the trace must now also
# carry stream (deltas + refresh) and serve (admission/cache/batch) spans
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --workload tier \
        --tenants 3 --tier-n 128 --max-theta 256 --duration 0.25 \
        --qps 64 --refresh-budget 128 --replicas 1 \
        --metrics-out "${TMPDIR:-/tmp}/obs_metrics.json" \
        --trace-out "${TMPDIR:-/tmp}/obs_trace.json"
python scripts/check_obs.py \
    --metrics "${TMPDIR:-/tmp}/obs_metrics.json" \
    --trace "${TMPDIR:-/tmp}/obs_trace.json" \
    --tiers engine,store,stream,serve

# the observability acceptance cell on the forced-8-device 2x4 mesh:
# obs fully enabled is seed-for-seed bitwise identical to obs disabled,
# nested spans land from every tier, and a meshed IMServe campaign
# reports per-tenant latency quantiles, cache hit/miss, queue depth,
# and SLO violations (tests/force_obs_check.py asserts all of it)
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
    python tests/force_obs_check.py --mesh 2x4

# docs health: files referenced from README/docs must exist
python scripts/check_docs.py
