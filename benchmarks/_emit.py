"""One schema for every BENCH_*.json emitter.

Every benchmark in this repo reports machine-readable rows with the same
five core keys —

    {"name": ..., "mesh": ..., "n": ..., "theta": ..., "wall_s": ...}

— plus two provenance keys stamped automatically at write time —

    {"git_sha": ..., "device_kind": ...}

— plus bench-specific extras (``model``/``backend`` for the sampler
matrix, ``bytes_per_device`` for the sharding scaling bench,
``p50_ms``/``p99_ms``/``cache_hit_rate`` for the serving tier, ...), so
the benchmark-trajectory tooling can diff any two BENCH files without
per-bench parsers.  ``mesh`` is the layout tag: ``"1"`` for
single-device, ``"R"`` for a 1D theta mesh, ``"RxC"`` for a 2D
theta x vertex mesh (`mesh_tag` derives it from a ``jax.sharding.Mesh``).
``git_sha`` is the commit the numbers were measured at and
``device_kind`` the device they were measured on, as JAX names it
(``cpu``, ``TPU v5 lite``) — committed BENCH files are only comparable
when both match.

Two *optional* cross-bench keys exist beyond the extras free-for-all
(PR 10): ``impl`` — which kernel implementation actually ran
(``pallas``/``interpret``/``oracle``, as proven by the
``kernels.dispatch`` obs counter rather than inferred from
``device_kind``) — and ``achieved_frac`` — the measured fraction of the
roofline bound per ``repro.launch.roofline.achieved_frac``.  They are
validated *when present* (`OPTIONAL_KEYS`), so BENCH files written
before they existed still pass the schema gate unchanged.

Use `bench_row` to build rows and `write_bench` to emit the file — both
validate the schema, so a bench cannot silently drop a core key.
"""
from __future__ import annotations

import json
import statistics
import subprocess

SCHEMA_KEYS = ("name", "mesh", "n", "theta", "wall_s")
STAMP_KEYS = ("git_sha", "device_kind")
# optional cross-bench keys: validators run only when the key is present,
# so rows (and whole files) written before a key existed still validate
OPTIONAL_KEYS = {
    "impl": lambda v: v in ("pallas", "interpret", "oracle"),
    "achieved_frac": lambda v: (isinstance(v, (int, float))
                                and 0.0 <= float(v) <= 1.0),
}


def git_sha() -> str:
    """Short commit sha of the working tree, with a ``-dirty`` suffix
    when it carries uncommitted changes ("unknown" outside git)."""
    try:
        out = subprocess.run(
            ["git", "describe", "--always", "--dirty"],
            capture_output=True, text=True, timeout=10)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except Exception:
        # no git binary, no checkout, an unreadable .git, a sandboxed
        # interpreter without subprocess — a bench must still emit,
        # just unstamped
        return "unknown"


def span_median_s(name: str, tier: str = None, last: int = None) -> float:
    """Median duration (seconds) of the completed ``repro.obs`` spans
    named ``name`` — the tracer-backed replacement for hand-rolled
    timer lists, so a BENCH row and a ``--trace-out`` timeline report
    the same measurement.  ``last`` keeps only the most recent N spans
    (repeated measurements in one process would otherwise mix);
    returns 0.0 when nothing was recorded."""
    from repro import obs
    durs = obs.get_tracer().durations_s(name, tier)
    if last is not None:
        durs = durs[-int(last):]
    if not durs:
        return 0.0
    return float(statistics.median(durs))


def snapshot_scalar(snapshot: dict, name: str, default: float = 0.0):
    """Pull one scalar out of a ``repro.obs`` registry snapshot by
    series key: counters return their count, gauges their last value,
    histograms their p50 — so BENCH emitters can lift columns straight
    from the runtime telemetry instead of keeping parallel counters."""
    if name in snapshot.get("counters", {}):
        return snapshot["counters"][name]
    if name in snapshot.get("gauges", {}):
        return snapshot["gauges"][name]["value"]
    if name in snapshot.get("histograms", {}):
        return snapshot["histograms"][name]["p50"]
    return default


def device_kind() -> str:
    """``device_kind`` of device 0 as JAX reports it (``"TPU v5 lite"``,
    ``"cpu"``) — the key `repro.launch.roofline.HW_PEAKS` uses."""
    import jax
    return jax.devices()[0].device_kind


def mesh_tag(mesh) -> str:
    """Layout tag for a mesh: ``"1"`` (None), ``"R"`` (1D), ``"RxC"``
    (2D, theta x vertex axis order as built by
    ``configs.imm_snap.make_im_mesh``)."""
    if mesh is None:
        return "1"
    sizes = tuple(int(mesh.shape[a]) for a in mesh.axis_names)
    return "x".join(str(s) for s in sizes)


def bench_row(name: str, *, n: int, theta: int, wall_s: float,
              mesh=None, **extra) -> dict:
    """One schema-conformant benchmark row.  ``mesh`` may be None, a
    ``jax.sharding.Mesh``, or a pre-built tag string; ``extra`` keys ride
    along after the core five.  Provenance (`STAMP_KEYS`) is stamped by
    `write_bench`."""
    tag = mesh if isinstance(mesh, str) else mesh_tag(mesh)
    row = {"name": str(name), "mesh": tag, "n": int(n),
           "theta": int(theta), "wall_s": round(float(wall_s), 4)}
    for k, v in extra.items():
        if k in row:
            raise ValueError(f"extra key {k!r} collides with the schema")
        row[k] = v
    return row


def write_bench(path: str, rows: list[dict]) -> str:
    """Validate, stamp provenance (``git_sha``, ``device_kind`` — once
    per file, identical on every row), and write BENCH rows; returns
    ``path``."""
    stamp = {"git_sha": git_sha(), "device_kind": device_kind()}
    for i, row in enumerate(rows):
        missing = [k for k in SCHEMA_KEYS if k not in row]
        if missing:
            raise ValueError(f"bench row {i} is missing {missing}: {row}")
        for k, ok in OPTIONAL_KEYS.items():
            if k in row and not ok(row[k]):
                raise ValueError(
                    f"bench row {i} has malformed optional key "
                    f"{k}={row[k]!r}: {row}")
        for k in STAMP_KEYS:
            row.setdefault(k, stamp[k])
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"wrote {path} ({len(rows)} rows)")
    return path
