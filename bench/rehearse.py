"""Compile every cell's window programs at full size for a described
TPU v5e, and print each one's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py

No chip is needed: the TPU compiler compiles for a chip that is
described, not attached.  A program over 16 GiB, or one the compiler
refuses, shows here before any chip time is spent.  The programs, at
each configuration's sizes (B = batch, n, m directed edges, theta):

* ``sample`` cells: the IC sparse traversal or the LT walk with (B, n)
  visited rows, the arena_commit kernel over one batch, and the
  capacity-shaped arena write at the top of the pow2 ladder;
* ``select`` cells: greedy selection, k = 50, over a (theta, n) uint8
  arena;
* ``serve`` cells: ``hits`` for the largest served batch (8 queries of
  16 seeds).

Exits non-zero when a program does not compile or does not fit.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 16 * 2**30


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core import fused, sampler, selection, store
    from repro.kernels import commit
    from bench.harness import read_json

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    files = {c["name"]: c["file"] for c in bench["configs"]}
    failed = False
    for cell in bench["workloads"]:
        cfg = read_json(os.path.join(ROOT, files[cell["config"]]))
        kind = read_json(os.path.join(ROOT, "bench", "traffic",
                                      cell["traffic"] + ".json"))["kind"]
        n, B, theta = (int(cfg["graph"]["n"]), int(cfg["batch"]),
                       int(cfg["theta_per_arena"]))
        m = 2 * int(cfg["graph"]["undirected_edges"])   # both ways
        key = sds((2,), jnp.uint32)
        i32, f32, u8 = jnp.int32, jnp.float32, jnp.uint8
        progs = {"commit": (commit.arena_commit, (sds((B, n), u8),), {}),
                 "commit_write": (fused._commit_write, (
                     sds((theta, n), u8), sds((theta,), i32),
                     sds((n,), i32), sds((B, n), u8), sds((B,), i32),
                     sds((n,), i32), sds((), i32)), {}),
                 "select": (selection.select_dense, (
                     sds((theta, n), u8), sds((theta,), jnp.bool_)),
                     {"k": int(cfg["k"])}),
                 "hits": (store._bitmap_hits, (
                     sds((theta, n), u8), sds((theta,), jnp.bool_),
                     sds((8, 16), i32)), {})}
        if cfg["model"] == "IC":
            progs["sample"] = (sampler._sparse_loop, (
                key, sds((m,), i32), sds((m,), i32), sds((m,), f32)),
                {"n_nodes": n, "batch": B})
        else:
            progs["sample"] = (sampler._walk_loop, (
                key, sds((n + 1,), i32), sds((m,), i32), sds((m,), f32),
                sds((n,), f32)), {"batch": B})
        used = {"sample": ("sample", "commit", "commit_write"),
                "select": ("select",), "serve": ("hits",)}[kind]
        for name in used:
            fn, args, kw = progs[name]
            try:
                c = fn.lower(*args, **kw).compile()
                ma = c.memory_analysis()
                total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                         + ma.temp_size_in_bytes
                         - ma.alias_size_in_bytes)
                ok = total <= HBM
                row = {"cell": cell["name"], "program": name,
                       "argument_bytes": ma.argument_size_in_bytes,
                       "output_bytes": ma.output_size_in_bytes,
                       "temp_bytes": ma.temp_size_in_bytes,
                       "alias_bytes": ma.alias_size_in_bytes,
                       "total_bytes": total, "fits_16GiB": ok}
            except Exception as e:  # report every program, then fail
                ok, row = False, {"cell": cell["name"], "program": name,
                                  "error": f"{type(e).__name__}: {e}"[:400]}
            failed |= not ok
            print(json.dumps(row), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
