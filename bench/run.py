"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; `bench.harness` finds their files by name.  The run makes
its inputs from ``--seed``, warms every program its window uses (set-up,
reported as ``setup_s``), measures for ``--seconds`` seconds, then
checks what the window produced against the plain references in
``bench/reference.py``.  ``--trace 1`` is a run of its own: the
program's spans on, a profiler trace of a few seconds of the window,
and the per-layer metrics read from it.

Standard error carries the set-up and window details and, as its last
lines, each number compared beside its limit.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``.  The command exits non-zero, and prints no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    ap.add_argument("--control", action="store_true",
                    help="also run each check with the cell's control "
                         "(the reference one precision lower) in the "
                         "program's place")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
    except (KeyError, OSError) as e:
        harness.log(f"cannot load workload {args.workload!r}: {e}")
        return 2
    try:
        from repro.launch.compile_cache import init_compile_cache
    except ImportError as e:
        harness.log(f"the program is not in this checkout: {e}")
        return 2
    init_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log(f"no TPU: jax.devices()[0].platform = "
                    f"{devices[0].platform!r}")
        return 1
    if len(devices) < cell.chips:
        harness.log(f"{args.workload} needs {cell.chips} chips, JAX finds "
                    f"{len(devices)}")
        return 1
    result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t_start=T_START,
                         control=args.control, trace_dir=args.trace_dir)
    print(json.dumps(result), flush=True)
    for k, c in result["checks"].items():
        harness.log(f"check {k}: {c['value']} (limit {c['limit']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
