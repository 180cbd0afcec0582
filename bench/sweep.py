"""Find the highest rate a serve cell sustains: one set-up, then one
open-loop window per offered rate, on the chip this process finds.

    python3 bench/sweep.py --workload amazon_ic.serve --seed 7 \\
        --seconds 10 --rates 50,100,200,400

A rate may be listed more than once; each window draws its arrivals
from its own seed (``--seed`` plus the window's place in the list).
Prints one JSON line per window: the offered and answered queries, the
latency percentiles from due time, the p95 of the window's first and
second halves (a backlog that grows shows as a second half far above
the first), how long the queue took to drain after the window, and how
late the generator ran.  The cell's ``rate_qps`` is then set, by hand,
to about four fifths of the highest rate whose backlog did not grow.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import numpy as np
    from bench import harness
    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        harness.log("no TPU")
        return 1
    cell = harness.load_cell(args.workload)
    if cell.traffic["kind"] != "serve":
        harness.log(f"{args.workload} is not a serve cell")
        return 2
    t = time.perf_counter()
    st = cell.kind.setup(cell, args.seed, harness.log)
    harness.log(f"setup_s={time.perf_counter() - t:.3f}")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        st.rate, st.answered, st.seed = rate, {}, args.seed + i
        clock = harness.Clock(args.seconds)
        win = cell.kind.window(st, clock)
        half = st.due < args.seconds / 2
        info = dict(win.info)
        info.update(rate_qps=rate, p95_ms=win.metrics["query_p95_ms"],
                    drain_s=clock.t1 - clock.t0 - info["window_s"])
        lat_ms = st.lat_ms
        if half.any() and (~half).any():
            info["p95_first_half_ms"] = float(np.percentile(lat_ms[half], 95))
            info["p95_second_half_ms"] = float(
                np.percentile(lat_ms[~half], 95))
        print(json.dumps(info), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
