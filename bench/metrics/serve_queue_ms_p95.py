"""95th percentile of the wait from a query's due time to the start of
the tier's ``serve.batch`` span that answered it, over the batches that
started inside the traced window (moves ``query_p95_ms``).

Due times are the benchmark's; span starts are the program's
(``repro.obs``).  The ``bench.clock`` span, opened as the window
starts, ties the program's span clock to the window's."""
import numpy as np


def read(run):
    st = run.state
    mark = [e for e in run.spans if e.get("name") == "bench.clock"]
    if not mark or not st.pumps:
        return None
    off = st.clock_mark - mark[0]["ts"] / 1e6
    lo, hi = st.traced
    waits = []
    for ev in run.spans:
        if ev.get("name") != "serve.batch":
            continue
        t = ev["ts"] / 1e6 + off
        if lo is None or not lo <= t < hi:
            continue
        for begin, end, by_tenant in st.pumps:
            if begin <= t <= end:
                waits += [t - st.due[q]
                          for q in by_tenant.get(ev["args"]["tenant"], [])]
                break
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
