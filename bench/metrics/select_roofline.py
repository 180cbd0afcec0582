"""Share of the HBM roofline in greedy selection: theta x n arena bytes,
the least any greedy must read, over the device time of one selection
program, at the chip's peak bandwidth (moves ``select_s``)."""
from bench import names, peaks, work


def read(run):
    t = run.trace
    n = t.runs(names.SELECT)
    if not n:
        return None
    cfg = run.cell.config
    least = work.select_bytes(int(cfg["theta_per_arena"]),
                              int(cfg["graph"]["n"]))
    return work.share(least, t.program_seconds(names.SELECT) / n,
                      peaks.peaks(run.device_kind)["hbm_bytes_per_s"])
