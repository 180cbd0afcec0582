"""Share of the HBM roofline in the arena commit: the bytes one batch's
commit must move (`bench.work.commit_bytes`) over its device time, the
arena_commit kernel per chain execution plus the capacity write per
execution, at the chip's peak bandwidth (moves ``rrr_sets_per_s``)."""
from bench import names, peaks, work


def read(run):
    t = run.trace
    n, w = t.runs(names.CHAIN), t.runs(names.COMMIT_WRITE)
    if not n or not w:
        return None
    per_batch = (t.op_seconds(names.CHAIN, names.COMMIT_KERNEL) / n
                 + t.program_seconds(names.COMMIT_WRITE) / w)
    cfg = run.cell.config
    least = work.commit_bytes(int(cfg["batch"]), int(cfg["graph"]["n"]))
    return work.share(least, per_batch,
                      peaks.peaks(run.device_kind)["hbm_bytes_per_s"])
