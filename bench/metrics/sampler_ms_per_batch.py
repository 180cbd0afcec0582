"""Device milliseconds per batch of the sampler: the fused chain's
program less its arena_commit kernel, over the chain executions wholly
inside the traced window (moves ``rrr_sets_per_s``)."""
from bench import names


def read(run):
    t = run.trace
    n = t.runs(names.CHAIN)
    if not n:
        return None
    s = (t.program_seconds(names.CHAIN)
         - t.op_seconds(names.CHAIN, names.COMMIT_KERNEL))
    return 1e3 * s / n
