"""Device milliseconds per batch of the mesh exchange inside the fused
chain: the collective ops (all-gather, all-reduce, the all-reduces JAX
names ``psum``, and their start and done halves) of the chain
executions wholly inside the traced window, per execution, averaged
over the devices (moves ``rrr_sets_per_s``).  Under a vertex axis the
sparse traversal gathers its frontier along it every step."""
from bench import names

COLLECTIVE = r"^%(all-gather|all-reduce|psum)(-start|-done)?(\.\d+)?$"


def read(run):
    t = run.trace
    n = t.runs(names.CHAIN)
    return 1e3 * t.op_seconds(names.CHAIN, COLLECTIVE) / n if n else None
