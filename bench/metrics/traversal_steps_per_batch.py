"""Sampler while-loop steps per batch: the ``steps`` the fused chain
returns, over the ``sample`` spans that start inside the traced window
(moves ``rrr_sets_per_s``).  Under IC a step is a BFS level over every
edge; under LT one move of every walk."""
from bench import spans


def read(run):
    ev = spans.in_window(run, "sample", "steps")
    return spans.ratio([e["args"]["steps"] for e in ev], [1] * len(ev))
