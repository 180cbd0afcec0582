import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def tiny():
    """Overrides that run a cell at a size a CPU test holds: every other
    key is the configuration's own."""
    return {"graph": {"name": "tiny", "n": 5000, "undirected_edges": 6000,
                      "directed": False, "seed": 0},
            "theta_per_arena": 1024, "batch": 64, "k": 5}
