"""Quickstart: influence maximization with EfficientIMM in ~20 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.core import imm, IMMConfig
from repro.graphs import rmat_graph

# a power-law social graph (synthetic stand-in for a SNAP graph)
graph = rmat_graph(n=2_000, m=16_000, seed=0)

# EfficientIMM defaults: fused counting (C3), selection with the adaptive
# counter update (C5), adaptive representation (C4)
result = imm(graph, IMMConfig(k=10, eps=0.5, model="IC", max_theta=4096))

print(f"graph: n={graph.n} m={graph.m}")
print(f"seeds: {list(result.seeds)}")
print(f"estimated influence: {result.influence:.1f} nodes "
      f"({100 * result.covered_frac:.1f}% RRR coverage)")
print(f"RRR sets sampled: {result.theta}  "
      f"(representation: {result.representation})")

# the Ripples-style baseline is one flag away (paper comparison)
baseline = imm(graph, IMMConfig(
    k=10, eps=0.5, model="IC", max_theta=4096,
    selection_method="decrement", adaptive_representation=False))
print(f"baseline influence (identical math): {baseline.influence:.1f}")
