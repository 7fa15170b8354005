"""Hierarchical fleet clustering (DESIGN.md §7).

Two-level clustering over row slices of the fleet (``hierarchy.py``):
each slice keeps its own online maintainer and a weighted global merge
turns the slice-local centroids into the fleet's, wired into the round
loop behind ``FLConfig(clustering="hierarchical")``.  The drift scan over
the ``fleet`` device mesh lives with the registry
(``repro.stream.SummaryRegistry``).
"""
from repro.shard.hierarchy import HierarchicalClusterMaintainer  # noqa: F401
