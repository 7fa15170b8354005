"""Streaming sketch summaries & online clustering (DESIGN.md §5).

Fleet-scale server-side state: mergeable count-min label sketches and
random-projection feature sketches (``sketch.py``), the fleet's summary
registry, whose drift scan runs chunked on a device mesh (``registry.py``),
and an online cluster maintainer that keeps assignments fresh with O(drifted)
work per round (``cluster.py``).
"""
from repro.stream.cluster import (  # noqa: F401
    OnlineClusterMaintainer,
    OnlinePolicy,
)
from repro.stream.registry import SummaryRegistry  # noqa: F401
from repro.stream.sketch import (  # noqa: F401
    FleetSketches,
    SketchSpec,
    cm_estimate,
    cm_label_dist,
    cm_merge,
    cm_update_batch,
    rp_matrix,
    rp_update_batch,
)
