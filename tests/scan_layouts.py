"""The registry's drift-scan layouts, as the tests vary them.

The CPU suite has one device, so a layout varies the scan's chunking and
its decision margin, not the shard count: one chunk over the whole fleet,
small chunks whose tail chunk is zero-padded, and either a margin wide
enough that every row goes through the exact host re-check or, where a
test builds through ``FLConfig`` (which exposes no margin), one-row
chunks.
"""
import numpy as np

# keyword arguments of ``repro.stream.SummaryRegistry``
REGISTRY = {"whole": {}, "padded": {"chunk_rows": 8},
            "rechecked": {"decision_margin": np.inf}}

# ``FLConfig`` fields
FLCONFIG = {"whole": {}, "padded": {"shard_chunk_rows": 7},
            "rows": {"shard_chunk_rows": 1}}

# (layout, clustering) cells the 24-seed sweeps rotate through
MATRIX = [("whole", "kmeans"), ("padded", "kmeans"), ("rows", "kmeans"),
          ("padded", "online"), ("rows", "hierarchical"),
          ("padded", "minibatch"), ("whole", "online")]
