from repro.core.batched_summary import (  # noqa: F401
    BatchedSummaryEngine,
    BatchStats,
    SummaryResult,
    batched_encoder_summary,
    batched_label_distribution,
    batched_per_label_mean,
    batched_pxy_histogram,
    bucket_size,
)
from repro.core.coreset import class_quotas, coreset_indices  # noqa: F401
from repro.core.dbscan import DBSCANResult, dbscan  # noqa: F401
from repro.core.kmeans import (  # noqa: F401
    KMeansResult,
    kmeans,
    minibatch_kmeans,
    pairwise_sq_dist,
    weighted_kmeans,
)
from repro.core.scheduler import (  # noqa: F401
    RefreshPolicy,
    batch_sym_kl,
    sym_kl,
)
from repro.core.selection import SelectionConfig, cluster_quotas, select_devices  # noqa: F401
from repro.core.summary import (  # noqa: F401
    encoder_summary,
    label_distribution,
    per_label_mean,
    pxy_histogram,
    quantize,
    summary_sizes,
)
