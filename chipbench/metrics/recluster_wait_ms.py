"""recluster_wait_ms: milliseconds per traced round the clustering refresh
waits on K-means or the online assign until its result is on the host
(``recluster/fit``)."""
from chipbench.program_spans import ms_per_round


def read(obs):
    return ms_per_round(obs, "recluster/fit")
