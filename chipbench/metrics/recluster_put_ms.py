"""recluster_put_ms: milliseconds per traced round of the clustering
refresh's host-to-device copies (``recluster/put``)."""
from chipbench.program_spans import ms_per_round


def read(obs):
    return ms_per_round(obs, "recluster/put")
