"""recluster_host_ms: milliseconds per traced round the clustering refresh
spends gathering its input on the host (``recluster/gather``: the summary
rows, their float32 cast and, online, the padded assign batch)."""
from chipbench.program_spans import ms_per_round


def read(obs):
    return ms_per_round(obs, "recluster/gather")
