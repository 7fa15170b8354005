"""recluster_ms: mean milliseconds per window round of the harness's host span
around the recluster stage (see chipbench/server.py)."""


def read(obs):
    if not obs["rounds"]:
        return None
    i = obs["stages"].index("recluster")
    return float(obs["spans"][:, i].mean() * 1e3)
