"""h2d_mb: megabytes (1e6 B) copied host to device per traced round: the
``bytes`` of every ``*/put`` span the program recorded under the profiler
(summary batches, drift-scan chunks, clustering input), over the traced
rounds (chipbench/program_spans.py)."""
from chipbench.program_spans import totals


def read(obs):
    t = totals(obs)
    if t is None:
        return None
    nbytes = sum(v for (name, key), v in t["args"].items()
                 if name.endswith("/put") and key == "bytes")
    return nbytes / t["rounds"] / 1e6
