"""summary_put_ms: milliseconds per traced round of the summary engine's
host-to-device copies of its padded batches (``summary/put``)."""
from chipbench.program_spans import ms_per_round


def read(obs):
    return ms_per_round(obs, "summary/put")
