"""summary_host_ms: milliseconds per traced round the summary engine spends
on the host before a copy: loading each chunk's client data
(``summary/load``) and padding it into the batch (``summary/assemble``)."""
from chipbench.program_spans import ms_per_round


def read(obs):
    return ms_per_round(obs, "summary/load", "summary/assemble")
