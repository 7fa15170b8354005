"""summary_wait_ms: milliseconds per traced round the summary engine waits
on its executables and reads their results back (``summary/execute``)."""
from chipbench.program_spans import ms_per_round


def read(obs):
    return ms_per_round(obs, "summary/execute")
