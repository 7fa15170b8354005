"""summary_fill_pct: the share of the summary engine's dispatched sample
slots that hold a real sample: 100 * sum ``filled`` / sum ``slots`` over
the traced ``summary/assemble`` spans (the rest is bucket and client-axis
padding).  Nothing when no batch was assembled."""
from chipbench.program_spans import totals


def read(obs):
    t = totals(obs)
    if t is None:
        return None
    slots = t["args"].get(("summary/assemble", "slots"), 0)
    if not slots:
        return None
    return 100.0 * t["args"].get(("summary/assemble", "filled"), 0) / slots
