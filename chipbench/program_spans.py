"""The program's own spans over the traced part of the window.

While a JAX profiler session is active, every ``repro.obs.span`` of the
program is written into the trace as an annotation and appended to the
process's profiled record, ``repro.obs.profiled()``, with its duration and
its arguments.  The harness starts and stops the session between rounds,
so the record holds whole rounds; they are counted as its
``select_devices`` spans (one a round).

``totals(obs)`` sums that record by span name: seconds, and each numeric
argument.  It gives nothing for a run without a trace, for a program
without ``repro.obs.profiled`` (one older than these spans), or when no
round was traced.
"""
from __future__ import annotations

ROUND_SPAN = "select_devices"


def totals(obs) -> dict | None:
    """{"rounds": traced rounds, "seconds": {name: s},
    "args": {(name, arg): sum}}, or None."""
    if obs.get("trace") is None:
        return None
    try:
        import repro.obs as program_obs
    except ImportError:
        return None
    profiled = getattr(program_obs, "profiled", None)
    if profiled is None:
        return None
    rounds, seconds, args = 0, {}, {}
    for ev in profiled().events:
        if ev.get("ph") != "X":
            continue
        name = ev["name"]
        rounds += name == ROUND_SPAN
        seconds[name] = seconds.get(name, 0.0) + ev["dur"] * 1e-6
        for key, value in (ev.get("args") or {}).items():
            if isinstance(value, (int, float)) and not isinstance(value, bool):
                args[(name, key)] = args.get((name, key), 0) + value
    if not rounds:
        return None
    return {"rounds": rounds, "seconds": seconds, "args": args}


def ms_per_round(obs, *names: str) -> float | None:
    """Milliseconds a traced round spends in spans ``names``, summed."""
    t = totals(obs)
    if t is None:
        return None
    return 1e3 * sum(t["seconds"].get(n, 0.0) for n in names) / t["rounds"]
