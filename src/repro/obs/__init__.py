"""Unified telemetry subsystem (DESIGN.md §10): stage tracing, metric
registry, percentile histograms, Perfetto export.

One process-local **observer** — a ``(tracer, metrics)`` pair — is
either the disabled null object (the default: every hook is a no-op and
stays off the clocks the paper measures) or a live one installed with
``enable()`` / the ``observe()`` context manager:

    import repro.obs as obs

    with obs.observe(trace_path="trace.json",
                     metrics_path="metrics.jsonl") as ob:
        history = run_federated(data, cfg, scenario=sc)
    # trace.json loads in https://ui.perfetto.dev; metrics.jsonl has one
    # JSON record per counter/gauge/histogram (exact p50/p99/p999).

Instrumented code never holds the observer: it calls the module-level
``span`` / ``instant`` / ``metrics`` helpers, which read the *current*
observer at call time, so enabling observability is one call with no
plumbing.

While a JAX profiler session is active (``jax.profiler.start_trace`` /
``jax.profiler.trace``), every ``span`` is recorded whether or not an
observer is enabled: it enters a ``TraceAnnotation`` of its name, so it
lands on the host plane of the device trace, and it appends its event
(name, duration, args) to the enabled observer's tracer or, with none
enabled, to the process-level record that ``profiled()`` returns.
``device_put`` makes a host→device copy explicit and counts its bytes
on a span of its own.
"""
from __future__ import annotations

import contextlib

import jax
from jax.profiler import TraceAnnotation

from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    NULL_REGISTRY,
    NullMetricRegistry,
    StageMeters,
)
from repro.obs.trace import (  # noqa: F401
    LANE_BACKGROUND,
    LANE_CRITICAL,
    NULL_SPAN,
    NULL_TRACER,
    NullTracer,
    Span,
    Tracer,
)
from repro.obs.recorder import (  # noqa: F401
    FlightRecorder,
    NULL_RECORDER,
    NullFlightRecorder,
)
from repro.obs import export  # noqa: F401


class Observer:
    """A tracer + metric registry + flight recorder triple; ``enabled``
    reflects the tracer.  The recorder stays the null object unless the
    observer was enabled with flight recording (``observe(flight_path=
    ...)`` / ``observe(report_path=...)`` / ``enable(flight=True)``) —
    provenance records are opt-in on top of tracing."""

    __slots__ = ("tracer", "metrics", "flight")

    def __init__(self, tracer=None, metrics=None, flight=None):
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else NULL_REGISTRY
        self.flight = flight if flight is not None else NULL_RECORDER

    @property
    def enabled(self) -> bool:
        return self.tracer.enabled


DISABLED = Observer()
_current = DISABLED
# spans recorded under a profiler session with no observer enabled
_PROFILED = Tracer()


def current() -> Observer:
    """The process-local observer (the disabled null one by default)."""
    return _current


def enable(flight: bool = False,
           flight_path: str | None = None) -> Observer:
    """Install (and return) a fresh live observer.  ``flight=True`` (or
    a ``flight_path``) arms the selection-provenance flight recorder;
    with a path, records stream to it as JSONL."""
    global _current
    rec = None
    if flight or flight_path is not None:
        if flight_path is not None:
            import os
            os.makedirs(os.path.dirname(flight_path) or ".",
                        exist_ok=True)
        rec = FlightRecorder(flight_path)
    _current = Observer(Tracer(), MetricRegistry(), flight=rec)
    return _current


def disable() -> Observer:
    """Restore the disabled default; returns the observer that was live
    (its tracer/metrics stay readable for export)."""
    global _current
    was = _current
    _current = DISABLED
    return was


@contextlib.contextmanager
def observe(trace_path: str | None = None, metrics_path: str | None = None,
            flight_path: str | None = None,
            report_path: str | None = None, flight: bool = False):
    """Scoped observability: enable on entry; on exit restore the
    disabled default and write the requested artifacts (Chrome trace
    JSON for Perfetto, metrics JSONL, flight-record JSONL, and the
    self-contained HTML fleet dashboard).  ``flight_path`` or
    ``report_path`` (which needs the records) arms the flight
    recorder."""
    ob = enable(flight=flight or report_path is not None,
                flight_path=flight_path)
    try:
        yield ob
    finally:
        disable()
        ob.flight.close()
        if trace_path is not None:
            export.write_trace(ob.tracer, trace_path)
        if metrics_path is not None:
            export.write_metrics_jsonl(ob.metrics, metrics_path)
        if report_path is not None:
            from repro.obs import report
            report.write_report(report_path, metrics=ob.metrics,
                                flight=list(ob.flight.records))


# ---------------------------------------------------------------------------
# hook helpers — read the current observer at call time


def span(name: str, cat: str = "server", lane: int = LANE_CRITICAL,
         **args):
    """A span on the current tracer; under a profiler session also an
    annotation of the device trace, recorded by ``profiled()`` when no
    observer is enabled.  The shared no-op when neither is on."""
    profiling = TraceAnnotation.is_enabled()
    if _current.enabled:
        return _current.tracer.span(name, cat=cat, lane=lane,
                                    profile=profiling, **args)
    if profiling:
        return _PROFILED.span(name, cat=cat, lane=lane, profile=True, **args)
    return NULL_SPAN


def profiled() -> Tracer:
    """The spans recorded under profiler sessions while no observer was
    enabled, in this process (``profiled().events``)."""
    return _PROFILED


def device_put(name: str, arrays: tuple, sharding=None) -> tuple:
    """Copy host ``arrays`` to the device (``sharding``: as
    ``jax.device_put``) inside span ``name``, whose ``bytes`` arg is their
    total size.  A recording span waits for the copy, so its duration is
    the copy's; otherwise nothing blocks here (whatever reads the arrays
    waits for them)."""
    with span(name, bytes=sum(int(a.nbytes) for a in arrays)) as sp:
        out = jax.device_put(tuple(arrays), sharding)
        if sp is not NULL_SPAN:
            jax.block_until_ready(out)
    return out


def instant(name: str, cat: str = "server", lane: int = LANE_CRITICAL,
            **args) -> None:
    _current.tracer.instant(name, cat=cat, lane=lane, **args)


def counter_sample(name: str, value: float) -> None:
    _current.tracer.counter(name, value)


def metrics() -> MetricRegistry:
    """The current metric registry (the no-op null one when disabled)."""
    return _current.metrics


def recorder():
    """The current flight recorder (the no-op null one unless the
    observer was armed with flight recording).  Hook sites check
    ``recorder().enabled`` before building any record fields."""
    return _current.flight


def enabled() -> bool:
    return _current.enabled
