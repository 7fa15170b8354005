"""Span tracing with Chrome-trace-event export (DESIGN.md §10).

``Tracer.span("scan", round=r)`` is a context manager that records one
*complete* Chrome trace event (``"ph": "X"``) when the block exits:
name, category, microsecond start/duration relative to the tracer's
epoch, and the keyword arguments as Perfetto ``args``.  Spans nest by
time containment on a *lane* (a Chrome ``tid``): everything that runs
on the round-critical path shares the default lane, background work
(off-path clustering rebuilds) gets its own, so the resulting trace —
``chrome_trace()`` / ``obs.export.write_trace`` — loads directly in
Perfetto / ``chrome://tracing`` with the critical path and the
background lane as two labelled rows per process.

``instant(name, ...)`` marks a point event (``"ph": "i"``), used for
atomic acts like a snapshot publish or an ingest enqueue; ``counter``
emits a Chrome counter sample (``"ph": "C"``) so slowly-evolving values
(snapshot age, queue depth) render as a chart track.

The **disabled** tracer is ``NULL_TRACER``: ``span()`` hands back one
shared no-op context manager, every other method returns immediately,
and ``enabled`` is ``False`` so hot loops can skip even the call.  An
*enabled* tracer's span costs two clock reads and one dict append —
``benchmarks/bench_obs.py`` measures both and asserts the end-to-end
overhead budget (<2 % of the sync critical path).

A span opened with ``profile=True`` (``repro.obs.span`` does so while a
JAX profiler session is active) also enters a
``jax.profiler.TraceAnnotation`` of its name, so the same span lands on
the host plane of the profiler's device trace, on that trace's clock.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

# Chrome tid values for the two execution lanes (names published via
# thread-metadata events so Perfetto labels the rows).
LANE_CRITICAL = 1
LANE_BACKGROUND = 2
LANE_NAMES = {LANE_CRITICAL: "round-critical", LANE_BACKGROUND: "background"}


class Span:
    """One in-flight span; records its complete event on ``__exit__``."""

    __slots__ = ("_tracer", "name", "cat", "tid", "args", "_start", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, tid: int,
                 args: dict | None, profile: bool = False):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.tid = tid
        self.args = args
        self._start = 0.0
        self._ann = TraceAnnotation(name) if profile else None

    def __enter__(self) -> "Span":
        if self._ann is not None:
            self._ann.__enter__()
        self._start = self._tracer._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        tr = self._tracer
        end = tr._clock()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        ev = {"name": self.name, "cat": self.cat, "ph": "X",
              "ts": (self._start - tr._t0) * 1e6,
              "dur": (end - self._start) * 1e6,
              "pid": tr.pid, "tid": self.tid}
        if self.args:
            ev["args"] = self.args
        tr._events.append(ev)

    def annotate(self, **kw) -> None:
        """Attach/extend args after entry (e.g. a result count that is
        only known once the work ran)."""
        if self.args is None:
            self.args = dict(kw)
        else:
            self.args.update(kw)


class _NullSpan:
    """Shared no-op span for the disabled tracer."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass

    def annotate(self, **kw) -> None:
        pass


NULL_SPAN = _NullSpan()


class Tracer:
    """Chrome-trace-event recorder.  One instance per observed process;
    per-shard tracers can be ``absorb``-ed into one timeline because all
    timestamps are relative to each tracer's own epoch."""

    enabled = True

    def __init__(self, pid: int = 1, clock=time.perf_counter):
        self.pid = int(pid)
        self._clock = clock
        self._t0 = clock()
        self._events: list[dict] = []

    # -- recording -----------------------------------------------------

    def span(self, name: str, cat: str = "server",
             lane: int = LANE_CRITICAL, profile: bool = False,
             **args) -> Span:
        return Span(self, name, cat, lane, args or None, profile)

    def instant(self, name: str, cat: str = "server",
                lane: int = LANE_CRITICAL, **args) -> None:
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": (self._clock() - self._t0) * 1e6,
              "pid": self.pid, "tid": lane}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def counter(self, name: str, value: float, cat: str = "server") -> None:
        """One sample of a Chrome counter track (renders as a chart)."""
        self._events.append(
            {"name": name, "cat": cat, "ph": "C",
             "ts": (self._clock() - self._t0) * 1e6,
             "pid": self.pid, "tid": 0,
             "args": {"value": float(value)}})

    # -- reading / export ----------------------------------------------

    @property
    def events(self) -> list[dict]:
        return self._events

    def span_names(self) -> set:
        return {ev["name"] for ev in self._events if ev["ph"] == "X"}

    def chrome_trace(self) -> dict:
        """The Perfetto-loadable JSON object: recorded events plus the
        thread-name metadata that labels the lanes."""
        meta = [{"name": "process_name", "ph": "M", "pid": self.pid,
                 "tid": 0, "args": {"name": "repro-server"}}]
        for tid, lane_name in LANE_NAMES.items():
            meta.append({"name": "thread_name", "ph": "M", "pid": self.pid,
                         "tid": tid, "args": {"name": lane_name}})
        return {"traceEvents": meta + list(self._events),
                "displayTimeUnit": "ms"}

    def absorb(self, other: "Tracer") -> None:
        """Fold another tracer's events into this timeline (events keep
        their own pid, so per-shard tracers appear as separate process
        rows in Perfetto)."""
        self._events.extend(other._events)


class NullTracer:
    """Disabled tracer: a no-op object with the same surface."""

    enabled = False
    pid = 0

    def span(self, name: str, cat: str = "server",
             lane: int = LANE_CRITICAL, profile: bool = False,
             **args) -> _NullSpan:
        return NULL_SPAN

    def instant(self, name: str, cat: str = "server",
                lane: int = LANE_CRITICAL, **args) -> None:
        pass

    def counter(self, name: str, value: float, cat: str = "server") -> None:
        pass

    @property
    def events(self) -> list[dict]:
        return []

    def span_names(self) -> set:
        return set()

    def chrome_trace(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def absorb(self, other) -> None:
        pass


NULL_TRACER = NullTracer()
