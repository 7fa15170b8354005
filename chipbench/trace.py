"""Reduction of a JAX profiler trace to device busy time, idle gaps and
each operation's device time.

The trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes.
Device planes are named ``/device:TPU:<i>``; their ``XLA Ops`` line holds
one event per operation run on the device.  The harness marks the traced
window with a host annotation (``WINDOW``) and each stage of a round with
``bench.<stage>``; both land on a host plane on the same clock.

* busy: the union of the op intervals of a device inside the window;
* each op's device time and count by its HLO instruction's text (the
  event's name: ``%pairwise_dist_kernel.22 = f32[11392,8] custom-call(...)``),
  which ``chipbench/roofline.py`` reads kernels from;
* idle gaps: the holes between busy intervals inside the window, each named
  by the harness stage the host was in at the gap's middle.
"""
from __future__ import annotations

import glob
import os

WINDOW = "bench.window"
STAGE_PREFIX = "bench."
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


class Event:
    __slots__ = ("name", "start", "end")

    def __init__(self, name: str, start: float, end: float):
        self.name, self.start, self.end = name, start, end


def _events(line) -> list:
    out = []
    for e in line.events:
        start = float(e.start_ns)
        out.append(Event(e.name, start, start + float(e.duration_ns)))
    return out


def read(trace_dir: str):
    """(device op events per device, host annotation events), in ns."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [e for line in plane.lines if line.name == OPS_LINE
                   for e in _events(line)]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e.name.startswith(STAGE_PREFIX))
    return devices, host


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window(host: list) -> tuple:
    spans = [e for e in host if e.name == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW} annotation in the trace")
    return min(e.start for e in spans), max(e.end for e in spans)


def reduce(devices: list, host: list) -> dict:
    """Busy and window seconds (busy averaged over devices), each op
    text's device seconds and count (summed over devices), the ten longest
    device ops by name and the ten longest idle gaps by host stage."""
    lo, hi = window(host)
    busy, texts, ops, gaps = [], {}, {}, []
    stages = sorted((e.start, e.end, e.name[len(STAGE_PREFIX):])
                    for e in host if e.name != WINDOW)
    for evs in devices:
        inside = [e for e in evs if e.end > lo and e.start < hi]
        merged = union(((e.start, e.end) for e in inside), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for e in inside:
            dur = min(e.end, hi) - max(e.start, lo)
            label = e.name.split("{", 1)[0].split("(", 1)[0][:120]
            ops[label] = ops.get(label, 0.0) + dur
            acc = texts.setdefault(e.name, [0.0, 0])
            acc[0] += dur * 1e-9
            acc[1] += 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, _stage_at(stages, (s + e) / 2)))
    n = max(len(devices), 1)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "op_texts": {k: tuple(v) for k, v in texts.items()},
        "device_ops": [[k, v * 1e-9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[st, g * 1e-9] for g, st in sorted(gaps, reverse=True)[:10]],
    }


def instruction(name: str) -> str:
    """The HLO instruction's own name in an op event's text:
    ``%pairwise_dist_kernel.22 = f32[...] custom-call(...)`` ->
    ``pairwise_dist_kernel.22``.  A Pallas kernel's instruction is named
    after its jitted wrapper, which is the kernel's stable name."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _stage_at(stages: list, t: float) -> str:
    name = "between_stages"
    for s, e, st in stages:
        if s <= t < e:
            name = st          # the innermost (latest-starting) span wins
        elif s > t:
            break
    return name
