"""The system under test, driven the way the sync server drives it.

The benchmark's contract with the program is the set of ``RoundContext``
stage methods that ``repro.fl.rounds._drive_sync`` calls, in its order and
without ``train_and_log``:

    begin_round -> scan_stale -> compute_summaries (or the uploads) ->
    ingest -> sync_recluster_due / sync_drifted / recluster_now -> select

plus ``ctx.engine.stats`` (``BatchStats``), ``ctx.registry`` and
``ctx.maintainer`` for counters and the correctness check.  A rename of
these needs a shim in the program or a benchmark change.

Each stage ends in host numpy, so a host span around a stage call covers
the work it blocks on.  ``Recorder`` keeps what the rounds produced for the
check after the window; it stores references and small copies only.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from chipbench import found

STAGES = ("begin", "scan", "summary", "ingest", "recluster", "select")


class Plans:
    """The scenario surface ``RoundContext`` reads: one ``RoundPlan`` per
    round, built in set-up from the traffic."""

    def __init__(self, traffic):
        from repro.sim.scenario import RoundPlan
        n = traffic.speeds.shape[0]
        empty = np.zeros(0, np.int64)
        ones, zeros = np.ones(n, bool), np.zeros(n)
        self.num_clients = n
        self.plans = [RoundPlan(
            round_idx=r, active=ones, available=traffic.available[r],
            speeds=traffic.speeds, drift=traffic.drift(r), joined=empty,
            departed=empty, fail_u=zeros, upload_cost=zeros, deadline=None,
            dropout_prob=0.0) for r in range(traffic.rounds)]

    def round_plan(self, rnd: int):
        return self.plans[rnd]

    def note_selected(self, ids) -> None:
        pass


def fl_config(cfg: dict, seed: int):
    """The program's ``FLConfig`` from the configuration's ``server``
    group, taken whole."""
    from repro.fl.rounds import FLConfig
    return FLConfig(**cfg["server"], seed=seed % 2 ** 31)


@dataclasses.dataclass
class Recorder:
    """What each round produced, for the check after the window."""
    stale: dict = dataclasses.field(default_factory=dict)
    selected: dict = dataclasses.field(default_factory=dict)
    summaries: dict = dataclasses.field(default_factory=dict)
    assignment: dict = dataclasses.field(default_factory=dict)
    centroids: dict = dataclasses.field(default_factory=dict)
    failed: list = dataclasses.field(default_factory=list)


class Server:
    """One ``RoundContext`` and the traffic that feeds it."""

    def __init__(self, cfg: dict, mix: dict, seed: int, root=found.HERE):
        from repro.fl.rounds import RoundContext
        self.cfg = cfg
        self.computed = cfg["summaries"] == "computed"
        self.traffic = found.module("traffic", mix["kind"], root).make(
            cfg, mix, seed)
        self.scenario = Plans(self.traffic)
        self.ctx = RoundContext(self.traffic.data, fl_config(cfg, seed),
                                self.scenario)
        self.rec = Recorder()
        self.next_round = 0

    def round(self, spans: np.ndarray | None = None,
              annotate=lambda stage: contextlib.nullcontext()) -> None:
        """Run the next round's stages; ``spans[i]`` gets stage i's seconds
        and ``annotate(stage)`` wraps each stage's call."""
        ctx, rnd = self.ctx, self.next_round
        if rnd >= self.traffic.rounds:
            raise RuntimeError(f"the traffic holds {self.traffic.rounds} "
                               "rounds; raise plan_rounds")
        self.next_round += 1
        t = [time.perf_counter()]

        def mark():
            t.append(time.perf_counter())

        with annotate("begin"):
            plan, fresh = ctx.begin_round(rnd)
        mark()
        with annotate("scan"):
            stale = ctx.scan_stale(rnd, plan, fresh)
        mark()
        with annotate("summary"):
            if self.computed:
                summaries, _times, _wall = ctx.compute_summaries(
                    rnd, stale, plan.drift)
        mark()
        with annotate("ingest"):
            if not self.computed:
                summaries = self.traffic.uploads_of(rnd, stale)
            ctx.ingest(rnd, summaries, fresh)
        mark()
        with annotate("recluster"):
            if ctx.sync_recluster_due(rnd, plan, stale):
                ctx.recluster_now(rnd, plan.active,
                                  ctx.sync_drifted(plan, stale))
        mark()
        with annotate("select"):
            sel = ctx.select(rnd, plan, fresh)
        mark()
        if spans is not None:
            spans[:] = np.diff(t)
        rec = self.rec
        rec.stale[rnd] = stale
        rec.selected[rnd] = sel
        rec.assignment[rnd] = ctx.assignment.copy()
        if self.computed:
            rec.summaries[rnd] = summaries
        if ctx.maintainer is not None and ctx.maintainer.centroids is not None:
            rec.centroids[rnd] = ctx.maintainer.centroids.copy()
        if len(sel) != self.cfg["server"]["clients_per_round"]:
            rec.failed.append(rnd)
