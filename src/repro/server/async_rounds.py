"""Event-driven async round driver (DESIGN.md §8).

``run_federated(..., server="async")`` lands here: the same per-round
stage methods the sync loop runs (``fl.rounds.RoundContext``), but
orchestrated through the deterministic event engine so summary ingest,
drift scanning and clustering refresh are *pipelined* instead of
serialized onto the round-critical path:

  round r:  MEMBERSHIP → PUBLISH → DRAIN → SCAN → COMPUTE → INGEST
            → REFRESH → SELECT → TRAIN

  * DRAIN lands summary batches whose ingest latency elapsed (computed in
    earlier rounds); INGEST lands zero-latency batches from this round's
    COMPUTE — both through the shared O(M) registry scatter.
  * REFRESH is the ``ClusterRefresher`` policy step: background rebuilds
    travel forward as PUBLISH events into round r+1, so their cost
    overlaps round r's training; blocking rebuilds (staleness bound hit,
    or ``server_refresh="sync"``) are charged to the critical path.
  * SELECT never touches the live registry: it reads the freshest
    complete ``RegistrySnapshot`` — a consistent (assignment, has_mask,
    num_clusters) view — while ingest may already be writing the next
    registry version.

Critical-path accounting: ``overhead_critical_s`` records, per round, the
server-side wall seconds selection actually had to wait for — everything
(scan + cluster + drain) under ``server_refresh="sync"`` (which is the
sync loop's charge by definition), only blocking rebuilds under
``server_refresh="staleness"``.  ``benchmarks/bench_server.py`` measures
the resulting ≥2× critical-path reduction at fleet scale.

With ``ingest_delay_rounds=0`` and ``server_refresh="sync"`` the event
schedule degenerates to exactly the sync stage sequence with exactly the
same arguments — ``tests/test_server.py`` and the differential harness
pin the resulting traces bitwise across seeds, churn scenarios, and all
registry × clustering backends.
"""
from __future__ import annotations

import numpy as np

import repro.obs as obs
from repro.checkpoint.server_state import (
    context_state, restore_server, server_state,
)
from repro.server.admission import AdmissionController
from repro.server.arrivals import ArrivalConfig, ArrivalProcess
from repro.server.events import EventQueue, Stage
from repro.server.frontend import CheckinFrontend
from repro.server.ingest import IngestQueue
from repro.server.refresher import ClusterRefresher, StalenessPolicy
from repro.server.snapshot import SnapshotStore, capture


def build_frontend(ctx):
    """(arrivals, frontend, admission) for ``cfg.frontend != "none"`` —
    shared by the fresh-start and checkpoint-restore paths so both build
    identically configured machinery."""
    cfg = ctx.cfg
    arrivals = ArrivalProcess(ArrivalConfig(
        rate=cfg.checkins_per_client, window_s=cfg.checkin_window_s,
        seed=cfg.seed))
    frontend = CheckinFrontend(
        workers=cfg.frontend_workers,
        service_s=cfg.frontend_service_us * 1e-6,
        slo_p99_s=cfg.frontend_slo_p99_s, metrics=ctx.metrics)
    admission = AdmissionController(
        max_depth=cfg.ingest_max_depth,
        retry_after=cfg.admission_retry_after, metrics=ctx.metrics)
    return arrivals, frontend, admission


def drive_async(ctx, session=None, faults=None, start_round: int = 0,
                restored: dict | None = None) -> dict:
    """Run one federated training under the async selection server.

    ``session`` (a ``checkpoint.DurableSession``) appends every committed
    event to the durable log and captures checkpoints at TRAIN
    boundaries — where the per-round pipeline state dict is empty and the
    next round's events are already queued, so the event queue + ingest
    queue + snapshot store + refresher serialize completely.  ``faults``
    injects crashes at event boundaries (the event stays queued — it was
    never committed) and seeded ingest-batch loss with bounded
    retry/backoff.  ``restored`` (with ``start_round``) is the
    ``server_state`` from a checkpoint: the queue resumes mid-pipeline
    and re-executes the crashed round deterministically.
    """
    cfg = ctx.cfg
    if restored is not None:
        queue, ingest_q, store, refresher, arrivals, frontend, admission = \
            restore_server(ctx, restored)
    else:
        queue = EventQueue()
        ingest_q = IngestQueue(max_depth=cfg.ingest_max_depth)
        # seed snapshot: the pre-training server state (no summaries, the
        # all-zeros assignment the sync loop also starts from)
        store = SnapshotStore(capture(0, -1, ctx.registry, ctx.assignment,
                                      ctx.num_clusters))
        refresher = ClusterRefresher(
            ctx, store, mode=cfg.server_refresh,
            policy=StalenessPolicy(max_snapshot_age=cfg.snapshot_max_age,
                                   drift_mass_trigger=cfg.drift_mass_trigger))
        arrivals = frontend = admission = None
        if cfg.frontend != "none":
            arrivals, frontend, admission = build_frontend(ctx)
    state: dict[int, dict] = {}   # per-round pipeline state, keyed by round
    # per-client tier labels for the front end's per-tier latency
    # dimension (fixed for a scenario's lifetime, so resolved once)
    tier_names = getattr(ctx.scenario, "tier_names", None)

    def schedule_round(rnd: int) -> None:
        obs.counter_sample("event_queue_depth", len(queue))
        obs.counter_sample("ingest_in_flight", len(ingest_q))
        rec = obs.recorder()
        if rec.enabled:
            # queue-depth track for the fleet dashboard — event counts
            # only, so the record is deterministic per seed
            rec.record("queue", round=rnd, events=len(queue),
                       in_flight=len(ingest_q))
        queue.push(rnd, Stage.MEMBERSHIP, "membership", rnd)
        queue.push(rnd, Stage.DRAIN, "drain", rnd)
        queue.push(rnd, Stage.SCAN, "scan", rnd)
        queue.push(rnd, Stage.COMPUTE, "compute", rnd)
        queue.push(rnd, Stage.REFRESH, "refresh", rnd)
        if frontend is not None:
            queue.push(rnd, Stage.CHECKIN, "checkin", rnd)
        queue.push(rnd, Stage.SELECT, "select", rnd)
        queue.push(rnd, Stage.TRAIN, "train", rnd)

    def on_membership(ev) -> None:
        rnd = ev.payload
        plan, fresh = ctx.begin_round(rnd)
        state[rnd] = {"plan": plan, "fresh": fresh, "stale": [],
                      "times": {}, "wall": 0.0, "blocking": 0.0,
                      "shed": [], "checkin": None}
        refresher.note_churn(plan)
        if admission is not None:
            admission.evict(plan.departed)

    def on_publish(ev) -> None:
        store.publish(ev.payload)

    def on_drain(ev) -> None:
        for batch in ingest_q.pop_ready(ev.payload):
            if faults is not None and faults.batch_lost():
                # injected transport loss: redeliver with backoff until
                # the retry budget runs out, then drop — the clients fall
                # out of the in-flight dedup set and the next drift scan
                # re-issues them (degradation, not failure)
                faults.lost_batches += 1
                obs.instant("ingest/batch_lost", cat="ingest",
                            round=ev.payload, retries=batch.retries)
                ctx.metrics.counter("server/ingest/lost_batches").inc()
                if batch.retries < faults.plan.max_retries:
                    redo = ingest_q.requeue(
                        batch,
                        ev.payload + faults.plan.retry_backoff_rounds)
                    faults.retried_batches += 1
                    if redo.ready_round < cfg.rounds:
                        queue.push(redo.ready_round, Stage.DRAIN, "drain",
                                   redo.ready_round)
                else:
                    faults.dropped_batches += 1
                continue
            ctx.ingest(batch.compute_round, batch.summaries,
                       batch.fresh_rows)
            refresher.note_ingested(batch.summaries)

    def on_scan(ev) -> None:
        rnd = ev.payload
        st = state[rnd]
        exclude = ingest_q.in_flight()
        if admission is not None:
            # shed-but-pending summaries are also in flight: the client
            # holds a computed summary it will re-offer after retry-after
            exclude = exclude | admission.in_flight()
        st["stale"] = ctx.scan_stale(rnd, st["plan"], st["fresh"],
                                     exclude=exclude)

    def _push_batch(rnd: int, batch) -> None:
        if batch is not None and batch.ready_round < cfg.rounds:
            # wake the drain when the latency elapses; zero-latency
            # batches land this round, after COMPUTE but before REFRESH.
            # Batches that would land after the final round stay queued
            # (still visible to in-flight dedup) but never scatter —
            # nothing reads the registry after the last selection
            stage = Stage.INGEST if batch.ready_round == rnd else Stage.DRAIN
            queue.push(batch.ready_round, stage, "drain", batch.ready_round)

    def on_compute(ev) -> None:
        rnd = ev.payload
        st = state[rnd]
        summaries, times, wall = ctx.compute_summaries(
            rnd, st["stale"], st["plan"].drift)
        st["times"], st["wall"] = times, wall
        if admission is None:
            _push_batch(rnd, ingest_q.enqueue(rnd, cfg.ingest_delay_rounds,
                                              summaries, st["fresh"]))
            return
        # admission control (DESIGN.md §12): drifted clients — stale by
        # KL while their row is still young — ride the priority lane;
        # age-refreshes are shed first under backpressure
        last = np.asarray(ctx.registry.last_refresh, np.int64)
        priority = {c for c in summaries
                    if last[c] >= 0 and rnd - int(last[c])
                    < cfg.refresh_max_age}
        decision = admission.plan(rnd, ingest_q, summaries, st["fresh"],
                                  priority_ids=priority)
        st["shed"] = decision.shed
        for compute_round, summ, rows in decision.batches:
            _push_batch(rnd, ingest_q.enqueue(
                compute_round, cfg.ingest_delay_rounds, summ, rows,
                ready_round=rnd + cfg.ingest_delay_rounds))

    def on_refresh(ev) -> None:
        rnd = ev.payload
        st = state[rnd]
        blocking, background = refresher.step(rnd, st["plan"], st["stale"])
        st["blocking"] = blocking
        if background is not None and rnd + 1 < cfg.rounds:
            queue.push(rnd + 1, Stage.PUBLISH, "publish", background)

    def on_checkin(ev) -> None:
        rnd = ev.payload
        st = state[rnd]
        sched = arrivals.schedule(rnd, st["plan"].available)
        # the stall is *modeled*, gated on the (deterministic) decision
        # that this round rebuilt blocking — never the measured wall
        # seconds, which would leak JIT/hardware jitter into the pinned
        # checkin_p99_s trace
        stall = (cfg.checkin_stall_model_s if st["blocking"] > 0.0
                 else 0.0)
        report = frontend.serve(sched, store.latest(), st["plan"].active,
                                stall_s=stall, tiers=tier_names)
        st["checkin"] = report
        if report.slo_breached:
            refresher.request_early_rebuild()

    def on_select(ev) -> None:
        rnd = ev.payload
        st = state[rnd]
        snap = store.latest()
        st["snap"] = snap
        st["sel"] = ctx.select(rnd, st["plan"], st["fresh"],
                               assignment=snap.assignment,
                               num_clusters=snap.num_clusters,
                               has_mask=snap.has_mask)

    def on_train(ev) -> None:
        rnd = ev.payload
        st = state.pop(rnd)
        critical = (ctx.round_overhead_s() if cfg.server_refresh == "sync"
                    else st["blocking"])
        ctx.train_and_log(rnd, st["plan"], st["fresh"], st["sel"],
                          st["times"], st["wall"], critical_s=critical,
                          snapshot_version=st["snap"].version,
                          snapshot_age=st["snap"].age(rnd))
        if frontend is not None:
            rep = st["checkin"]
            h = ctx.history
            h["checkins"].append(0 if rep is None else rep.checkins)
            h["checkins_shed"].append(len(st["shed"]))
            h["checkin_p99_s"].append(0.0 if rep is None else rep.p99_s)
        if rnd + 1 < cfg.rounds:
            schedule_round(rnd + 1)

    if restored is None:
        schedule_round(start_round)

    before = None
    if faults is not None:
        def before(ev) -> None:
            faults.maybe_crash(ev.round_idx, ev.stage)

    after = None
    if session is not None:
        def after(ev) -> None:
            session.log_event(ev.round_idx, int(ev.stage), ev.seq, ev.kind)
            if ev.kind != "train":
                return
            rnd = ev.payload
            session.commit_round(
                rnd, cfg.rounds, ctx.history["selected"][-1],
                registry_version=ctx.registry.version,
                snapshot_version=store.version,
                state_fn=lambda: {
                    "round": rnd,
                    "context": context_state(ctx),
                    "server": server_state(queue, ingest_q, store,
                                           refresher, frontend=frontend,
                                           admission=admission)})

    queue.run({"membership": on_membership, "publish": on_publish,
               "drain": on_drain, "scan": on_scan, "compute": on_compute,
               "refresh": on_refresh, "checkin": on_checkin,
               "select": on_select,
               "train": on_train}, before=before, after=after)

    history = ctx.finish()
    history["server"] = {
        "mode": "async", "refresh": cfg.server_refresh,
        "ingest_delay_rounds": cfg.ingest_delay_rounds,
        "events": queue.processed,
        "snapshots_published": store.published,
        "ingest_batches": ingest_q.enqueued_batches,
        "blocking_refreshes": refresher.blocking_builds,
        "background_refreshes": refresher.background_builds,
        "background_s": refresher.background_s,
    }
    if frontend is not None:
        history["server"]["frontend"] = {
            "checkins": frontend.total_checkins,
            "slo_breaches": frontend.slo_breaches,
            "slo_builds": refresher.slo_builds,
            "admitted": admission.admitted_total,
            "shed": admission.shed_total,
            "deferred_served": admission.deferred_served_total,
            "still_deferred": len(admission.in_flight()),
        }
    if faults is not None:
        history["server"]["faults"] = faults.counters()
    return history
