"""(De)serializers for the server's durable state (DESIGN.md §9).

Everything the round loop cannot rebuild deterministically from the
config is captured here: registry contents + write-version counters,
cluster-maintainer state, the driver RNG, model params, the history
trace, and — for the async server — the ``(round, stage, seq)`` event
queue, in-flight ingest batches, the snapshot store and the refresher's
drift-mass bookkeeping.  Each
``*_state`` function returns a plain nested dict of arrays/scalars fit
for ``checkpoint.save_state``; each ``restore_*`` is its exact inverse
against a freshly constructed object, so a resumed run re-executes the
remaining rounds bitwise-identically to the uninterrupted one.

Deliberately *not* captured (pure functions of the config, rebuilt by
``RoundContext.__init__``): encoder params (fixed PRNGKey), jitted
functions, the batched summary engine, and all per-round PRNG keys.
"""
from __future__ import annotations

import numpy as np

from repro.checkpoint.checkpoint import restore_like
from repro.server.events import Event, EventQueue, Stage
from repro.server.ingest import IngestQueue, SummaryBatch
from repro.server.refresher import ClusterRefresher, StalenessPolicy
from repro.server.snapshot import RegistrySnapshot, SnapshotStore, _frozen
from repro.shard.hierarchy import HierarchicalClusterMaintainer
from repro.stream.cluster import OnlineClusterMaintainer
from repro.stream.registry import SummaryRegistry


def _opt(a):
    """None-preserving array copy (lazily allocated matrices)."""
    return None if a is None else np.array(a, copy=True)


def _expect(cond: bool, what: str) -> None:
    if not cond:
        raise ValueError(f"checkpoint/runtime mismatch: {what}")


# ---------------------------------------------------------------------------
# the registry


def registry_state(reg: SummaryRegistry) -> dict:
    # "sharded" is the kind checkpoints carried for this registry when it
    # was one of three, so those states still restore
    return {
        "kind": "sharded",
        "num_clients": int(reg.num_clients),
        "refresh_count": int(reg.refresh_count),
        "version": int(reg.version),
        "last_refresh": reg.last_refresh.copy(),
        "has_summary": reg.has_summary.copy(),
        "summaries": _opt(reg.summaries),
        "label_dists": _opt(reg.label_dists),
        "scan_chunks": int(reg.scan_chunks),
        "rechecked_rows": int(reg.rechecked_rows),
    }


def restore_registry(reg: SummaryRegistry, st: dict) -> None:
    """Restore serialized registry state into a freshly built registry."""
    _expect(st["kind"] == "sharded",
            f"registry backend 'sharded' vs checkpointed {st['kind']!r}")
    _expect(int(st["num_clients"]) == reg.num_clients,
            f"registry num_clients {reg.num_clients} vs "
            f"checkpointed {st['num_clients']}")
    reg.refresh_count = int(st["refresh_count"])
    reg.version = int(st["version"])
    reg.last_refresh = np.asarray(st["last_refresh"], np.int64)
    reg.has_summary = np.asarray(st["has_summary"], bool)
    reg.summaries = _opt(st["summaries"])
    reg.label_dists = _opt(st["label_dists"])
    reg.scan_chunks = int(st["scan_chunks"])
    reg.rechecked_rows = int(st["rechecked_rows"])


# ---------------------------------------------------------------------------
# cluster maintainers (online / hierarchical)


def maintainer_state(m) -> dict | None:
    if m is None:
        return None
    if isinstance(m, HierarchicalClusterMaintainer):
        return {
            "kind": "hierarchical",
            "merges": int(m.merges),
            "last_merge_inertia": float(m.last_merge_inertia),
            "n": None if getattr(m, "_n", None) is None else int(m._n),
            "centroids": _opt(m.centroids),
            "assignment": _opt(m.assignment),
            "shards": [maintainer_state(s) for s in m.shards],
        }
    if isinstance(m, OnlineClusterMaintainer):
        return {
            "kind": "online",
            "centroids": _opt(m.centroids),
            "assignment": _opt(m.assignment),
            "dists": _opt(m.dists),
            "last_full_inertia": float(m.last_full_inertia),
            "full_fits": int(m.full_fits),
            "reseeds": int(m.reseeds),
            "refreshes": int(m._refreshes),
            "live": _opt(m._live),
        }
    raise TypeError(f"unknown maintainer type {type(m).__name__}")


def restore_maintainer(m, st: dict | None) -> None:
    if m is None or st is None:
        _expect(m is None and st is None,
                "maintainer present on exactly one side")
        return
    if isinstance(m, HierarchicalClusterMaintainer):
        _expect(st["kind"] == "hierarchical", "maintainer kind")
        _expect(len(st["shards"]) == len(m.shards),
                f"{len(m.shards)} shard maintainers vs "
                f"checkpointed {len(st['shards'])}")
        m.merges = int(st["merges"])
        m.last_merge_inertia = float(st["last_merge_inertia"])
        if st["n"] is not None:
            m._n = int(st["n"])
        m.centroids = _opt(st["centroids"])
        m.assignment = (None if st["assignment"] is None
                        else np.asarray(st["assignment"], np.int64))
        for shard, sub in zip(m.shards, st["shards"]):
            restore_maintainer(shard, sub)
        return
    _expect(st["kind"] == "online", "maintainer kind")
    m.centroids = _opt(st["centroids"])
    m.assignment = (None if st["assignment"] is None
                    else np.asarray(st["assignment"], np.int64))
    m.dists = _opt(st["dists"])
    m.last_full_inertia = float(st["last_full_inertia"])
    m.full_fits = int(st["full_fits"])
    m.reseeds = int(st["reseeds"])
    m._refreshes = int(st["refreshes"])
    m._live = None if st["live"] is None else np.asarray(st["live"], bool)


# ---------------------------------------------------------------------------
# snapshots + driver RNG


def snapshot_state(s: RegistrySnapshot) -> dict:
    return {"version": int(s.version), "round_idx": int(s.round_idx),
            "registry_version": int(s.registry_version),
            "assignment": np.asarray(s.assignment, np.int64),
            "num_clusters": int(s.num_clusters),
            "has_mask": np.asarray(s.has_mask, bool),
            "drift_mass": float(s.drift_mass)}


def restore_snapshot(st: dict) -> RegistrySnapshot:
    return RegistrySnapshot(
        version=int(st["version"]), round_idx=int(st["round_idx"]),
        registry_version=int(st["registry_version"]),
        assignment=_frozen(np.asarray(st["assignment"], np.int64)),
        num_clusters=int(st["num_clusters"]),
        has_mask=_frozen(np.asarray(st["has_mask"], bool)),
        drift_mass=float(st["drift_mass"]))


def rng_state(rs: np.random.RandomState) -> dict:
    algo, keys, pos, has_gauss, cached = rs.get_state()
    return {"algo": str(algo), "keys": np.asarray(keys, np.uint32),
            "pos": int(pos), "has_gauss": int(has_gauss),
            "cached": float(cached)}


def restore_rng(rs: np.random.RandomState, st: dict) -> None:
    rs.set_state((st["algo"], np.asarray(st["keys"], np.uint32),
                  int(st["pos"]), int(st["has_gauss"]),
                  float(st["cached"])))


# ---------------------------------------------------------------------------
# RoundContext (shared by both servers)


def context_state(ctx) -> dict:
    """Everything ``RoundContext`` accumulated up to a round boundary."""
    import jax  # deferred: keep module import light for pure-numpy callers

    return {
        "params": jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                               ctx.params),
        "rng": rng_state(ctx.rng),
        "registry": registry_state(ctx.registry),
        "maintainer": maintainer_state(ctx.maintainer),
        "assignment": np.asarray(ctx.assignment, np.int64),
        "num_clusters": int(ctx.num_clusters),
        # selection-policy training history (DESIGN.md §11): policies are
        # stateless, so this is the only cross-round selection memory —
        # restoring it replays history-aware selection bitwise
        "client_stats": ctx.client_stats.state(),
        "history": {k: v for k, v in ctx.history.items()},
        "sim_time": float(ctx.sim_time),
        "dropped_rounds": int(ctx.dropped_rounds),
        "recluster_count": int(ctx.recluster_count),
        "acc": float(ctx._acc),
    }


def restore_context(ctx, st: dict) -> None:
    """Restore a round-boundary ``context_state`` into a freshly built
    ``RoundContext`` (same data + config ⇒ same treedefs/backends)."""
    ctx.params = restore_like(ctx.params, st["params"])
    restore_rng(ctx.rng, st["rng"])
    restore_registry(ctx.registry, st["registry"])
    restore_maintainer(ctx.maintainer, st["maintainer"])
    ctx.assignment = np.asarray(st["assignment"], np.int64)
    ctx.num_clusters = int(st["num_clusters"])
    ctx.client_stats.load(st["client_stats"])
    _expect(set(st["history"]) == set(ctx.history),
            "history keys differ (checkpoint from another code version?)")
    ctx.history = {k: list(st["history"][k]) for k in ctx.history}
    ctx.sim_time = float(st["sim_time"])
    ctx.dropped_rounds = int(st["dropped_rounds"])
    ctx.recluster_count = int(st["recluster_count"])
    ctx._acc = float(st["acc"])


# ---------------------------------------------------------------------------
# async server machinery (event queue / ingest queue / snapshots / refresher)


def _event_state(ev: Event) -> dict:
    st = {"round": int(ev.round_idx), "stage": int(ev.stage),
          "seq": int(ev.seq), "kind": ev.kind}
    if isinstance(ev.payload, RegistrySnapshot):
        st["snapshot"] = snapshot_state(ev.payload)
    else:
        st["payload"] = None if ev.payload is None else int(ev.payload)
    return st


def _restore_event(st: dict) -> Event:
    payload = (restore_snapshot(st["snapshot"]) if "snapshot" in st
               else st["payload"])
    return Event(int(st["round"]), Stage(int(st["stage"])), int(st["seq"]),
                 st["kind"], payload)


def _batch_state(b: SummaryBatch) -> dict:
    ids = list(b.summaries)               # dict order == ingest order
    return {"compute_round": int(b.compute_round),
            "ready_round": int(b.ready_round),
            "retries": int(b.retries),
            "ids": np.asarray(ids, np.int64),
            "summaries": np.stack([b.summaries[c] for c in ids]),
            "fresh_rows": np.stack([b.fresh_rows[c] for c in ids])}


def _restore_batch(st: dict) -> SummaryBatch:
    ids = [int(c) for c in np.asarray(st["ids"], np.int64)]
    return SummaryBatch(
        compute_round=int(st["compute_round"]),
        ready_round=int(st["ready_round"]),
        summaries={c: st["summaries"][i] for i, c in enumerate(ids)},
        fresh_rows={c: st["fresh_rows"][i] for i, c in enumerate(ids)},
        retries=int(st["retries"]))


def server_state(queue: EventQueue, ingest_q: IngestQueue,
                 store: SnapshotStore, refresher: ClusterRefresher,
                 frontend=None, admission=None) -> dict:
    """The async server's machinery at an event boundary."""
    st = {
        "queue": {"seq": int(queue._seq), "processed": int(queue.processed),
                  "events": [_event_state(ev) for ev in queue.pending()]},
        "ingest": {"enqueued": int(ingest_q.enqueued_batches),
                   "drained": int(ingest_q.drained_batches),
                   "requeued": int(ingest_q.requeued_batches),
                   "batches": [_batch_state(b) for b in ingest_q.pending()]},
        "store": {"latest": snapshot_state(store.latest()),
                  "published": int(store.published)},
        "refresher": {
            "version": int(refresher._version),
            "pending_ids": np.asarray(sorted(refresher._pending_ids),
                                      np.int64),
            "slo_rebuild": bool(refresher._slo_rebuild),
            "blocking_builds": int(refresher.blocking_builds),
            "slo_builds": int(refresher.slo_builds),
            "background_builds": int(refresher.background_builds),
            "background_s": float(refresher.background_s),
            "skipped_empty": int(refresher.skipped_empty),
        },
    }
    # the check-in front end (DESIGN.md §12): arrival schedules are pure
    # per-round functions of (seed, round) and need no state; what must
    # survive a kill is the admission controller's deferred store (the
    # shed-with-retry-after summaries) and the front end's counters
    if frontend is not None:
        st["frontend"] = frontend.state()
    if admission is not None:
        st["admission"] = admission.state()
    return st


def restore_server(ctx, st: dict):
    """Rebuild the async server machinery from a ``server_state`` dict.
    Returns ``(queue, ingest_q, store, refresher, arrivals, frontend,
    admission)`` — the front-end triple is ``(None, None, None)`` when
    the config has no front end."""
    cfg = ctx.cfg
    queue = EventQueue()
    queue.load([_restore_event(e) for e in st["queue"]["events"]],
               seq=int(st["queue"]["seq"]),
               processed=int(st["queue"]["processed"]))
    ingest_q = IngestQueue(max_depth=cfg.ingest_max_depth)
    ingest_q.load([_restore_batch(b) for b in st["ingest"]["batches"]],
                  enqueued=int(st["ingest"]["enqueued"]),
                  drained=int(st["ingest"]["drained"]),
                  requeued=int(st["ingest"]["requeued"]))
    store = SnapshotStore(restore_snapshot(st["store"]["latest"]))
    store.published = int(st["store"]["published"])
    refresher = ClusterRefresher(
        ctx, store, mode=cfg.server_refresh,
        policy=StalenessPolicy(max_snapshot_age=cfg.snapshot_max_age,
                               drift_mass_trigger=cfg.drift_mass_trigger))
    rst = st["refresher"]
    refresher._version = int(rst["version"])
    refresher._pending_ids = {int(c) for c in
                              np.asarray(rst["pending_ids"], np.int64)}
    refresher._slo_rebuild = bool(rst.get("slo_rebuild", False))
    refresher.blocking_builds = int(rst["blocking_builds"])
    refresher.slo_builds = int(rst.get("slo_builds", 0))
    refresher.background_builds = int(rst["background_builds"])
    refresher.background_s = float(rst["background_s"])
    refresher.skipped_empty = int(rst["skipped_empty"])
    arrivals = frontend = admission = None
    if cfg.frontend != "none":
        from repro.server.async_rounds import build_frontend
        arrivals, frontend, admission = build_frontend(ctx)
        _expect("frontend" in st and "admission" in st,
                "front-end configured but checkpoint has no front-end "
                "state (checkpoint from a front-end-less run?)")
        frontend.load(st["frontend"])
        admission.load(st["admission"])
    return queue, ingest_q, store, refresher, arrivals, frontend, admission
