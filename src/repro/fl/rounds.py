"""The federated round loop — HACCS workflow (paper Fig. 1) with the paper's
efficient summaries as a first-class feature, driven by a fleet
``Scenario`` (DESIGN.md §6) and executed by one of two *servers*
(DESIGN.md §8):

  * ``server="sync"`` — the classic sequential loop: refresh → drift-scan
    → cluster → select → train, every stage on the round-critical path;
  * ``server="async"`` — the event-driven pipelined selection server
    (``repro.server``): summary ingest, drift scanning and clustering
    refresh run off the critical path against versioned registry
    snapshots, and selection reads the freshest complete snapshot under a
    bounded-staleness policy.

Per round (stage semantics shared by both servers via ``RoundContext``):
  1. the scenario emits a ``RoundPlan``: fleet membership (churn), per-device
     speeds/availability, label-drift positions, deadline and dropout draws,
  2. departed clients are evicted from the summary registry,
  3. summary refresh: the registry decides which *active* clients are stale
     (age or cheap-P(y)-drift); stale clients recompute the configured
     summary — by default through the fleet-scale batched engine (one jitted
     dispatch per shape bucket, DESIGN.md §4) — and the measured seconds are
     charged to the simulated clock,
  4. (re-)cluster the summaries of active clients with K-means (or DBSCAN;
     ``online`` keeps assignments fresh with O(drifted) work per round and
     only refits when inertia degrades — DESIGN.md §5),
  5. selection by the configured ``SelectionPolicy`` (DESIGN.md §11;
     default HACCS: per-cluster quotas, fastest available devices) —
     restricted to the current fleet,
  6. deadline semantics: selected clients whose summary + compute + upload
     time exceeds the round deadline are dropped (straggler timeout), as are
     mid-round dropouts; survivors run real local SGD in JAX and FedAvg
     aggregates whatever arrived,
  7. evaluate on the global test set; advance the simulated clock (the full
     deadline is charged when any selected client missed it).

``scenario=None`` reproduces the fixed-fleet PR-2 behavior bit-for-bit via
``LegacySystemScenario`` (same ``SystemModel`` RNG stream, no churn, no
deadline) — the baseline the differential tests pin against.  Likewise
``server="async"`` with zero ingest latency and the sync refresh cadence is
bit-identical to ``server="sync"`` (the async differential pins).
"""
from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.checkpoint.durable import Durability, DurableSession
from repro.checkpoint.server_state import context_state, restore_context
from repro.core import (
    BatchedSummaryEngine, RefreshPolicy, SummaryRegistry,
    dbscan, kmeans, minibatch_kmeans, sym_kl,
)
from repro.policies import ClientStats, PolicyContext, make_policy
from repro.shard import HierarchicalClusterMaintainer, ShardedSummaryRegistry
from repro.stream import (
    OnlineClusterMaintainer, OnlinePolicy, StreamingSummaryRegistry,
)
from repro.data.synthetic import FederatedDataset
from repro.fl.aggregation import fedavg
from repro.fl.client import ClientRuntime, local_train, timed_summary
from repro.fl.models import make_classifier, xent_loss
from repro.fl.system import SystemModel, SystemSpec, completion_times
from repro.kernels.ops import on_tpu
from repro.utils.tree import global_norm
from repro.models.cnn import CNNConfig, build_cnn, cnn_apply
from repro.optim import sgd
from repro.server.events import Stage
from repro.sim.faults import FaultInjector
from repro.sim.scenario import RoundPlan


@dataclasses.dataclass(frozen=True)
class FLConfig:
    rounds: int = 30
    clients_per_round: int = 10
    local_steps: int = 10
    batch_size: int = 16
    lr: float = 0.2
    fedprox_mu: float = 0.0          # FedProx proximal term (0 = FedAvg)
    model: str = "mlp"               # mlp | cnn
    hidden: int = 64
    # --- paper technique ---
    summary: str = "encoder"         # encoder | py | pxy | none
    selection: str = "haccs"         # any repro.policies registered name:
                                     # haccs | random | fastest |
                                     # grad-importance | grey-relational |
                                     # oort | ... (DESIGN.md §11)
    summary_engine: str = "batched"  # batched (one dispatch per bucket) |
                                     # perclient (legacy per-client jit loop)
    registry: str = "dict"           # dict (baseline SummaryRegistry) |
                                     # streaming (dense [N,·] matrices,
                                     # batched drift scan, DESIGN.md §5) |
                                     # sharded (chunked drift scan over a
                                     # fleet device mesh, DESIGN.md §7)
    clustering: str = "kmeans"       # kmeans | minibatch | dbscan |
                                     # online (assign-only maintenance) |
                                     # hierarchical (shard-local online
                                     # + weighted global merge, §7)
    online_inertia_ratio: float = 1.5   # online: full-refit trigger
    online_reseed_every: int = 8        # online: split/merge cadence
    # --- sharded fleet pipeline (DESIGN.md §7) ---
    n_shards: int = 0                # 0 = one shard per local device
    shard_chunk_rows: int = 131072   # scan chunk (caps device memory)
    hier_local_k: int = 0            # per-shard centroids (0 = num_clusters)
    # --- async selection server (DESIGN.md §8) ---
    server: str = "sync"             # sync (sequential round loop) |
                                     # async (event-driven pipelined server)
    ingest_delay_rounds: int = 0     # async: rounds a computed summary is
                                     # in flight before it lands in the
                                     # registry (0 = same round — the
                                     # degenerate sync-equivalent setting)
    server_refresh: str = "sync"     # async refresh policy:
                                     # sync (blocking, the sync cadence —
                                     # snapshot republished every round;
                                     # pinned ≡ server="sync") |
                                     # staleness (bounded-staleness
                                     # background refresher, §8)
    snapshot_max_age: int = 3        # staleness: blocking refresh when the
                                     # selection snapshot is older (rounds)
    drift_mass_trigger: float = 0.05 # staleness: background refresh when
                                     # this fraction of the live fleet
                                     # re-ingested/churned since snapshot
    # --- check-in front end (DESIGN.md §12; requires server="async") ---
    frontend: str = "none"           # none | poisson (request-level
                                     # check-in storm served from the
                                     # published snapshot)
    checkins_per_client: float = 2.0 # mean check-ins per available client
                                     # per round (Poisson)
    checkin_window_s: float = 60.0   # simulated serving window per round
    frontend_workers: int = 4        # parallel deciders (latency model)
    frontend_service_us: float = 50.0  # modeled per-check-in service time
    frontend_slo_p99_s: float = 0.0  # round p99 SLO; breach requests an
                                     # early background rebuild (0 = off)
    ingest_max_depth: int = 0        # bound on in-flight summaries (rows);
                                     # 0 = unbounded (the no-shed pin)
    admission_retry_after: int = 1   # rounds a shed summary waits before
                                     # its client re-offers it
    checkin_stall_model_s: float = 0.0  # modeled service stall when the
                                     # round rebuilt blocking (the decision
                                     # is deterministic; wall seconds are
                                     # not, so they never enter the trace)
    num_clusters: int = 8
    coreset_k: int = 64
    encoder_dim: int = 32
    bins: int = 8
    recluster_every: int = 10
    refresh_max_age: int = 20
    refresh_kl: float = 0.1
    # --- non-stationarity (legacy path; scenarios carry their own) ---
    drift_start: int = 10 ** 9       # round when drift begins
    drift_per_round: float = 0.0
    # --- eval ---
    eval_every: int = 1
    seed: int = 0


class LegacySystemScenario:
    """Adapter: the PR-2 fixed-fleet ``SystemModel`` behavior expressed as a
    scenario.  Same seed ⇒ the same speed walk and availability draws as the
    old round loop, every client always in the fleet, no deadline, no churn
    — so ``run_federated(..., scenario=None)`` is bit-identical to before.
    """

    def __init__(self, num_clients: int, system_spec: SystemSpec, seed: int,
                 drift_start: int, drift_per_round: float):
        self.num_clients = num_clients
        self.system_spec = system_spec
        self.seed = seed
        self.drift_start = drift_start
        self.drift_per_round = drift_per_round
        self._empty = np.zeros(0, np.int64)
        self.reset()

    def reset(self) -> None:
        """Rebuild the SystemModel from (spec, seed) — same RNG stream, so
        a reset adapter replays the identical availability/speed trace."""
        self.system = SystemModel(self.num_clients, self.system_spec,
                                  seed=self.seed)

    def round_plan(self, rnd: int) -> RoundPlan:
        n = self.num_clients
        avail = self.system.tick()
        drift = float(np.clip((rnd - self.drift_start) * self.drift_per_round,
                              0, 1))
        return RoundPlan(
            round_idx=rnd,
            active=np.ones(n, bool),
            available=avail,
            speeds=self.system.speeds.copy(),   # tick() mutates in place;
                                                # stored plans must not alias
            drift=np.full(n, drift),
            joined=self._empty,
            departed=self._empty,
            fail_u=np.ones(n),
            upload_cost=np.zeros(n),
            deadline=None,
            dropout_prob=0.0,
            step_cost=self.system.spec.step_cost,
            summary_cost=None,           # charge measured wall seconds
        )

    def note_selected(self, ids) -> None:
        pass

    def to_config(self) -> dict:
        """Full state for an exact rebuild via ``from_config`` (the
        ``legacy: True`` marker makes ``sim.Scenario.from_config`` reject
        this dict loudly instead of building a different fleet)."""
        return {"name": "legacy-system", "legacy": True,
                "num_clients": self.num_clients, "seed": self.seed,
                "system_spec": dataclasses.asdict(self.system_spec),
                "drift_start": self.drift_start,
                "drift_per_round": self.drift_per_round}

    @classmethod
    def from_config(cls, d: dict) -> "LegacySystemScenario":
        return cls(int(d["num_clients"]),
                   SystemSpec(**d.get("system_spec", {})),
                   seed=int(d["seed"]), drift_start=int(d["drift_start"]),
                   drift_per_round=float(d["drift_per_round"]))


class RoundContext:
    """Shared state + per-round pipeline stages for one federated run.

    Both servers — the inline sync loop (``_drive_sync``) and the
    event-driven async selection server (``repro.server.async_rounds``) —
    execute the *same* stage methods below; only the orchestration differs
    (what runs on the round-critical path, and whether selection reads the
    live registry or a published snapshot).  That shared core is the
    structural half of the async ≡ sync differential pin: with zero ingest
    latency and the sync refresh cadence, the async event schedule calls
    exactly this sequence with exactly these arguments.
    """

    def __init__(self, data: FederatedDataset, cfg: FLConfig, scenario):
        spec = data.spec
        self.data, self.cfg, self.spec, self.scenario = data, cfg, spec, \
            scenario
        self.rng = np.random.RandomState(cfg.seed)
        key = jax.random.PRNGKey(cfg.seed)

        init_fn, apply_fn = make_classifier(cfg.model, spec.feature_shape,
                                            spec.num_classes,
                                            hidden=cfg.hidden)
        loss_fn = xent_loss(apply_fn)
        self.runtime = ClientRuntime(loss_fn, sgd(cfg.lr), cfg.batch_size,
                                     fedprox_mu=cfg.fedprox_mu)
        self.params = init_fn(key)

        # summary encoder (paper: pretrained MobileNet hidden layer)
        enc_cfg = CNNConfig(in_channels=spec.feature_shape[-1],
                            feature_dim=cfg.encoder_dim)
        enc_params = build_cnn(enc_cfg, jax.random.PRNGKey(7))
        self.enc_fn = jax.jit(lambda imgs: cnn_apply(enc_params, imgs))
        # the Pallas kernels on the chip, the jnp references elsewhere
        self.use_kernel = on_tpu()

        if cfg.summary_engine not in ("batched", "perclient"):
            raise ValueError(f"unknown summary_engine: {cfg.summary_engine}")
        self.engine = None
        if cfg.summary != "none" and cfg.summary_engine == "batched":
            self.engine = BatchedSummaryEngine(
                cfg.summary, spec.num_classes, encoder_fn=self.enc_fn,
                coreset_k=cfg.coreset_k, bins=cfg.bins,
                use_kernel=self.use_kernel, encoder_dim=cfg.encoder_dim)
        policy = RefreshPolicy(cfg.refresh_max_age, cfg.refresh_kl)
        if cfg.registry == "streaming":
            self.registry = StreamingSummaryRegistry(
                spec.num_clients, policy, num_classes=spec.num_classes)
        elif cfg.registry == "sharded":
            self.registry = ShardedSummaryRegistry(
                spec.num_clients, policy, num_classes=spec.num_classes,
                n_shards=cfg.n_shards or None,
                chunk_rows=cfg.shard_chunk_rows)
        elif cfg.registry == "dict":
            self.registry = SummaryRegistry(spec.num_clients, policy)
        else:
            raise ValueError(f"unknown registry: {cfg.registry}")
        if cfg.clustering not in ("kmeans", "minibatch", "dbscan", "online",
                                  "hierarchical"):
            raise ValueError(f"unknown clustering: {cfg.clustering}")
        if cfg.server not in ("sync", "async"):
            raise ValueError(f"unknown server: {cfg.server}")
        if cfg.server_refresh not in ("sync", "staleness"):
            raise ValueError(f"unknown server_refresh: {cfg.server_refresh}")
        if cfg.frontend not in ("none", "poisson"):
            raise ValueError(f"unknown frontend: {cfg.frontend}")
        self.maintainer = None
        online_policy = OnlinePolicy(inertia_ratio=cfg.online_inertia_ratio,
                                     reseed_every=cfg.online_reseed_every,
                                     use_kernel=self.use_kernel)
        if cfg.clustering == "online":
            self.maintainer = OnlineClusterMaintainer(cfg.num_clusters,
                                                      online_policy)
        elif cfg.clustering == "hierarchical":
            self.maintainer = HierarchicalClusterMaintainer(
                cfg.num_clusters, n_shards=cfg.n_shards or None,
                local_k=cfg.hier_local_k or None, policy=online_policy)
        # pluggable selection policy (DESIGN.md §11): the config string
        # maps through the registry; unknown names ValueError here, like
        # every other backend string.  Policies are stateless — all
        # cross-round memory lives in client_stats (checkpointed).
        self.policy = make_policy(cfg.selection)
        self.client_stats = ClientStats(spec.num_clients)
        self._select_s = 0.0
        self._flight_sel = None

        test_x, test_y = data.test_set()
        test_x, test_y = jnp.asarray(test_x), jnp.asarray(test_y)

        @jax.jit
        def evaluate(p):
            logits = apply_fn(p, test_x)
            return jnp.mean((jnp.argmax(logits, -1)
                             == test_y).astype(jnp.float32))

        self.evaluate = evaluate

        self.assignment = np.zeros(spec.num_clients, np.int64)
        self.num_clusters = 1
        self.history: dict = {
            "round": [], "acc": [], "sim_time": [], "refreshes": [],
            "wall_summary_s": [], "selected": [], "completed": [],
            "dropped": [], "kl_coverage": [], "kl_reachable": [],
            "n_active": [],
            "n_joined": [], "n_departed": [], "select_s": [],
            # server-overhead accounting (DESIGN.md §8): wall seconds of
            # the server-side stages and the share that sat on the
            # round-critical path; snapshot lineage for async runs
            "server_scan_s": [], "server_cluster_s": [], "server_drain_s": [],
            "overhead_critical_s": [], "snapshot_version": [],
            "snapshot_age": [],
            # check-in front end (DESIGN.md §12): per-round stream size,
            # shed set size and modeled tail latency — empty lists when
            # no front end is configured (the key set stays fixed so
            # checkpoints restore across server modes)
            "checkins": [], "checkins_shed": [], "checkin_p99_s": []}
        self.sim_time = 0.0
        self.dropped_rounds = 0
        self.recluster_count = 0
        self._acc = float("nan")
        # per-run metric registry (DESIGN.md §10): the history's
        # server_*_s keys are per-round views over these meters, the
        # registry keeps the lifetime latency histograms / percentiles
        self.metrics = obs.MetricRegistry()
        self._meters = obs.StageMeters(self.metrics,
                                       ("scan", "cluster", "drain"))

    # ------------------------------------------------------------------
    # stage: membership + cheap drift signal

    @property
    def uses_summaries(self) -> bool:
        return self.cfg.summary != "none" and self.policy.needs_clusters

    def begin_round(self, rnd: int):
        """Advance the scenario, evict departures, refresh the cheap P(y)
        drift signal.  Resets the per-round server-overhead meters."""
        self._meters.reset()
        plan = self.scenario.round_plan(rnd)
        for c in plan.departed:
            self.registry.remove(int(c))
        # cheap drift signal: current P(y) for every client (pure, no RNG)
        fresh = self.data.client_label_dists(plan.drift)
        return plan, fresh

    # ------------------------------------------------------------------
    # stage: drift scan

    def scan_stale(self, rnd: int, plan: RoundPlan, fresh: np.ndarray,
                   exclude=None) -> list[int]:
        """The registry's staleness scan over the *active* fleet.
        ``exclude`` drops clients whose refresh is already in flight
        (async ingest pipelining) — empty in sync mode by construction."""
        if not self.uses_summaries:
            return []
        with obs.span("drift_scan", round=rnd) as sp:
            t0 = time.perf_counter()
            mask = self.registry.stale_mask(rnd, fresh, active=plan.active)
            self._meters.add("scan", time.perf_counter() - t0)
            stale = [int(c) for c in np.flatnonzero(mask)]
            sp.annotate(n_stale=len(stale))
        if exclude:
            stale = [c for c in stale if c not in exclude]
        return stale

    # ------------------------------------------------------------------
    # stage: client summary computation (the paper's measured overhead)

    def compute_summaries(self, rnd: int, stale: list[int],
                          drift: np.ndarray):
        """-> (summaries {c: array} in ingest order, seconds {c: s}, wall).

        Pure compute — nothing is written to the registry here, so the
        async server can hold results in its ingest queue.  Each client's
        PRNG seed is a pure function of (round, client),
        ``rnd * 100003 + c``: the batched engine makes
        ``jax.random.PRNGKey(seed)`` inside its dispatch, the per-client
        path makes the same key on the host, so the two stay
        bitwise-identical, and so do sync and async servers.
        """
        summaries: dict[int, np.ndarray] = {}
        times: dict[int, float] = {}
        wall = 0.0
        if not stale:
            return summaries, times, wall
        with obs.span("client_summaries", cat="client", round=rnd,
                      n_stale=len(stale)):
            self._compute_summaries(rnd, stale, drift, summaries, times)
        wall = sum(times.values())
        return summaries, times, wall

    def _compute_summaries(self, rnd, stale, drift, summaries, times):
        if self.engine is not None:
            results = self.engine.summarize_clients(
                stale, self.data.sizes,
                lambda c: self.data.client_data(c, float(drift[c])),
                lambda c: rnd * 100003 + c)
            for c, res in results.items():
                summaries[c] = res.summary
                times[c] = res.seconds
        else:
            cfg = self.cfg
            for c in stale:
                feats, labels, valid = self.data.client_data(
                    c, float(drift[c]))
                s, _ld_emp, dt = timed_summary(
                    cfg.summary, feats, labels, valid, self.spec.num_classes,
                    encoder_fn=self.enc_fn, coreset_k=cfg.coreset_k,
                    bins=cfg.bins, use_kernel=self.use_kernel,
                    key=jax.random.PRNGKey(rnd * 100003 + c))
                summaries[c] = s
                times[c] = dt

    # ------------------------------------------------------------------
    # stage: registry ingest (O(M) scatter)

    def ingest(self, rnd: int, summaries: dict[int, np.ndarray],
               fresh_rows) -> None:
        """Absorb one batch of recomputed summaries into the live registry.
        ``rnd`` is the *compute* round (the data's age), ``fresh_rows`` is
        indexable by client id — the full ``[N, C]`` array in sync mode, a
        per-id dict for queued async batches.  We store the same signal the
        scan compares against (cheap P(y)), so the KL drift test fires on
        real drift, not sampling noise."""
        if not summaries:
            return
        with obs.span("registry_scatter", round=rnd, batch=len(summaries)):
            t0 = time.perf_counter()
            if isinstance(self.registry, StreamingSummaryRegistry):
                ids = list(summaries)
                self.registry.update_batch(
                    ids, rnd, np.stack([summaries[c] for c in ids]),
                    np.stack([fresh_rows[c] for c in ids]))
            else:
                for c, s in summaries.items():
                    self.registry.update(c, rnd, s, fresh_rows[c])
            self._meters.add("drain", time.perf_counter() - t0)

    # ------------------------------------------------------------------
    # stage: clustering refresh

    def sync_recluster_due(self, rnd: int, plan: RoundPlan,
                           stale: list[int]) -> bool:
        """The sync loop's clustering-refresh cadence.  The async server's
        ``server_refresh="sync"`` policy calls exactly this predicate —
        the other structural half of the differential pin."""
        if not self.uses_summaries:
            return False
        churned = plan.joined.size > 0 or plan.departed.size > 0
        if self.maintainer is not None:
            # online maintenance runs whenever anything moved (the
            # maintainer escalates to a full refit itself)
            return bool(stale) or churned or self.maintainer.centroids is None
        cfg = self.cfg
        return bool(stale) and (rnd % cfg.recluster_every == 0 or rnd == 0
                                or len(stale) > self.spec.num_clients // 4
                                or churned)

    def sync_drifted(self, plan: RoundPlan, stale: list[int]) -> np.ndarray:
        """The drifted-row set the sync cadence hands the maintainer:
        this round's stale clients plus any churned ids (rows keep fleet
        indexing, so the maintainer's state stays aligned under churn)."""
        drifted = np.asarray(stale, np.int64)
        if plan.joined.size > 0 or plan.departed.size > 0:
            drifted = np.union1d(
                drifted, np.concatenate([plan.joined, plan.departed]))
        return drifted

    def recluster_now(self, rnd: int, active: np.ndarray,
                      drifted: np.ndarray) -> float:
        """Unconditional clustering rebuild/refresh from the live registry
        (the caller owns the cadence: sync gating or the async staleness
        policy).  Returns the wall seconds this rebuild took.

        Full clustering (``kmeans``/``minibatch``/``dbscan``) copies the
        registry's live ``dense()`` buffer to the device as it is, with no
        host gather; when not every row is live (churn, inactive clients)
        the live rows are picked on the device, in ``matrix_rows`` order.
        The registry's buffer is the source of that asynchronous copy (on
        the CPU backend the device array may alias it), so nothing may
        write the registry until the assignment is read back below, and
        no device array made from it outlives this call: this method is
        synchronous and the clustering functions donate no buffer."""
        cfg, spec = self.cfg, self.spec
        with obs.span("recluster", round=rnd, n_drifted=int(len(drifted))):
            t0 = time.perf_counter()
            if self.maintainer is not None:
                # online maintenance: assign-only for the drifted set; rows
                # keep fleet indexing (zeros for absent clients) so the
                # maintainer's state stays aligned under churn
                with obs.span("recluster/gather"):
                    dense = np.asarray(self.registry.dense(), np.float32)
                    live = self.registry.has_mask() & active
                self.maintainer.refresh(
                    dense, np.asarray(drifted, np.int64),
                    jax.random.PRNGKey(cfg.seed + rnd), live=live)
                if self.maintainer.assignment is not None:
                    self.assignment = self.maintainer.assignment
                    self.num_clusters = cfg.num_clusters
            else:
                with obs.span("recluster/gather") as sp:
                    have_ids = np.flatnonzero(self.registry.has_mask()
                                              & active)
                    if have_ids.size:
                        rows = np.asarray(self.registry.dense(), np.float32)
                    else:   # nothing to cluster: as matrix_rows gives it
                        rows = self.registry.matrix_rows(have_ids)
                    # every row, in order: the buffer goes whole
                    take = (0 if have_ids.size == rows.shape[0]
                            else int(have_ids.size))
                    sp.annotate(rows=int(have_ids.size), device_take=take)
                (X,) = obs.device_put("recluster/put", (rows,))
                if take:
                    X = jnp.take(X, jnp.asarray(have_ids), axis=0)
                assignment = np.full(spec.num_clients, -1, np.int64)
                with obs.span("recluster/fit"):
                    if cfg.clustering in ("kmeans", "minibatch"):
                        cluster_fn = (minibatch_kmeans
                                      if cfg.clustering == "minibatch"
                                      else kmeans)
                        res = cluster_fn(X, cfg.num_clusters,
                                         jax.random.PRNGKey(cfg.seed + rnd),
                                         use_kernel=self.use_kernel)
                        assignment[have_ids] = np.asarray(res.assignment,
                                                          np.int64)
                        self.num_clusters = cfg.num_clusters
                    else:
                        med = float(jnp.median(jnp.sqrt(
                            jnp.sum(jnp.square(X - X.mean(0)), -1))))
                        res = dbscan(X, eps=med * 0.5, min_samples=3)
                        assignment[have_ids] = np.asarray(res.labels,
                                                          np.int64)
                        self.num_clusters = max(int(res.num_clusters), 1)
                self.assignment = assignment
            dt = time.perf_counter() - t0
            self._meters.add("cluster", dt)
        self.recluster_count += 1
        return dt

    # ------------------------------------------------------------------
    # stage: selection

    def select(self, rnd: int, plan: RoundPlan, fresh=None, assignment=None,
               num_clusters=None, has_mask=None) -> np.ndarray:
        """Policy selection restricted to the current fleet.  The sync
        server reads the live registry/clustering (defaults); the async
        server passes a published snapshot's view instead.  ``fresh`` is
        this round's cheap per-client P(y) signal (from ``begin_round``)
        — the data-heterogeneity input for distribution-aware policies."""
        cfg = self.cfg
        if assignment is None:
            assignment = self.assignment
        if num_clusters is None:
            num_clusters = self.num_clusters
        # selection sees only the current fleet: clients without a live
        # summary row (departed / just joined between reclusters) fall out
        # of cluster quotas, absent clients out of the candidate pool
        if self.uses_summaries:
            if has_mask is None:
                has_mask = self.registry.has_mask()
            sel_assignment = assignment.copy()
            sel_assignment[~(np.asarray(has_mask, bool) & plan.active)] = -1
        else:
            sel_assignment = assignment
        pctx = PolicyContext(
            round_idx=rnd, per_round=cfg.clients_per_round,
            assignment=sel_assignment, num_clusters=num_clusters,
            speeds=plan.speeds, available=plan.available, rng=self.rng,
            active=plan.active, label_dists=fresh,
            data_sizes=self.data.sizes, stats=self.client_stats)
        rec = obs.recorder()
        if rec.enabled:
            # arm the policy's score-component scratchpad; write-only
            # for the policy, so decisions are identical recorder on/off
            pctx.explain = {}
        with obs.span("select_devices", round=rnd,
                      policy=self.policy.name) as sp:
            t0 = time.perf_counter()
            selected = self.policy.select(pctx)
            self._select_s = time.perf_counter() - t0
            sp.annotate(n_selected=int(np.asarray(selected).size))
        selected = np.asarray(selected, np.int64)
        # per-cluster quota fill — the drill-down answer to "which
        # cluster is starved".  Counters accumulate across rounds in the
        # per-run registry (history["metrics"]), one stream per cluster.
        fill = None
        if self.uses_summaries and num_clusters:
            asg_sel = np.asarray(sel_assignment, np.int64)[selected]
            fill = np.bincount(asg_sel[asg_sel >= 0],
                               minlength=num_clusters)
            fam = self.metrics.family("select/cluster_fill",
                                      labels=("cluster",))
            for c, n_sel in enumerate(fill.tolist()):
                if n_sel:
                    fam.labeled(c).inc(n_sel)
        if rec.enabled:
            self._flight_sel = {
                "sel_assignment": np.asarray(sel_assignment, np.int64),
                "available": plan.available, "explain": pctx.explain,
                "num_clusters": int(num_clusters),
                "fill": fill.tolist() if fill is not None else None}
        else:
            self._flight_sel = None
        self.scenario.note_selected(selected)
        self.client_stats.note_selected(selected, rnd)
        return selected

    # ------------------------------------------------------------------
    # stage: training + accounting

    def train_and_log(self, rnd: int, plan: RoundPlan, fresh: np.ndarray,
                      sel: np.ndarray, summary_times: dict[int, float],
                      wall_summary: float, critical_s: float,
                      snapshot_version: int, snapshot_age: int) -> None:
        cfg = self.cfg
        drift = plan.drift
        if sel.size:
            if plan.summary_cost is None:
                # legacy accounting: measured wall seconds on the critical
                # path (nondeterministic — only sound without a deadline)
                t = completion_times(plan.speeds, sel, cfg.local_steps,
                                     plan.step_cost, summary_times)
            else:
                # modeled summary cost: deterministic, so deadline
                # decisions and the sim clock replay exactly
                refreshed = np.asarray([float(int(i) in summary_times)
                                        for i in sel])
                t = (completion_times(plan.speeds, sel, cfg.local_steps,
                                      plan.step_cost)
                     + plan.summary_cost * refreshed / plan.speeds[sel])
            t = t + plan.upload_cost[sel]
            failed = plan.fail_u[sel] < plan.dropout_prob
            timed_out = (t > plan.deadline if plan.deadline is not None
                         else np.zeros(sel.size, bool))
            completed = ~(failed | timed_out)
            t_round = (float(plan.deadline)
                       if plan.deadline is not None
                       and (timed_out.any() or failed.any())
                       else float(np.max(t)))
        else:
            completed = np.zeros(0, bool)
            t_round = 0.0

        deltas, sizes = [], []
        with obs.span("local_train", cat="client", round=rnd,
                      n_completed=int(completed.sum())):
            for i, c in enumerate(sel):
                if not completed[i]:
                    continue
                feats, labels, valid = self.data.client_data(int(c),
                                                             float(drift[c]))
                delta, n, loss = local_train(self.runtime, self.params, feats,
                                             labels, valid, cfg.local_steps,
                                             self.rng)
                deltas.append(delta)
                sizes.append(n)
                # per-client history the history-aware policies consume
                # (Oort's loss utility, gradient-importance norms)
                self.client_stats.note_result(int(c), loss,
                                              float(global_norm(delta)))
        self.params = fedavg(self.params, deltas, sizes)
        if sel.size and not completed.any():
            self.dropped_rounds += 1

        # selected-client KL coverage, against two reference mixtures
        # (DESIGN.md §11): the *active fleet* (everyone enrolled — the
        # statistical target, availability-blind) and the *reachable
        # fleet* (active AND available this round — the best any selector
        # could have covered).  The two disagree exactly where selection
        # quality lives: a policy that allocates over phantom offline
        # clients looks fine on the first and bad on the second.
        act_ids = np.flatnonzero(plan.active)
        avail_ids = np.flatnonzero(plan.available)
        comp_ids = sel[completed] if sel.size else sel
        kl_cov = (sym_kl(fresh[comp_ids].mean(0), fresh[act_ids].mean(0))
                  if comp_ids.size and act_ids.size else float("nan"))
        kl_reach = (sym_kl(fresh[comp_ids].mean(0), fresh[avail_ids].mean(0))
                    if comp_ids.size and avail_ids.size else float("nan"))

        self.sim_time += t_round
        if rnd % cfg.eval_every == 0 or rnd == cfg.rounds - 1:
            with obs.span("evaluate", round=rnd):
                self._acc = float(self.evaluate(self.params))
        h = self.history
        h["round"].append(rnd)
        h["acc"].append(self._acc)
        h["sim_time"].append(self.sim_time)
        h["refreshes"].append(self.registry.refresh_count)
        h["wall_summary_s"].append(wall_summary)
        h["selected"].append(sel.tolist())
        h["completed"].append(sel[completed].tolist())
        h["dropped"].append(int(sel.size - completed.sum()))
        h["kl_coverage"].append(kl_cov)
        h["kl_reachable"].append(kl_reach)
        h["n_active"].append(int(plan.active.sum()))
        h["n_joined"].append(int(plan.joined.size))
        h["n_departed"].append(int(plan.departed.size))
        h["select_s"].append(self._select_s)
        h["server_scan_s"].append(self._meters["scan"])
        h["server_cluster_s"].append(self._meters["cluster"])
        h["server_drain_s"].append(self._meters["drain"])
        h["overhead_critical_s"].append(critical_s)
        h["snapshot_version"].append(snapshot_version)
        h["snapshot_age"].append(snapshot_age)
        # lifetime per-round distributions (reported as p50/p99/p999 in
        # history["metrics"] and by benchmarks/bench_server.py)
        self.metrics.histogram("server/critical_s").record(critical_s)
        self.metrics.gauge("server/snapshot_age").set(snapshot_age)
        self.metrics.histogram("server/snapshot_age_rounds",
                               lo=0.5, hi=1e4, per_decade=16) \
            .record(max(snapshot_age, 0))
        obs.counter_sample("snapshot_age", snapshot_age)
        obs.counter_sample("accuracy", self._acc)

        rec = obs.recorder()
        if rec.enabled:
            # the per-round decision record: everything explain.why()
            # needs to reconstruct this round's selection, byte-exact.
            # No wall-clock values — only modeled/decision state — so
            # the record stream is deterministic per seed.
            from repro.obs.recorder import (
                pack_bool, pack_floats, pack_ints,
            )
            fs = self._flight_sel or {}
            sel_asg = fs.get("sel_assignment")
            rec.record(
                "round", round=rnd, policy=self.policy.name,
                per_round=cfg.clients_per_round,
                selected=sel.tolist(),
                completed=sel[completed].tolist(),
                dropped=int(sel.size - completed.sum()),
                n_active=int(plan.active.sum()),
                n_available=int(plan.available.sum()),
                acc=self._acc, sim_time=self.sim_time,
                snapshot_version=int(snapshot_version),
                snapshot_age=int(snapshot_age),
                num_clusters=fs.get("num_clusters", self.num_clusters),
                cluster_fill=fs.get("fill"),
                active=pack_bool(plan.active),
                available=pack_bool(plan.available),
                speeds=pack_floats(plan.speeds),
                assignment=(pack_ints(sel_asg)
                            if sel_asg is not None else None),
                explain=fs.get("explain"))
            self._flight_sel = None

    def round_overhead_s(self) -> float:
        """This round's server-side wall seconds so far (scan + cluster +
        ingest scatter) — the sync server's critical-path charge."""
        return self._meters.round_total()

    def finish(self) -> dict:
        h = self.history
        h["final_acc"] = h["acc"][-1]
        h["params"] = self.params
        h["dropped_rounds"] = self.dropped_rounds
        h["scenario"] = self.scenario.to_config()
        # roll the per-run registry up into the process observer (when
        # one is live) and expose the snapshot; added here — never during
        # rounds — so checkpoint restore sees a stable history key set
        obs.metrics().merge(self.metrics)
        h["metrics"] = self.metrics.snapshot()
        if self.maintainer is not None:
            h["online_cluster"] = {"full_fits": self.maintainer.full_fits,
                                   "reseeds": self.maintainer.reseeds}
            if isinstance(self.maintainer, HierarchicalClusterMaintainer):
                h["online_cluster"]["merges"] = self.maintainer.merges
        return h


def _drive_sync(ctx: RoundContext, session=None, faults=None,
                start_round: int = 0) -> dict:
    """The sequential server: every stage on the round-critical path.

    The stage boundaries mirror the async event schedule (same ``Stage``
    ids), so a fault plan's crash points are portable between servers and
    the durable log records the same trace either way.  A crash raises
    *before* the stage runs — the interrupted stage was never committed.
    """
    cfg = ctx.cfg
    seq = 0

    def step(rnd, stage, fn):
        nonlocal seq
        if faults is not None:
            faults.maybe_crash(rnd, stage)
        with obs.span(stage.name.lower(), cat="stage", round=rnd):
            out = fn()
        if session is not None:
            session.log_event(rnd, int(stage), seq, stage.name.lower())
        seq += 1
        return out

    for rnd in range(start_round, cfg.rounds):
        plan, fresh = step(rnd, Stage.MEMBERSHIP,
                           lambda: ctx.begin_round(rnd))
        stale = step(rnd, Stage.SCAN,
                     lambda: ctx.scan_stale(rnd, plan, fresh))
        summaries, times, wall = step(
            rnd, Stage.COMPUTE,
            lambda: ctx.compute_summaries(rnd, stale, plan.drift))
        step(rnd, Stage.INGEST, lambda: ctx.ingest(rnd, summaries, fresh))

        def refresh():
            if ctx.sync_recluster_due(rnd, plan, stale):
                ctx.recluster_now(rnd, plan.active,
                                  ctx.sync_drifted(plan, stale))
                rec = obs.recorder()
                if rec.enabled:
                    rec.record("refresh", round=rnd, kind="sync",
                               n_stale=len(stale),
                               version=ctx.recluster_count)
        step(rnd, Stage.REFRESH, refresh)
        sel = step(rnd, Stage.SELECT, lambda: ctx.select(rnd, plan, fresh))
        step(rnd, Stage.TRAIN,
             lambda: ctx.train_and_log(rnd, plan, fresh, sel, times, wall,
                                       critical_s=ctx.round_overhead_s(),
                                       snapshot_version=ctx.recluster_count,
                                       snapshot_age=0))
        if session is not None:
            session.commit_round(
                rnd, cfg.rounds, sel,
                registry_version=getattr(ctx.registry, "version", 0),
                snapshot_version=ctx.recluster_count,
                state_fn=lambda: {"round": rnd,
                                  "context": context_state(ctx)})
    return ctx.finish()


def _replay_scenario(scenario, selected_per_round) -> None:
    """Re-derive scenario-internal state (RNG walk, battery drain) for the
    completed rounds.  Scenarios are pure functions of (config, round
    sequence, selections) with a fixed per-round draw count, so replaying
    ``round_plan`` + ``note_selected`` reproduces their state exactly —
    no scenario state ever needs checkpointing."""
    scenario.reset()
    for rnd, sel in enumerate(selected_per_round):
        scenario.round_plan(rnd)
        scenario.note_selected(np.asarray(sel, np.int64))


def _as_durability(durable) -> Durability:
    return durable if isinstance(durable, Durability) else \
        Durability(dir=str(durable))


def run_federated(data: FederatedDataset, cfg: FLConfig,
                  system_spec: SystemSpec | None = None,
                  scenario=None, *, durable=None, resume_from: str | None =
                  None, faults=None) -> dict:
    """Run one federated training (legacy flat-config entry point).

    This is now a thin shim over the typed ``repro.api`` surface: the
    flat ``FLConfig`` is lifted into a validated ``repro.api.RunConfig``
    (same unknown-string errors, plus the cross-field contracts) and
    handed to the shared executor, so both entry points produce
    identical histories, traces and checkpoints.

    Fault-tolerance knobs (DESIGN.md §9):

      * ``durable`` — a directory path or ``Durability``: append every
        server event to ``<dir>/events.jsonl`` and capture resumable
        state at round boundaries;
      * ``resume_from`` — a durable directory from a previous (killed)
        run: verify the config matches, reload the latest checkpoint,
        replay the scenario, and continue — the completed run is bitwise
        identical (decisions, snapshots, history trace) to one that was
        never interrupted;
      * ``faults`` — a ``FaultPlan`` / ``FaultInjector``: deterministic
        crash injection at stage boundaries (raises ``ServerKilled``)
        and, for the async server, seeded ingest-batch loss with bounded
        retry/backoff.
    """
    # lazy: repro.api imports FLConfig from this module at load time
    from repro.api import RunConfig
    return _execute(data, RunConfig.from_flconfig(cfg),
                    system_spec=system_spec, scenario=scenario,
                    durable=durable, resume_from=resume_from, faults=faults)


def _execute(data: FederatedDataset, run_cfg, *,
             system_spec: SystemSpec | None = None, scenario=None,
             durable=None, resume_from: str | None = None,
             faults=None) -> dict:
    """Shared executor behind ``repro.api.run`` and the legacy
    ``run_federated`` shim.  ``run_cfg`` is a validated
    ``repro.api.RunConfig``; its ``to_dict()`` form is what travels in
    the durable-log header and the history ``config`` echo."""
    cfg = run_cfg.to_flconfig()
    cfg_dict = run_cfg.to_dict()
    spec = data.spec
    if scenario is None:
        scenario = LegacySystemScenario(
            spec.num_clients, system_spec or SystemSpec(), seed=cfg.seed + 1,
            drift_start=cfg.drift_start, drift_per_round=cfg.drift_per_round)
    else:
        if system_spec is not None:
            raise ValueError(
                "system_spec and scenario are mutually exclusive — a "
                "scenario carries its own device/system model")
        if scenario.num_clients != spec.num_clients:
            raise ValueError(
                f"scenario models {scenario.num_clients} clients but the "
                f"dataset has {spec.num_clients}")
        scenario.reset()

    injector = None
    if faults is not None:
        injector = (faults if isinstance(faults, FaultInjector)
                    else FaultInjector(faults))

    ctx = RoundContext(data, cfg, scenario)
    session = None
    start_round = 0
    server_st = None
    if resume_from is not None:
        dur = _as_durability(durable if durable is not None else resume_from)
        if os.path.abspath(dur.dir) != os.path.abspath(resume_from):
            raise ValueError(
                "resume_from and durable.dir must agree — a resumed run "
                "keeps appending to the durable directory it resumes from")
        session = DurableSession(dur, cfg_dict,
                                 scenario.to_config(), resume=True)
        ckpt = session.latest_checkpoint()
        if ckpt is not None:
            rnd, state = ckpt
            # scenario first (pure replay), then the checkpointed state
            _replay_scenario(scenario, state["context"]["history"]["selected"])
            restore_context(ctx, state["context"])
            server_st = state.get("server")
            start_round = rnd + 1
        session.log_resume(start_round)
    elif durable is not None:
        session = DurableSession(_as_durability(durable), cfg_dict,
                                 scenario.to_config(), resume=False)
    try:
        if cfg.server == "async":
            # imported lazily: repro.server imports this module's
            # RoundContext
            from repro.server.async_rounds import drive_async
            h = drive_async(ctx, session=session, faults=injector,
                            start_round=start_round, restored=server_st)
        else:
            h = _drive_sync(ctx, session=session, faults=injector,
                            start_round=start_round)
    finally:
        if session is not None:
            session.close()
    # echo the typed config with the results — added post-finish so the
    # checkpointed history key set stays fixed across server modes
    h["config"] = cfg_dict
    return h
