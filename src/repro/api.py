"""Typed front door for the reproduction (DESIGN.md §12).

``repro.fl.FLConfig`` grew organically: ~40 flat fields, every backend
chosen by a raw string, cross-field contracts (the check-in front end
needs the async server) enforced only deep inside ``RoundContext`` — or
not at all.
This module is the redesigned entry surface:

  * enum-backed knobs (``Clustering.ONLINE``, ``Server.ASYNC``, ...)
    whose *values* are exactly the legacy strings, so configs remain
    greppable and serialize to the same tokens;
  * small composable sub-configs (``RegistryConfig``,
    ``ClusteringConfig``, ``ServerConfig``, ``PolicyConfig``,
    ``DurabilityConfig``) grouping the fields that vary together;
  * eager validation at *construction* time — unknown strings and
    incoherent combinations fail before any data is touched, with the
    same ``unknown <knob>: <value>`` messages the old path raised;
  * a lossless bridge to the legacy surface
    (``to_flconfig``/``from_flconfig``) so ``run_federated`` survives
    as a thin shim and every existing call site keeps working;
  * ``to_dict``/``from_dict`` round-trip used by the durable-log
    header and the history ``config`` echo, so a run's exact
    configuration travels with its artifacts.

The one entry point::

    import repro.api as api

    cfg = api.RunConfig(
        rounds=20, summary=api.Summary.PY,
        registry=api.RegistryConfig(n_shards=4),
        clustering=api.ClusteringConfig(kind=api.Clustering.HIERARCHICAL),
        server=api.ServerConfig(kind=api.Server.ASYNC,
                                refresh=api.Refresh.STALENESS),
    )
    history = api.run(data, cfg, scenario=scenario)
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Mapping

from repro.fl.rounds import FLConfig

__all__ = [
    "Summary", "Model", "Clustering",
    "Server", "Refresh", "Frontend",
    "RegistryConfig", "ClusteringConfig", "FrontendConfig", "ServerConfig",
    "PolicyConfig", "DurabilityConfig", "RunConfig", "run",
]


# ---------------------------------------------------------------------------
# enums — values are the legacy FLConfig strings, bit for bit


class Summary(str, enum.Enum):
    """Client data-distribution summary family (paper §3)."""
    ENCODER = "encoder"
    PY = "py"
    PXY = "pxy"
    NONE = "none"


class Model(str, enum.Enum):
    MLP = "mlp"
    CNN = "cnn"


class Clustering(str, enum.Enum):
    KMEANS = "kmeans"
    MINIBATCH = "minibatch"
    DBSCAN = "dbscan"
    ONLINE = "online"
    HIERARCHICAL = "hierarchical"


class Server(str, enum.Enum):
    SYNC = "sync"
    ASYNC = "async"


class Refresh(str, enum.Enum):
    SYNC = "sync"
    STALENESS = "staleness"


class Frontend(str, enum.Enum):
    NONE = "none"
    POISSON = "poisson"


def _coerce(cls: type, value: Any, knob: str):
    """String/enum -> enum member; unknown values raise the exact
    ``unknown <knob>: <value>`` message the legacy path used."""
    if isinstance(value, cls):
        return value
    try:
        return cls(value)
    except ValueError:
        raise ValueError(f"unknown {knob}: {value}") from None


def _set(obj, field: str, value) -> None:
    object.__setattr__(obj, field, value)   # frozen-dataclass write


# ---------------------------------------------------------------------------
# sub-configs


@dataclasses.dataclass(frozen=True)
class RegistryConfig:
    """How the registry's drift scan is laid out (DESIGN.md §5, §7)."""
    n_shards: int = 0               # 0 = one shard per device
    chunk_rows: int = 131072        # scan chunk (device-memory cap)

    def __post_init__(self):
        if self.n_shards < 0:
            raise ValueError("n_shards must be >= 0")
        if self.chunk_rows < 1:
            raise ValueError("chunk_rows must be >= 1")


@dataclasses.dataclass(frozen=True)
class ClusteringConfig:
    """How the server groups clients by distribution (DESIGN.md §6, §7)."""
    kind: Clustering = Clustering.KMEANS
    num_clusters: int = 8
    recluster_every: int = 10
    online_inertia_ratio: float = 1.5
    online_reseed_every: int = 8
    hier_local_k: int = 0           # hierarchical: per-shard k (0 = global k)

    def __post_init__(self):
        _set(self, "kind", _coerce(Clustering, self.kind, "clustering"))
        if self.num_clusters < 1:
            raise ValueError("num_clusters must be >= 1")
        if self.recluster_every < 1:
            raise ValueError("recluster_every must be >= 1")


@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    """Request-level check-in front end (DESIGN.md §12).  Requires the
    async server; ``kind=Frontend.NONE`` disables the whole stage."""
    kind: Frontend = Frontend.NONE
    checkins_per_client: float = 2.0   # Poisson mean per available client
    window_s: float = 60.0             # simulated serving window per round
    workers: int = 4                   # parallel deciders (latency model)
    service_us: float = 50.0           # modeled per-check-in service time
    slo_p99_s: float = 0.0             # 0 = SLO feedback off
    ingest_max_depth: int = 0          # 0 = unbounded (the no-shed pin)
    retry_after: int = 1               # rounds a shed summary waits
    stall_model_s: float = 0.0         # modeled stall per blocking rebuild

    def __post_init__(self):
        _set(self, "kind", _coerce(Frontend, self.kind, "frontend"))
        if self.checkins_per_client < 0:
            raise ValueError("checkins_per_client must be >= 0")
        if self.window_s <= 0:
            raise ValueError("window_s must be > 0")
        if self.workers < 1:
            raise ValueError("frontend workers must be >= 1")
        if self.service_us <= 0:
            raise ValueError("service_us must be > 0")
        if self.slo_p99_s < 0:
            raise ValueError("slo_p99_s must be >= 0")
        if self.ingest_max_depth < 0:
            raise ValueError("ingest_max_depth must be >= 0")
        if self.retry_after < 1:
            raise ValueError("retry_after must be >= 1")
        if self.stall_model_s < 0:
            raise ValueError("stall_model_s must be >= 0")


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Round-driver topology: sync loop or the pipelined async server
    with its refresh policy and check-in front end (DESIGN.md §8, §12)."""
    kind: Server = Server.SYNC
    refresh: Refresh = Refresh.SYNC
    ingest_delay_rounds: int = 0
    snapshot_max_age: int = 3
    drift_mass_trigger: float = 0.05
    frontend: FrontendConfig = dataclasses.field(default_factory=FrontendConfig)

    def __post_init__(self):
        _set(self, "kind", _coerce(Server, self.kind, "server"))
        _set(self, "refresh", _coerce(Refresh, self.refresh, "server_refresh"))
        if isinstance(self.frontend, Mapping):
            _set(self, "frontend", FrontendConfig(**self.frontend))
        if self.ingest_delay_rounds < 0:
            raise ValueError("ingest_delay_rounds must be >= 0")
        if self.snapshot_max_age < 1:
            raise ValueError("snapshot_max_age must be >= 1")
        if not 0.0 < self.drift_mass_trigger <= 1.0:
            raise ValueError("drift_mass_trigger must be in (0, 1]")


@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    """Pluggable selection policy (DESIGN.md §11).  Any name registered
    in ``repro.policies`` — validated at construction."""
    name: str = "haccs"

    def __post_init__(self):
        from repro.policies import make_policy
        make_policy(self.name)   # raises "unknown selection policy ..."


@dataclasses.dataclass(frozen=True)
class DurabilityConfig:
    """Durable event log + round checkpoints (DESIGN.md §9)."""
    dir: str = ""
    checkpoint_every: int = 1
    fsync: bool = False

    def __post_init__(self):
        if not self.dir:
            raise ValueError("DurabilityConfig.dir must be a directory path")
        if self.checkpoint_every < 1:
            raise ValueError("checkpoint_every must be >= 1")


# ---------------------------------------------------------------------------
# the run config

_SUBCONFIGS = (("registry", RegistryConfig), ("clustering", ClusteringConfig),
               ("server", ServerConfig), ("policy", PolicyConfig))


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Complete, validated configuration for one federated run."""
    # --- training ---
    rounds: int = 30
    clients_per_round: int = 10
    local_steps: int = 10
    batch_size: int = 16
    lr: float = 0.2
    fedprox_mu: float = 0.0
    model: Model = Model.MLP
    hidden: int = 64
    # --- paper technique ---
    summary: Summary = Summary.ENCODER
    coreset_k: int = 64
    encoder_dim: int = 32
    bins: int = 8
    refresh_max_age: int = 20
    refresh_kl: float = 0.1
    # --- subsystems ---
    registry: RegistryConfig = dataclasses.field(default_factory=RegistryConfig)
    clustering: ClusteringConfig = dataclasses.field(
        default_factory=ClusteringConfig)
    server: ServerConfig = dataclasses.field(default_factory=ServerConfig)
    policy: PolicyConfig = dataclasses.field(default_factory=PolicyConfig)
    durability: DurabilityConfig | None = None
    # --- non-stationarity (legacy path; scenarios carry their own) ---
    drift_start: int = 10 ** 9
    drift_per_round: float = 0.0
    # --- eval ---
    eval_every: int = 1
    seed: int = 0

    def __post_init__(self):
        _set(self, "model", _coerce(Model, self.model, "model"))
        _set(self, "summary", _coerce(Summary, self.summary, "summary"))
        for field, cls in _SUBCONFIGS:
            v = getattr(self, field)
            if isinstance(v, Mapping):
                _set(self, field, cls(**v))
            elif not isinstance(v, cls):
                raise TypeError(f"{field} must be a {cls.__name__} "
                                f"(got {type(v).__name__})")
        if isinstance(self.durability, Mapping):
            _set(self, "durability", DurabilityConfig(**self.durability))
        if self.rounds < 1:
            raise ValueError("rounds must be >= 1")
        if self.clients_per_round < 1:
            raise ValueError("clients_per_round must be >= 1")
        # --- cross-field contracts the flat config silently ignored ---
        if (self.server.frontend.kind is not Frontend.NONE
                and self.server.kind is not Server.ASYNC):
            raise ValueError(
                "frontend=poisson requires server=async — check-ins are "
                "served from the event engine's published snapshots "
                "(DESIGN.md §12)")
        if (self.server.kind is Server.SYNC
                and self.server.refresh is not Refresh.SYNC):
            raise ValueError(
                "server_refresh=staleness requires server=async — the "
                "sync loop has no background refresh lane")

    # ------------------------------------------------------------------
    # legacy bridge — lossless in both directions (durability excepted:
    # the flat config never carried it)

    def to_flconfig(self) -> FLConfig:
        s, c, r, fe = self.server, self.clustering, self.registry, \
            self.server.frontend
        return FLConfig(
            rounds=self.rounds, clients_per_round=self.clients_per_round,
            local_steps=self.local_steps, batch_size=self.batch_size,
            lr=self.lr, fedprox_mu=self.fedprox_mu, model=self.model.value,
            hidden=self.hidden, summary=self.summary.value,
            selection=self.policy.name, clustering=c.kind.value,
            online_inertia_ratio=c.online_inertia_ratio,
            online_reseed_every=c.online_reseed_every,
            n_shards=r.n_shards, shard_chunk_rows=r.chunk_rows,
            hier_local_k=c.hier_local_k,
            server=s.kind.value, ingest_delay_rounds=s.ingest_delay_rounds,
            server_refresh=s.refresh.value,
            snapshot_max_age=s.snapshot_max_age,
            drift_mass_trigger=s.drift_mass_trigger,
            frontend=fe.kind.value,
            checkins_per_client=fe.checkins_per_client,
            checkin_window_s=fe.window_s, frontend_workers=fe.workers,
            frontend_service_us=fe.service_us,
            frontend_slo_p99_s=fe.slo_p99_s,
            ingest_max_depth=fe.ingest_max_depth,
            admission_retry_after=fe.retry_after,
            checkin_stall_model_s=fe.stall_model_s,
            num_clusters=c.num_clusters, coreset_k=self.coreset_k,
            encoder_dim=self.encoder_dim, bins=self.bins,
            recluster_every=c.recluster_every,
            refresh_max_age=self.refresh_max_age, refresh_kl=self.refresh_kl,
            drift_start=self.drift_start,
            drift_per_round=self.drift_per_round,
            eval_every=self.eval_every, seed=self.seed)

    @classmethod
    def from_flconfig(cls, cfg: FLConfig,
                      durability: DurabilityConfig | None = None
                      ) -> "RunConfig":
        return cls(
            rounds=cfg.rounds, clients_per_round=cfg.clients_per_round,
            local_steps=cfg.local_steps, batch_size=cfg.batch_size,
            lr=cfg.lr, fedprox_mu=cfg.fedprox_mu, model=cfg.model,
            hidden=cfg.hidden, summary=cfg.summary,
            coreset_k=cfg.coreset_k,
            encoder_dim=cfg.encoder_dim, bins=cfg.bins,
            refresh_max_age=cfg.refresh_max_age, refresh_kl=cfg.refresh_kl,
            registry=RegistryConfig(n_shards=cfg.n_shards,
                                    chunk_rows=cfg.shard_chunk_rows),
            clustering=ClusteringConfig(
                kind=cfg.clustering, num_clusters=cfg.num_clusters,
                recluster_every=cfg.recluster_every,
                online_inertia_ratio=cfg.online_inertia_ratio,
                online_reseed_every=cfg.online_reseed_every,
                hier_local_k=cfg.hier_local_k),
            server=ServerConfig(
                kind=cfg.server, refresh=cfg.server_refresh,
                ingest_delay_rounds=cfg.ingest_delay_rounds,
                snapshot_max_age=cfg.snapshot_max_age,
                drift_mass_trigger=cfg.drift_mass_trigger,
                frontend=FrontendConfig(
                    kind=cfg.frontend,
                    checkins_per_client=cfg.checkins_per_client,
                    window_s=cfg.checkin_window_s,
                    workers=cfg.frontend_workers,
                    service_us=cfg.frontend_service_us,
                    slo_p99_s=cfg.frontend_slo_p99_s,
                    ingest_max_depth=cfg.ingest_max_depth,
                    retry_after=cfg.admission_retry_after,
                    stall_model_s=cfg.checkin_stall_model_s)),
            policy=PolicyConfig(name=cfg.selection),
            durability=durability,
            drift_start=cfg.drift_start,
            drift_per_round=cfg.drift_per_round,
            eval_every=cfg.eval_every, seed=cfg.seed)

    # ------------------------------------------------------------------
    # serialization — plain JSON-safe dicts (enums -> their string
    # values); used for the durable-log header and the history echo.
    # ``durability`` is deliberately excluded: it says where artifacts
    # land, not what the run computes, and the durable header must
    # identify the *computation* so a resume from the log's own
    # directory never self-mismatches.

    def to_dict(self) -> dict:
        def conv(v):
            if isinstance(v, enum.Enum):
                return v.value
            if dataclasses.is_dataclass(v) and not isinstance(v, type):
                return {f.name: conv(getattr(v, f.name))
                        for f in dataclasses.fields(v)}
            return v
        d = conv(self)
        del d["durability"]
        return d

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "RunConfig":
        kw = dict(d)
        unknown = set(kw) - {f.name for f in dataclasses.fields(cls)}
        for field, sub in _SUBCONFIGS:
            if isinstance(kw.get(field), Mapping):
                unknown |= {f"{field}.{k}" for k in set(kw[field])
                            - {f.name for f in dataclasses.fields(sub)}}
        if unknown:
            raise ValueError(f"unknown RunConfig fields: {sorted(unknown)}")
        # __post_init__ coerces nested mappings into the sub-configs
        return cls(**kw)


# ---------------------------------------------------------------------------
# the entry point


def run(data, config: RunConfig, *, scenario=None, system_spec=None,
        resume_from: str | None = None, faults=None) -> dict:
    """Run one federated training under a validated ``RunConfig``.

    This is the same executor ``repro.fl.run_federated`` drives — the
    legacy function is now a shim over this surface — so histories,
    traces, checkpoints and the differential pins are identical between
    the two entry points.
    """
    if not isinstance(config, RunConfig):
        raise TypeError(
            f"repro.api.run takes a RunConfig (got {type(config).__name__}); "
            "legacy FLConfig callers should use repro.fl.run_federated")
    from repro.fl.rounds import _execute
    durable = None
    if config.durability is not None:
        from repro.checkpoint.durable import Durability
        d = config.durability
        durable = Durability(dir=d.dir, checkpoint_every=d.checkpoint_every,
                             fsync=d.fsync)
    return _execute(data, config, system_spec=system_spec, scenario=scenario,
                    durable=durable, resume_from=resume_from, faults=faults)
