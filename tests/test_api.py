"""The redesigned entry surface: ``repro.api`` (DESIGN.md §12).

Covers eager validation (unknown strings fail with the legacy message
at *construction*), the cross-field contracts the flat config silently
ignored, both bridges (to/from FLConfig, to/from dict), and the shim
equivalence pin: ``repro.fl.run_federated`` and ``repro.api.run`` are
the same executor, so their histories match bitwise.
"""
import dataclasses
import json
import pathlib

import pytest

import repro.api as api
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl.rounds import FLConfig, run_federated


# ---------------------------------------------------------------------------
# construction-time validation


@pytest.mark.parametrize("kw,msg", [
    (dict(model="resnet"), "unknown model: resnet"),
    (dict(summary="sketch"), "unknown summary: sketch"),
    (dict(clustering={"kind": "spectral"}), "unknown clustering: spectral"),
    (dict(server={"kind": "threads"}), "unknown server: threads"),
    (dict(server={"kind": "async", "refresh": "eager"}),
     "unknown server_refresh: eager"),
    (dict(server={"kind": "async", "frontend": {"kind": "uniform"}}),
     "unknown frontend: uniform"),
])
def test_unknown_strings_fail_eagerly_with_legacy_message(kw, msg):
    with pytest.raises(ValueError, match=msg):
        api.RunConfig(**kw)


@pytest.mark.parametrize("kw,msg", [
    (dict(rounds=0), "rounds must be >= 1"),
    (dict(clients_per_round=0), "clients_per_round must be >= 1"),
    (dict(registry={"n_shards": -1}), "n_shards must be >= 0"),
    (dict(clustering={"num_clusters": 0}), "num_clusters must be >= 1"),
    (dict(server={"snapshot_max_age": 0}), "snapshot_max_age must be >= 1"),
    (dict(server={"drift_mass_trigger": 0.0}),
     r"drift_mass_trigger must be in \(0, 1\]"),
    (dict(server={"kind": "async",
                  "frontend": {"kind": "poisson", "window_s": 0.0}}),
     "window_s must be > 0"),
    (dict(server={"kind": "async",
                  "frontend": {"kind": "poisson", "retry_after": 0}}),
     "retry_after must be >= 1"),
    (dict(server={"kind": "async",
                  "frontend": {"kind": "poisson", "stall_model_s": -1.0}}),
     "stall_model_s must be >= 0"),
])
def test_range_validation(kw, msg):
    with pytest.raises(ValueError, match=msg):
        api.RunConfig(**kw)


def test_cross_field_contracts():
    with pytest.raises(ValueError, match="requires server=async"):
        api.RunConfig(server={"kind": "sync",
                              "frontend": {"kind": "poisson"}})
    with pytest.raises(ValueError, match="requires server=async"):
        api.RunConfig(server={"kind": "sync", "refresh": "staleness"})
    # the coherent combinations construct fine
    api.RunConfig(clustering={"kind": "hierarchical"})
    api.RunConfig(server={"kind": "async", "refresh": "staleness",
                          "frontend": {"kind": "poisson"}})


def test_policy_validated_at_construction():
    with pytest.raises(ValueError, match="unknown selection policy"):
        api.PolicyConfig(name="oracle-9000")
    # registered aliases are fine
    api.PolicyConfig(name="random")


def test_durability_requires_dir():
    with pytest.raises(ValueError, match="dir must be a directory path"):
        api.DurabilityConfig(dir="")


def test_subconfig_type_errors():
    with pytest.raises(TypeError, match="server must be a ServerConfig"):
        api.RunConfig(server="async")


def test_mapping_coercion_matches_explicit_subconfigs():
    a = api.RunConfig(server={"kind": "async", "refresh": "staleness"},
                      registry={"n_shards": 2})
    b = api.RunConfig(
        server=api.ServerConfig(kind=api.Server.ASYNC,
                                refresh=api.Refresh.STALENESS),
        registry=api.RegistryConfig(n_shards=2))
    assert a == b


# ---------------------------------------------------------------------------
# bridges


def _rich_config(**kw):
    base = dict(
        rounds=5, clients_per_round=6, local_steps=2, lr=0.1,
        summary="py", bins=6, refresh_max_age=4, refresh_kl=0.07,
        registry={"n_shards": 2, "chunk_rows": 64},
        clustering={"kind": "hierarchical", "num_clusters": 4,
                    "recluster_every": 3, "hier_local_k": 2},
        server={"kind": "async", "refresh": "staleness",
                "ingest_delay_rounds": 1, "snapshot_max_age": 2,
                "drift_mass_trigger": 0.2,
                "frontend": {"kind": "poisson", "checkins_per_client": 1.5,
                             "window_s": 30.0, "workers": 2,
                             "service_us": 75.0, "slo_p99_s": 0.5,
                             "ingest_max_depth": 8, "retry_after": 2,
                             "stall_model_s": 0.1}},
        policy={"name": "random"}, eval_every=2, seed=3)
    base.update(kw)
    return api.RunConfig(**base)


def test_flconfig_bridge_round_trips():
    cfg = _rich_config()
    flat = cfg.to_flconfig()
    assert isinstance(flat, FLConfig)
    # enum values are the legacy strings, bit for bit
    assert flat.registry == "sharded" and flat.clustering == "hierarchical"
    assert flat.frontend == "poisson" and flat.server_refresh == "staleness"
    assert flat.checkin_stall_model_s == 0.1
    assert api.RunConfig.from_flconfig(flat) == cfg


def test_dict_round_trip_is_json_safe_and_lossless():
    cfg = _rich_config()
    d = cfg.to_dict()
    # JSON-safe: every enum became its plain string value
    restored = api.RunConfig.from_dict(json.loads(json.dumps(d)))
    assert restored == cfg
    assert d["server"]["frontend"]["kind"] == "poisson"


def test_to_dict_excludes_durability(tmp_path):
    cfg = _rich_config(durability={"dir": str(tmp_path)})
    d = cfg.to_dict()
    assert "durability" not in d
    # identical computation, different artifact dir -> identical dict
    assert d == _rich_config().to_dict()


def test_from_dict_rejects_unknown_fields():
    d = _rich_config().to_dict()
    d["warp_speed"] = 9
    with pytest.raises(ValueError, match="unknown RunConfig fields"):
        api.RunConfig.from_dict(d)


def test_replace_revalidates():
    cfg = _rich_config()
    with pytest.raises(ValueError, match="requires server=async"):
        dataclasses.replace(cfg, server=dataclasses.replace(cfg.server,
                                                            kind="sync"))


# ---------------------------------------------------------------------------
# one registry and one summary engine: the removed values are refused


@pytest.mark.parametrize("build,msg", [
    (lambda: FLConfig(registry="dict"), "unknown registry: dict"),
    (lambda: FLConfig(registry="streaming"), "unknown registry: streaming"),
    (lambda: FLConfig(summary_engine="perclient"),
     "unknown summary_engine: perclient"),
    (lambda: api.RunConfig.from_dict({"summary_engine": "perclient"}),
     r"unknown RunConfig fields: \['summary_engine'\]"),
    (lambda: api.RunConfig.from_dict({"registry": {"kind": "dict"}}),
     r"unknown RunConfig fields: \['registry.kind'\]"),
    (lambda: api.RunConfig.from_dict({"registry": {"kind": "streaming"}}),
     r"unknown RunConfig fields: \['registry.kind'\]"),
], ids=["fl-dict", "fl-streaming", "fl-perclient", "run-perclient",
        "run-dict", "run-streaming"])
def test_removed_registries_and_engine_refused(build, msg):
    with pytest.raises(ValueError, match=msg):
        build()


@pytest.mark.parametrize("path", sorted(
    (pathlib.Path(__file__).resolve().parent.parent
     / "chipbench" / "configs").glob("*.json")), ids=lambda p: p.stem)
def test_benchmark_server_groups_build_flconfig(path):
    """The benchmark builds ``FLConfig(**cfg["server"])``: each
    configuration's server group names the one registry and the one
    engine, which are also the defaults."""
    server = json.loads(path.read_text())["server"]
    cfg = FLConfig(**server)
    assert (cfg.registry, cfg.summary_engine) == ("sharded", "batched")
    assert (FLConfig().registry, FLConfig().summary_engine) == \
        (cfg.registry, cfg.summary_engine)
    assert api.RunConfig.from_flconfig(cfg).to_flconfig() == cfg


# ---------------------------------------------------------------------------
# the entry point and the shim


@pytest.fixture(scope="module")
def tiny_data():
    return FederatedDataset(small_spec(num_clients=10, num_classes=4, side=8,
                                       avg_samples=20), seed=7)


def _tiny_cfg(**kw):
    base = dict(rounds=2, clients_per_round=4, local_steps=1, summary="py",
                clustering={"num_clusters": 3}, eval_every=2, seed=0)
    base.update(kw)
    return api.RunConfig(**base)


def test_run_rejects_legacy_flconfig(tiny_data):
    with pytest.raises(TypeError, match="takes a RunConfig"):
        api.run(tiny_data, FLConfig(rounds=1))


def _det_view(h):
    """Strip the measured wall-clock columns (``*_s`` timings and the
    wall-derived ``sim_time``) — everything else is deterministic and
    must match bitwise between the two entry points."""
    out = {}
    for k, v in h.items():
        # "metrics" is the obs registry dump — wall-clock stage timings
        if k in ("sim_time", "metrics") or k.endswith("_s"):
            continue
        if k == "server" and isinstance(v, dict):
            v = {kk: vv for kk, vv in v.items() if not kk.endswith("_s")}
        out[k] = v
    return out


def test_shim_and_api_histories_identical(tiny_data):
    import jax
    import numpy as np
    cfg = _tiny_cfg()
    h_api = _det_view(api.run(tiny_data, cfg))
    h_shim = _det_view(run_federated(tiny_data, cfg.to_flconfig()))
    assert set(h_api) == set(h_shim)
    for k in h_api:
        la = jax.tree_util.tree_leaves(h_api[k])
        lb = jax.tree_util.tree_leaves(h_shim[k])
        assert len(la) == len(lb), k
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y)), k


def test_history_echoes_config(tiny_data):
    cfg = _tiny_cfg()
    h = api.run(tiny_data, cfg)
    assert h["config"] == cfg.to_dict()
    # the echo survives a JSON round trip (it IS the durable header)
    assert api.RunConfig.from_dict(json.loads(json.dumps(h["config"]))) == cfg


def test_durable_run_and_resume_through_api(tiny_data, tmp_path):
    cfg = _tiny_cfg(durability={"dir": str(tmp_path / "wal")})
    h1 = api.run(tiny_data, cfg)
    # a resume against the completed log replays to the same history
    h2 = api.run(tiny_data, cfg, resume_from=str(tmp_path / "wal"))
    for k in ("selected", "acc", "sim_time"):
        assert h1[k] == h2[k]
