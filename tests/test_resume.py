"""Kill-and-resume differential harness (DESIGN.md §9).

The fault-tolerance guarantee: a run killed at a stage boundary and
resumed from its durable directory completes with a history trace
**bitwise identical** (decisions, snapshot lineage, sim clock, accuracy)
to the run that was never interrupted.

The workhorse is a *kill chain*: one durable run is killed at boundary
b₁, resumed and killed at b₂, resumed and killed at b₃, ... through
every ``(round, stage)`` boundary of the run, then completed.  Each
segment exercises resume-from-the-previous-crash, so a single chain
covers crash + resume at *every* boundary for the cost of a few
uninterrupted runs (instead of one full run per boundary).  The slow
sweep joins the 24-seed harness across the scan layout × clustering ×
churn-preset matrix from ``tests/test_server.py``, for both servers.
"""
import os

import numpy as np
import pytest

from repro.checkpoint import read_log
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl import FLConfig, run_federated
from repro.server.events import Stage
from repro.sim import (
    FaultPlan, Scenario, ServerKilled, make_scenario, resume_trace,
)
from scan_layouts import FLCONFIG, MATRIX as _MATRIX

SEEDS = range(24)
_PRESETS = ("mobile-churn", "straggler", "diurnal")

# every boundary guaranteed to fire each round, per server (async INGEST
# and PUBLISH boundaries are conditional — the fuzz test reaches them via
# seeded schedules instead)
_STAGES = {
    "sync": (Stage.MEMBERSHIP, Stage.SCAN, Stage.COMPUTE, Stage.INGEST,
             Stage.REFRESH, Stage.SELECT, Stage.TRAIN),
    "async": (Stage.MEMBERSHIP, Stage.DRAIN, Stage.SCAN, Stage.COMPUTE,
              Stage.REFRESH, Stage.SELECT, Stage.TRAIN),
}


@pytest.fixture(scope="module")
def resume_data():
    return FederatedDataset(small_spec(num_clients=16, num_classes=5, side=8,
                                       avg_samples=24), seed=13)


def _cfg(seed, server, layout="whole", clustering="kmeans", rounds=3,
         **kw):
    base = dict(rounds=rounds, clients_per_round=4, local_steps=1,
                summary="py", clustering=clustering, num_clusters=3,
                refresh_max_age=3, refresh_kl=0.05, recluster_every=2,
                hier_local_k=3, eval_every=2, seed=seed, server=server,
                **FLCONFIG[layout])
    base.update(kw)
    return FLConfig(**base)


def _kill_chain(data, cfg, sc_config, boundaries, tmpdir):
    """Kill one durable run at each boundary in turn, resuming between
    kills; returns (final_history, kills_fired)."""
    resume, killed = False, 0
    for point in boundaries:
        try:
            h = run_federated(data, cfg,
                              scenario=Scenario.from_config(sc_config),
                              durable=None if resume else tmpdir,
                              resume_from=tmpdir if resume else None,
                              faults=FaultPlan(crash_points=(point,)))
        except ServerKilled:
            resume, killed = True, killed + 1
            continue
        return h, killed          # a boundary never fired — caller asserts
    h = run_federated(data, cfg, scenario=Scenario.from_config(sc_config),
                      resume_from=tmpdir)
    return h, killed


def _chain_cell(data, seed, server, layout, clustering, preset, tmpdir,
                rounds=3):
    sc = make_scenario(preset, data.spec.num_clients, seed=seed).to_config()
    cfg = _cfg(seed, server, layout, clustering, rounds=rounds)
    h0 = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    boundaries = [(r, s) for r in range(rounds) for s in _STAGES[server]]
    h1, killed = _kill_chain(data, cfg, sc, boundaries, tmpdir)
    assert killed == len(boundaries), \
        f"only {killed}/{len(boundaries)} crash points fired"
    assert resume_trace(h0) == resume_trace(h1)
    return h0, h1


# ---------------------------------------------------------------------------
# quick CI variants: one cell per server


@pytest.mark.parametrize("server", ["sync", "async"])
def test_kill_chain_every_boundary_quick(resume_data, server, tmp_path):
    h0, h1 = _chain_cell(resume_data, seed=1, server=server,
                         layout="padded", clustering="kmeans",
                         preset="mobile-churn", tmpdir=str(tmp_path))
    if server == "async":
        # resumed counters match the uninterrupted run too — the
        # checkpoint carried the server machinery, not just decisions
        for key in ("events", "snapshots_published", "ingest_batches"):
            assert h0["server"][key] == h1["server"][key]


def test_resume_before_first_checkpoint_restarts(resume_data, tmp_path):
    """A crash in round 0 predates any checkpoint: resume restarts from
    scratch and still completes identically."""
    data = resume_data
    sc = make_scenario("mobile-churn", 16, seed=2).to_config()
    cfg = _cfg(2, "sync")
    h0 = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    with pytest.raises(ServerKilled):
        run_federated(data, cfg, scenario=Scenario.from_config(sc),
                      durable=str(tmp_path),
                      faults=FaultPlan(crash_points=((0, Stage.SELECT),)))
    h1 = run_federated(data, cfg, scenario=Scenario.from_config(sc),
                       resume_from=str(tmp_path))
    assert resume_trace(h0) == resume_trace(h1)


def test_resume_config_mismatch_fails(resume_data, tmp_path):
    data = resume_data
    sc = make_scenario("mobile-churn", 16, seed=3).to_config()
    with pytest.raises(ServerKilled):
        run_federated(data, _cfg(3, "sync"),
                      scenario=Scenario.from_config(sc),
                      durable=str(tmp_path),
                      faults=FaultPlan(crash_points=((1, Stage.TRAIN),)))
    with pytest.raises(ValueError, match="config mismatch"):
        run_federated(data, _cfg(3, "sync", clients_per_round=5),
                      scenario=Scenario.from_config(sc),
                      resume_from=str(tmp_path))
    # a different scenario is just as fatal
    sc2 = make_scenario("mobile-churn", 16, seed=4).to_config()
    with pytest.raises(ValueError, match="scenario mismatch"):
        run_federated(data, _cfg(3, "sync"),
                      scenario=Scenario.from_config(sc2),
                      resume_from=str(tmp_path))


def test_resume_from_empty_dir_fails(resume_data, tmp_path):
    with pytest.raises(FileNotFoundError, match="no event log"):
        run_federated(resume_data, _cfg(0, "sync"),
                      resume_from=str(tmp_path))


def test_durable_log_records(resume_data, tmp_path):
    """The event log narrates the run: header, per-event commits, round
    lineage, checkpoints — and a resume marker after a crash."""
    data = resume_data
    sc = make_scenario("mobile-churn", 16, seed=5).to_config()
    cfg = _cfg(5, "async")
    with pytest.raises(ServerKilled):
        run_federated(data, cfg, scenario=Scenario.from_config(sc),
                      durable=str(tmp_path),
                      faults=FaultPlan(crash_points=((2, Stage.SELECT),)))
    run_federated(data, cfg, scenario=Scenario.from_config(sc),
                  resume_from=str(tmp_path))
    records = read_log(os.path.join(str(tmp_path), "events.jsonl"))
    kinds = [r["type"] for r in records]
    assert kinds[0] == "header"
    assert records[0]["log_schema"] == 1
    assert "resume" in kinds
    rounds = [r for r in records if r["type"] == "round"]
    # rounds 0..1 committed pre-crash; the crashed round 2 was
    # re-executed and committed by the resumed process
    assert [r["round"] for r in rounds] == [0, 1, 2]
    for rec in rounds:
        assert rec["registry_version"] >= 0
        assert rec["snapshot_version"] >= 0
        assert all(isinstance(c, int) for c in rec["selected"])
    ckpts = [r for r in records if r["type"] == "checkpoint"]
    assert ckpts and all(
        os.path.exists(os.path.join(str(tmp_path), c["base"] + ".npz"))
        for c in ckpts)
    events = [r for r in records if r["type"] == "event"]
    assert events, "no event records"
    # committed events respect the (round, stage, seq) total order
    # within each process lifetime (the resume marker splits lifetimes)
    assert all({"round", "stage", "seq", "kind"} <= set(e) for e in events)


def test_torn_log_tail_is_recovered(resume_data, tmp_path):
    """A crash mid-append leaves a torn final line; resume drops it and
    still replays to the identical trace."""
    data = resume_data
    sc = make_scenario("mobile-churn", 16, seed=6).to_config()
    cfg = _cfg(6, "sync")
    h0 = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    with pytest.raises(ServerKilled):
        run_federated(data, cfg, scenario=Scenario.from_config(sc),
                      durable=str(tmp_path),
                      faults=FaultPlan(crash_points=((2, Stage.REFRESH),)))
    log = os.path.join(str(tmp_path), "events.jsonl")
    with open(log, "a") as f:
        f.write('{"type": "event", "round": 2, "sta')   # torn append
    h1 = run_federated(data, cfg, scenario=Scenario.from_config(sc),
                       resume_from=str(tmp_path))
    assert resume_trace(h0) == resume_trace(h1)


def test_checkpoint_cadence(resume_data, tmp_path):
    """checkpoint_every > 1 thins the captures; resume re-executes the
    uncheckpointed suffix and still matches."""
    from repro.checkpoint import Durability
    data = resume_data
    sc = make_scenario("diurnal", 16, seed=7).to_config()
    cfg = _cfg(7, "async", rounds=4)
    h0 = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    dur = Durability(dir=str(tmp_path), checkpoint_every=2)
    with pytest.raises(ServerKilled):
        run_federated(data, cfg, scenario=Scenario.from_config(sc),
                      durable=dur,
                      faults=FaultPlan(crash_points=((3, Stage.SCAN),)))
    names = os.listdir(str(tmp_path))
    assert "ckpt_000001.npz" in names and "ckpt_000000.npz" not in names
    h1 = run_federated(data, cfg, scenario=Scenario.from_config(sc),
                       durable=dur, resume_from=str(tmp_path))
    assert resume_trace(h0) == resume_trace(h1)


# ---------------------------------------------------------------------------
# the full sweep: 24 seeds × both servers, rotating through the
# scan layout × clustering × churn-preset matrix (same rotation as
# tests/test_server.py, so every combo is hit across the seed range)


@pytest.mark.slow
@pytest.mark.parametrize("server", ["sync", "async"])
@pytest.mark.parametrize("seed", SEEDS)
def test_kill_chain_matrix(resume_data, seed, server, tmp_path):
    layout, clustering = _MATRIX[seed % len(_MATRIX)]
    preset = _PRESETS[seed % len(_PRESETS)]
    _chain_cell(resume_data, seed=seed, server=server, layout=layout,
                clustering=clustering, preset=preset, tmpdir=str(tmp_path),
                rounds=2)
