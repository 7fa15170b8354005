"""Checkpoint round-trip tests (DESIGN.md §9): the registry under every
scan layout, both cluster maintainers, snapshots and the driver RNG
serialize through ``checkpoint.save_state``/``load_state`` and restore
*bitwise* — version counters, ``has_mask``, ``dense()`` bytes, and future
behavior all identical."""
import os

import jax
import numpy as np
import pytest

from repro.checkpoint import (
    load_state, maintainer_state, registry_state, restore_maintainer,
    restore_registry, restore_snapshot, save_state, snapshot_state,
)
from repro.checkpoint.server_state import restore_rng, rng_state
from repro.core import RefreshPolicy
from repro.server.snapshot import capture
from repro.shard import HierarchicalClusterMaintainer
from repro.stream import OnlineClusterMaintainer, OnlinePolicy, SummaryRegistry
from scan_layouts import REGISTRY as LAYOUTS

N, C, D = 20, 5, 8
POLICY = RefreshPolicy(max_age_rounds=4, kl_threshold=0.08)


def _mk_registry(layout):
    return SummaryRegistry(N, POLICY, num_classes=C, **LAYOUTS[layout])


def _populate(reg, seed, rounds=3):
    """A realistic mutation history: updates, partial rounds, evictions."""
    rs = np.random.RandomState(seed)
    for rnd in range(rounds):
        fresh = rs.dirichlet([0.4] * C, N).astype(np.float32)
        ids = [int(c) for c in
               np.flatnonzero(reg.stale_mask(rnd, fresh))
               if rs.rand() > 0.25]
        if ids:
            summaries = rs.rand(len(ids), D).astype(np.float32)
            reg.update_batch(ids, rnd, summaries, fresh[ids])
        if rs.rand() > 0.5:
            reg.remove(int(rs.randint(N)))
    return rs


# ---------------------------------------------------------------------------
# generic mixed-tree state files


def test_save_state_roundtrip_mixed_tree(tmp_path):
    tree = {
        "arrays": {"f32": np.arange(6, dtype=np.float32).reshape(2, 3),
                   "i64": np.array([1, -2, 3], np.int64),
                   "bool": np.array([True, False]),
                   "empty": np.zeros((0, 4), np.float32)},
        "scalars": {"i": 3, "f": 1.5, "nan": float("nan"),
                    "inf": float("inf"), "s": "text", "none": None,
                    "flag": True, "np_int": np.int64(7)},
        "listy": [1, [2.5, None], {"deep": np.ones(2)}],
        "tup": (1, 2),
    }
    base = os.path.join(str(tmp_path), "state")
    save_state(base, tree)
    got = load_state(base)
    np.testing.assert_array_equal(got["arrays"]["f32"],
                                  tree["arrays"]["f32"])
    assert got["arrays"]["f32"].dtype == np.float32
    assert got["arrays"]["i64"].dtype == np.int64
    assert got["arrays"]["empty"].shape == (0, 4)
    s = got["scalars"]
    assert s["i"] == 3 and s["f"] == 1.5 and s["s"] == "text"
    assert s["none"] is None and s["flag"] is True and s["np_int"] == 7
    assert np.isnan(s["nan"]) and np.isinf(s["inf"])
    assert got["listy"][0] == 1 and got["listy"][1] == [2.5, None]
    np.testing.assert_array_equal(got["listy"][2]["deep"], np.ones(2))
    assert got["tup"] == [1, 2]          # JSON has no tuples
    # atomic write: no temp files survive a successful save
    assert not [f for f in os.listdir(str(tmp_path)) if ".tmp" in f]


def test_save_state_overwrites_atomically(tmp_path):
    base = os.path.join(str(tmp_path), "ck")
    save_state(base, {"v": 1, "a": np.zeros(3)})
    save_state(base, {"v": 2, "a": np.ones(3)})
    got = load_state(base)
    assert got["v"] == 2
    np.testing.assert_array_equal(got["a"], np.ones(3))


def test_save_state_rejects_unserializable(tmp_path):
    with pytest.raises(TypeError, match="unsupported state leaf"):
        save_state(os.path.join(str(tmp_path), "bad"), {"x": object()})
    with pytest.raises(TypeError, match="keys must be str"):
        save_state(os.path.join(str(tmp_path), "bad"), {1: "intkey"})


# ---------------------------------------------------------------------------
# the registry, under each scan layout


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("seed", range(5))
def test_registry_roundtrip(tmp_path, layout, seed):
    reg = _mk_registry(layout)
    rs = _populate(reg, seed)
    base = os.path.join(str(tmp_path), "reg")
    save_state(base, {"registry": registry_state(reg)})
    fresh_reg = _mk_registry(layout)
    restore_registry(fresh_reg, load_state(base)["registry"])

    assert fresh_reg.version == reg.version
    assert fresh_reg.refresh_count == reg.refresh_count
    np.testing.assert_array_equal(fresh_reg.has_mask(), reg.has_mask())
    np.testing.assert_array_equal(fresh_reg.last_refresh, reg.last_refresh)
    have = np.flatnonzero(reg.has_mask())
    if have.size:
        # matrix bytes are identical, not just close
        assert (fresh_reg.matrix_rows(have).tobytes()
                == reg.matrix_rows(have).tobytes())
        assert fresh_reg.dense().tobytes() == reg.dense().tobytes()
    # future decisions replay: same stale set on a fresh drift signal
    fresh = rs.dirichlet([0.4] * C, N).astype(np.float32)
    np.testing.assert_array_equal(fresh_reg.stale_mask(7, fresh),
                                  reg.stale_mask(7, fresh))
    assert fresh_reg.scan_chunks == reg.scan_chunks
    assert fresh_reg.rechecked_rows == reg.rechecked_rows


def test_registry_full_matrix_bytes(tmp_path):
    """With every client populated, the full ``dense()`` matrix
    round-trips bitwise under every layout."""
    for layout in LAYOUTS:
        reg = _mk_registry(layout)
        rs = np.random.RandomState(0)
        fresh = rs.dirichlet([0.4] * C, N).astype(np.float32)
        reg.update_batch(np.arange(N), 0,
                         rs.rand(N, D).astype(np.float32), fresh)
        base = os.path.join(str(tmp_path), f"full-{layout}")
        save_state(base, registry_state(reg))
        other = _mk_registry(layout)
        restore_registry(other, load_state(base))
        assert other.has_mask().all()
        assert other.dense().tobytes() == reg.dense().tobytes()


def test_registry_restore_mismatch_fails(tmp_path):
    reg = _mk_registry("padded")
    _populate(reg, 0)
    st = registry_state(reg)
    # states of the removed dict and streaming registries are refused
    for kind in ("dict", "streaming"):
        with pytest.raises(ValueError, match="backend"):
            restore_registry(_mk_registry("padded"), dict(st, kind=kind))
    with pytest.raises(ValueError, match="num_clients"):
        restore_registry(SummaryRegistry(N + 1, POLICY, num_classes=C), st)


# ---------------------------------------------------------------------------
# cluster maintainers


def _drive_maintainer(m, rs, rounds=4, n=N):
    x = rs.rand(n, D).astype(np.float32)
    live = np.ones(n, bool)
    for rnd in range(rounds):
        drifted = np.flatnonzero(rs.rand(n) < 0.4).astype(np.int64)
        x[drifted] += rs.rand(drifted.size, D).astype(np.float32)
        m.refresh(x, drifted, jax.random.PRNGKey(rnd), live=live)
    return x, live


@pytest.mark.parametrize("kind", ["online", "hierarchical"])
def test_maintainer_roundtrip(tmp_path, kind):
    policy = OnlinePolicy(inertia_ratio=1.5, reseed_every=3)
    def mk():
        if kind == "online":
            return OnlineClusterMaintainer(3, policy)
        return HierarchicalClusterMaintainer(3, n_shards=2, local_k=3,
                                             policy=policy)
    m = mk()
    rs = np.random.RandomState(1)
    x, live = _drive_maintainer(m, rs)
    base = os.path.join(str(tmp_path), f"mnt-{kind}")
    save_state(base, {"m": maintainer_state(m)})
    other = mk()
    restore_maintainer(other, load_state(base)["m"])

    assert other.centroids.tobytes() == m.centroids.tobytes()
    assert other.assignment.tobytes() == m.assignment.tobytes()
    assert other.full_fits == m.full_fits
    assert other.reseeds == m.reseeds
    if kind == "online":
        assert other.dists.tobytes() == m.dists.tobytes()
        assert other.last_full_inertia == m.last_full_inertia
        assert other._refreshes == m._refreshes
    else:
        assert other.merges == m.merges
        assert other.last_merge_inertia == m.last_merge_inertia
    # behavioral equivalence: the *next* refresh decides identically
    drifted = np.arange(0, N, 3, dtype=np.int64)
    m.refresh(x, drifted, jax.random.PRNGKey(99), live=live)
    other.refresh(x, drifted, jax.random.PRNGKey(99), live=live)
    np.testing.assert_array_equal(other.assignment, m.assignment)
    np.testing.assert_array_equal(other.centroids, m.centroids)
    assert other.full_fits == m.full_fits


def test_maintainer_none_roundtrip():
    assert maintainer_state(None) is None
    restore_maintainer(None, None)            # no-op, no raise
    with pytest.raises(ValueError, match="maintainer"):
        restore_maintainer(None, {"kind": "online"})


# ---------------------------------------------------------------------------
# snapshots + RNG


def test_snapshot_roundtrip(tmp_path):
    reg = _mk_registry("padded")
    _populate(reg, 2)
    snap = capture(5, 3, reg, np.arange(N) % 3, 3, drift_mass=0.25)
    base = os.path.join(str(tmp_path), "snap")
    save_state(base, {"snap": snapshot_state(snap)})
    got = restore_snapshot(load_state(base)["snap"])
    assert got.version == 5 and got.round_idx == 3
    assert got.registry_version == reg.version
    assert got.num_clusters == 3 and got.drift_mass == 0.25
    np.testing.assert_array_equal(got.assignment, snap.assignment)
    np.testing.assert_array_equal(got.has_mask, snap.has_mask)
    # restored snapshots stay immutable
    assert not got.assignment.flags.writeable
    assert not got.has_mask.flags.writeable


def test_rng_roundtrip(tmp_path):
    rs = np.random.RandomState(42)
    rs.rand(137)                              # mid-stream state
    rs.randn(3)                               # with a cached gaussian
    base = os.path.join(str(tmp_path), "rng")
    save_state(base, {"rng": rng_state(rs)})
    other = np.random.RandomState(0)
    restore_rng(other, load_state(base)["rng"])
    np.testing.assert_array_equal(other.rand(50), rs.rand(50))
    np.testing.assert_array_equal(other.randn(50), rs.randn(50))
    np.testing.assert_array_equal(other.permutation(100), rs.permutation(100))
