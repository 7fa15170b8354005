"""The traffic generator at a tiny size: the same seed gives the same
inputs, every seed and every round the same amount of work, and the stale
share is exact."""
from __future__ import annotations

import json

import numpy as np

from chipbench import reference as ref
from chipbench.tests.conftest import HERE, MIX, TOY
from chipbench.traffic import drift


def config(name):
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    return dict(cfg, **TOY[name])


CFG = config("femnist")
SEED = 2 ** 31 + 977      # seeds run past 32 signed bits


def test_deterministic_per_seed():
    a, b = drift.make(CFG, MIX, SEED), drift.make(CFG, MIX, SEED)
    assert np.array_equal(a.states, b.states)
    assert np.array_equal(a.available, b.available)
    assert np.array_equal(a.fleet.py, b.fleet.py)
    assert np.array_equal(a.fleet.sizes, b.fleet.sizes)
    for s in range(2):
        assert np.array_equal(a.data.images[s], b.data.images[s])
    c = drift.make(CFG, MIX, SEED + 1)
    assert not np.array_equal(a.states, c.states)


def test_same_work_for_every_seed_and_round():
    sizes = [np.sort(drift.Fleet(CFG, s).sizes) for s in (1, 2, SEED)]
    assert all(np.array_equal(sizes[0], s) for s in sizes)
    assert sizes[0].min() >= CFG["min_samples"]
    assert sizes[0].max() <= CFG["max_samples"]
    for seed in (1, SEED):
        tr = drift.make(CFG, MIX, seed)
        work = {tuple(np.sort(tr.fleet.sizes[f])) for f in tr.flips[1:]}
        assert len(work) == 1
    # the levels a small drift share switches are spread over the sizes
    few = drift.flip_sets(drift.Fleet(CFG, 3), 2, 4, np.random.default_rng(0))
    levels = drift.Fleet(CFG, 3).level
    assert all(sorted(levels[f]) == [2, 6] for f in few)


def test_sizes_follow_the_source_moments():
    cfg = dict(CFG, num_clients=28000, size_levels=2800, samples_mean=226.83,
               samples_sd=88.94, max_samples=10 ** 6)
    q = drift.quantile_sizes(cfg)
    assert abs(q.mean() / 226.83 - 1) < 0.01
    assert abs(q.std() / 88.94 - 1) < 0.05


def test_stale_share_exact():
    traffic = drift.make(CFG, MIX, SEED)
    n_flip = round(MIX["drift_share"] * CFG["num_clients"])
    for r in range(1, traffic.rounds):
        assert traffic.flips[r].size == n_flip
        stale = ref.stale_set(traffic.label_dists(r - 1),
                              traffic.label_dists(r),
                              CFG["server"]["refresh_kl"])
        assert np.array_equal(stale, traffic.flips[r])
    assert traffic.available.sum(1).tolist() == [
        round(MIX["available_share"] * CFG["num_clients"])] * traffic.rounds


def test_uploads_shape_and_structure():
    cfg = config("openimage")
    fleet = drift.Fleet(cfg, SEED)
    up = drift.Uploads(fleet)
    c, h = cfg["num_classes"], cfg["server"]["encoder_dim"]
    kept = np.minimum(fleet.sizes, cfg["server"]["coreset_k"])
    for s in range(2):
        rows = up.rows[s]
        assert rows.shape == (cfg["num_clients"], c * h + c)
        assert np.array_equal(rows[:, c * h:], fleet.py[s])
        present = np.any(rows[:, :c * h].reshape(-1, c, h) != 0, axis=2)
        assert np.all(present.sum(1) <= kept)
