"""A whole run of each toy cell on the CPU, past the harness's look for a
chip: sound runs are correct, the bfloat16 control is not, and neither is
a run whose timed path is broken underneath.

The faults a cell of this benchmark can have (there is no exchange between
chips on one chip):

* a step that returns its state unchanged: ingest stores nothing, or the
  clustering refresh does nothing;
* half of the batch left out: summaries for half the stale clients only;
* an answer altered where it is produced: one summary value, one selected
  client, or a clustering collapsed into one cluster.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from chipbench import run

SEED = 2 ** 31 + 5
SECONDS = 0.5


def run_toy(root, cell, **kw):
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    return run.run_cell(manifest, cell, SEED, SECONDS, False,
                        require_tpu=False, root=root, **kw)


def failing(checks):
    return {k for k, v in checks.items() if v["value"] > v["limit"]}


@pytest.mark.parametrize("cell", ["femnist.toy", "openimage.toy"])
def test_sound_run_is_correct_and_control_is_not(toy_root, cell):
    res = run_toy(toy_root, cell, control=True)
    assert res["correct"] and res["attempted"] > 0 and res["failed"] == 0
    assert not failing(res["checks"])
    assert {"round_s", "setup_s"} <= set(res["metrics"])
    assert failing(res["control_checks"])
    assert list(res)[-1] == "checks"


def _ingest_nothing(self, rnd, summaries, fresh_rows):
    return None


def _half_batch(orig):
    def compute(self, rnd, stale, drift):
        return orig(self, rnd, stale[: len(stale) // 2], drift)
    return compute


def _altered_summary(orig):
    def compute(self, rnd, stale, drift):
        s, t, w = orig(self, rnd, stale, drift)
        if s:
            c = next(iter(s))
            s[c] = s[c].copy()
            s[c][0] += 0.5
        return s, t, w
    return compute


def _altered_selection(orig):
    def select(self, rnd, plan, fresh=None, **kw):
        sel = orig(self, rnd, plan, fresh, **kw).copy()
        pool = np.setdiff1d(np.flatnonzero(plan.available), sel)
        sel[0] = pool[0]
        return sel
    return select


def _recluster_nothing(orig):
    def recluster(self, *a, **kw):
        return None
    return recluster


def _collapsed(orig):
    def recluster(self, *a, **kw):
        out = orig(self, *a, **kw)
        self.assignment = np.zeros_like(self.assignment)
        return out
    return recluster


FAULTS = {
    "state_unchanged": ("ingest", lambda orig: _ingest_nothing),
    "half_batch": ("compute_summaries", _half_batch),
    "altered_summary": ("compute_summaries", _altered_summary),
    "altered_selection": ("select", _altered_selection),
    "recluster_unchanged": ("recluster_now", _recluster_nothing),
    "collapsed_clustering": ("recluster_now", _collapsed),
}
CASES = [(f, "femnist.toy") for f in sorted(FAULTS)] + [
    (f, "openimage.toy") for f in ("state_unchanged", "altered_selection",
                                   "recluster_unchanged",
                                   "collapsed_clustering")]


@pytest.mark.parametrize("fault,cell", CASES)
def test_broken_timed_path_is_not_correct(toy_root, monkeypatch, fault, cell):
    from repro.fl.rounds import RoundContext
    method, wrap = FAULTS[fault]
    orig = getattr(RoundContext, method)

    # break the rounds after set-up, as a fault in the timed path would
    calls = {"n": 0}
    broken = wrap(orig)

    def patched(self, *a, **kw):
        calls["n"] += 1
        if self.scenario.plans and a and a[0] >= 2:
            return broken(self, *a, **kw)
        return orig(self, *a, **kw)
    monkeypatch.setattr(RoundContext, method, patched)
    res = run_toy(toy_root, cell)
    assert not res["correct"], res["checks"]
    assert failing(res["checks"])
