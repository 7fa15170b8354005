"""The readers of the program's own spans (chipbench/program_spans.py) on
a hand-built profiled record: two traced rounds of known spans."""
from __future__ import annotations

import pytest

import repro.obs as program_obs
from chipbench.run import load_reader
from repro.obs import Tracer

READERS = ("h2d_mb", "summary_host_ms", "summary_put_ms", "summary_wait_ms",
           "summary_fill_pct", "recluster_host_ms", "recluster_put_ms",
           "recluster_wait_ms")
# per round: (name, microseconds, args)
ROUND = [
    ("drift_scan/chunks", 150.0, {"rows": 10, "chunks": 1}),
    ("drift_scan/put", 100.0, {"bytes": 1_000_000}),
    ("summary/load", 1000.0, {}),
    ("summary/assemble", 500.0, {"slots": 100, "filled": 60}),
    ("summary/put", 2000.0, {"bytes": 3_000_000}),
    ("summary/execute", 4000.0, {}),
    ("recluster/gather", 300.0, {"flag": True}),
    ("recluster/put", 700.0, {"bytes": 500_000}),
    ("recluster/fit", 9000.0, {}),
    ("select_devices", 50.0, {"round": 7, "n_selected": 10}),
]
# two such rounds: 9 MB copied, 4.5 MB a round
WANT = {"h2d_mb": 4.5, "summary_host_ms": 1.5, "summary_put_ms": 2.0,
        "summary_wait_ms": 4.0, "summary_fill_pct": 60.0,
        "recluster_host_ms": 0.3, "recluster_put_ms": 0.7,
        "recluster_wait_ms": 9.0}
TRACED = {"trace": {"window_s": 1.0, "busy_s": 0.1}}


def record(rounds: int, spans=ROUND) -> Tracer:
    tr = Tracer()
    ts = 0.0
    for _ in range(rounds):
        for name, dur, args in spans:
            ev = {"name": name, "cat": "server", "ph": "X", "ts": ts,
                  "dur": dur, "pid": 1, "tid": 1}
            if args:
                ev["args"] = dict(args)
            tr.events.append(ev)
            ts += dur
    # a point event and a counter sample are not spans: never counted
    tr.events.append({"name": "summary/put", "ph": "i", "ts": ts,
                      "args": {"bytes": 10 ** 9}})
    tr.events.append({"name": "depth", "ph": "C", "ts": ts,
                      "args": {"value": 3.0}})
    return tr


@pytest.fixture()
def profiled(monkeypatch):
    def use(tracer):
        monkeypatch.setattr(program_obs, "profiled", lambda: tracer)
    return use


@pytest.mark.parametrize("name", READERS)
def test_reader_on_two_traced_rounds(profiled, name):
    profiled(record(2))
    assert load_reader(name)(TRACED) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_nothing_when_no_round_was_traced(profiled, name):
    no_rounds = [s for s in ROUND if s[0] != "select_devices"]
    profiled(record(2, no_rounds))
    assert load_reader(name)(TRACED) is None
    profiled(Tracer())
    assert load_reader(name)(TRACED) is None


def test_readers_give_nothing_for_a_run_without_a_trace(profiled):
    profiled(record(2))
    for name in READERS:
        assert load_reader(name)({"trace": None}) is None


def test_readers_give_nothing_for_a_program_without_the_record(monkeypatch):
    """The parent of this change has no ``repro.obs.profiled``: its traced
    runs leave these metrics out and do not raise."""
    monkeypatch.delattr(program_obs, "profiled")
    for name in READERS:
        assert load_reader(name)(TRACED) is None


def test_rounds_without_the_summary_engine(profiled):
    """Uploaded summaries (openimage): the engine's spans are absent, so
    its times read 0 and its fill share nothing; clustering still reads."""
    uploads = [s for s in ROUND if not s[0].startswith("summary/")]
    profiled(record(3, uploads))
    assert load_reader("summary_put_ms")(TRACED) == 0.0
    assert load_reader("summary_fill_pct")(TRACED) is None
    assert load_reader("recluster_wait_ms")(TRACED) == pytest.approx(9.0)
    assert load_reader("h2d_mb")(TRACED) == pytest.approx(1.5)
