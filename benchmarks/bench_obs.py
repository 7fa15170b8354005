"""§10 — telemetry overhead: instrumentation must be off the clock.

The paper's headline numbers are *overhead* measurements, so the
telemetry that measures them must not move them.  Three records, two
asserts:

  * **A/B server rounds at fleet scale** — the 100k-client headless
    server loop (``bench_server.run_server``, the paper-scale critical
    path) with the observer disabled (the default null object) vs
    enabled (``obs.observe``).  Arms are interleaved in alternating
    order and each round's floor is the min across repeats, so linear
    machine drift cancels and heavy-tail scheduler noise is clipped.
    Asserted: enabled adds less than ``OVERHEAD_BUDGET`` (2%) *plus the
    measured noise floor* — the disabled arm's own split-half
    disagreement, so a genuinely hot instrumentation path fails the
    gate while container jitter does not.
  * **accounted upper bound** — events-per-round from a real
    ``fl.rounds`` federation run under ``obs.observe`` (hook counts are
    scale-independent) × the measured per-hook cost, charged against
    the fleet-scale per-round critical floor *as if every hook sat on
    the critical path* (it does not: spans open outside the timed
    windows by design).  Even this overestimate must stay under the 2%
    budget — asserted unconditionally; it is deterministic, so it is
    the CI-stable teeth of the gate.
  * **hook microcosts** — per-call cost of a disabled span (the no-op
    everyone pays by default), an enabled span, an instant event, a
    counter inc and a histogram record, so a regression in any hook is
    visible as its own record instead of hiding inside a 2% budget.

The gate covers the PR-10 drill-down surfaces too: the enabled A/B arm
arms the **flight recorder** (``flight_path`` streaming to disk), the
accounted bound charges flight-record appends and labeled-family
writes at their measured per-call costs on top of the span events, and
``obs/labeled/*`` / ``obs/recorder/*`` records expose those costs
individually (the disabled recorder check must stay at one attribute
read).

CSV: ``obs/overhead/critical`` (A/B floors + fractions),
``obs/overhead/accounted`` (the upper bound), ``obs/hook/*``,
``obs/labeled/*`` and ``obs/recorder/*``.
"""
from __future__ import annotations

import os
import tempfile
import time

import numpy as np

import repro.api as api
import repro.obs as obs
from benchmarks._record import emit
from benchmarks.bench_server import run_server
from repro.data.synthetic import FederatedDataset, small_spec
from repro.obs.metrics import split_labeled
from repro.obs.recorder import FlightRecorder

OVERHEAD_BUDGET = 0.02     # enabled tracer may add <2% to the critical path
N_CLIENTS = 100_000        # the paper-scale fleet the claim is about


def _critical_rounds(out_dir: str | None, rounds: int,
                     seed: int) -> np.ndarray:
    """One headless server run; per-round critical-path seconds."""
    if out_dir is None:
        r = run_server(N_CLIENTS, "sync", rounds=rounds, seed=seed)
    else:
        with obs.observe(
                trace_path=os.path.join(out_dir, "trace.json"),
                metrics_path=os.path.join(out_dir, "metrics.jsonl"),
                flight_path=os.path.join(out_dir, "flight.jsonl")):
            r = run_server(N_CLIENTS, "sync", rounds=rounds, seed=seed)
    return np.asarray(r["critical_per_round"])


def run_ab(rounds: int = 8, repeats: int = 4, seed: int = 0) -> dict:
    """Disabled-vs-enabled A/B over the fleet-scale server loop."""
    _critical_rounds(None, 3, seed)            # warmup: jit compile etc.
    disabled, enabled = [], []
    with tempfile.TemporaryDirectory() as tmp:
        for i in range(repeats):               # alternate arm order so
            arms = [(disabled, None), (enabled, tmp)]   # slow machine
            for acc, out in (arms if i % 2 == 0 else arms[::-1]):  # drift
                acc.append(_critical_rounds(out, rounds, seed))    # cancels
    dis = np.minimum.reduce(disabled)          # per-round floors
    en = np.minimum.reduce(enabled)
    # the disabled arm's own split-half disagreement is the wall-clock
    # noise this box cannot measure below — the A/B assert budgets it
    half_a = np.minimum.reduce(disabled[0::2]).sum()
    half_b = np.minimum.reduce(disabled[1::2]).sum()
    noise = abs(half_a / max(half_b, 1e-12) - 1.0)
    return {"rounds": rounds, "repeats": repeats,
            "disabled_s": float(dis.sum()), "enabled_s": float(en.sum()),
            "overhead_frac": float(en.sum() / max(dis.sum(), 1e-12) - 1.0),
            "noise_frac": noise}


def _percall(fn, n: int = 20000) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def run_hooks() -> dict:
    """Per-call hook costs, both observer states."""
    assert not obs.enabled()
    out = {"span_disabled": _percall(lambda: obs.span("x", round=1))}

    def span_body():
        with obs.span("x", round=1):
            pass
    ob = obs.enable()
    try:
        out["span_enabled"] = _percall(span_body)
        out["instant_enabled"] = _percall(lambda: obs.instant("x", v=1))
        out["counter_inc"] = _percall(
            ob.metrics.counter("bench/hook").inc)
        hist = ob.metrics.histogram("bench/hook_s")
        out["histogram_record"] = _percall(lambda: hist.record(1e-3))
        # labeled-family writes: the hot path is child-cache hit + the
        # underlying instrument write — a get-or-create per call would
        # show up here as a regression
        cfam = ob.metrics.family("bench/labeled", labels=("k",))
        out["labeled_counter_inc"] = _percall(
            lambda: cfam.labeled("a").inc())
        hfam = ob.metrics.family("bench/labeled_s", labels=("k",),
                                 kind="histogram")
        out["labeled_histogram_record"] = _percall(
            lambda: hfam.labeled("a").record(1e-3))
    finally:
        obs.disable()
    # recorder costs: the disabled check every hook site pays (one
    # attribute read off the null object) and an in-memory record append
    out["recorder_disabled"] = _percall(lambda: obs.recorder().enabled)
    rec = FlightRecorder()
    out["recorder_record"] = _percall(
        lambda: rec.record("bench", round=1, n=3, ids=[1, 2, 3]))
    return out


def hooks_per_round(seed: int = 0) -> dict:
    """Telemetry events per round of a fully-hooked *real* federation
    run (async server, staleness refresher, bounded-ingest check-in
    front end, flight recorder armed) — the hook counts are a property
    of the code path, not the fleet size.  Returns per-round rates for
    tracer events, flight-record appends and labeled-family writes."""
    from repro.sim import presets
    data = FederatedDataset(small_spec(num_clients=64, num_classes=5,
                                       side=8, avg_samples=24), seed=seed)
    cfg = api.RunConfig(
        rounds=6, clients_per_round=8, local_steps=1, summary="py",
        refresh_max_age=3, refresh_kl=0.05, eval_every=6, seed=seed,
        clustering=api.ClusteringConfig(kind="online", num_clusters=4),
        server=api.ServerConfig(kind="async", refresh="staleness",
                                ingest_delay_rounds=1, snapshot_max_age=2,
                                drift_mass_trigger=0.1,
                                frontend=api.FrontendConfig(
                                    kind="poisson", slo_p99_s=0.002,
                                    ingest_max_depth=8)))
    scen = presets.make_scenario("mobile-churn", 64, seed=seed)
    with obs.observe(flight=True) as ob:
        h = api.run(data, cfg, scenario=scen)
    # labeled writes land in the run's own registry (history metrics);
    # count value/count/writes per child — an overestimate for bulk incs,
    # which only strengthens the accounted upper bound
    labeled = 0.0
    for name, snap in h["metrics"].items():
        if split_labeled(name)[1] is None:
            continue
        labeled += (snap.get("count") or snap.get("writes")
                    or abs(snap.get("value") or 0))
    return {"events": len(ob.tracer.events) / cfg.rounds,
            "flight": len(ob.flight.records) / cfg.rounds,
            "labeled": labeled / cfg.rounds}


def main(fast: bool = True, seed: int = 0):
    ab = run_ab(rounds=8 if fast else 12, seed=seed)
    per_round = (ab["enabled_s"] - ab["disabled_s"]) / ab["rounds"]
    emit("obs/overhead/critical", us=max(per_round, 0.0) * 1e6,
         disabled_s=f"{ab['disabled_s']:.5f}",
         enabled_s=f"{ab['enabled_s']:.5f}",
         overhead_frac=f"{ab['overhead_frac']:.4f}",
         noise_frac=f"{ab['noise_frac']:.4f}",
         budget=f"{OVERHEAD_BUDGET:.2f}", rounds=ab["rounds"],
         n=N_CLIENTS)
    hooks = run_hooks()
    for name, s in hooks.items():
        group = ("obs/labeled" if name.startswith("labeled_")
                 else "obs/recorder" if name.startswith("recorder_")
                 else "obs/hook")
        emit(f"{group}/{name}", us=s * 1e6)
    rates = hooks_per_round(seed=seed)
    # worst-case accounting: every tracer event charged at full
    # enabled-span cost, every flight record at the in-memory append
    # cost, every labeled write at the child-lookup+inc cost — all of it
    # on the critical path
    accounted_s = (rates["events"] * hooks["span_enabled"]
                   + rates["flight"] * hooks["recorder_record"]
                   + rates["labeled"] * hooks["labeled_counter_inc"])
    critical_floor = ab["disabled_s"] / ab["rounds"]
    accounted_frac = accounted_s / max(critical_floor, 1e-12)
    emit("obs/overhead/accounted", us=accounted_s * 1e6,
         events_per_round=f"{rates['events']:.1f}",
         flight_per_round=f"{rates['flight']:.1f}",
         labeled_per_round=f"{rates['labeled']:.1f}",
         accounted_frac=f"{accounted_frac:.5f}",
         budget=f"{OVERHEAD_BUDGET:.2f}")
    # the acceptance gates: enabled telemetry (spans + labeled metrics +
    # flight recorder) stays under 2% of the fleet-scale critical path —
    # deterministically by accounting, and by wall-clock A/B up to this
    # box's measured noise floor
    assert accounted_frac < OVERHEAD_BUDGET, (
        f"accounted telemetry upper bound {accounted_frac:.2%} exceeds the "
        f"{OVERHEAD_BUDGET:.0%} budget ({rates['events']:.0f} events + "
        f"{rates['flight']:.0f} flight records + {rates['labeled']:.0f} "
        f"labeled writes per round vs {critical_floor * 1e3:.2f}ms "
        f"critical)")
    assert ab["overhead_frac"] < OVERHEAD_BUDGET + ab["noise_frac"], (
        f"enabled-tracer A/B overhead {ab['overhead_frac']:.2%} exceeds the "
        f"{OVERHEAD_BUDGET:.0%} budget plus the {ab['noise_frac']:.2%} "
        f"measured noise floor (disabled {ab['disabled_s']:.4f}s, enabled "
        f"{ab['enabled_s']:.4f}s over {ab['rounds']} round floors)")
    return [ab | {"name": "obs/overhead/critical"},
            {"name": "obs/overhead/accounted",
             "accounted_frac": accounted_frac} | rates,
            {"name": "obs/hooks"} | hooks]


if __name__ == "__main__":
    main(fast=False)
