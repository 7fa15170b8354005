"""Observability quickstart (DESIGN.md §10): one traced federation run.

    PYTHONPATH=src python examples/fl_observe.py --out obs_artifacts
    PYTHONPATH=src python examples/fl_observe.py --rounds 6 --clients 64 \
        --out obs_artifacts                      # CI quick mode

Runs an async federation (bounded-staleness refresher — the
configuration with the most moving parts) under ``repro.obs.observe``
and writes two artifacts:

  * ``<out>/trace.json``   — Chrome trace-event JSON.  Open
    https://ui.perfetto.dev and drag the file in (or load it in
    ``chrome://tracing``): the ``round-critical`` lane shows every stage
    span (scan → summaries → scatter → recluster → select → train), the
    ``background`` lane the off-path clustering rebuilds, with counter
    tracks for snapshot age, accuracy and queue depths.
  * ``<out>/metrics.jsonl`` — one JSON record per metric: counters,
    gauges (with running max) and log-scale histograms with exact
    p50/p99/p999 — including labeled-family children
    (``frontend/tier_latency_s{tier=phone-low}``-style names).
  * ``<out>/flight.jsonl``  — the selection-provenance flight record
    (DESIGN.md §13): per-round decision records with packed candidate
    masks and policy score components.
  * ``<out>/fleet.html``    — the self-contained fleet dashboard
    rendered from the metrics + flight record; open it in any browser,
    no server or external assets needed.

Then prints the per-stage latency percentile table straight from the
metric registry — the same numbers CI exports, no trace viewer needed —
and a sample ``explain.why(client, round)`` drill-down reconstructed
from the flight record alone.
"""
import argparse
import json
import os

import repro.api as api
import repro.obs as obs
from repro.data.synthetic import FederatedDataset, small_spec
from repro.obs.explain import Flight, format_why, why
from repro.obs.export import validate_chrome_trace
from repro.sim import presets
from repro.utils.cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=96)
    ap.add_argument("--max-age", type=int, default=2,
                    help="snapshot staleness bound (rounds)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="obs_artifacts",
                    help="artifact directory (trace.json, metrics.jsonl)")
    args = ap.parse_args()

    data = FederatedDataset(small_spec(num_clients=args.clients,
                                       num_classes=5, side=8,
                                       avg_samples=24), seed=args.seed)
    cfg = api.RunConfig(
        rounds=args.rounds, clients_per_round=8, local_steps=1,
        summary="py", refresh_max_age=3, refresh_kl=0.05,
        eval_every=max(args.rounds // 2, 1), seed=args.seed,
        registry=api.RegistryConfig(kind="streaming"),
        clustering=api.ClusteringConfig(kind="online", num_clusters=4),
        server=api.ServerConfig(kind="async", refresh="staleness",
                                ingest_delay_rounds=1,
                                snapshot_max_age=args.max_age,
                                drift_mass_trigger=0.1,
                                frontend=api.FrontendConfig(
                                    kind="poisson", slo_p99_s=0.002,
                                    ingest_max_depth=args.clients // 4)))
    # a churn scenario gives the front end tiers, the admission stage
    # sheds, and the dashboard something worth drilling into
    scenario = presets.make_scenario("mobile-churn", args.clients,
                                     seed=args.seed)

    trace_path = os.path.join(args.out, "trace.json")
    metrics_path = os.path.join(args.out, "metrics.jsonl")
    flight_path = os.path.join(args.out, "flight.jsonl")
    report_path = os.path.join(args.out, "fleet.html")
    with obs.observe(trace_path=trace_path, metrics_path=metrics_path,
                     flight_path=flight_path, report_path=report_path) as ob:
        history = api.run(data, cfg, scenario=scenario)

    errors = validate_chrome_trace(json.load(open(trace_path)))
    assert not errors, errors
    print(f"wrote {trace_path} ({len(ob.tracer.events)} events, valid — "
          f"open in https://ui.perfetto.dev)")
    print(f"wrote {metrics_path} ({len(ob.metrics.names())} metrics)")
    print(f"wrote {flight_path} ({len(ob.flight.records)} flight records)")
    print(f"wrote {report_path} (self-contained dashboard — open in a "
          f"browser)")

    print(f"\nfinal accuracy {history['acc'][-1]:.3f}; snapshot age "
          f"max {max(history['snapshot_age'])} "
          f"(bound {cfg.server.snapshot_max_age})"
          f"\n\nper-stage latency (exact percentiles from the log-scale "
          f"histograms):")
    print(f"{'stage':36s} {'count':>6s} {'p50':>10s} {'p99':>10s} "
          f"{'p999':>10s}")
    metrics = ob.metrics
    for name in metrics.names():
        m = metrics.get(name)
        if getattr(m, "kind", "") != "histogram" or not name.endswith("_s") \
                or m.count == 0:
            continue
        p = m.percentiles()
        print(f"{name:36s} {m.count:6d} {p['p50'] * 1e3:8.3f}ms "
              f"{p['p99'] * 1e3:8.3f}ms {p['p999'] * 1e3:8.3f}ms")

    # selection provenance, reconstructed from the flight record alone:
    # one selected client and one that wasn't, from the last round
    fl = Flight(ob.flight.records)
    last = fl.rounds()[-1]
    rec = fl.round_record(last)
    selected = [int(c) for c in rec["selected"]]
    skipped = [c for c in range(args.clients) if c not in selected]
    print("\nwhy(client, round) — selection provenance from the flight "
          "record:")
    for client in (selected[:1] + skipped[:1]):
        print(format_why(why(client, last, fl)))


if __name__ == "__main__":
    use_compile_cache()
    main()
