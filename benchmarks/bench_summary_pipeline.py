"""§Perf — summary-pipeline hillclimb (the paper's own hot loop, measured
for real on this host):

  iteration 1: eager per-client summary (baseline; retraces every client)
               -> jitted + power-of-two size bucketing (compile once per
               bucket, reuse across the federation and across refresh rounds)
  iteration 2: fleet-scale batched engine (DESIGN.md §4) — stale clients
               stacked into padded [M, N_bucket, ...] buckets, ONE jitted
               vmap dispatch per bucket chunk instead of one per client.
  iteration 3: server-side registry scan (DESIGN.md §5) — per-client
               needs_refresh python loop vs one batched sym-KL over [N, C]
               vs the streaming registry (dense matrices, O(drifted)
               scatter) at 10k-100k simulated clients.

CSV: pipeline/<...>,us_per_call,derived
"""
from __future__ import annotations

import time

import numpy as np

import jax

from benchmarks._record import emit
from repro.core import BatchedSummaryEngine, RefreshPolicy, SummaryRegistry
from repro.stream import StreamingSummaryRegistry
from repro.data.synthetic import DatasetSpec, FederatedDataset, small_spec
from repro.fl.client import timed_summary
from repro.models.cnn import CNNConfig, build_cnn, cnn_apply


def run(num_clients: int = 12, seed: int = 0) -> list:
    """Iteration 1: eager vs jit+bucket, per client (paper Table 2 regime)."""
    spec = DatasetSpec("femnist-like", 2800, 62, (28, 28, 1),
                       avg_samples=109, max_samples=512)
    data = FederatedDataset(spec, seed=seed)
    enc_params = build_cnn(CNNConfig(in_channels=1, feature_dim=64))
    enc_fn = jax.jit(lambda x: cnn_apply(enc_params, x))
    order = np.argsort(data.sizes)
    cids = order[np.linspace(0, len(order) - 1, num_clients).astype(int)]

    rows = []
    for method in ("py", "pxy", "encoder"):
        for variant, jit in (("eager", False), ("jit+bucket", True)):
            times = []
            for i, cid in enumerate(cids):
                feats, labels, valid = data.client_data(int(cid))
                _, _, dt = timed_summary(
                    method, feats, labels, valid, spec.num_classes,
                    encoder_fn=enc_fn, coreset_k=128, bins=16,
                    key=jax.random.PRNGKey(int(cid)), jit=jit)
                if i > 0:
                    times.append(dt)
            rows.append({"name": f"pipeline/{method}/{variant}",
                         "method": method, "variant": variant,
                         "avg_s": float(np.mean(times))})
    return rows


def run_fleet(num_clients: int = 512, methods=("py", "encoder", "pxy"),
              seed: int = 0) -> list:
    """Iteration 2: refresh a whole fleet of stale clients, per-client jit
    loop vs the batched engine — dispatch counts and wall time, with the
    numerical-equivalence check the new test also asserts."""
    spec = small_spec(num_clients=num_clients, num_classes=10, side=12,
                      avg_samples=48)
    data = FederatedDataset(spec, seed=seed)
    enc_params = build_cnn(CNNConfig(in_channels=1, feature_dim=32),
                           jax.random.PRNGKey(7))
    enc_fn = jax.jit(lambda x: cnn_apply(enc_params, x))
    clients = [(c, *data.client_data(c), seed * 7 + c)
               for c in range(num_clients)]

    rows = []
    for method in methods:
        # per-client path: one jitted dispatch per client (timed_summary
        # already excludes compiles via its warm call)
        per_client_s, per_summaries = 0.0, {}
        for c, feats, labels, valid, seed_c in clients:
            s, _, dt = timed_summary(method, feats, labels, valid,
                                     spec.num_classes, encoder_fn=enc_fn,
                                     coreset_k=32, bins=8,
                                     key=jax.random.PRNGKey(seed_c))
            per_client_s += dt
            per_summaries[c] = s
        # batched engine: one dispatch per (bucket, chunk)
        engine = BatchedSummaryEngine(
            method, spec.num_classes, encoder_fn=enc_fn, coreset_k=32,
            bins=8, max_batch=64 if method == "pxy" else 256)
        t0 = time.perf_counter()
        results = engine.summarize(clients)
        end_to_end = time.perf_counter() - t0
        equal = all(np.allclose(per_summaries[c], results[c].summary,
                                atol=1e-5) for c in range(num_clients))
        st = engine.stats
        rows.append({
            "method": method, "clients": num_clients,
            "perclient_s": per_client_s, "perclient_dispatches": num_clients,
            "batched_s": st.wall_s, "batched_dispatches": st.dispatches,
            "end_to_end_s": end_to_end, "equal": equal,
        })
    return rows


def run_registry(n: int = 20_000, num_classes: int = 62, dim: int = 64,
                 drift_frac: float = 0.01, seed: int = 0) -> list:
    """Iteration 3: one server round of refresh decisions + state absorption
    at fleet scale — the python-loop scan vs the vectorized dict registry vs
    the streaming registry's batched scan + O(drifted) scatter."""
    rs = np.random.RandomState(seed)
    policy = RefreshPolicy(max_age_rounds=10 ** 6, kl_threshold=0.05)
    dists = rs.dirichlet([0.5] * num_classes, n).astype(np.float32)
    summaries = rs.rand(n, dim).astype(np.float32)

    base = SummaryRegistry(n, policy)
    stream = StreamingSummaryRegistry(n, policy)
    for c in range(n):
        base.update(c, 0, summaries[c], dists[c])
    stream.update_batch(np.arange(n), 0, summaries, dists)

    # low drift: a few % of clients move, the rest stay put
    fresh = dists.copy()
    ids = rs.choice(n, max(1, int(drift_frac * n)), replace=False)
    fresh[ids] = rs.dirichlet([0.5] * num_classes, ids.size) \
        .astype(np.float32)

    t0 = time.perf_counter()
    loop_stale = [c for c in range(n)
                  if base.needs_refresh(c, 1, fresh[c])]
    loop_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    vec_stale = base.stale_clients(1, fresh)
    vec_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    stream_stale = stream.stale_clients(1, fresh)
    stream.update_batch(stream_stale, 1,
                        summaries[stream_stale], fresh[stream_stale])
    _ = stream.matrix()                      # zero-copy clustering handoff
    stream_s = time.perf_counter() - t0
    assert loop_stale == vec_stale == stream_stale.tolist()
    return [{
        "name": f"pipeline/registry/n{n}", "n": n, "num_classes": num_classes,
        "stale": len(loop_stale), "loop_s": loop_s, "vectorized_s": vec_s,
        "streaming_s": stream_s,
    }]


def main(fast: bool = True):
    rows = run(num_clients=6 if fast else 16)
    by = {}
    for r in rows:
        by[(r["method"], r["variant"])] = r["avg_s"]
        emit(r["name"], us=r["avg_s"] * 1e6)
    for m in ("py", "pxy", "encoder"):
        if (m, "eager") in by and (m, "jit+bucket") in by:
            sp = by[(m, "eager")] / max(by[(m, "jit+bucket")], 1e-9)
            emit(f"pipeline/{m}/speedup", text=f"{sp:.1f}x")

    # fleet scale: the acceptance bar is >=512 clients refreshed with >=5x
    # fewer jitted dispatches than the per-client path, equal summaries
    fleet = run_fleet(num_clients=512,
                      methods=("py", "encoder") if fast
                      else ("py", "encoder", "pxy"))
    for r in fleet:
        m = r["method"]
        emit(f"pipeline/fleet/{m}/perclient",
             us=r["perclient_s"] / r["clients"] * 1e6,
             dispatches=r["perclient_dispatches"])
        emit(f"pipeline/fleet/{m}/batched",
             us=r["batched_s"] / r["clients"] * 1e6,
             dispatches=r["batched_dispatches"])
        disp_ratio = (r["perclient_dispatches"]
                      / max(r["batched_dispatches"], 1))
        emit(f"pipeline/fleet/{m}/dispatch_reduction",
             text=f"{disp_ratio:.1f}x")
        emit(f"pipeline/fleet/{m}/speedup",
             text=f"{r['perclient_s'] / max(r['batched_s'], 1e-9):.1f}x")
        emit(f"pipeline/fleet/{m}/equal", text=str(r["equal"]))

    # registry scan at fleet scale (DESIGN.md §5)
    reg = run_registry(n=20_000 if fast else 100_000)
    for r in reg:
        emit(f"{r['name']}/loop", us=r["loop_s"] * 1e6, n=r["n"],
             stale=r["stale"])
        emit(f"{r['name']}/vectorized", us=r["vectorized_s"] * 1e6,
             text=f"{r['loop_s'] / max(r['vectorized_s'], 1e-9):.1f}x_vs_loop")
        emit(f"{r['name']}/streaming", us=r["streaming_s"] * 1e6,
             text=f"{r['loop_s'] / max(r['streaming_s'], 1e-9):.1f}x_vs_loop "
                  f"(scan + O(drifted) scatter + zero-copy matrix)")
    return rows + fleet + reg


if __name__ == "__main__":
    main(fast=False)
