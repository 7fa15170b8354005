"""Paper Table 2 (right): device-clustering time.

HACCS clusters P(y)/P(X|y) summaries with DBSCAN; the paper replaces both
the summary (smaller) and the algorithm (K-means).  We measure:

    dbscan  over p_y / pxy / encoder summaries      (baseline pipeline)
    kmeans  over encoder summaries                  (the paper's pipeline)

at several client counts N, and report measured seconds + the fitted N²
extrapolation to the paper's full scales (2 800 / 11 325 clients) for
configurations that exceed container memory.

CSV: pipeline,dataset,n_clients,seconds
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._record import emit
from repro.core import dbscan, kmeans, minibatch_kmeans
from repro.stream import OnlineClusterMaintainer, OnlinePolicy


def _synth_summaries(rs, n, dim, groups=8, sep=4.0):
    """Summaries with latent group structure (as real clients exhibit)."""
    centers = rs.normal(0, sep, (groups, dim)).astype(np.float32)
    g = rs.randint(0, groups, n)
    return (centers[g] + rs.normal(0, 1.0, (n, dim)).astype(np.float32)), g


def _time(fn, *args, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return time.perf_counter() - t0, out


def run(scales=((500, "femnist"), (2000, "openimage")),
        dims=None, k_clusters: int = 10, seed: int = 0) -> list:
    rs = np.random.RandomState(seed)
    dims = dims or {
        # summary dims at paper-like settings
        "femnist": {"py": 62, "pxy": 62 * 196 * 8, "encoder": 62 * 64 + 62},
        "openimage": {"py": 600, "pxy": 600 * 192 * 8,
                      "encoder": 600 * 64 + 600},
    }
    rows = []
    for n, dname in scales:
        for sname, dim in dims[dname].items():
            dim_capped = min(dim, 60_000)      # container memory guard
            x_np, _ = _synth_summaries(rs, n, dim_capped)
            x = jnp.asarray(x_np)
            med = float(np.median(np.linalg.norm(
                x_np - x_np.mean(0), axis=1)))
            dt_db, res = _time(dbscan, x, med * 0.5, 4)
            rows.append({"name": f"clustering/dbscan-{sname}/{dname}",
                         "pipeline": f"dbscan-{sname}", "dataset": dname,
                         "n": n, "dim": dim_capped, "seconds": dt_db,
                         "clusters": int(res.num_clusters)})
            if sname == "encoder":
                dt_km, resk = _time(kmeans, x, k_clusters,
                                    jax.random.PRNGKey(seed))
                rows.append({"name": f"clustering/kmeans-encoder/{dname}",
                             "pipeline": "kmeans-encoder", "dataset": dname,
                             "n": n, "dim": dim_capped, "seconds": dt_km,
                             "clusters": k_clusters,
                             "inertia": float(resk.inertia)})
                # mini-batch path: per-step cost independent of N — the
                # fleet-scale engine's clustering side (DESIGN.md §4)
                dt_mb, resm = _time(minibatch_kmeans, x, k_clusters,
                                    jax.random.PRNGKey(seed))
                rows.append({"name": f"clustering/minibatch-encoder/{dname}",
                             "pipeline": "minibatch-encoder",
                             "dataset": dname, "n": n, "dim": dim_capped,
                             "seconds": dt_mb, "clusters": k_clusters,
                             "inertia": float(resm.inertia)})
    return rows


def run_fleet(n: int, dim: int, k_clusters: int = 10, seed: int = 0) -> list:
    """Fleet-scale client counts: full Lloyd vs mini-batch K-means over
    encoder-sized summaries.  Mini-batch per-step cost is independent of N
    (batch_size·K·D), which is what makes clustering affordable past the
    scales where every-client Lloyd iterations dominate the round."""
    rs = np.random.RandomState(seed)
    x_np, _ = _synth_summaries(rs, n, dim, groups=16)
    x = jnp.asarray(x_np)
    rows = []
    dt_km, res = _time(kmeans, x, k_clusters, jax.random.PRNGKey(seed))
    rows.append({"name": f"clustering/fleet-kmeans/n{n}",
                 "pipeline": "fleet-kmeans", "dataset": f"n{n}", "n": n,
                 "dim": dim, "seconds": dt_km, "clusters": k_clusters,
                 "inertia": float(res.inertia)})
    dt_mb, res = _time(minibatch_kmeans, x, k_clusters,
                       jax.random.PRNGKey(seed), batch_size=512, iters=30)
    rows.append({"name": f"clustering/fleet-minibatch/n{n}",
                 "pipeline": "fleet-minibatch", "dataset": f"n{n}", "n": n,
                 "dim": dim, "seconds": dt_mb, "clusters": k_clusters,
                 "inertia": float(res.inertia)})
    return rows


def run_online(n: int = 10_000, dim: int = 64, k_clusters: int = 16,
               rounds: int = 5, drift_frac: float = 0.01,
               seed: int = 0) -> list:
    """Low-drift maintenance: per round, ``drift_frac`` of clients move to a
    new latent group; compare re-running full K-means every round (the
    registry's ``dense()`` + ``kmeans`` baseline) against the online
    maintainer's assign-only updates (DESIGN.md §5).  Both paths see
    identical data."""
    rs = np.random.RandomState(seed)
    centers = rs.normal(0, 4.0, (k_clusters, dim)).astype(np.float32)
    g = rs.randint(0, k_clusters, n)
    x = (centers[g] + rs.normal(0, 1.0, (n, dim)).astype(np.float32))
    _time(kmeans, jnp.asarray(x), k_clusters, jax.random.PRNGKey(seed))  # warm

    m = OnlineClusterMaintainer(k_clusters, OnlinePolicy(reseed_every=1000))
    t0 = time.perf_counter()
    m.refresh(x, np.arange(n), jax.random.PRNGKey(seed))
    init_s = time.perf_counter() - t0

    n_drift = max(1, int(drift_frac * n))
    # warm the assign-only path (same drift-set bucket) so timed rounds
    # measure steady-state maintenance, not first-call compilation
    m.refresh(x, rs.choice(n, n_drift, replace=False),
              jax.random.PRNGKey(seed))

    full_s = online_s = 0.0
    full_inertias, online_inertias = [], []
    for r in range(rounds):
        ids = rs.choice(n, n_drift, replace=False)
        g[ids] = rs.randint(0, k_clusters, n_drift)
        x[ids] = (centers[g[ids]]
                  + rs.normal(0, 1.0, (n_drift, dim)).astype(np.float32))
        t0 = time.perf_counter()
        res = kmeans(jnp.asarray(x), k_clusters,
                     jax.random.PRNGKey(seed + 1 + r))
        jax.block_until_ready(res.centroids)
        full_s += time.perf_counter() - t0
        full_inertias.append(float(res.inertia))
        t0 = time.perf_counter()
        m.refresh(x, ids, jax.random.PRNGKey(seed + 1 + r))
        online_s += time.perf_counter() - t0
        online_inertias.append(m.inertia)
    # mean-over-rounds: kmeans++ quality is seed-noisy, single-round
    # inertia comparisons mostly measure seeding luck
    return [{
        "name": f"clustering/online-vs-full/n{n}",
        "pipeline": "online-vs-full", "n": n, "dim": dim,
        "rounds": rounds, "drift_frac": drift_frac,
        "full_recluster_s": full_s, "online_s": online_s,
        "online_init_s": init_s,
        "full_inertia": float(np.mean(full_inertias)),
        "online_inertia": float(np.mean(online_inertias)),
        "full_fits": m.full_fits,
    }]


def main(fast: bool = True):
    scales = ((300, "femnist"), (800, "openimage")) if fast else \
        ((2800, "femnist"), (4000, "openimage"))
    rows = run(scales=scales)
    by = {}
    for r in rows:
        emit(r["name"], us=r["seconds"] * 1e6, n=r["n"], dim=r["dim"],
             clusters=r["clusters"])
        by[(r["pipeline"], r["dataset"])] = r
    for d in ("femnist", "openimage"):
        a = by.get(("dbscan-pxy", d))
        b = by.get(("kmeans-encoder", d))
        if a and b:
            emit(f"clustering/speedup_dbscanpxy_over_kmeans/{d}",
                 text=f"{a['seconds'] / max(b['seconds'], 1e-9):.1f}x")
        mb = by.get(("minibatch-encoder", d))
        if b and mb:
            q = mb["inertia"] / max(b["inertia"], 1e-9)
            emit(f"clustering/minibatch_speedup_over_kmeans/{d}",
                 text=f"{b['seconds'] / max(mb['seconds'], 1e-9):.1f}x "
                      f"(inertia ratio {q:.2f}; <1x expected at small N — "
                      f"mini-batch pays off at fleet scale, see fleet rows)")
    # fleet scale: the batched engine's clustering side (DESIGN.md §4)
    fleet = run_fleet(n=6000 if fast else 20000, dim=4030)
    rows += fleet
    for r in fleet:
        emit(r["name"], us=r["seconds"] * 1e6, n=r["n"], dim=r["dim"],
             inertia=f"{r['inertia']:.3g}")
    emit("clustering/fleet_speedup_minibatch",
         text=f"{fleet[0]['seconds'] / max(fleet[1]['seconds'], 1e-9):.1f}x "
              f"(inertia ratio "
              f"{fleet[1]['inertia'] / max(fleet[0]['inertia'], 1e-9):.2f})")

    # online maintenance vs full recluster at >=10k clients (DESIGN.md §5)
    online = run_online(n=10_000 if fast else 100_000,
                        rounds=3 if fast else 5)
    rows += online
    for r in online:
        per_round_full = r["full_recluster_s"] / r["rounds"]
        per_round_online = r["online_s"] / r["rounds"]
        emit(f"{r['name']}/full_per_round", us=per_round_full * 1e6,
             n=r["n"], dim=r["dim"], drift=r["drift_frac"])
        emit(f"{r['name']}/online_per_round", us=per_round_online * 1e6,
             full_fits=r["full_fits"], init_s=f"{r['online_init_s']:.3f}")
        emit(f"{r['name']}/speedup",
             text=f"{per_round_full / max(per_round_online, 1e-9):.1f}x "
                  f"(inertia ratio "
                  f"{r['online_inertia'] / max(r['full_inertia'], 1e-9):.3f})")

    # paper-scale extrapolation: DBSCAN is O(N²·D); K-means O(N·K·D·iters).
    # Scale the measured times to the paper's client counts and the real
    # (uncapped) P(X|y) summary dim, where the paper observed ">2 days".
    a = by.get(("dbscan-pxy", "openimage"))
    b = by.get(("kmeans-encoder", "openimage"))
    if a and b:
        n_full, d_pxy_full = 11_325, 600 * 192 * 8
        t_db = a["seconds"] * (n_full / a["n"]) ** 2 * (d_pxy_full / a["dim"])
        t_km = b["seconds"] * (n_full / b["n"])
        emit("clustering/extrapolated_dbscanpxy_full_s",
             text=f"{t_db:.0f} ({t_db / 3600:.1f}h; paper: >2 days)")
        emit("clustering/extrapolated_speedup_full",
             text=f"{t_db / max(t_km, 1e-9):.0f}x (paper: >=360x)")
    return rows


if __name__ == "__main__":
    main(fast=False)
