"""Smoke run of the selection server's main path on a TPU.

    python chip_smoke.py              # one chip: the FEMNIST-shaped fleet
    python chip_smoke.py --chips 4    # four chips: the sharded fleet mesh

One chip.  ``repro.api.run`` serves the FEMNIST-shaped fleet at its
published size (2,800 clients, 62 classes, 28x28x1 images, lognormal sizes
up to 512) for a few rounds: encoder summaries through the batched engine
with the Pallas kernels, the registry's drift scan on one shard, online
clustering, the async server with the staleness refresher and the Poisson
check-in front end, HACCS selection and a CNN payload.  Round 0 summarizes
and clusters the whole fleet.  Then one summary dispatch with the kernels
is compared with the jnp reference on the same clients.

Four chips.  Only the sharded path runs: ``SummaryRegistry``'s drift scan
on a 4-device ``fleet_mesh`` over a million-client arena at C = 62 in
131072-row chunks, and ``HierarchicalClusterMaintainer`` with 4 shards.
Its drift decisions must equal the exact host math's (``batch_sym_kl``
over the same arenas).

The script exits non-zero, and prints no result line, when JAX finds no
TPU, when a phase raises, when the summary dispatch holds no Pallas kernel
(``tpu_custom_call``) or when a check fails.  The last line of a passing
run is one JSON object naming the device.  Times printed here are a
smoke run's, not a benchmark's.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

NUM_CLUSTERS = 8
# kernel summary vs the jnp reference on the same encoder features, relative
# to the largest encoder feature of those clients.  The label one-hots are
# exact in any precision; the chip's matmul may round each float32 feature
# to bfloat16 (error <= 2**-8 of it), so a mean of them is off by at most
# 2**-8 of the largest feature.  A fault (a row in the wrong class, a
# dropped row among <= 64) moves an entry by ~1/64 of a feature difference.
KERNEL_RTOL = 2.0 ** -8


def fail(msg: str, code: int = 1):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, from JAX's
    monitoring events (a cache hit is counted in the compile seconds as
    its retrieval time)."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def phase(clock: CompileClock, name: str, fn):
    c0, h0, t0 = clock.secs, clock.hits, time.perf_counter()
    try:
        out = fn()
    except SystemExit:
        raise
    except Exception:  # noqa: BLE001 — any failure fails the smoke run
        traceback.print_exc()
        fail(f"phase {name} raised")
    print(f"phase {name}: wall {time.perf_counter() - t0:.3f} s, "
          f"backend compile {clock.secs - c0:.3f} s, "
          f"persistent-cache hits {clock.hits - h0}", flush=True)
    return out


# ---------------------------------------------------------------------------
# one chip


def run_config(rounds: int, seed: int):
    import repro.api as api
    return api.RunConfig(
        rounds=rounds, model=api.Model.CNN, summary=api.Summary.ENCODER,
        # label drift from round 2 makes later rounds re-summarize and
        # re-assign the drifted clients
        drift_start=2, drift_per_round=0.25, seed=seed,
        registry=api.RegistryConfig(n_shards=1),
        clustering=api.ClusteringConfig(kind=api.Clustering.ONLINE,
                                        num_clusters=NUM_CLUSTERS),
        server=api.ServerConfig(
            kind=api.Server.ASYNC, refresh=api.Refresh.STALENESS,
            frontend=api.FrontendConfig(kind=api.Frontend.POISSON)),
        policy=api.PolicyConfig(name="haccs"))


def fleet_phase(data, cfg) -> dict:
    import numpy as np

    import repro.api as api
    import repro.obs as obs
    from repro.obs.recorder import unpack_ints

    with obs.observe(flight=True) as ob:
        h = api.run(data, cfg)
    n = data.spec.num_clients
    rounds = [r for r in ob.flight.records if r["type"] == "round"]
    for r, rec in enumerate(rounds):
        asg = unpack_ints(rec["assignment"])
        print(f"  round {r}: summarized (cumulative) {h['refreshes'][r]}, "
              f"clusters in snapshot {len(np.unique(asg[asg >= 0]))}, "
              f"selected {len(h['selected'][r])}, "
              f"check-ins {h['checkins'][r]}, "
              f"snapshot v{h['snapshot_version'][r]}, acc {h['acc'][r]:.4f}",
              flush=True)
    print(f"  online clustering: {h['online_cluster']}")
    print(f"final accuracy {h['final_acc']:.4f}")
    if h["refreshes"][0] != n:
        fail(f"round 0 summarized {h['refreshes'][0]} of {n} clients")
    # the async server publishes round 0's clustering for round 1
    published = [r for r, v in enumerate(h["snapshot_version"]) if v >= 1]
    if not published:
        fail("no clustering snapshot was published")
    first = unpack_ints(rounds[published[0]]["assignment"])
    if len(np.unique(first[first >= 0])) < 2:
        fail("the first clustering holds fewer than 2 clusters")
    if any(len(s) != cfg.clients_per_round for s in h["selected"]):
        fail(f"a round selected other than {cfg.clients_per_round} clients")
    if not np.isfinite(h["final_acc"]):
        fail("final accuracy is not finite")
    return h


def kernel_phase(data, cfg) -> None:
    import jax
    import numpy as np

    from repro.core.batched_summary import BatchedSummaryEngine, bucket_size
    from repro.fl.rounds import RoundContext

    ctx = RoundContext(data, cfg.to_flconfig(), scenario=None)
    if not ctx.use_kernel:
        fail("the round loop chose the jnp reference path on this device")
    # one full dispatch: the 256 first clients of the 128-sample bucket
    ids = [c for c in range(data.spec.num_clients)
           if bucket_size(int(data.sizes[c])) == 128][:ctx.engine.max_batch]
    items = [(c, *data.client_data(c), c) for c in ids]

    main = ctx.engine.summarize(items)
    (exec_,) = ctx.engine._execs.values()
    has_kernel = "tpu_custom_call" in exec_.as_text()
    print(f"  encoder dispatch (M={len(ids)}, bucket 128): "
          f"tpu_custom_call {'present' if has_kernel else 'ABSENT'}")
    if not has_kernel:
        fail("the encoder summary dispatch compiled without a Pallas kernel")

    # the reference takes the encoder at the main path's precision and
    # computes the per-label means at precision="highest"
    def encoder_as_main(x):
        with jax.default_matmul_precision("default"):
            return ctx.enc_fn(x)

    with jax.default_matmul_precision("highest"):
        ref = BatchedSummaryEngine(
            cfg.summary.value, data.spec.num_classes,
            encoder_fn=encoder_as_main, coreset_k=cfg.coreset_k,
            bins=cfg.bins).summarize(items)
    err = max(float(np.max(np.abs(main[c].summary - ref[c].summary)))
              for c in ids)
    imgs = np.concatenate([it[1] for it in items])
    scale = float(jax.numpy.max(jax.numpy.abs(encoder_as_main(imgs))))
    print(f"  kernel dispatch vs jnp reference (means at precision=highest):"
          f" max |diff| {err:.3e}, max |feature| {scale:.3e}, relative "
          f"{err / scale:.3e} (tolerance {KERNEL_RTOL:.3e})")
    if not err <= KERNEL_RTOL * scale:
        fail(f"kernel summary differs from the reference by {err:.3e}")


def one_chip(clock: CompileClock, rounds: int, seed: int) -> None:
    from repro.data.synthetic import FEMNIST_LIKE, FederatedDataset

    data = phase(clock, "data", lambda: FederatedDataset(FEMNIST_LIKE,
                                                         seed=seed))
    cfg = run_config(rounds, seed)
    print(f"fleet: {FEMNIST_LIKE.num_clients} clients, "
          f"{FEMNIST_LIKE.num_classes} classes, "
          f"{'x'.join(map(str, FEMNIST_LIKE.feature_shape))} images; "
          f"{rounds} rounds", flush=True)
    phase(clock, "fleet_run", lambda: fleet_phase(data, cfg))
    phase(clock, "kernel_check", lambda: kernel_phase(data, cfg))


# ---------------------------------------------------------------------------
# four chips


def four_chips(clock: CompileClock, seed: int) -> None:
    import jax
    import numpy as np

    from repro.core.scheduler import RefreshPolicy, batch_sym_kl
    from repro.kernels.ops import on_tpu
    from repro.shard import HierarchicalClusterMaintainer
    from repro.sim import drift_fleet, synthetic_fleet
    from repro.stream import OnlinePolicy, SummaryRegistry
    from repro.utils.sharding import fleet_mesh

    n, c, chunk, kl = 1_000_000, 62, 131072, 0.05
    mesh = fleet_mesh(4)
    print(f"fleet mesh: {mesh.devices.size} devices "
          f"({', '.join(str(d) for d in mesh.devices.flat)})", flush=True)
    if mesh.devices.size != 4:
        fail(f"fleet_mesh(4) built a {mesh.devices.size}-device mesh")

    def setup():
        fleet = synthetic_fleet(n, c, seed=seed)
        policy = RefreshPolicy(max_age_rounds=10 ** 6, kl_threshold=kl)
        shard = SummaryRegistry(n, policy, mesh=mesh, chunk_rows=chunk)
        shard.update_batch(np.arange(n), 0, fleet.summaries,
                           fleet.label_dists)
        return fleet, shard

    fleet, shard = phase(clock, "arena", setup)
    hm = HierarchicalClusterMaintainer(
        NUM_CLUSTERS, n_shards=4, local_k=16,
        policy=OnlinePolicy(use_kernel=on_tpu()))

    def rounds():
        out = hm.refresh(shard.dense(), np.arange(n), jax.random.PRNGKey(0))
        print(f"  round 0: hierarchical fit, inertia {out['inertia']:.6g}")
        ld = fleet.label_dists
        for rnd in (1, 2):
            fresh, _ = drift_fleet(ld, 0.01, seed=seed + rnd)
            # every row holds a summary and none ages out: the exact
            # stale set is the rows whose host sym-KL passes the threshold
            want = np.flatnonzero(batch_sym_kl(shard.label_dists, fresh) > kl)
            got = shard.stale_clients(rnd, fresh)
            same = np.array_equal(got, want)
            print(f"  round {rnd}: stale sharded {got.size}, exact "
                  f"{want.size}, identical {same}", flush=True)
            if not same:
                fail(f"round {rnd}: sharded drift decisions differ from "
                     "the exact host math's")
            shard.update_batch(got, rnd, fleet.summaries[got], fresh[got])
            out = hm.refresh(shard.dense(), got,
                             jax.random.PRNGKey(rnd))
            print(f"  round {rnd}: hierarchical refresh over {got.size} "
                  f"drifted rows, inertia {out['inertia']:.6g}", flush=True)
            ld = fresh
        print(f"  scan chunks {shard.scan_chunks}, borderline re-checks "
              f"{shard.rechecked_rows}, merges {hm.merges}")

    phase(clock, "sharded_rounds", rounds)


# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        from repro.utils.cache import use_compile_cache
    except ImportError as e:
        fail(f"the repository's sources are not next to this script: {e}", 2)
    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's devices are {dev.platform} "
             f"({len(devices)} x {dev.device_kind})")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPU devices, "
             f"found {len(devices)}")
    print(f"device: {dev.platform}, {dev.device_kind}, count {len(devices)}; "
          f"compile cache {cache}", flush=True)

    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(clock, args.seed)
    else:
        one_chip(clock, args.rounds, args.seed)
    print(f"total: wall {time.perf_counter() - t0:.3f} s, backend compile "
          f"{clock.secs:.3f} s, persistent-cache hits {clock.hits}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
