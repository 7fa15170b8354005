"""The selection server's chip benchmark: one cell, one run, one JSON line.

    python3 chipbench/run.py --workload femnist.refresh --seed 7 \
        --seconds 20 --trace 0

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``)
and a traffic mix (``traffic/<name>.json``, whose ``kind`` names its
generator, ``traffic/<kind>.py``); every metric is read by its own file,
``metrics/<name>.py`` (see ``found.py``).  So a later cell, mix, generator
or metric is new files and new entries, and no edit here.

A run: set-up (traffic and data from the seed, the server, round 0 over
the whole fleet and the warm rounds, whose work is the window's), then
closed-loop server rounds back to back for ``--seconds``, then the check
against the plain references.  ``--trace 1`` also records
a profiler trace of a short steady part of the window and prints the
per-layer metrics instead of the end-to-end ones.  The last line on
standard output is the result; the last lines on standard error are the
numbers compared, each beside its limit.

Exits 2 when the program is not in the checkout, 3 when JAX finds no TPU
or fewer chips than the cell asks for; neither prints a result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"
TRACE_AFTER = 0.3          # share of the window before the traced part
TRACE_SECONDS = 3.0        # least length of the traced part
TRACE_ROUNDS = 3           # least rounds in the traced part


class NoResult(Exception):
    """A run that must exit without a result line."""

    def __init__(self, code: int, msg: str):
        super().__init__(msg)
        self.code = code


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_manifest(root=ROOT) -> dict:
    with open(pathlib.Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def cell_metrics(manifest: dict, cell: str, kind: str) -> list:
    """The ``kind`` ("end_to_end" or "per_layer") metrics a cell reports."""
    e2e = [m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    if kind == "end_to_end":
        return e2e
    return [m for m in manifest["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]


def load_reader(name: str, root=HERE):
    from chipbench import found
    return found.module("metrics", name, root).read


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(manifest: dict, name: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True, root=HERE,
             control: bool = False) -> dict:
    """One run of cell ``name``; returns the result object.  ``control``
    adds ``control_checks``: the same numbers with the reference one
    precision step below the configuration's in the program's place
    (chipbench/control.py)."""
    import jax
    import numpy as np

    from chipbench import check as chk
    from chipbench import found
    from chipbench import server as srv
    from chipbench import trace as tr
    from chipbench.compile_clock import CompileClock
    from chipbench.peaks import peaks

    cells = {c["name"]: c for c in manifest["workloads"]}
    if name not in cells:
        raise NoResult(2, f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < cell["chips"]):
        raise NoResult(3, f"cell {name} needs {cell['chips']} TPU chip(s); "
                          f"JAX found {len(devs)} {devs[0].platform} device(s)")
    clock = CompileClock()
    split = {}

    def phase(label, t0, c0, h0):
        split[label] = {"wall_s": time.perf_counter() - t0,
                        "compile_s": clock.secs - c0,
                        "cache_hits": clock.hits - h0}

    cfg = found.data("configs", cell["config"], root)
    mix = found.data("traffic", cell["traffic"], root)
    t0, c0, h0 = time.perf_counter(), clock.secs, clock.hits
    server = srv.Server(cfg, mix, seed, root)
    phase("traffic_and_server", t0, c0, h0)
    annotate = ((lambda s: jax.profiler.TraceAnnotation(tr.STAGE_PREFIX + s))
                if trace else (lambda s: contextlib.nullcontext()))

    # round 0 over the whole fleet, then the warm rounds: every round after
    # round 0 asks the same work of the server, so these dispatch every
    # shape the window will
    t0, c0, h0 = time.perf_counter(), clock.secs, clock.hits
    for _ in range(1 + server.traffic.warm_rounds):
        server.round(annotate=annotate)
    phase("warm_rounds", t0, c0, h0)
    setup_s = time.perf_counter() - T_PROCESS

    # the window: rounds back to back
    first = server.next_round
    n_max = server.traffic.rounds - first
    spans = np.zeros((n_max, len(srv.STAGES)))
    durations = np.zeros(n_max)
    engine = server.ctx.engine
    disp0 = engine.stats.dispatches if engine is not None else 0
    comp0 = clock.compiles
    traced, trace_dir, raised = 0, None, False
    state = "before"
    t_start = time.perf_counter()
    i = 0
    while i < n_max:
        now = time.perf_counter()
        if trace and state == "before" and now - t_start >= TRACE_AFTER * seconds:
            trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            jax.profiler.start_trace(trace_dir)
            win = jax.profiler.TraceAnnotation(tr.WINDOW)
            win.__enter__()
            state, t_trace = "tracing", time.perf_counter()
        r0 = time.perf_counter()
        try:
            server.round(spans[i], annotate)
        except Exception:  # noqa: BLE001 — a round that raises is failed
            traceback.print_exc()
            raised = True
            break
        durations[i] = time.perf_counter() - r0
        traced += state == "tracing"
        i += 1
        now = time.perf_counter()
        if state == "tracing" and (now - t_trace >= TRACE_SECONDS
                                   and traced >= TRACE_ROUNDS):
            win.__exit__(None, None, None)
            jax.profiler.stop_trace()
            state = "traced"
        if now - t_start >= seconds:
            break
    window_s = time.perf_counter() - t_start
    if state == "tracing":
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
    n = i
    window_compiles = clock.compiles - comp0
    dispatches = (engine.stats.dispatches - disp0) if engine is not None else 0
    if server.next_round >= server.traffic.rounds and not raised:
        log(f"the window used all {server.traffic.rounds} planned rounds")
    log(f"window: {n} rounds in {window_s:.3f} s, {window_compiles} compiles")
    device = device_info(jax, cell["chips"])
    in_use = max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                 for d in jax.devices()[:cell["chips"]])
    log(f"device memory: peak {device['memory_peak_bytes']} B, in use at "
        f"the window's end {in_use} B")

    # the check, once the program's device state is freed
    t0 = time.perf_counter()
    snap = chk.Snapshot(server)
    server.ctx = None
    gc.collect()
    window = range(first, first + n)
    try:
        numbers = chk.run_check(server, snap, window, seed, root=root)
        correct = chk.passed(numbers) and not raised and n > 0
        if control:
            control_numbers = chk.run_check(server, snap, window, seed,
                                            control=True, root=root)
    except Exception:  # noqa: BLE001 — a check that raises is not correct
        traceback.print_exc()
        numbers, correct, control_numbers = {}, False, {}
    failed = len([r for r in server.rec.failed if r >= first]) + int(raised)
    log(f"check: {time.perf_counter() - t0:.3f} s for {n} rounds")

    obs = {
        "setup_s": setup_s, "window_s": window_s, "rounds": n,
        "durations": durations[:n], "spans": spans[:n],
        "stages": srv.STAGES, "dispatches": dispatches,
        "window_compiles": window_compiles, "trace": None,
        "config": cfg, "root": root,
    }
    result = {"correct": bool(correct), "attempted": n, "failed": failed}
    if trace and trace_dir is not None:
        red = tr.reduce(*tr.read(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs["trace"] = red
        obs["peaks"] = peaks(device["kind"]) if require_tpu else None
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(manifest, name, kind):
        value = load_reader(m["name"], root)(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if control:
        result["control_checks"] = {k: {"value": v, "limit": lim}
                                    for k, (v, lim) in control_numbers.items()}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in numbers.items()}
    log("setup split: " + json.dumps(split))
    for k, (v, lim) in numbers.items():
        log(f"check {k}: {v!r} (limit {lim!r})")
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        manifest = load_manifest()
        if not (ROOT / "src" / "repro").is_dir():
            raise NoResult(2, "the program (src/repro) is not in this checkout")
        sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
        from repro.utils.cache import use_compile_cache
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        log(f"compile cache: {use_compile_cache()}")
        result = run_cell(manifest, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoResult as e:
        log(f"chipbench: {e}")
        return e.code
    except (FileNotFoundError, ImportError) as e:
        log(f"chipbench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
