"""Readings for the check's limits: the program's numbers and the
control's (the reference one precision step below the configuration's,
put in the program's place) on several seeds, in one process.

    python3 chipbench/control.py --workload femnist.refresh --seconds 5 \
        --seeds 11 12 13

Prints one JSON line per seed, then one line with, for each number, the
largest reading of the program and the smallest of the control.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import ROOT, NoResult, load_manifest, log, run_cell


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.utils.cache import use_compile_cache
    use_compile_cache()
    manifest = load_manifest()
    lower, upper, correct = {}, {}, []
    for seed in args.seeds:
        try:
            res = run_cell(manifest, args.workload, seed, args.seconds,
                           False, control=True)
        except NoResult as e:
            log(f"chipbench: {e}")
            return e.code
        correct.append(res["correct"])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"],
                          "control_checks": res["control_checks"]}),
              flush=True)
        for k, v in res["checks"].items():
            lower[k] = max(lower.get(k, 0), v["value"])
        for k, v in res["control_checks"].items():
            upper[k] = min(upper.get(k, float("inf")), v["value"])
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "program_correct": correct,
                      "program_largest": lower, "control_smallest": upper}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
