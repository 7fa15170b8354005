import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, and extract the roofline terms from the compiled
artifact.  MUST keep the three lines above as the very first statements —
jax locks the device count on first initialization.  The tool runs on 512
forced host devices only: ``JAX_PLATFORMS=cpu`` keeps it, and the children
``--all`` starts (which inherit the environment), off any attached chip.

Usage:
    python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
    python -m repro.launch.dryrun --arch deepseek-v3-671b --shape decode_32k --multi-pod
    python -m repro.launch.dryrun --all --out results/dryrun.jsonl

Each invocation appends one JSON record (roofline terms, memory analysis,
collective mix, compile time) to the output file; --all fans out over
subprocesses so a failing combo can't poison the rest.
"""
import argparse
import json
import math
import subprocess
import sys
import time


def count_params(model) -> int:
    import jax
    return sum(math.prod(x.shape) for x in jax.tree.leaves(model.abstract_params()))


def count_active_params(model) -> int:
    """Activated parameters (MoE: only top-k routed experts count)."""
    cfg = model.cfg
    total = count_params(model)
    inactive = 0
    for st in model.stages:
        for plan in st.pattern:
            if plan.ffn == "moe":
                per_expert = 3 * cfg.d_model * plan.d_ff
                inactive += st.repeats * (
                    cfg.num_experts - cfg.num_experts_per_tok) * per_expert
    return total - inactive


def model_flops(model, shape) -> float:
    n_active = count_active_params(model)
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch      # decode: 1 new token


def run_one(arch: str, shape_name: str, multi_pod: bool, rules_name: str,
            remat: str = "block", banded: bool = False,
            opt_dtype: str = "float32", tag: str = "",
            quant_experts: bool = False) -> dict:
    import jax
    from repro.configs import INPUT_SHAPES, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.rules import get_rules
    from repro.launch.serve import lower_decode, lower_prefill
    from repro.launch.train import lower_train
    from repro.models import build_model
    from repro.utils.hlo import analyze_hlo
    from repro.utils.roofline import Roofline

    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "train":
        cfg = cfg.replace(remat=remat)   # activation checkpointing default on
    cfg = cfg.replace(banded_attention=banded, opt_state_dtype=opt_dtype,
                      quant_experts=quant_experts)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "rules": rules_name, "remat": remat, "banded": banded,
           "opt_dtype": opt_dtype, "quant_experts": quant_experts,
           "tag": tag, "status": "ok"}

    if shape_name == "long_500k" and not cfg.sub_quadratic:
        rec["status"] = "skipped"
        rec["reason"] = ("full-attention architecture: 524k decode requires "
                         "sub-quadratic attention (DESIGN.md §Arch-applicability)")
        return rec

    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rules = get_rules(rules_name)

    t0 = time.time()
    if shape.kind == "train":
        lowered = lower_train(model, shape, mesh, rules)
    elif shape.kind == "prefill":
        lowered = lower_prefill(model, shape, mesh, rules)
    else:
        lowered = lower_decode(model, shape, mesh, rules)
    rec["lower_s"] = round(time.time() - t0, 2)

    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    ma = compiled.memory_analysis()
    mem = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        mem[f] = int(getattr(ma, f, 0) or 0)
    rec["memory"] = mem
    bytes_per_device = mem["argument_size_in_bytes"] + mem["temp_size_in_bytes"] \
        + max(mem["output_size_in_bytes"] - mem["alias_size_in_bytes"], 0)
    rec["bytes_per_device"] = bytes_per_device

    hlo = analyze_hlo(compiled.as_text())
    rec["hlo"] = {k: hlo[k] for k in
                  ("flops", "bytes", "bytes_fused", "collective_bytes",
                   "collectives", "collective_counts", "top_collectives",
                   "top_mem_ops", "num_computations")}
    rec["params"] = count_params(model)
    rec["active_params"] = count_active_params(model)
    rec["model_flops"] = model_flops(model, shape)

    rl = Roofline(arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
                  hlo_flops=hlo["flops"], hlo_bytes=hlo["bytes"],
                  collective_bytes=hlo["collective_bytes"],
                  model_flops=rec["model_flops"],
                  bytes_per_device=bytes_per_device,
                  hlo_bytes_fused=hlo["bytes_fused"])
    rec["roofline"] = rl.row()
    print(json.dumps({k: rec[k] for k in
                      ("arch", "shape", "mesh", "lower_s", "compile_s",
                       "bytes_per_device")}), file=sys.stderr)
    print(compiled.memory_analysis(), file=sys.stderr)
    return rec


ALL_ARCHS = (
    "llama4-scout-17b-a16e", "moonshot-v1-16b-a3b", "llama-3.2-vision-90b",
    "hymba-1.5b", "phi4-mini-3.8b", "deepseek-v3-671b", "whisper-large-v3",
    "deepseek-coder-33b", "gemma3-1b", "xlstm-350m",
)
ALL_SHAPES = ("train_4k", "prefill_32k", "decode_32k", "long_500k")


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch")
    p.add_argument("--shape")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--rules", default="baseline")
    p.add_argument("--remat", default="block", choices=["block", "none"])
    p.add_argument("--banded", action="store_true",
                   help="window-limited KV scanning (perf variant)")
    p.add_argument("--opt-dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--quant-experts", action="store_true",
                   help="int8 expert weights (serving perf variant)")
    p.add_argument("--tag", default="", help="label for perf-variant records")
    p.add_argument("--out", default="results/dryrun.jsonl")
    p.add_argument("--all", action="store_true")
    p.add_argument("--skip-existing", action="store_true")
    p.add_argument("--timeout", type=int, default=3600)
    args = p.parse_args(argv)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    if args.all:
        done = set()
        if args.skip_existing and os.path.exists(args.out):
            with open(args.out) as f:
                for line in f:
                    try:
                        r = json.loads(line)
                        done.add((r["arch"], r["shape"], r["mesh"], r["rules"]))
                    except json.JSONDecodeError:
                        pass
        mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
        for arch in ALL_ARCHS:
            for shape in ALL_SHAPES:
                if (arch, shape, mesh_name, args.rules) in done:
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--rules", args.rules, "--remat", args.remat,
                       "--out", args.out]
                if args.multi_pod:
                    cmd.append("--multi-pod")
                print(f"=== {arch} × {shape} × {mesh_name}", flush=True)
                try:
                    subprocess.run(cmd, check=False, timeout=args.timeout)
                except subprocess.TimeoutExpired:
                    with open(args.out, "a") as f:
                        f.write(json.dumps({
                            "arch": arch, "shape": shape, "mesh": mesh_name,
                            "rules": args.rules, "status": "timeout"}) + "\n")
        return

    try:
        rec = run_one(args.arch, args.shape, args.multi_pod, args.rules,
                      args.remat, args.banded, args.opt_dtype, args.tag,
                      args.quant_experts)
    except Exception as e:  # noqa: BLE001 — recorded, not raised
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "pod2x16x16" if args.multi_pod else "pod16x16",
               "rules": args.rules, "status": "error",
               "error": f"{type(e).__name__}: {e}"}
        print(rec["error"], file=sys.stderr)
    with open(args.out, "a") as f:
        f.write(json.dumps(rec) + "\n")
    if rec["status"] == "error":
        sys.exit(1)


if __name__ == "__main__":
    main()
