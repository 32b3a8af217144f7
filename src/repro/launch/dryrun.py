import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × input shape) on the
production mesh, prove memory fits, and extract roofline terms.

CPU-only: it forces 512 host devices and runs each combination in a
child process, so it never holds a chip and nothing on the serving path
imports it.

Single combo:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k --mesh single --out out.json
Full sweep (subprocess per combo for isolation):
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import json
import re
import subprocess
import sys
import time

import numpy as np


DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def shape_bytes(m):
    dt, dims = m.group(1), m.group(2)
    if dt not in DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str):
    """Per-device bytes moved by collectives: sum of result-shape sizes of
    every collective op (start/done pairs counted once)."""
    totals = {op: 0 for op in COLLECTIVE_OPS}
    counts = {op: 0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        for op in COLLECTIVE_OPS:
            token = f" {op}("
            start = f" {op}-start("
            if token in line or start in line:
                # result type sits between '=' and the op name
                rhs = line.split("=", 1)[-1]
                typestr = rhs.split(op, 1)[0]
                b = sum(shape_bytes(m) for m in _SHAPE_RE.finditer(typestr))
                totals[op] += b
                counts[op] += 1
                break
    return totals, counts


def _parse_val(v):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    if v in ("True", "False"):
        return v == "True"
    return v


def apply_overrides(cfg, sets):
    """--set moe.dispatch=gather --set attn_causal_skip=True ..."""
    import dataclasses
    for kv in sets or []:
        key, val = kv.split("=", 1)
        val = _parse_val(val)
        if "." in key:
            sub, field = key.split(".", 1)
            subcfg = dataclasses.replace(getattr(cfg, sub), **{field: val})
            cfg = dataclasses.replace(cfg, **{sub: subcfg})
        else:
            cfg = dataclasses.replace(cfg, **{key: val})
    return cfg


def lower_one(arch: str, shape_name: str, multi_pod: bool, sets=None):
    import jax
    from repro.configs import INPUT_SHAPES, get_config
    from repro.launch import sharding, specs, steps
    from repro.launch.mesh import (PRODUCTION_KIND, chip_peaks,
                                   make_production_mesh, num_chips)
    from repro.optim.optimizers import make_optimizer

    cfg = get_config(arch)
    cfg = apply_overrides(cfg, sets)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = num_chips(mesh)
    kind = INPUT_SHAPES[shape_name]["kind"]
    seq = INPUT_SHAPES[shape_name]["seq_len"]
    gbatch = INPUT_SHAPES[shape_name]["global_batch"]

    p_shape = specs.params_specs(cfg)
    p_shard = sharding.params_shardings(mesh, cfg, p_shape)
    t0 = time.time()
    if kind == "train":
        init_opt, _ = make_optimizer(cfg.optimizer)
        opt_shape = jax.eval_shape(init_opt, p_shape)
        opt_shard = sharding.opt_state_shardings(mesh, cfg, opt_shape, p_shape)
        batch = specs.input_specs(cfg, shape_name)["batch"]
        b_shard = sharding.batch_shardings(mesh, batch)
        step = steps.make_train_step(cfg, mesh)
        jit = jax.jit(step, in_shardings=(p_shard, opt_shard, b_shard),
                      out_shardings=(p_shard, opt_shard, None),
                      donate_argnums=(0, 1))
        lowered = jit.lower(p_shape, opt_shape, batch)
    elif kind == "prefill":
        batch = specs.input_specs(cfg, shape_name)["batch"]
        b_shard = sharding.batch_shardings(mesh, batch)
        step = steps.make_prefill_step(cfg, mesh)
        jit = jax.jit(step, in_shardings=(p_shard, b_shard))
        lowered = jit.lower(p_shape, batch)
    else:  # decode
        sp = specs.input_specs(cfg, shape_name)
        tok_shard = sharding.batch_shardings(mesh, sp["tokens"])
        cache_shard = sharding.cache_shardings(mesh, cfg, sp["cache"])
        ex_shard = sharding.batch_shardings(mesh, sp["extras"])
        step = steps.make_serve_step(cfg, mesh)
        jit = jax.jit(step,
                      in_shardings=(p_shard, tok_shard, cache_shard, None,
                                    ex_shard),
                      out_shardings=(None, cache_shard),
                      donate_argnums=(2,))
        lowered = jit.lower(p_shape, sp["tokens"], sp["cache"], sp["pos"],
                            sp["extras"])
    lower_s = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    # trip-count-aware static count over the global (unsharded) step —
    # XLA's cost_analysis visits while bodies once (see flopcount.py)
    from repro.launch.flopcount import count_fn
    if kind == "train":
        flops_g, bytes_g = count_fn(step, p_shape, opt_shape, batch)
    elif kind == "prefill":
        flops_g, bytes_g = count_fn(step, p_shape, batch)
    else:
        flops_g, bytes_g = count_fn(step, p_shape, sp["tokens"], sp["cache"],
                                    sp["pos"], sp["extras"])

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    coll, coll_n = collective_bytes(compiled.as_text())
    coll_dev = float(sum(coll.values()))

    # tokens processed per step (global)
    if kind == "train":
        tokens = gbatch * seq
        mf_factor = 6.0
    elif kind == "prefill":
        tokens = gbatch * seq
        mf_factor = 2.0
    else:
        tokens = gbatch
        mf_factor = 2.0
    n_active = cfg.active_param_count()
    model_flops = mf_factor * n_active * tokens

    peaks = chip_peaks(PRODUCTION_KIND)
    compute_s = flops_g / (chips * peaks["flops_bf16"])
    memory_s = bytes_g / (chips * peaks["hbm_bw"])
    collective_s = coll_dev / peaks["ici_bw"]  # per-device bytes over link bw

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "kind": kind, "seq": seq, "global_batch": gbatch,
        "lower_s": round(lower_s, 1), "compile_s": round(compile_s, 1),
        "flops_per_device": flops_dev, "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "collective_breakdown": coll, "collective_counts": coll_n,
        "memory": {
            "argument_gb": mem.argument_size_in_bytes / 2**30,
            "output_gb": mem.output_size_in_bytes / 2**30,
            "temp_gb": mem.temp_size_in_bytes / 2**30,
            "code_mb": mem.generated_code_size_in_bytes / 2**20,
            "peak_gb": (mem.argument_size_in_bytes
                        + mem.temp_size_in_bytes) / 2**30,
        },
        "roofline": {
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s,
            "bottleneck": max(
                [("compute", compute_s), ("memory", memory_s),
                 ("collective", collective_s)], key=lambda kv: kv[1])[0],
        },
        "model_flops": model_flops,
        "counted_flops_global": flops_g,
        "counted_bytes_global": bytes_g,
        "useful_flops_ratio": model_flops / max(flops_g, 1.0),
        "params": cfg.param_count(),
        "active_params": n_active,
    }
    return result


ALL_SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def run_all(archs=None, shapes=None, meshes=("single", "multi"),
            out_dir="benchmarks/results/dryrun", timeout=3600):
    from repro.configs import ASSIGNED_ARCHS
    os.makedirs(out_dir, exist_ok=True)
    archs = archs or ASSIGNED_ARCHS
    shapes = shapes or ALL_SHAPES
    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh in meshes:
                tag = f"{arch}_{shape}_{mesh}".replace("/", "-")
                out = os.path.join(out_dir, tag + ".json")
                if os.path.exists(out):
                    print(f"skip {tag} (cached)")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh,
                       "--out", out]
                print(f"== {tag}", flush=True)
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=timeout,
                                   env={**os.environ, "PYTHONPATH": "src"})
                if r.returncode != 0:
                    failures.append(tag)
                    print(f"FAIL {tag}\n{r.stdout[-2000:]}\n{r.stderr[-4000:]}")
                else:
                    print(f"ok {tag} ({time.time()-t0:.0f}s)")
    print(f"done; {len(failures)} failures: {failures}")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=ALL_SHAPES)
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--out")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--archs", nargs="*")
    ap.add_argument("--shapes", nargs="*")
    ap.add_argument("--meshes", nargs="*", default=["single", "multi"])
    ap.add_argument("--set", action="append", default=None,
                    help="config overrides, e.g. --set moe.dispatch=gather")
    args = ap.parse_args()
    if args.all:
        fails = run_all(args.archs or None, args.shapes or None,
                        tuple(args.meshes))
        sys.exit(1 if fails else 0)
    res = lower_one(args.arch, args.shape, args.mesh == "multi",
                    sets=getattr(args, "set", None))
    print(json.dumps(res, indent=2, default=float))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=2, default=float)


if __name__ == "__main__":
    main()
