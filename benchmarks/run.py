"""Benchmark entrypoint: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--quick]``

Prints ``name,us_per_call,derived`` CSV rows per benchmark plus the
reproduction-vs-paper comparison blocks.
"""
from __future__ import annotations

import argparse
import sys
import time


def timed(name, fn, derived_fn=lambda r: ""):
    t0 = time.perf_counter()
    r = fn()
    us = (time.perf_counter() - t0) * 1e6
    print(f"CSV,{name},{us:.0f},{derived_fn(r)}", flush=True)
    return r


def bench_table4(quick):
    from benchmarks import table4
    kw = dict(n_intervals=30, seeds=(0,), substeps=6,
              pretrain_intervals=60) if quick else \
         dict(n_intervals=100, seeds=(0, 1, 2), substeps=10,
              pretrain_intervals=200)
    rows = table4.run(out_json="benchmarks/results/table4.json", **kw)
    sp = rows["splitplace"]
    return rows, f"splitplace_reward={sp['reward']:.4f};viol={sp['sla_violations']:.3f}"


def bench_splitnets(quick):
    from benchmarks import splitnets_fig2
    rows = splitnets_fig2.run(steps=120 if quick else 300,
                              out_json="benchmarks/results/splitnets_fig2.json")
    mn = rows["mnist"]
    return rows, (f"acc_layer={mn['acc_layer']:.3f};"
                  f"acc_sem={mn['acc_semantic']:.3f}")


def bench_serving(quick):
    from benchmarks import serving_plans
    s = serving_plans.run(n_requests=16 if quick else 40,
                          out_json="benchmarks/results/serving_plans.json")
    return s, f"speedup={s['speedup']:.2f};met={s['deadline_met_frac']:.2f}"


def bench_roofline(quick):
    from benchmarks import roofline
    rows = roofline.load_all()
    if rows:
        print(roofline.table(rows, "16x16"))
    return rows, f"n_dryrun_results={len(rows)}"


def bench_decomposition(quick):
    from benchmarks import decomposition_a6
    out = decomposition_a6.run(
        n_tasks=6 if quick else 12, n_placements=3 if quick else 5,
        out_json="benchmarks/results/decomposition_a6.json")
    return out, f"split_over_placement={out['split_over_placement_ratio']:.1f}x"


def bench_sim_throughput(quick):
    from benchmarks import sim_throughput
    out = sim_throughput.run(
        n_intervals=30 if quick else 100,
        out_json="benchmarks/results/sim_throughput.json")
    return out, (f"speedup={out['speedup']:.1f}x;"
                 f"ips={out['soa']['intervals_per_sec']:.0f}")


def bench_jaxsim_grid(quick):
    from benchmarks import jaxsim_grid
    out = jaxsim_grid.run(sizes=(1, 8) if quick else (1, 4, 8, 16, 32, 64),
                          out_json="benchmarks/results/jaxsim_grid.json")
    return out, (f"speedup8={out['speedup_8_traces']:.2f}x;"
                 f"max_rel_err={out['parity']['max_rel_err']:.1e}")


def bench_sensitivity(quick):
    from benchmarks import sensitivity
    out = {}
    out["lambda"] = sensitivity.sweep_lambda(
        lams=(2, 6) if quick else (2, 6, 12, 24),
        n_intervals=10 if quick else 40, substeps=5 if quick else 8)
    return out, "ok"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes for CI-style runs")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    benches = {
        "splitnets_fig2": bench_splitnets,
        "serving_plans": bench_serving,
        "table4": bench_table4,
        "roofline": bench_roofline,
        "decomposition_a6": bench_decomposition,
        "sensitivity_lambda": bench_sensitivity,
        "sim_throughput": bench_sim_throughput,
        "jaxsim_grid": bench_jaxsim_grid,
    }
    todo = args.only or list(benches)
    failures = []
    for name in todo:
        print(f"\n==== {name} ====", flush=True)
        try:
            r = benches[name](args.quick)
            timed(name, lambda: r, lambda rr: rr[1])
        except Exception as e:  # pragma: no cover
            failures.append((name, repr(e)))
            print(f"FAILED {name}: {e!r}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
