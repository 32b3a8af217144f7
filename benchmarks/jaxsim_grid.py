"""Batched jitted-grid benchmark: traces/sec + parity vs the host loop.

Two measurements over (seed × λ) BestFit grids:

  * **parity** — the 8-trace acceptance grid run through
    ``run_grid_batched`` must match per-trace ``EdgeSim`` replays of the
    same compiled workloads within ``allclose(rtol=1e-4)`` on every
    summary metric;
  * **throughput** — warm traces/sec of the one-compiled-call batched
    backend for grids of 1–64 traces vs looping the host
    ``launch.experiments.run_trace`` over the same cells (the batched
    path must clear 3×).

``--devices N`` adds a third measurement: the shard_map grid dispatcher
(1-D ``"grid"`` device mesh, forced host devices on CPU) vs the same
whole-grid vmap on a single device — the ≥3× scaling floor is asserted
only when the host actually has ``N`` cores to back the forced devices
(timeshared cores can't speed anything up; the column is informational
there).

``PYTHONPATH=src python -m benchmarks.jaxsim_grid [--quick] [--devices N]``
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import time

import numpy as np

try:
    from benchmarks._provenance import obs_scope as _obs_scope
    from benchmarks._provenance import provenance
except ImportError:       # run as a loose script from benchmarks/
    from _provenance import obs_scope as _obs_scope
    from _provenance import provenance

PARITY_KEYS = ("accuracy", "sla_violations", "reward", "response_intervals",
               "wait_intervals", "exec_intervals", "energy_mwhr", "fairness",
               "cost_per_container", "layer_fraction", "tasks_completed")

#: hard ceiling on the warm-path cost of ``telemetry="interval"`` vs
#: ``"summary"`` on the 8-trace grid (interleaved min-of-N; the static
#: grid writes one 18-column row per interval, measured ~0.5%)
MAX_TELEMETRY_OVERHEAD = 0.05


def grid_cells(n: int):
    """First ``n`` cells of the canonical (λ × seed) benchmark grid."""
    lams, seeds = (2.0, 4.0, 6.0, 8.0), (0, 1, 2, 3, 4, 5, 6, 7,
                                         8, 9, 10, 11, 12, 13, 14, 15)
    return list(itertools.product(lams, seeds))[:n] if n != 8 else \
        [(l, s) for l in lams for s in (0, 1)]


def run(n_intervals=20, substeps=10, sizes=(1, 4, 8, 16, 32, 64),
        max_active=96, out_json=None, devices=None, substep_impl=None,
        telemetry="summary", profile_dir=None):
    from repro.env import jaxsim
    from repro.launch import experiments

    pol = jaxsim.resolve("bestfit-rr")

    def compile_cells(cells):
        return [jaxsim.compile_trace(pol.decider, lam=lam, seed=seed,
                                     n_intervals=n_intervals,
                                     substeps=substeps)
                for lam, seed in cells]

    def run_grid(traces, **kw):
        return jaxsim.run_grid_engine(pol.engine, traces, pol.state,
                                      max_active=max_active,
                                      substep_impl=substep_impl, **kw)

    out = {"policy": "bestfit-rr", "n_intervals": n_intervals,
           "substeps": substeps, "max_active": max_active,
           "provenance": provenance(substep_impl=substep_impl or
                                    os.environ.get("JAXSIM_SUBSTEP_IMPL",
                                                   "xla"),
                                    devices=devices,
                                    telemetry=telemetry)}

    # ---- parity: 8-trace acceptance grid vs per-trace EdgeSim ----------
    cells8 = grid_cells(8)
    traces8 = compile_cells(cells8)
    t0 = time.perf_counter()
    batched = run_grid(traces8, telemetry=telemetry)
    compile_s = time.perf_counter() - t0
    if telemetry == "interval":
        from repro.obs import get_ledger
        get_ledger().add_series("trace0", batched[0]["telemetry"]["cols"],
                                batched[0]["telemetry"]["series"])
    max_rel = 0.0
    ok = True
    for tr, b in zip(traces8, batched):
        ref = jaxsim.replay_trace_edgesim(tr)
        for k in PARITY_KEYS:
            denom = max(abs(ref[k]), 1e-12)
            max_rel = max(max_rel, abs(ref[k] - b[k]) / denom)
            if not np.isclose(ref[k], b[k], rtol=1e-4, atol=1e-9):
                ok = False
    dropped = sum(b["dropped_tasks"] for b in batched)
    out["parity"] = {"allclose_rtol1e4": ok, "max_rel_err": max_rel,
                     "dropped_tasks": dropped, "n_traces": len(traces8)}
    print(f"parity (8-trace grid): allclose={ok} "
          f"max_rel_err={max_rel:.2e} dropped={dropped}")
    assert ok and dropped == 0, "jaxsim parity failure"

    # ---- throughput scaling: batched one-call vs host loop -------------
    # interleaved min-of-N on both sides: the container CPUs are shared,
    # so back-to-back blocks see different machine windows — alternating
    # samples keeps the comparison honest, min is the capability statistic
    def measure(size, reps):
        cells = grid_cells(size)
        traces = compile_cells(cells)
        run_grid(traces, telemetry=telemetry)  # warm/compile
        tb, th = [], []
        for _ in range(reps):
            tb.append(_timed(lambda: run_grid(traces, telemetry=telemetry)))
            th.append(_timed(lambda: [experiments.run_trace(
                policy=jaxsim.host_policy("bestfit-rr"),
                n_intervals=n_intervals, lam=lam, seed=seed,
                substeps=substeps) for lam, seed in cells]))
        return min(tb), min(th)

    out["grids"] = {}
    for size in sizes:
        tb, th = measure(size, reps=4)
        # shared-CPU containers hit multi-second noise windows; escalate
        # the sample count (min is the capability statistic) before
        # concluding the acceptance grid missed its bar
        for reps in (8, 12):
            if size != 8 or th / tb >= 3.0:
                break
            tb2, th2 = measure(size, reps=reps)
            tb, th = min(tb, tb2), min(th, th2)
        rec = {"batched_s": tb, "batched_traces_per_sec": size / tb,
               "host_s": th, "host_traces_per_sec": size / th,
               "speedup": th / tb}
        out["grids"][str(size)] = rec
        print(f"grid {size:3d}: batched {size / tb:7.1f} tr/s  "
              f"host {size / th:6.1f} tr/s  speedup {th / tb:5.2f}x")

    g8 = out["grids"].get("8")
    if g8:
        out["speedup_8_traces"] = g8["speedup"]
        print(f"8-trace grid speedup: {g8['speedup']:.2f}x "
              f"(compile+first-call {compile_s:.1f}s, amortized across "
              f"every later grid of the same shape)")
        assert g8["speedup"] >= 3.0, \
            f"acceptance: expected >= 3x, got {g8['speedup']:.2f}x"

    # ---- telemetry overhead: the in-carry series must be ~free ---------
    def run8(tel):
        return run_grid(traces8, telemetry=tel)

    run8("interval")                          # warm/compile interval mode
    run8("summary")                           # warm (cache hit)
    t_sum, t_int = [], []
    for _ in range(8):                        # interleaved: shared CPUs
        t_sum.append(_timed(lambda: run8("summary")))
        t_int.append(_timed(lambda: run8("interval")))
    overhead = min(t_int) / min(t_sum) - 1.0
    out["telemetry"] = {"mode": telemetry,
                        "summary_s": min(t_sum), "interval_s": min(t_int),
                        "overhead_8_traces": overhead,
                        "max_overhead": MAX_TELEMETRY_OVERHEAD}
    print(f"telemetry overhead (8-trace grid): {overhead * 100:+.1f}% "
          f"(summary {min(t_sum):.3f}s, interval {min(t_int):.3f}s)")
    assert overhead <= MAX_TELEMETRY_OVERHEAD, \
        f"telemetry overhead ceiling: expected <= " \
        f"{MAX_TELEMETRY_OVERHEAD:.0%}, got {overhead:.1%}"

    # ---- device scaling: shard_map mesh vs single-device whole-grid ----
    if devices:
        d = int(devices)
        traces = compile_cells(grid_cells(2 * d))
        nt = len(traces)

        def single():
            return run_grid(traces, threads=1)

        def sharded():
            return run_grid(traces, devices=d)

        base, shd = single(), sharded()      # warm/compile both paths
        for i, (b, s) in enumerate(zip(base, shd)):
            for k in PARITY_KEYS:
                assert np.isclose(b[k], s[k], rtol=1e-4, atol=1e-9), \
                    f"sharded row {i} {k}: single={b[k]!r} sharded={s[k]!r}"
        t1 = min(_timed(single) for _ in range(4))
        td = min(_timed(sharded) for _ in range(4))
        rec = {"devices": d, "n_traces": nt,
               "single_device_s": t1, "sharded_s": td,
               "single_device_traces_per_sec": nt / t1,
               "sharded_traces_per_sec": nt / td,
               "speedup_vs_single_device": t1 / td}
        out["devices_scaling"] = rec
        print(f"devices {d}: sharded {nt / td:7.1f} tr/s  "
              f"single-dev {nt / t1:7.1f} tr/s  speedup {t1 / td:5.2f}x")
        cores = os.cpu_count() or 1
        if cores >= d:
            assert t1 / td >= 3.0, \
                f"device scaling: expected >= 3x on {cores} cores, " \
                f"got {t1 / td:.2f}x"
        else:
            print(f"note: {cores} host cores < {d} forced devices — "
                  "timeshared cores, speedup informational only")

    if profile_dir:
        from repro.obs import get_ledger
        with get_ledger().profile(profile_dir):
            run_grid(traces8, telemetry=telemetry)

    if out_json:
        os.makedirs(os.path.dirname(out_json), exist_ok=True)
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
    return out


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run (parity + 1/8-trace grids)")
    ap.add_argument("--devices", type=int, default=None,
                    help="measure shard_map grid dispatch over N devices "
                         "(forces N host devices on CPU)")
    ap.add_argument("--substep-impl", default=None,
                    choices=("xla", "pallas", "ref"),
                    help="substep physics implementation")
    ap.add_argument("--devices-only", action="store_true",
                    help="parity + device scaling only; skip the "
                         "host-loop throughput grids (the xla leg owns "
                         "that floor)")
    ap.add_argument("--telemetry", default="summary",
                    choices=("summary", "interval"),
                    help="run the measured grids with the in-carry "
                         "interval telemetry series on")
    ap.add_argument("--profile-dir", default=None,
                    help="also capture a jax.profiler trace of one warm "
                         "grid call under this directory")
    ap.add_argument("--out", default="benchmarks/results/jaxsim_grid.json")
    args = ap.parse_args()
    if args.devices and args.devices > 1:
        # must land before the first jax import (run() imports lazily)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                + str(args.devices)).strip()
    kw = dict(out_json=args.out, devices=args.devices,
              substep_impl=args.substep_impl, telemetry=args.telemetry,
              profile_dir=args.profile_dir)
    with _obs_scope("jaxsim_grid", telemetry=args.telemetry,
                    devices=args.devices):
        if args.devices_only:
            run(sizes=(), **kw)
        elif args.quick:
            # acceptance-shaped grid, fewer sizes (compile dominates
            # CI time)
            run(sizes=(1, 8), **kw)
        else:
            run(**kw)


if __name__ == "__main__":
    main()
