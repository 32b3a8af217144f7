"""Learned-policy batched-grid benchmark: in-kernel SplitPlace vs host loop.

PR 2's batched backend only covered static BestFit policies; this
benchmark pins the PR 3 claim — the *learned* SplitPlace policy (online
MAB decider + array-form DASO placer) running inside the jitted interval
kernel.  Two measurements over (seed × λ) dual-trace grids:

  * **parity** — the 8-trace acceptance grid run through
    the ``"splitplace"`` engine of ``jaxsim.resolve`` must match
    per-trace host-loop replays
    (``replay_trace_edgesim_learned``: EdgeSim physics + the identical
    shared MAB/DASO pure functions) within ``allclose(rtol=1e-4)`` on
    every summary metric, including the final carried-MAB scalars;
  * **throughput** — warm traces/sec of the one-compiled-call batched
    backend vs looping the host replay over the same cells (the batched
    path must clear 3×; in practice the win is far larger because the
    host loop pays a Python round trip per interval for the surrogate
    ascent and MAB feedback).

The MAB state and DASO surrogate come from a real §6.3 host pretraining
pass (``launch.experiments.pretrain``), i.e. the same states a Table-4
SplitPlace row would deploy.

``--train`` benchmarks PR 4's claim instead: the full in-kernel
*training* loop (``mode="train"`` — ε-greedy MAB decisions + online
DASO finetuning in the interval carry) vs looping the host training
replay (``replay_trace_edgesim_trained``), parity extended to the
finetuned theta and the same floor on the 8-trace grid.

``--baselines`` benchmarks the unified-engine arms PR 5 brought
in-kernel — the Gillis contextual Q-learner and the decision-blind
MAB+GOBI ablation — against their host oracles
(``replay_trace_edgesim_gillis`` / ``replay_trace_edgesim_learned``
with a blind config), under the same parity + throughput contract.

Every mode enforces ``MIN_SPEEDUP`` (≥3× traces/sec vs the host loop)
as a hard floor, so a driver-unification or engine change cannot
silently regress the compiled hot path — the ``--quick`` CI runs fail
the build when the floor breaks.

``PYTHONPATH=src python -m benchmarks.jaxsim_learned
    [--quick] [--train] [--baselines]``
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import time

try:
    from benchmarks._provenance import obs_scope as _obs_scope
    from benchmarks._provenance import provenance
except ImportError:       # run as a loose script from benchmarks/
    from _provenance import obs_scope as _obs_scope
    from _provenance import provenance

import numpy as np

PARITY_KEYS = ("accuracy", "sla_violations", "reward", "response_intervals",
               "wait_intervals", "exec_intervals", "energy_mwhr", "fairness",
               "cost_per_container", "layer_fraction", "tasks_completed",
               "mab_eps", "mab_rho", "mab_t")

GILLIS_PARITY_KEYS = PARITY_KEYS[:-3] + ("gillis_eps",)

#: hard throughput floor — batched traces/sec must clear this multiple
#: of the host loop on the 8-trace acceptance grid, in every mode
MIN_SPEEDUP = 3.0

#: hard ceiling on the warm-path cost of ``telemetry="interval"`` vs
#: ``"summary"`` on the 8-trace grid (interleaved min-of-N on both
#: modes) — the in-carry series must stay within 5% of free
MAX_TELEMETRY_OVERHEAD = 0.05

def grid_cells(n: int):
    """First ``n`` cells of the canonical (λ × seed) benchmark grid."""
    lams, seeds = (2.0, 4.0, 6.0, 8.0), tuple(range(16))
    return list(itertools.product(lams, seeds))[:n] if n != 8 else \
        [(l, s) for l in lams for s in (0, 1)]


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _parity(refs, outs, check_theta=False, keys=PARITY_KEYS,
            tree_keys=()):
    """Shared cross-backend parity check: allclose(rtol=1e-4) over
    ``keys`` (optionally incl. pytree payloads — the finetuned theta,
    the Gillis Q-table) plus the dropped-task count; returns
    (ok, max_rel_err, dropped)."""
    import jax
    tree_keys = tuple(tree_keys) + (("daso_theta",) if check_theta else ())
    max_rel, ok = 0.0, True
    for ref, b in zip(refs, outs):
        for k in keys:
            denom = max(abs(ref[k]), 1e-12)
            max_rel = max(max_rel, abs(ref[k] - b[k]) / denom)
            if not np.isclose(ref[k], b[k], rtol=1e-4, atol=1e-9):
                ok = False
        for tk in tree_keys:
            for x, y in zip(jax.tree_util.tree_leaves(ref[tk]),
                            jax.tree_util.tree_leaves(b[tk])):
                if not np.allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-9):
                    ok = False
    dropped = sum(b["dropped_tasks"] for b in outs)
    return ok, max_rel, dropped


def run(n_intervals=20, substeps=10, sizes=(1, 8, 16), max_active=96,
        pretrain_intervals=16, pretrain_substeps=5, out_json=None,
        telemetry="summary", profile_dir=None):
    from repro.env import jaxsim
    from repro.launch import experiments

    with _obs_scope("jaxsim_learned", telemetry=telemetry,
                    profile_dir=profile_dir) as led:
        out = _run_ledgered(jaxsim, experiments, led, n_intervals,
                            substeps, sizes, max_active,
                            pretrain_intervals, pretrain_substeps,
                            telemetry, profile_dir)
    out["cache_stats"] = {k: v for k, v in jaxsim.cache_stats().items()
                          if k != "keys"}
    if out_json:
        os.makedirs(os.path.dirname(out_json), exist_ok=True)
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
    return out


def _run_ledgered(jaxsim, experiments, led, n_intervals, substeps, sizes,
                  max_active, pretrain_intervals, pretrain_substeps,
                  telemetry, profile_dir):
    t0 = time.perf_counter()
    pre = experiments.pretrain(pretrain_intervals, lam=5.0, seed=7,
                               substeps=pretrain_substeps)
    pretrain_s = time.perf_counter() - t0
    print(f"pretrain ({pretrain_intervals} intervals): {pretrain_s:.1f}s")

    def compile_cells(cells):
        return [jaxsim.compile_trace_dual(lam=lam, seed=seed,
                                          n_intervals=n_intervals,
                                          substeps=substeps)
                for lam, seed in cells]

    sp = jaxsim.resolve("splitplace", mab_state=pre.mab_state,
                        daso_theta=pre.daso_theta, daso_cfg=pre.daso_cfg)

    def batched(traces, tel=telemetry):
        return jaxsim.run_grid_engine(sp.engine, traces, sp.state,
                                      max_active=max_active, telemetry=tel)

    def host_loop(traces):
        return [jaxsim.replay_trace_edgesim_learned(
            tr, pre.mab_state, daso_theta=pre.daso_theta,
            daso_cfg=pre.daso_cfg) for tr in traces]

    out = {"policy": "splitplace", "n_intervals": n_intervals,
           "substeps": substeps, "max_active": max_active,
           "pretrain_intervals": pretrain_intervals,
           "pretrain_s": pretrain_s}

    # ---- parity: 8-trace acceptance grid vs per-trace host replay ------
    traces8 = compile_cells(grid_cells(8))
    t0 = time.perf_counter()
    batched8 = batched(traces8)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    refs8 = host_loop(traces8)           # timed: reused as the 8-trace
    host8_s = time.perf_counter() - t0   # throughput sample below
    ok, max_rel, dropped = _parity(refs8, batched8)
    out["parity"] = {"allclose_rtol1e4": ok, "max_rel_err": max_rel,
                     "dropped_tasks": dropped, "n_traces": len(traces8)}
    print(f"parity (8-trace grid): allclose={ok} "
          f"max_rel_err={max_rel:.2e} dropped={dropped}")
    assert ok and dropped == 0, "learned-policy jaxsim parity failure"

    # ---- throughput: batched one-call vs host interval loop ------------
    # batched side is min-of-N (machine-noise capability statistic); the
    # host loop is ~2 orders slower per trace, one sample is plenty —
    # and the 8-trace grid reuses the parity pass's host sample instead
    # of paying for the slow loop twice
    out["grids"] = {}
    for size in sizes:
        traces = traces8 if size == 8 else compile_cells(grid_cells(size))
        batched(traces)                       # warm/compile
        tb = min(_timed(lambda: batched(traces)) for _ in range(3))
        th = host8_s if size == 8 else _timed(lambda: host_loop(traces))
        rec = {"batched_s": tb, "batched_traces_per_sec": size / tb,
               "host_s": th, "host_traces_per_sec": size / th,
               "speedup": th / tb}
        out["grids"][str(size)] = rec
        print(f"grid {size:3d}: batched {size / tb:7.1f} tr/s  "
              f"host {size / th:6.2f} tr/s  speedup {th / tb:7.1f}x")

    g8 = out["grids"].get("8")
    if g8:
        out["speedup_8_traces"] = g8["speedup"]
        print(f"8-trace grid speedup: {g8['speedup']:.1f}x "
              f"(compile+first-call {compile_s:.1f}s, amortized across "
              f"every later grid of the same shape)")
        assert g8["speedup"] >= MIN_SPEEDUP, \
            f"throughput floor: expected >= {MIN_SPEEDUP}x, " \
            f"got {g8['speedup']:.2f}x"

    # ---- telemetry overhead: the in-carry series must be ~free ---------
    # interleaved min-of-N on both modes (shared-CPU containers see
    # different machine windows back-to-back); the ceiling is a hard
    # floor-style assertion so the series can never silently tax the
    # compiled hot path
    tel8 = batched(traces8, tel="interval")   # warm/compile interval mode
    batched(traces8, tel="summary")           # warm (cache hit)
    t_sum, t_int = [], []
    for _ in range(5):
        t_sum.append(_timed(lambda: batched(traces8, tel="summary")))
        t_int.append(_timed(lambda: batched(traces8, tel="interval")))
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
    led.add_series("trace0", tel8[0]["telemetry"]["cols"],
                   tel8[0]["telemetry"]["series"])

    if profile_dir:
        with led.profile(profile_dir):
            batched(traces8)

    out["provenance"] = provenance(telemetry=telemetry)
    return out


def run_train(n_intervals=40, substeps=5, max_active=160,
              pretrain_intervals=16, pretrain_substeps=5, out_json=None,
              train_hp=None, telemetry="summary"):
    """mode="train" measurement: the FULL §6.3 training loop — ε-greedy
    MAB decisions + in-kernel DASO finetuning — batched in the jitted
    kernel vs looping the host training replay
    (``replay_trace_edgesim_trained``) over the same 8 dual-trace cells.
    Parity covers every summary metric, the final MAB scalars AND the
    finetuned DASO theta; the acceptance bar is ≥3× traces/sec (in
    practice far larger: the host loop pays per-interval Python round
    trips for the surrogate ascent AND the weighted train epochs).

    The default 40-interval horizon opens the host-default cold-start
    gates (place_min=32), so the *finetuned-surrogate-ascended*
    placement path is exercised; ``--quick`` shortens the horizon and
    lowers the gates via ``train_hp`` instead, keeping the same path
    coverage at CI cost."""
    from repro.env import jaxsim
    from repro.launch import experiments

    train_hp = train_hp or jaxsim.TRAIN_HP

    t0 = time.perf_counter()
    pre = experiments.pretrain(pretrain_intervals, lam=5.0, seed=7,
                               substeps=pretrain_substeps)
    pretrain_s = time.perf_counter() - t0
    print(f"pretrain ({pretrain_intervals} intervals): {pretrain_s:.1f}s")

    traces = [jaxsim.compile_trace_dual(lam=lam, seed=seed,
                                        n_intervals=n_intervals,
                                        substeps=substeps)
              for lam, seed in grid_cells(8)]

    sp = jaxsim.resolve("splitplace", mode="train",
                        mab_state=pre.mab_state, daso_theta=pre.daso_theta,
                        daso_cfg=pre.daso_cfg,
                        daso_opt_state=pre.daso_opt_state, train_hp=train_hp)

    def batched():
        return jaxsim.run_grid_engine(sp.engine, traces, sp.state,
                                      max_active=max_active,
                                      telemetry=telemetry)

    def host_loop():
        return [jaxsim.replay_trace_edgesim_trained(
            tr, pre.mab_state, daso_theta=pre.daso_theta,
            daso_cfg=pre.daso_cfg, daso_opt_state=pre.daso_opt_state,
            train_hp=train_hp) for tr in traces]

    t0 = time.perf_counter()
    b8 = batched()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    refs = host_loop()
    host_s = time.perf_counter() - t0

    ok, max_rel, dropped = _parity(refs, b8, check_theta=True)
    print(f"train parity (8-trace grid incl. theta): allclose={ok} "
          f"max_rel_err={max_rel:.2e} dropped={dropped}")
    assert ok and dropped == 0, "train-mode jaxsim parity failure"

    tb = min(_timed(batched) for _ in range(3))
    speedup = host_s / tb
    print(f"train grid 8: batched {8 / tb:7.1f} tr/s  "
          f"host {8 / host_s:6.2f} tr/s  speedup {speedup:7.1f}x "
          f"(compile+first-call {compile_s:.1f}s)")
    assert speedup >= MIN_SPEEDUP, \
        f"throughput floor: expected >= {MIN_SPEEDUP}x, " \
        f"got {speedup:.2f}x"

    out = {"policy": "splitplace", "mode": "train",
           "n_intervals": n_intervals, "substeps": substeps,
           "max_active": max_active, "train_hp": list(train_hp),
           "pretrain_s": pretrain_s,
           "parity": {"allclose_rtol1e4": ok, "max_rel_err": max_rel,
                      "dropped_tasks": dropped, "n_traces": 8},
           "batched_s": tb, "batched_traces_per_sec": 8 / tb,
           "host_s": host_s, "host_traces_per_sec": 8 / host_s,
           "speedup_8_traces": speedup}
    out["provenance"] = provenance()
    if out_json:
        os.makedirs(os.path.dirname(out_json), exist_ok=True)
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
    return out


def run_baselines(n_intervals=20, substeps=10, max_active=96,
                  pretrain_intervals=16, pretrain_substeps=5,
                  out_json=None, telemetry="summary"):
    """The unified-engine baseline arms — the in-kernel Gillis
    contextual Q-learner and the decision-blind MAB+GOBI ablation —
    under the same parity + ``MIN_SPEEDUP`` throughput contract as the
    SplitPlace arms, on the 8-trace acceptance grid.  Gillis' parity
    covers the final Q-table and ε; GOBI's the final MAB scalars.  The
    floor makes the engine unification's hot path a CI invariant for
    the new arms too."""
    from repro.env import jaxsim
    from repro.env.workload import COMPRESSED, LAYER
    from repro.launch import experiments

    out = {"n_intervals": n_intervals, "substeps": substeps,
           "max_active": max_active, "arms": {}}

    # ---- gillis: no pretraining products needed ------------------------
    gtr = [jaxsim.compile_trace_dual(lam=lam, seed=seed,
                                     n_intervals=n_intervals,
                                     substeps=substeps,
                                     variants=(LAYER, COMPRESSED))
           for lam, seed in grid_cells(8)]

    gillis = jaxsim.resolve("gillis")

    def g_batched():
        return jaxsim.run_grid_engine(gillis.engine, gtr, gillis.state,
                                      max_active=max_active,
                                      telemetry=telemetry)

    def g_host():
        return [jaxsim.replay_trace_edgesim_gillis(tr) for tr in gtr]

    b8 = g_batched()                       # warm/compile
    t0 = time.perf_counter()
    refs = g_host()
    host_s = time.perf_counter() - t0
    ok, max_rel, dropped = _parity(refs, b8, keys=GILLIS_PARITY_KEYS,
                                   tree_keys=("gillis_q",))
    print(f"gillis parity (8-trace grid incl. Q/ε): allclose={ok} "
          f"max_rel_err={max_rel:.2e} dropped={dropped}")
    assert ok and dropped == 0, "gillis jaxsim parity failure"
    tb = min(_timed(g_batched) for _ in range(3))
    speedup = host_s / tb
    print(f"gillis grid 8: batched {8 / tb:7.1f} tr/s  "
          f"host {8 / host_s:6.2f} tr/s  speedup {speedup:7.1f}x")
    assert speedup >= MIN_SPEEDUP, \
        f"gillis throughput floor: expected >= {MIN_SPEEDUP}x, " \
        f"got {speedup:.2f}x"
    out["arms"]["gillis"] = {
        "parity": {"allclose_rtol1e4": ok, "max_rel_err": max_rel},
        "batched_traces_per_sec": 8 / tb, "host_traces_per_sec": 8 / host_s,
        "speedup_8_traces": speedup}

    # ---- mab+gobi: blind surrogate from a real pretraining pass --------
    pre = experiments.pretrain(pretrain_intervals, lam=5.0, seed=7,
                               substeps=pretrain_substeps)
    blind = pre.daso_cfg._replace(decision_aware=False)
    btr = [jaxsim.compile_trace_dual(lam=lam, seed=seed,
                                     n_intervals=n_intervals,
                                     substeps=substeps)
           for lam, seed in grid_cells(8)]

    gobi = jaxsim.resolve("mab+gobi", mab_state=pre.mab_state,
                          daso_theta=pre.daso_theta, daso_cfg=pre.daso_cfg)

    def b_batched():
        return jaxsim.run_grid_engine(gobi.engine, btr, gobi.state,
                                      max_active=max_active,
                                      telemetry=telemetry)

    def b_host():
        return [jaxsim.replay_trace_edgesim_learned(
            tr, pre.mab_state, daso_theta=pre.daso_theta, daso_cfg=blind)
            for tr in btr]

    b8 = b_batched()                       # warm/compile
    t0 = time.perf_counter()
    refs = b_host()
    host_s = time.perf_counter() - t0
    ok, max_rel, dropped = _parity(refs, b8)
    print(f"mab+gobi parity (8-trace grid): allclose={ok} "
          f"max_rel_err={max_rel:.2e} dropped={dropped}")
    assert ok and dropped == 0, "mab+gobi jaxsim parity failure"
    tb = min(_timed(b_batched) for _ in range(3))
    speedup = host_s / tb
    print(f"mab+gobi grid 8: batched {8 / tb:7.1f} tr/s  "
          f"host {8 / host_s:6.2f} tr/s  speedup {speedup:7.1f}x")
    assert speedup >= MIN_SPEEDUP, \
        f"mab+gobi throughput floor: expected >= {MIN_SPEEDUP}x, " \
        f"got {speedup:.2f}x"
    out["arms"]["mab+gobi"] = {
        "parity": {"allclose_rtol1e4": ok, "max_rel_err": max_rel},
        "batched_traces_per_sec": 8 / tb, "host_traces_per_sec": 8 / host_s,
        "speedup_8_traces": speedup}

    out["provenance"] = provenance()
    if out_json:
        os.makedirs(os.path.dirname(out_json), exist_ok=True)
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run (parity + the 8-trace grid)")
    ap.add_argument("--train", action="store_true",
                    help="benchmark mode='train' (in-kernel ε-greedy MAB "
                         "+ DASO finetuning) instead of deploy mode")
    ap.add_argument("--baselines", action="store_true",
                    help="benchmark the in-kernel baseline arms (gillis, "
                         "mab+gobi) instead of the SplitPlace arms")
    ap.add_argument("--telemetry", default="summary",
                    choices=("summary", "interval"),
                    help="run the measured grids with the in-carry "
                         "interval telemetry series on (the overhead "
                         "check always measures both modes)")
    ap.add_argument("--profile-dir", default=None,
                    help="also capture a jax.profiler trace of one warm "
                         "grid call under this directory")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.baselines:
        out = args.out or "benchmarks/results/jaxsim_baselines.json"
        with _obs_scope("jaxsim_baselines", telemetry=args.telemetry):
            if args.quick:
                run_baselines(n_intervals=10, substeps=5, max_active=96,
                              pretrain_intervals=8, out_json=out,
                              telemetry=args.telemetry)
            else:
                run_baselines(out_json=out, telemetry=args.telemetry)
        return
    if args.train:
        out = args.out or "benchmarks/results/jaxsim_learned_train.json"
        with _obs_scope("jaxsim_learned_train", telemetry=args.telemetry):
            if args.quick:
                # short horizon + open gates: same path coverage, CI cost
                run_train(n_intervals=12, substeps=5, max_active=96,
                          train_hp=(0.5, 0.5, 4, 6, 4), out_json=out,
                          telemetry=args.telemetry)
            else:
                run_train(out_json=out, telemetry=args.telemetry)
        return
    out = args.out or "benchmarks/results/jaxsim_learned.json"
    if args.quick:
        run(sizes=(8,), out_json=out, telemetry=args.telemetry,
            profile_dir=args.profile_dir)
    else:
        run(out_json=out, telemetry=args.telemetry,
            profile_dir=args.profile_dir)


if __name__ == "__main__":
    main()
