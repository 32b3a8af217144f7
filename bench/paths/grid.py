"""The grid window.

Set-up makes one ``run_grid_batched`` call (the user entry: trace
building on the host, then the batched program, sharded over the
cell's chips when it asks for more than one), which compiles or loads
the program.  The window repeats the same call until the first that
ends after ``seconds``.  Every call's records, the warm call's too, are
compared with the plain reference's summary of each grid cell.
"""
from __future__ import annotations

import time

from bench import common, compare
from bench.ref import replay


def run(ctx) -> dict:
    from repro.env.jaxsim import driver
    from repro.launch.experiments import run_grid_batched
    spans, watch = ctx["spans"], ctx["watch"]
    tr, cfg = ctx["traffic"], ctx["config"]
    base = common.sub_seed(ctx["seed"], 0)
    seeds = [base + i for i in range(tr["n_seeds"])]
    chips = ctx["cell"]["chips"]
    kw = dict(policy=tr["engine"], seeds=seeds, lams=tr["lams"],
              n_intervals=tr["n_intervals"], interval_s=cfg["interval_s"],
              substeps=cfg["substeps"], apps=cfg["apps"],
              cluster=common.program_cluster(cfg),
              devices=chips if chips > 1 else None)
    calls = [run_grid_batched(**kw)]
    ctx["start_window"]()
    programs, misses = watch.programs, driver.cache_stats()["misses"]
    t0 = time.perf_counter()
    while True:
        with spans.span("grid_call"):
            calls.append(run_grid_batched(**kw))
        ctx["tick"]()
        if time.perf_counter() - t0 >= ctx["seconds"]:
            break
    t1 = time.perf_counter()
    ctx["end_window"]()
    compiled = watch.programs - programs \
        + driver.cache_stats()["misses"] - misses
    if compiled:
        raise common.CellError(f"{compiled} compiles inside the window")
    n_cells = len(seeds) * len(tr["lams"])
    done = (len(calls) - 1) * n_cells * tr["n_intervals"]
    counts = {"window_s": t1 - t0,
              "cell_intervals_per_call": n_cells * tr["n_intervals"]}
    e2e = {"cell_intervals_per_s": done / (t1 - t0)}
    device = ctx["device_record"]()
    refs = {(r["lam"], r["seed"]): replay.grid_summary(
        cfg, tr["engine"], r["seed"], r["lam"], tr["n_intervals"])
        for r in calls[0]}
    gap = max(compare.summary_gap(rec, refs[(rec["lam"], rec["seed"])])
              if len(recs) == n_cells else float("inf")
              for recs in calls for rec in recs)
    failed = sum(any(rec["dropped_tasks"] for rec in recs)
                 for recs in calls[1:])
    checks = {"summary_gap": {"value": gap,
                              "limit": tr["limits"]["summary_gap"]}}
    walls = [e - s for n, s, e in spans.events if n == "grid_call"]
    return {"e2e": e2e, "counts": counts, "checks": checks,
            "attempted": len(calls) - 1, "failed": int(failed),
            "device": device, "window": common.spread("grid_call", walls)}
