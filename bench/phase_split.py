#!/usr/bin/env python3
"""Split a served cell's device time by program phase and its idle time
by the program's own host spans, on the chip.

    python3 bench/phase_split.py --workload <cell> --seeds <n>[,<n>...]
        [--seconds 30] [--trace 0|1] [--ledger off,on]

For each seed and ledger mode the cell's served window runs as
``bench/paths/serve.py`` runs it (the system's ``StreamFeeder`` on its
own thread, ``StreamRunner.run_chunk`` on the main one), in one
process, so later runs reuse the first one's compiled program.  With
``--ledger on`` the program's ``RunLedger(annotate=True)`` is scoped over
the window; each line then adds to the slowest chunk's host account
(``serve.slowest``) its ``stream_put``, ``stream_dispatch``,
``stream_sync`` and ``stream_fetch`` milliseconds, and gives each
step's median over the window and the tape's bytes.
With ``--trace 1`` the last ``trace_seconds`` (1.5 s) of the window are
profiled and the line adds, from ``bench/trace_phases.py``: device
milliseconds per interval in each phase scope and unscoped, the leaves'
coverage of the loop, the unscoped operations that took most time, and
device idle milliseconds per chunk by program span.  The window's
results are not compared with the reference: ``bench/run.py`` decides
``correct``.  One JSON line per run on standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import common, trace_phases, trace_reduce  # noqa: E402
from bench.paths import serve  # noqa: E402
from bench.run import TRACE_SECONDS  # noqa: E402

STEPS = ("stream_put", "stream_dispatch", "stream_sync", "stream_fetch")


def hlo_scopes(jax, runner, feeder, T):
    """``trace_phases.op_scopes`` of the chunk program the runner ran
    (its executable is in the runner cache: no compile)."""
    import jax.numpy as jnp

    from repro.env.jaxsim import driver
    tape = feeder.next_chunk(T)
    key = driver._static_key(runner.engine, tape, runner.K, runner.cl.n,
                             runner.substeps, runner.interval_s,
                             runner.swap_slowdown, runner.impl, "stream")
    with jax.enable_x64(True):
        leaves = {k: jnp.asarray(v) for k, v in tape.items()}
        text = driver._get_stream_runner(key).lower(
            leaves, runner._cld, runner.carry,
            jnp.asarray(runner.t0, jnp.int64)).compile().as_text()
    return trace_phases.op_scopes(text)


def one(spec, jax, seed, seconds, trace, ledger_on):
    from repro.obs import RunLedger, get_ledger, use_ledger
    T = int(spec["traffic"]["chunk_intervals"])
    _, _, runner, feeder, _ = serve.build(dict(spec, jax=jax, seed=seed))
    runner.run_chunk(feeder.next_chunk(T))
    spans = common.Spans(trace=bool(trace))
    led = RunLedger(spec["cell"]["name"], annotate=True) if ledger_on \
        else get_ledger()
    trace_s = float(spec["traffic"].get("trace_seconds", TRACE_SECONDS))
    tmp = tempfile.mkdtemp(prefix="bench-phases-") if trace else None
    marks = {}

    def tick():
        if trace and "ann" not in marks and \
                time.perf_counter() - marks["window"] >= seconds - trace_s:
            jax.profiler.start_trace(tmp)
            marks["ann"] = jax.profiler.TraceAnnotation("bench.window")
            marks["ann"].__enter__()
            marks["traced"] = [time.perf_counter(), None]

    try:
        gc.collect()
        marks["window"] = time.perf_counter()
        with use_ledger(led):
            chunks, t0, t1, stall = serve.window(runner, feeder, T, seconds,
                                                 spans, tick)
        if "ann" in marks:
            marks["ann"].__exit__(None, None, None)
            marks["traced"][1] = time.perf_counter()
            jax.profiler.stop_trace()
        walls = [e - s for n, s, e in spans.events if n == "chunk"]
        import numpy as np
        line = {"workload": spec["cell"]["name"], "seed": seed,
                "ledger": "on" if ledger_on else "off", "trace": trace,
                "tasks_per_s": float(np.concatenate(chunks)[:, 0].sum())
                / (t1 - t0),
                "chunk_p95_ms": float(np.percentile(walls, 95)) * 1e3,
                "chunks": len(chunks), "slowest_chunk": stall}
        if ledger_on:
            steps = {s: led.spans(s) for s in STEPS}
            i = stall["index"]
            stall.update({s + "_ms": steps[s][i]["dur_s"] * 1e3
                          for s in STEPS})
            line["median_ms"] = {s: float(np.median(
                [e["dur_s"] for e in steps[s]])) * 1e3 for s in STEPS}
            line["put_bytes"] = steps["stream_put"][0]["attrs"]["bytes"]
        if trace:
            line.update(split(spans, marks["traced"], tmp,
                              hlo_scopes(jax, runner, feeder, T), T))
        return line
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


def split(spans, traced, tmp, scopes, T):
    """The traced part's device ms per interval by phase and idle ms per
    chunk by program span."""
    path = trace_reduce.find_xplane(tmp)
    devices, tspans = trace_phases.load(path)
    window = trace_reduce.window_of(tspans)
    n_chunks = spans.count("chunk", *traced)
    ph = trace_phases.phases(devices, window, scopes)
    idle = trace_phases.idle_by_span(devices, tspans, window)
    per_int = 1e3 / max(1, n_chunks * T)
    out = {"traced_chunks": n_chunks, "coverage": ph["coverage"],
           "device_ms_per_interval": ph["busy_s"] * per_int,
           "window_s": (window[0][1] - window[0][0]) * 1e-9,
           "unscoped_ops_ms_per_interval": [[k, v * per_int]
                                            for k, v in ph["unscoped_ops"]],
           "idle_ms_per_chunk": {k: v * 1e3 / max(1, n_chunks)
                                 for k, v in idle.items()}}
    if ph["by_phase"] is not None:
        out["phase_ms_per_interval"] = {k: v * per_int
                                        for k, v in ph["by_phase"].items()}
        out["phase_ms_per_interval"]["unscoped"] = ph["unscoped"] * per_int
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--ledger", default="on")
    args = ap.parse_args(argv)
    spec = common.resolve(args.workload)
    jax = common.set_up_jax()
    try:
        common.chips(jax, spec["cell"]["chips"])
    except common.CellError as e:
        print(f"phase_split: {e}", file=sys.stderr)
        return 2
    modes = [m == "on" for m in args.ledger.split(",")]
    for seed in (int(s) for s in args.seeds.split(",")):
        for on in modes:
            print(json.dumps(one(spec, jax, seed, args.seconds, args.trace,
                                 on)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
