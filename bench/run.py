#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and traffic files and the per-layer metrics
that read it are found by name from ``BENCHMARK.json``.  The run names
the device it measured and exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1``
the per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``,
and last the ``checks``, each number compared beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import common, trace_reduce  # noqa: E402
from bench.peaks import peaks  # noqa: E402

#: seconds at the end of the window that a ``--trace 1`` run profiles
#: (a traffic file may set ``trace_seconds``): every operation
#: inside the interval loops is an event, some 1.5 M a second, and the
#: profiler drops events past a few million
TRACE_SECONDS = 1.5


def run_cell(workload, seed, seconds, trace, root=common.ROOT,
             allow_cpu=False, overrides=None):
    """Run one cell; returns (result, checks).  ``allow_cpu`` and
    ``overrides`` (keys merged into the traffic and configuration) are
    for rehearsals at tiny sizes on the CPU, which give no device
    number."""
    spec = common.resolve(workload, root)
    for part in ("traffic", "config"):
        spec[part] = dict(spec[part], **(overrides or {}).get(part, {}))
    jax = common.set_up_jax(root, cache=not allow_cpu)
    need = spec["cell"]["chips"]
    if allow_cpu:
        devs = jax.devices()
    else:
        devs = common.chips(jax, need)
        peaks(devs[0].device_kind)
    used = devs[:need]
    import repro  # noqa: F401  (the system under test must import)
    spans = common.Spans(trace=bool(trace))
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    trace_s = float(spec["traffic"].get("trace_seconds", TRACE_SECONDS))
    marks = {}

    def start_window():
        gc.collect()
        marks["setup_s"] = time.perf_counter() - T_START
        marks["window"] = time.perf_counter()

    def tick():
        """Start the profiler for the last ``trace_s`` of the window."""
        if trace and "ann" not in marks and \
                time.perf_counter() - marks["window"] >= seconds - trace_s:
            jax.profiler.start_trace(tmp)
            marks["ann"] = jax.profiler.TraceAnnotation("bench.window")
            marks["ann"].__enter__()
            marks["traced"] = [time.perf_counter(), None]

    def end_window():
        if "ann" in marks:
            marks["ann"].__exit__(None, None, None)
            marks["traced"][1] = time.perf_counter()
            jax.profiler.stop_trace()

    ctx = dict(spec, jax=jax, seed=seed, seconds=seconds, spans=spans,
               watch=common.CompileWatch(), start_window=start_window,
               end_window=end_window, tick=tick,
               device_record=lambda: common.device_record(devs, used))
    try:
        mod = importlib.import_module("bench.paths." + spec["traffic"]["path"])
        out = mod.run(ctx)
        correct = all(c["value"] <= c["limit"] for c in out["checks"].values())
        result = {"correct": correct, "attempted": out["attempted"],
                  "failed": out["failed"], "metrics": {},
                  "device": out["device"], "window": out["window"]}
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]
                 + spec["per_layer"]}
        if not trace:
            vals = dict(out["e2e"], setup_s=marks["setup_s"])
            for m in spec["end_to_end"]:
                result["metrics"][m["name"]] = {"value": vals[m["name"]],
                                                "unit": m["unit"]}
            return result, out["checks"]
        devices, tspans = trace_reduce.load(trace_reduce.find_xplane(tmp))
        prof = trace_reduce.reduce(devices, tspans,
                                   trace_reduce.window_of(tspans))
        run = {"spans": spans, "counts": out["counts"], "profile": prof,
               "traced": tuple(marks["traced"])}
        for m in spec["per_layer"]:
            v = common.load_reader(m["reader"])(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": units[m["name"]]}
        result["device"].update(busy_s=prof["busy_s"],
                                window_s=prof["window_s"])
        result["breakdown"] = prof["breakdown"]
        return result, out["checks"]
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, checks = run_cell(args.workload, args.seed, args.seconds,
                                  args.trace)
    except (common.CellError, ImportError, FileNotFoundError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    common.print_result(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
