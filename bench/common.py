"""Shared pieces of the benchmark harness: finding a cell's files by
name, seeds, compile counting, host spans, the device record and the
result line."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import threading
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class CellError(RuntimeError):
    """The run cannot measure this cell (no chip, missing files, a
    compile inside the window)."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, root: str = ROOT) -> dict:
    """Everything a cell needs, found by name from ``BENCHMARK.json``:
    the cell entry, its configuration file, its traffic file
    (``bench/workloads/<traffic>.json``) and the per-layer metrics that
    read it, each with the path of its reader
    (``bench/metrics/<metric>.py``)."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no cell {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "workloads",
                                     cell["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [dict(m, reader=os.path.join(root, "bench", "metrics",
                                             m["name"] + ".py"))
                 for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return {"cell": cell, "config": cfg, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def load_reader(path):
    """The ``read(run)`` function of one per-layer metric's file."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def sub_seed(seed: int, k: int = 0) -> int:
    """A NumPy- and JAX-safe seed below 2**31 - 64 drawn from ``seed``
    (any whole number) and a stream index ``k``: the traffic, the
    surrogate's weights and the grid's cells each take their own."""
    import numpy as np
    ss = np.random.SeedSequence([int(seed) % (1 << 63), k])
    return int(ss.generate_state(1)[0] % ((1 << 31) - 64))


class CompileWatch:
    """Counts XLA compiles (or persistent-cache loads), from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.programs = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.programs += 1


class Spans:
    """Host spans of the harness, (name, start, end) on the
    ``perf_counter`` clock; with ``trace=True`` each one is also a
    profiler annotation ``bench.<name>``, so the trace reduction can
    say which span was open in each idle gap of the device."""

    def __init__(self, trace: bool = False):
        self.trace = trace
        self.events = []
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.trace:
            import jax
            ann = jax.profiler.TraceAnnotation("bench." + name)
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                t1 = time.perf_counter()
                with self._lock:
                    self.events.append((name, t0, t1))

    def total(self, name: str, t0: float = float("-inf"),
              t1: float = float("inf")) -> float:
        """Seconds in spans ``name`` that start inside [t0, t1)."""
        return sum(e - s for n, s, e in self.events
                   if n == name and t0 <= s < t1)

    def count(self, name: str, t0: float = float("-inf"),
              t1: float = float("inf")) -> int:
        """Spans ``name`` that lie inside [t0, t1]."""
        return sum(1 for n, s, e in self.events
                   if n == name and t0 <= s and e <= t1)


def set_up_jax(root: str = ROOT, cache: bool = True):
    """The system's sources on the path and, with ``cache``, its
    persistent compilation cache turned on by the system's own
    ``enable_compile_cache`` (the directory ``JAX_COMPILATION_CACHE_DIR``
    names, else the checkout's fixed ``.jax_cache``), keeping every
    program however fast it compiled.  Rehearsals pass ``cache=False``
    and leave JAX's configuration as they found it.  Returns the ``jax``
    module."""
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    if cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def chips(jax, need: int):
    """The TPU devices of this host; raises without ``need`` of them."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise CellError(f"no TPU: JAX runs on {devs[0].platform!r}; this "
                        "benchmark measures the chip only")
    if len(devs) < need:
        raise CellError(f"the cell needs {need} TPU chips, JAX sees "
                        f"{len(devs)}")
    return devs


def device_record(devs, used) -> dict:
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def program_cluster(cfg):
    """The system's ``Cluster`` for the configuration's fleet, built
    from the configuration's worker table and scales."""
    import dataclasses

    from repro.env.cluster import Cluster, WorkerType
    sc = cfg.get("scales", {})
    types = []
    for name, qty in cfg["fleet"]:
        row = dict(cfg["worker_types"][name], name=name)
        t = WorkerType(**row)
        t = dataclasses.replace(t, mips=t.mips * sc.get("compute", 1.0),
                                ram_mb=t.ram_mb * sc.get("ram", 1.0),
                                net_bw=t.net_bw * sc.get("net", 1.0))
        types.extend([t] * qty)
    return Cluster(types)


def spread(name: str, walls) -> dict:
    """How the window's units of work (chunks, grid calls) spread: their
    count, median and longest wall, and how many took over twice the
    median (stalls that a rate over the window absorbs)."""
    import numpy as np
    w = np.asarray(walls, np.float64) * 1e3
    med = float(np.median(w))
    return {name + "s": len(w), name + "_ms_median": med,
            name + "_ms_max": float(w.max()),
            name + "s_over_2x_median": int((w > 2 * med).sum())}


def print_result(result: dict, checks: dict):
    """Every compared number beside its limit on standard error, then
    the result line, with the checks last, on standard output."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(dict(result, checks=checks)), flush=True)
