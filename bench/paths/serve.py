"""The served-stream window.

Set-up builds the engine, the system's ``StreamFeeder`` and its
``StreamRunner``, and runs one chunk of the cell's own shape, which
compiles or loads the chunk program.  The window then streams: the
feeder builds tapes on its own thread (``prefetch`` deep, as the
system's ``stream.serve`` does) while the main thread runs
``StreamRunner.run_chunk`` on each, until the first chunk that ends
after ``seconds``.  Once the window has closed, every interval the
stream ran, the warm chunk's too, is compared with the plain reference
replaying the same seed from interval 0.
"""
from __future__ import annotations

import gc
import queue
import resource
import threading
import time

import numpy as np

from bench import common, compare, inputs
from bench.ref import replay


def build(ctx):
    """(engine name, traffic seed, runner, feeder, reference inputs)."""
    from repro.core.daso import DASOConfig
    from repro.core.mab import MABState
    from repro.env.jaxsim import stream
    jax, cfg, tr = ctx["jax"], ctx["config"], ctx["traffic"]
    cluster = common.program_cluster(cfg)
    seed = common.sub_seed(ctx["seed"], 0)
    engine = tr["engine"]
    kw, ref_inputs = {}, None
    if engine == "splitplace":
        mab = inputs.mab_state(tr["mab_state"])
        theta, dcfg = inputs.surrogate(jax, common.sub_seed(ctx["seed"], 1),
                                       cluster.n, tr["daso"])
        kw = dict(mab_state=MABState(*mab), daso_theta=theta,
                  daso_cfg=DASOConfig(**dcfg._asdict()))
        ref_inputs = (mab, theta, dcfg)
    eng, es0, fkw = stream.make_stream_policy(engine, cluster=cluster,
                                              seed=seed, **kw)
    feeder = stream.StreamFeeder(lam=cfg["lam"], seed=seed,
                                 interval_s=cfg["interval_s"],
                                 substeps=cfg["substeps"], cluster=cluster,
                                 apps=cfg["apps"], **fkw)
    runner = stream.StreamRunner(eng, es0, interval_s=cfg["interval_s"],
                                 substeps=cfg["substeps"],
                                 max_active=cfg["max_active"],
                                 cluster=cluster,
                                 swap_slowdown=cfg["swap_slowdown"])
    return engine, seed, runner, feeder, ref_inputs


def window(runner, feeder, T, seconds, spans, tick, prefetch=2):
    """Stream chunks until the first that ends after ``seconds``;
    returns (series per chunk, window start, window end, the slowest
    chunk's account).  ``tick`` runs after every chunk (it starts the
    profile of a traced run in time for the window's last seconds)."""
    q = queue.Queue(maxsize=prefetch)
    stop = threading.Event()
    err = []

    def feed():
        try:
            while not stop.is_set():
                with spans.span("feed"):
                    tape = feeder.next_chunk(T)
                while not stop.is_set():
                    try:
                        q.put(tape, timeout=0.05)
                        break
                    except queue.Full:
                        pass
        except Exception as e:  # re-raised on the main thread
            err.append(e)
            stop.set()

    th = threading.Thread(target=feed, name="bench-feeder", daemon=True)
    out, gcs, worst = [], [], None
    t_gc = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            t_gc[0] = time.perf_counter()
        else:
            gcs.append((t_gc[0], time.perf_counter(), info["generation"]))

    gc.callbacks.append(on_gc)
    t0 = time.perf_counter()
    th.start()
    try:
        while True:
            with spans.span("wait"):
                while True:
                    if err:
                        raise err[0]
                    try:
                        tape = q.get(timeout=0.05)
                        break
                    except queue.Empty:
                        pass
            ru0, th0, c0 = resource.getrusage(resource.RUSAGE_SELF), \
                time.thread_time(), time.perf_counter()
            with spans.span("chunk"):
                out.append(runner.run_chunk(tape))
            c1 = time.perf_counter()
            if worst is None or c1 - c0 > worst[1] - worst[0]:
                worst = (c0, c1, ru0, resource.getrusage(
                    resource.RUSAGE_SELF), time.thread_time() - th0)
            tick()
            if time.perf_counter() - t0 >= seconds:
                break
    finally:
        stop.set()
        th.join()
        gc.callbacks.remove(on_gc)
    return out, t0, time.perf_counter(), slowest(worst, gcs, spans)


def slowest(worst, gcs, spans) -> dict:
    """What the host did during the window's slowest chunk: its wall,
    the garbage collections and feeder time inside it, the CPU the
    dispatching thread and the whole process spent, and the process's
    context switches; a stall that the host does not account for was
    spent waiting on the device or its runtime."""
    c0, c1, ru0, ru1, th_cpu = worst

    def inside(s, e):
        return max(0.0, min(e, c1) - max(s, c0))
    return {
        "index": sum(1 for n, s, _ in spans.events
                     if n == "chunk" and s < c0),
        "wall_ms": (c1 - c0) * 1e3,
        "gc_ms": sum(inside(s, e) for s, e, _ in gcs) * 1e3,
        "gc_gen2": sum(1 for s, e, g in gcs if g == 2 and inside(s, e)),
        "feed_ms": sum(inside(s, e) for n, s, e in spans.events
                       if n == "feed") * 1e3,
        "thread_cpu_ms": th_cpu * 1e3,
        "process_user_ms": (ru1.ru_utime - ru0.ru_utime) * 1e3,
        "process_sys_ms": (ru1.ru_stime - ru0.ru_stime) * 1e3,
        "involuntary_switches": ru1.ru_nivcsw - ru0.ru_nivcsw,
        "voluntary_switches": ru1.ru_nvcsw - ru0.ru_nvcsw,
        "major_faults": ru1.ru_majflt - ru0.ru_majflt}


def run(ctx) -> dict:
    from repro.env.jaxsim import driver
    spans, watch = ctx["spans"], ctx["watch"]
    tr, cfg = ctx["traffic"], ctx["config"]
    T = int(tr["chunk_intervals"])
    engine, seed, runner, feeder, ref_inputs = build(ctx)
    warm = [runner.run_chunk(feeder.next_chunk(T))]
    ctx["start_window"]()
    programs, misses = watch.programs, driver.cache_stats()["misses"]
    chunks, t0, t1, stall = window(runner, feeder, T, ctx["seconds"],
                                   spans, ctx["tick"])
    ctx["end_window"]()
    compiled = watch.programs - programs \
        + driver.cache_stats()["misses"] - misses
    if compiled:
        raise common.CellError(f"{compiled} compiles inside the window")
    walls = [e - s for n, s, e in spans.events if n == "chunk"]
    got = np.concatenate(warm + chunks)
    n_fin = float(np.concatenate(chunks)[:, 0].sum())
    i_drop = runner.tcols.index("n_dropped")
    counts = {"window_s": t1 - t0, "chunk_intervals": T}
    e2e = {"tasks_per_s": n_fin / (t1 - t0),
           "chunk_p95_ms": float(np.percentile(walls, 95)) * 1e3}
    device = ctx["device_record"]()
    n_total = runner.t0
    max_arrivals = feeder.max_arrivals
    failed = int(sum(c[:, i_drop].sum() > 0 for c in chunks)) \
        + int(feeder.overflow > 0)
    runner.carry = None
    del runner
    ref = replay.stream_series(cfg, engine, seed, cfg["lam"], n_total,
                               max_arrivals, ref_inputs)
    checks = {"series_gap": {"value": compare.series_gap(got, ref),
                             "limit": tr["limits"]["series_gap"]}}
    return {"e2e": e2e, "counts": counts, "checks": checks,
            "attempted": len(chunks), "failed": failed, "device": device,
            "window": dict(common.spread("chunk", walls),
                           slowest_chunk=stall)}
