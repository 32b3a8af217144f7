#!/usr/bin/env python3
"""Smoke run of the SplitPlace edge simulator on a TPU.

    python chip_smoke.py             # one chip: serve, parity, grid
    python chip_smoke.py --chips 4   # four chips: the sharded grid only

One process drives the simulator's main path through its user entry
points (``launch.experiments.run_stream`` / ``run_grid_batched`` and
``jaxsim.resolve`` → ``jaxsim.run_trace_engine``), phase by phase.
Every check raises on failure, so the script exits non-zero unless all
phases pass; it also exits non-zero, before any phase, when JAX finds no
TPU.  The last line of standard output is one JSON object naming the
device:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Scale is the paper's own deployment (arXiv 2205.10635, §6): the Table-3
50-worker fleet, 300 s intervals, 30 substeps, the AIoTBench-style apps
0-2, Poisson arrivals at λ=6.  SplitPlace is the MAB decider with the
DASO placer; its surrogate has random weights from seed 0 at the host
``SurrogatePlacer`` sizes, so nothing is downloaded or pretrained.
Wall times printed here are smoke timings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: the host-oracle parity contract of tests/test_jaxsim_parity.py
RTOL, ATOL = 1e-4, 1e-9
LAM = 6.0
#: the serving size: ring slots and intervals per jitted chunk
MAX_ACTIVE, CHUNK = 512, 64
GRID_SEEDS, GRID_LAMS = tuple(range(4)), (6.0, 12.0)


class SmokeFailure(RuntimeError):
    """A phase produced a result that fails its check."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileWatch:
    """XLA compiles (or persistent-cache loads) and cache lookups, read
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.programs, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self):
        return (f"XLA compile or cache load {self.seconds:.1f} s over "
                f"{self.programs} programs; persistent cache "
                f"{self.hits} hits, {self.misses} misses")


def smoke_mab_state():
    """The handcrafted MAB state of the parity tests: both arms and
    both deadline contexts are live, so decisions really vary."""
    import jax.numpy as jnp

    from repro.core import mab
    return mab.init_state(3)._replace(
        R=jnp.array([700.0, 1800.0, 3500.0], jnp.float32),
        Q=jnp.array([[0.8, 0.6], [0.3, 0.7]], jnp.float32),
        N=jnp.array([[20.0, 10.0], [5.0, 25.0]], jnp.float32),
        eps=jnp.asarray(0.4, jnp.float32),
        rho=jnp.asarray(0.06, jnp.float32),
        t=jnp.asarray(40, jnp.int32))


def splitplace_inputs(n_workers):
    """(mab_state, daso_theta, daso_cfg) as host NumPy trees, so the
    device runs and the CPU oracle each place their own copy."""
    import jax
    import numpy as np

    from repro.launch.experiments import seeded_surrogate
    theta, cfg = seeded_surrogate(n_workers, seed=0)
    to_host = lambda tree: jax.tree_util.tree_map(np.asarray, tree)
    return to_host(smoke_mab_state()), to_host(theta), cfg


def assert_close(name, ref, got):
    """Every metric of ``ref`` (labels such as ``policy`` aside) within
    the parity tolerance of ``got``."""
    import numpy as np
    check(set(ref) == set(got), f"{name}: keys differ {set(ref) ^ set(got)}")
    bad = [f"{k}: ref={ref[k]!r} got={got[k]!r}" for k in sorted(ref)
           if k != "policy"
           and not np.isclose(ref[k], got[k], rtol=RTOL, atol=ATOL)]
    check(not bad, f"{name}: outside rtol={RTOL}: " + "; ".join(bad))


def phase_serve(inputs, target_tasks=10_000, max_active=MAX_ACTIVE,
                chunk=CHUNK):
    """Serve a Poisson stream through SplitPlace (MAB + DASO) and the
    static ``mc`` engine; check the admission identities, one stream
    compile per engine, and that the carry is donated chunk to chunk."""
    from repro.env.jaxsim import driver
    from repro.launch.experiments import run_stream
    mab_state, theta, cfg = inputs
    check(driver._donation_ok(),
          "the backend did not release a donated buffer, so the stream's "
          "no-copy check would be skipped")
    for policy in ("splitplace", "mc"):
        before = driver.cache_stats()["keys"]
        t0 = time.perf_counter()
        rep = run_stream(policy=policy, lam=LAM, seed=0,
                         target_tasks=target_tasks, chunk_intervals=chunk,
                         max_active=max_active, mab_state=mab_state,
                         daso_theta=theta, daso_cfg=cfg)
        wall = time.perf_counter() - t0
        new = {k: v - before.get(k, 0)
               for k, v in driver.cache_stats()["keys"].items()
               if "'stream'" in k and v != before.get(k, 0)}
        check(rep["offered"] == rep["fed"] + rep["feeder_overflow"],
              f"{policy}: offered != fed + feeder_overflow: {rep}")
        check(rep["admitted"] == rep["finished"] + rep["live"],
              f"{policy}: admitted != finished + live: {rep}")
        check(rep["finished"] > 0, f"{policy}: no task finished")
        check(len(new) == 1 and list(new.values()) == [1],
              f"{policy}: expected one stream compile, got {new}")
        if policy == "splitplace":
            check(rep["summary"].get("mab_t", 0) > 0,
                  "splitplace: the MAB engine did not run")
        print(f"serve {policy}: offered={rep['offered']} "
              f"fed={rep['fed']} overflow={rep['feeder_overflow']} "
              f"admitted={rep['admitted']} finished={rep['finished']} "
              f"live={rep['live']} dropped={rep['dropped']} "
              f"intervals={rep['n_intervals']} chunks={rep['n_chunks']} "
              f"reward={rep['summary']['reward']:.4f}", flush=True)
        print(f"serve {policy}: smoke timing, not a benchmark number: "
              f"{wall:.1f} s wall incl. compile, "
              f"{rep['finished'] / wall:.0f} finished tasks/s", flush=True)


def phase_parity(inputs, n_intervals=100):
    """One seed-0 trace on the device against its host oracle, for the
    static ``mc`` engine and the SplitPlace deploy engine.  The oracle's
    own jax calls (the shared mab/daso functions) are pinned to the CPU,
    so the device is compared with the host and not with itself."""
    import jax

    from repro.env import jaxsim
    mab_state, theta, cfg = inputs
    cpu = jax.devices("cpu")[0]
    mc = jaxsim.resolve("mc")
    sp = jaxsim.resolve("splitplace", mab_state=mab_state, daso_theta=theta,
                        daso_cfg=cfg)
    tr = jaxsim.compile_trace(mc.decider, lam=LAM, seed=0,
                              n_intervals=n_intervals)
    dual = jaxsim.compile_trace_dual(lam=LAM, seed=0,
                                     n_intervals=n_intervals)
    got = jaxsim.run_trace_engine(mc.engine, tr, mc.state(tr.seed))
    with jax.default_device(cpu):
        ref = jaxsim.replay_trace_edgesim(tr)
    assert_close("parity mc", ref, got)
    print(f"parity mc: {len(ref)} metrics within rtol={RTOL}, "
          f"{ref['tasks_completed']} tasks", flush=True)
    got_sp = jaxsim.run_trace_engine(sp.engine, dual, sp.state(dual.seed))
    with jax.default_device(cpu):
        ref = jaxsim.replay_trace_edgesim_learned(dual, mab_state,
                                                  daso_theta=theta,
                                                  daso_cfg=cfg)
    assert_close("parity splitplace", ref, got_sp)
    print(f"parity splitplace: {len(ref)} metrics within rtol={RTOL}, "
          f"{ref['tasks_completed']} tasks, layer_fraction="
          f"{ref['layer_fraction']:.4f}", flush=True)
    return got


def _batched_executables():
    """Compiled executables behind the cached batched (vmapped) grid
    runners: a chunk of another size compiles again under one key."""
    from repro.env.jaxsim import driver
    return sum(r._cache_size() for k, r in driver._RUNNER_CACHE.items()
               if k[-1] is True)


def phase_grid(solo=None, n_intervals=100):
    """The (seed × λ) grid of the static ``mc`` engine through the
    default thread-chunk dispatcher; counts the executables it
    compiled, and checks the (λ=6, seed 0) cell against the parity
    phase's solo run of the same trace."""
    import numpy as np

    from repro.launch.experiments import run_grid_batched
    from repro.obs import RunLedger, use_ledger
    cells = len(GRID_SEEDS) * len(GRID_LAMS)
    before = _batched_executables()
    with use_ledger(RunLedger("grid")) as led:
        t0 = time.perf_counter()
        recs = run_grid_batched(policy="mc", seeds=GRID_SEEDS,
                                lams=GRID_LAMS, n_intervals=n_intervals)
        wall = time.perf_counter() - t0
    compiled = _batched_executables() - before
    chunks = [e["attrs"]["n_traces"] for e in led.events
              if e["kind"] == "span" and e["name"] == "chunk"]
    check(len(recs) == cells, f"grid: {len(recs)} records for {cells} cells")
    for r in recs:
        check(all(np.isfinite(v) for k, v in r.items() if k != "policy"),
              f"grid: non-finite metric in {r}")
        check(r["tasks_completed"] > 0 and r["dropped_tasks"] == 0,
              f"grid: cell lam={r['lam']} seed={r['seed']}: {r}")
    if solo is not None:
        cell = next(r for r in recs if r["lam"] == LAM and r["seed"] == 0)
        assert_close("grid cell vs solo", solo, {k: cell[k] for k in solo})
    print(f"grid mc: {cells} cells in {len(chunks)} thread chunks of "
          f"{sorted(chunks)} cells; executables compiled: {compiled}",
          flush=True)
    print(f"grid mc: smoke timing, not a benchmark number: {wall:.1f} s "
          f"wall incl. compile", flush=True)
    check(compiled == 1, f"grid: {compiled} executables for one grid")


def phase_grid_sharded(n_devices, n_intervals=100):
    """The same grid sharded over ``n_devices`` chips
    (``run_grid_batched(devices=...)``) against the grid on one chip,
    cell by cell; checks that every chip held its share of the cells."""
    from repro.launch.experiments import run_grid_batched
    from repro.obs import RunLedger, use_ledger
    kw = dict(policy="mc", seeds=GRID_SEEDS, lams=GRID_LAMS,
              n_intervals=n_intervals)
    one = run_grid_batched(**kw)
    with use_ledger(RunLedger("sharded")) as led:
        t0 = time.perf_counter()
        many = run_grid_batched(devices=n_devices, **kw)
        wall = time.perf_counter() - t0
    spread = {k.rsplit(".", 1)[-1]: v for k, v in led.counters.items()
              if k.startswith("grid.cells_on_device.")}
    check(len(one) == len(many), "sharded grid: record count differs")
    for a, b in zip(one, many):
        check((a["lam"], a["seed"]) == (b["lam"], b["seed"]),
              "sharded grid: cell order differs")
        assert_close(f"sharded cell lam={a['lam']} seed={a['seed']}", a, b)
    per = -(-len(one) // n_devices)
    check(len(spread) == n_devices and set(spread.values()) == {per},
          f"sharded grid: cells per device {spread}, want {per} on each "
          f"of {n_devices}")
    print(f"grid sharded: {len(many)} cells over {n_devices} devices "
          f"(cells per device id: {spread}) match the one-device grid "
          f"within rtol={RTOL}", flush=True)
    print(f"grid sharded: smoke timing, not a benchmark number: "
          f"{wall:.1f} s wall incl. compile", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the grid sharded over four chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r}); "
              "this smoke runs on the chip only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    print(f"device: platform={platform} kind={kind} count={len(devices)}; "
          f"compile cache {cache_dir}", flush=True)
    watch = CompileWatch()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_grid_sharded(4)
    else:
        from repro.env.cluster import make_cluster
        inputs = splitplace_inputs(make_cluster().n)
        phase_serve(inputs)
        phase_grid(solo=phase_parity(inputs))
    print(f"compile: {watch.line()}", flush=True)
    print(f"total: smoke timing, not a benchmark number: "
          f"{time.perf_counter() - t0:.1f} s wall", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
