"""CPU rehearsals of each benchmark path at a tiny size through the
harness's internal entry (``run.run_cell(allow_cpu=True)``), and the
faults each cell can have, planted under the timed path: each must turn
``correct`` false.  Nothing here is a device number."""
import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import common, run

SEED = 2**31 + 77
TINY = {"t3-50w.splitplace.serve": {},
        "t3-50w.mc.serve": {"traffic": {"chunk_intervals": 4}}}


def _run(cell, trace=0, seconds=0.3):
    from repro.env.jaxsim import driver
    driver.clear_cache()
    return run.run_cell(cell, SEED, seconds, trace, allow_cpu=True,
                        overrides=TINY.get(cell))


@pytest.mark.parametrize("cell", sorted(TINY))
def test_serve_rehearsal(cell):
    result, checks = _run(cell)
    assert result["correct"], checks
    assert set(result["metrics"]) == {"tasks_per_s", "chunk_p95_ms",
                                      "setup_s"}
    assert result["attempted"] >= 1 and result["failed"] == 0


def test_serve_rehearsal_traced():
    result, checks = _run("t3-50w.mc.serve", trace=1)
    assert result["correct"], checks
    # the CPU has no device plane: only the host-span metrics read
    assert set(result["metrics"]) == {"feed_ms_per_interval.serve",
                                      "feed_wait_share.serve"}
    assert result["device"]["window_s"] > 0


def _state_unchanged(driver, monkeypatch):
    orig = driver._stream_program

    def prog(*a, **k):
        f = orig(*a, **k)
        return lambda trace, cl, carry, t0: (carry, f(trace, cl, carry,
                                                      t0)[1])
    monkeypatch.setattr(driver, "_stream_program", prog)


def _half_batch(stream, monkeypatch):
    orig = stream.StreamFeeder._arrivals

    def arrivals(self):
        tasks = orig(self)
        return tasks[:(len(tasks) + 1) // 2]
    monkeypatch.setattr(stream.StreamFeeder, "_arrivals", arrivals)


def _answer_altered(driver, monkeypatch):
    orig = driver._stream_program

    def prog(*a, **k):
        f = orig(*a, **k)

        def g(trace, cl, carry, t0):
            carry, series = f(trace, cl, carry, t0)
            return carry, series.at[-1, 1].add(1.0)
        return g
    monkeypatch.setattr(driver, "_stream_program", prog)


def _feedback_skipped(engines, monkeypatch):
    monkeypatch.setattr(engines.MABDeployEngine, "feedback",
                        lambda self, es, *a, **k: es)


@pytest.mark.parametrize("cell,fault", [
    ("t3-50w.mc.serve", "state_unchanged"),
    ("t3-50w.mc.serve", "half_batch"),
    ("t3-50w.mc.serve", "answer_altered"),
    ("t3-50w.splitplace.serve", "feedback_skipped"),
])
def test_serve_fault_is_caught(cell, fault, monkeypatch):
    from repro.env.jaxsim import driver, engines, stream
    plant = {"state_unchanged": lambda: _state_unchanged(driver, monkeypatch),
             "half_batch": lambda: _half_batch(stream, monkeypatch),
             "answer_altered": lambda: _answer_altered(driver, monkeypatch),
             "feedback_skipped": lambda: _feedback_skipped(engines,
                                                           monkeypatch)}
    plant[fault]()
    result, checks = _run(cell)
    assert not result["correct"], checks


GRID_FAULTS = ["sound", "state_unchanged", "half_batch", "exchange_lost",
               "answer_altered"]


def _grid_case(fault):
    """Run the four-chip grid cell at a tiny size with ``fault`` planted
    (in a process with four virtual CPU devices); returns ``correct``."""
    import jax.numpy as jnp

    from repro.env.jaxsim import driver
    from repro.launch import experiments
    saved = {(driver, n): getattr(driver, n) for n in (
        "_interval_physics", "_run_grid_sharded", "_summarize")}
    saved[(experiments, "run_grid_batched")] = experiments.run_grid_batched
    if fault == "state_unchanged":
        driver._interval_physics = lambda state, acc, bw, cl, *a, **k: (
            state, acc, jnp.zeros_like(cl["mips"]))
    elif fault == "half_batch":
        orig = experiments.run_grid_batched
        experiments.run_grid_batched = \
            lambda **kw: orig(**kw)[:len(kw["seeds"]) * len(kw["lams"]) // 2]
    elif fault == "exchange_lost":
        orig = driver._run_grid_sharded

        def lost(engine, traces, *a, **k):
            out = orig(engine, traces, *a, **k)
            per = -(-len(traces) // 4)
            return {key: (np.concatenate([v[:per]] * 4)[:len(v)]
                          if np.ndim(v) else v) for key, v in out.items()}
        driver._run_grid_sharded = lost
    elif fault == "answer_altered":
        orig = driver._summarize

        def altered(*a, **k):
            s = orig(*a, **k)
            s["reward"] += 1e-3
            return s
        driver._summarize = altered
    driver.clear_cache()
    try:
        result, _ = run.run_cell(
            "t3-50w.mc.grid4", SEED, 0.3, 0, root=_grid_root(),
            allow_cpu=True,
            overrides={"traffic": {"n_intervals": 5, "n_seeds": 2}})
    finally:
        for (mod, name), fn in saved.items():
            setattr(mod, name, fn)
    return result["correct"]


@functools.lru_cache(maxsize=None)
def _grid_root():
    """A checkout whose ``BENCHMARK.json`` also lists the four-chip grid
    cell, which the benchmark leaves out until it is measured on the
    chip (``bench/workloads/t3-50w.mc.grid4.json``)."""
    import tempfile
    root = tempfile.mkdtemp(prefix="bench-grid-")
    os.symlink(common.BENCH, os.path.join(root, "bench"))
    bench = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    bench["workloads"].append({"name": "t3-50w.mc.grid4",
                               "config": "table3-50w",
                               "traffic": "t3-50w.mc.grid4", "chips": 4,
                               "why": "the sharded grid"})
    bench["end_to_end"] = [
        m for m in bench["end_to_end"] if "workloads" not in m] + [
        {"name": "cell_intervals_per_s", "unit": "intervals/s",
         "better": "higher", "bound": 0.25, "source": "host_clock",
         "workloads": ["t3-50w.mc.grid4"]}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_grid_rehearsal_and_faults():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(common.ROOT, "src"), common.ROOT]))
    p = subprocess.run([sys.executable, "-m", "bench.tests.test_bench_rehearsal"]
                       + GRID_FAULTS, cwd=common.ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == {f: f == "sound" for f in GRID_FAULTS}, p.stderr[-2000:]


if __name__ == "__main__":
    out = {}
    for f in sys.argv[1:]:
        out[f] = _grid_case(f)
    print(json.dumps(out))
