"""The benchmark's plain reference agrees with the system's own host
oracle (``jaxsim.replay_trace_edgesim*``) on the same seeds, and its
float32 control does not: the comparison's limits rest on both."""
import dataclasses

import numpy as np
import pytest

from bench import common, compare, inputs
from bench.ref import replay

CFG = common.load_json(f"{common.BENCH}/configs/table3-50w.json")


def test_config_matches_the_systems_tables():
    from repro.env.cluster import FLEET_SPEC, WORKER_TYPES
    from repro.env.workload import APP_PROFILES
    for name, row in CFG["worker_types"].items():
        assert dataclasses.asdict(WORKER_TYPES[name]) == dict(row, name=name)
    assert [list(f) for f in FLEET_SPEC] == CFG["fleet"]
    for prof, row in zip(APP_PROFILES, CFG["app_profiles"]):
        assert dict(dataclasses.asdict(prof), model_mb=list(prof.model_mb)) \
            == row


def test_mc_stream_equals_the_host_oracle():
    from repro.env import jaxsim
    tr = jaxsim.compile_trace(jaxsim.make_static_decider("mc"), lam=6.0,
                              seed=5, n_intervals=40)
    want = jaxsim.replay_trace_edgesim(tr, telemetry="interval")
    got = replay.stream_series(CFG, "mc", 5, 6.0, 40, 1 << 30)
    np.testing.assert_array_equal(got, want["telemetry"]["series"])
    summ = replay.grid_summary(CFG, "mc", 5, 6.0, 40)
    for k, v in summ.items():
        assert v == pytest.approx(want[k], rel=1e-12, abs=1e-15), k


def test_splitplace_stream_equals_the_host_oracle():
    import jax

    from repro.core.daso import DASOConfig
    from repro.core.mab import MABState
    from repro.env import jaxsim
    spec = common.load_json(
        f"{common.BENCH}/workloads/t3-50w.splitplace.serve.json")
    mab = inputs.mab_state(spec["mab_state"])
    theta, dcfg = inputs.surrogate(jax, 9, 50, spec["daso"])
    dual = jaxsim.compile_trace_dual(lam=6.0, seed=6, n_intervals=24)
    want = jaxsim.replay_trace_edgesim_learned(
        dual, MABState(*mab), daso_theta=theta, daso_cfg=DASOConfig(**dcfg._asdict()),
        telemetry="interval")
    got = replay.stream_series(CFG, "splitplace", 6, 6.0, 24, 1 << 30,
                               (mab, theta, dcfg))
    np.testing.assert_array_equal(got, want["telemetry"]["series"])


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_float32_control_fails_the_limits(seed):
    """The reference computed with float32 physics in the program's
    place reads a gap far above each cell's limit, on every seed."""
    traffic = common.load_json(
        f"{common.BENCH}/workloads/t3-50w.mc.serve.json")
    grid = common.load_json(
        f"{common.BENCH}/workloads/t3-50w.mc.grid4.json")
    ref = replay.stream_series(CFG, "mc", seed, 6.0, 120, 1 << 30)
    low = replay.stream_series(CFG, "mc", seed, 6.0, 120, 1 << 30,
                               dtype=np.float32)
    assert compare.series_gap(low, ref) > traffic["limits"]["series_gap"]
    g_ref = replay.grid_summary(CFG, "mc", seed, 12.0, 100)
    g_low = replay.grid_summary(CFG, "mc", seed, 12.0, 100,
                                dtype=np.float32)
    assert compare.summary_gap(g_low, g_ref) > grid["limits"]["summary_gap"]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_splitplace_float32_control_fails_the_limit(seed):
    """The reference with float32 physics, accounting and DASO ascent in
    the program's place reads a gap far above the SplitPlace cell's
    limit, on every seed."""
    import jax
    spec = common.load_json(
        f"{common.BENCH}/workloads/t3-50w.splitplace.serve.json")
    with jax.default_device(jax.devices("cpu")[0]):
        theta, dcfg = inputs.surrogate(jax, seed, 50, spec["daso"])
    ins = (inputs.mab_state(spec["mab_state"]), theta, dcfg)
    ref = replay.stream_series(CFG, "splitplace", seed, 6.0, 40, 1 << 30,
                               ins)
    low = replay.stream_series(CFG, "splitplace", seed, 6.0, 40, 1 << 30,
                               ins, dtype=np.float32)
    assert compare.series_gap(low, ref) > spec["limits"]["series_gap"]
