"""shard_map grid dispatch ≡ thread-chunk dispatch under 8 forced host
devices.

The acceptance contract for the device-scale dispatcher: an *uneven*
grid (G not a multiple of the mesh size, so dead padded cells are in
play) run through ``run_grid_engine(devices=8)`` must match the
thread-chunk path within ``allclose(rtol=1e-4)`` on every summary
metric, for the static engine AND the splitplace learned engine in both
deploy and train modes.  Runs in a subprocess so the forced host-device
count doesn't leak into this process (tier-1 runs single-device).
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from repro.core import daso, mab
from repro.env import jaxsim

assert len(jax.devices()) == 8, jax.devices()

def check(name, thr, shd):
    assert len(thr) == len(shd) != 0, (name, len(thr), len(shd))
    for i, (a, b) in enumerate(zip(thr, shd)):
        for k in a:
            if isinstance(a[k], (int, float)):
                assert np.isclose(a[k], b[k], rtol=1e-4, atol=1e-9), \
                    (name, i, k, a[k], b[k])
    print(f"{name}: {len(thr)} rows match OK")

# uneven: 5 traces on an 8-device mesh -> 3 dead padded cells
mc = jaxsim.resolve("mc")
traces = [jaxsim.compile_trace(mc.decider, lam=lam, seed=s, n_intervals=4,
                               substeps=4)
          for lam in (3.0, 6.0) for s in (0, 1, 2)][:5]
from repro.obs import RunLedger, use_ledger
with use_ledger(RunLedger("sharded")) as led:
    sharded = jaxsim.run_grid_engine(mc.engine, traces, mc.state, devices=8)
check("static",
      jaxsim.run_grid_engine(mc.engine, traces, mc.state, threads=2),
      sharded)
# every device holds one (possibly dead, padded) cell of the 8-cell mesh
spread = {k: v for k, v in led.counters.items()
          if k.startswith("grid.cells_on_device.")}
assert len(spread) == 8 and set(spread.values()) == {1}, spread

st = mab.init_state(3)._replace(
    R=jnp.array([700.0, 1800.0, 3500.0], jnp.float32),
    Q=jnp.array([[0.8, 0.6], [0.3, 0.7]], jnp.float32),
    N=jnp.array([[20.0, 10.0], [5.0, 25.0]], jnp.float32),
    eps=jnp.asarray(0.4, jnp.float32), rho=jnp.asarray(0.06, jnp.float32),
    t=jnp.asarray(40, jnp.int32))
cfg = daso.DASOConfig(num_workers=50, max_containers=16, state_features=4,
                      hidden=32, depth=2, place_iters=12)
theta = daso.init_surrogate(jax.random.PRNGKey(0), cfg)
dtr = [jaxsim.compile_trace_dual(lam=lam, seed=s, n_intervals=4,
                                 substeps=4)
       for lam in (3.0, 6.0) for s in (0, 1, 2)][:5]
for name, policy, mode in (("splitplace deploy", "splitplace", "deploy"),
                           ("splitplace train", "splitplace", "train"),
                           ("static-daso random arm", "random+daso",
                            "deploy")):
    r = jaxsim.resolve(policy, mode=mode, mab_state=st, daso_theta=theta,
                       daso_cfg=cfg)
    check(name,
          jaxsim.run_grid_engine(r.engine, dtr, r.state, threads=2),
          jaxsim.run_grid_engine(r.engine, dtr, r.state, devices=8))

# devices="auto" takes the whole fleet; bogus counts raise
out = jaxsim.run_grid_engine(mc.engine, traces, mc.state, devices="auto")
assert len(out) == 5
try:
    jaxsim.run_grid_engine(mc.engine, traces, mc.state, devices=9)
except ValueError as e:
    print("devices=9 rejected:", e)
else:
    raise AssertionError("devices=9 should have raised")
print("GRID_SHARDED_OK")
"""


@pytest.mark.slow
def test_sharded_grid_matches_thread_chunk():
    env = {**os.environ, "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "GRID_SHARDED_OK" in r.stdout, r.stdout[-2000:]
