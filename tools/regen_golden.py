"""Regenerate the golden-trace regression fixtures under tests/data/.

The fixtures pin the jitted backend's per-trace summary metrics (and,
for the train-mode fixture, a finetuned-DASO-theta fingerprint) for two
small fully-deterministic configurations, so backend drift is caught
even when JAX/XLA versions move and the EdgeSim replay oracle would
drift along with the kernels (``tests/test_golden.py`` compares at
``RTOL``).  Everything is derived from literal seeds — no host
pretraining pass — so the fixtures are regeneratable bit-for-bit:

    PYTHONPATH=src python tools/regen_golden.py

Run that (and commit the diff) only when a change *intentionally* moves
the numbers; the test failure message says so too.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

DATA_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "data")

#: comparison tolerance for the loader test — summary metrics are
#: observed stable to ~1e-15 across reduction orders, so 1e-6 relative
#: flags genuine numeric drift while tolerating XLA refusion noise
RTOL, ATOL = 1e-6, 1e-12


def _mab_state():
    import jax.numpy as jnp

    from repro.core import mab
    return mab.init_state(3)._replace(
        R=jnp.array([700.0, 1800.0, 3500.0], jnp.float32),
        Q=jnp.array([[0.8, 0.6], [0.3, 0.7]], jnp.float32),
        N=jnp.array([[20.0, 10.0], [5.0, 25.0]], jnp.float32),
        eps=jnp.asarray(0.4, jnp.float32),
        rho=jnp.asarray(0.06, jnp.float32),
        t=jnp.asarray(40, jnp.int32))


def _daso(n_workers):
    import jax

    from repro.core import daso
    cfg = daso.DASOConfig(num_workers=n_workers, max_containers=16,
                          state_features=4, hidden=32, depth=2,
                          place_iters=12)
    return daso.init_surrogate(jax.random.PRNGKey(0), cfg), cfg


def theta_fingerprint(theta):
    """Per-layer (L2 norm, abs-sum) pairs — a drift-sensitive digest of
    the finetuned surrogate that stays JSON-small."""
    out = []
    for layer in theta:
        for k in ("w", "b"):
            a = np.asarray(layer[k], np.float64)
            out.append([float(np.sqrt(np.sum(a * a))),
                        float(np.sum(np.abs(a)))])
    return out


def compute_static():
    """Golden case 1: static mixed-decision BestFit trace."""
    from repro.env import jaxsim
    r = jaxsim.resolve("bestfit-rr")
    tr = jaxsim.compile_trace(r.decider, lam=5.0, seed=0, n_intervals=8,
                              substeps=4)
    out = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed))
    return {"case": "static bestfit-rr lam=5 seed=0 T=8 substeps=4",
            "summary": {k: float(v) for k, v in out.items()}}


def compute_train():
    """Golden case 2: full in-kernel training loop (ε-greedy MAB +
    DASO finetuning) on a dual trace, incl. the theta fingerprint."""
    from repro.env import jaxsim
    st = _mab_state()
    tr = jaxsim.compile_trace_dual(lam=5.0, seed=3, n_intervals=12,
                                   substeps=4)
    theta, cfg = _daso(50)
    r = jaxsim.resolve("splitplace", mode="train", mab_state=st,
                       daso_theta=theta, daso_cfg=cfg)
    out = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed))
    theta_fin = out.pop("daso_theta")
    return {"case": "train splitplace lam=5 seed=3 T=12 substeps=4",
            "summary": {k: float(v) for k, v in out.items()},
            "theta_fingerprint": theta_fingerprint(theta_fin)}


def compute_gillis():
    """Golden case 3: in-kernel Gillis baseline (contextual ε-greedy
    Q-learning, layer vs compressed) on a (LAYER, COMPRESSED) dual
    trace, incl. the full final Q-table."""
    from repro.env import jaxsim
    from repro.env.workload import COMPRESSED, LAYER
    tr = jaxsim.compile_trace_dual(lam=5.0, seed=2, n_intervals=12,
                                   substeps=4,
                                   variants=(LAYER, COMPRESSED))
    r = jaxsim.resolve("gillis")
    out = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed))
    q = out.pop("gillis_q")
    return {"case": "gillis lam=5 seed=2 T=12 substeps=4",
            "summary": {k: float(v) for k, v in out.items()},
            "gillis_q": np.asarray(q, np.float64).tolist()}


def compute_gobi():
    """Golden case 4: in-kernel MAB + decision-blind GOBI ablation —
    the splitplace surrogate machinery with the decision one-hot masked
    out of the surrogate input."""
    from repro.env import jaxsim
    st = _mab_state()
    theta, cfg = _daso(50)
    tr = jaxsim.compile_trace_dual(lam=5.0, seed=4, n_intervals=10,
                                   substeps=4)
    r = jaxsim.resolve("mab+gobi", mab_state=st, daso_theta=theta,
                       daso_cfg=cfg)
    out = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed))
    return {"case": "deploy mab+gobi lam=5 seed=4 T=10 substeps=4",
            "summary": {k: float(v) for k, v in out.items()}}


CASES = {
    "golden_static_bestfit_rr.json": compute_static,
    "golden_train_splitplace.json": compute_train,
    "golden_gillis.json": compute_gillis,
    "golden_mab_gobi.json": compute_gobi,
}


def main(argv=None):
    """Regenerate all fixtures, or only the ones named on the command
    line (``python tools/regen_golden.py golden_gillis.json``) — adding
    a new case must not rewrite (and so silently re-bless) the others."""
    args = list(argv if argv is not None else sys.argv[1:])
    names = args or list(CASES)
    unknown = [n for n in names if n not in CASES]
    if unknown:
        raise SystemExit(f"unknown fixture(s) {unknown}; have {list(CASES)}")
    os.makedirs(DATA_DIR, exist_ok=True)
    for fname in names:
        path = os.path.join(DATA_DIR, fname)
        with open(path, "w") as f:
            json.dump(CASES[fname](), f, indent=1, sort_keys=True)
        print(f"wrote {path}")


if __name__ == "__main__":
    sys.exit(main())
