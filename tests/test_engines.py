"""Engine-protocol equivalence: the unified interval program must be a
pure refactor of the former per-mode driver copies.

Three pins:

  * a unified-engine run of the golden static configuration is
    allclose-identical to the committed **pre-refactor** fixture
    (``tests/data/golden_static_bestfit_rr.json`` was generated by the
    dedicated ``_trace_program`` before the engine unification and is
    intentionally NOT regenerated);
  * a ``MABTrainEngine`` with every learning gate closed (ε=0, UCB c=0,
    no surrogate) walks the exact same trajectory as the
    ``MABDeployEngine`` on the same trace — the two programs really are
    one program with different hooks;
  * the runner cache keys on engine *value*, not identity, so equal
    engine configs share compiled executables.
"""
import json
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _mab_state(eps=0.4):
    import jax.numpy as jnp

    from repro.core import mab
    return mab.init_state(3)._replace(
        R=jnp.array([700.0, 1800.0, 3500.0], jnp.float32),
        Q=jnp.array([[0.8, 0.6], [0.3, 0.7]], jnp.float32),
        N=jnp.array([[20.0, 10.0], [5.0, 25.0]], jnp.float32),
        eps=jnp.asarray(eps, jnp.float32),
        rho=jnp.asarray(0.06, jnp.float32),
        t=jnp.asarray(40, jnp.int32))


def test_static_engine_matches_prerefactor_fixture():
    """Run the golden static config explicitly through the generic
    engine entry point and compare against the committed fixture — the
    fixture predates the unification, so this is the refactor's
    behavior-preservation proof independent of the wrapper layer."""
    from repro.env import jaxsim
    with open(os.path.join(_HERE, "data",
                           "golden_static_bestfit_rr.json")) as f:
        golden = json.load(f)
    dec = jaxsim.make_static_decider("bestfit-rr")
    tr = jaxsim.compile_trace(dec, lam=5.0, seed=0, n_intervals=8,
                              substeps=4)
    out = jaxsim.run_trace_engine(jaxsim.engines.StaticEngine(), tr, ())
    assert set(out) == set(golden["summary"])
    for k, v in golden["summary"].items():
        assert np.isclose(out[k], v, rtol=1e-6, atol=1e-12), \
            f"{k}: fixture={v!r} engine={out[k]!r}"


def test_train_engine_gates_closed_matches_deploy():
    """With ε=0 (no exploration), UCB c=0 (zero bonus) and no surrogate,
    both engines reduce to greedy-argmax decisions + identical
    Algorithm-1 feedback — their trajectories must agree bit-close,
    including the final carried MAB scalars."""
    from repro.env import jaxsim
    st = _mab_state(eps=0.0)
    hp = (0.0, 0.3, 0.3, 0.1)          # ucb_c = 0 → pure Q argmax
    tr = jaxsim.compile_trace_dual(lam=5.0, seed=1, n_intervals=8,
                                   substeps=4)
    outs = []
    for mode in ("deploy", "train"):
        r = jaxsim.resolve("mab", mode=mode, mab_state=st, mab_hp=hp)
        outs.append(jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed)))
    dep, trn = outs
    assert dep["tasks_completed"] > 0
    assert set(dep) == set(trn)
    for k in dep:
        assert np.isclose(dep[k], trn[k], rtol=1e-12, atol=1e-12), \
            f"{k}: deploy={dep[k]!r} train={trn[k]!r}"


def test_runner_cache_hits_on_engine_value():
    """Engines are frozen dataclasses compared by value: two separately
    constructed equal configs must map to ONE cached executable, and a
    different hyperparameter tuple to another."""
    from repro.env.jaxsim import driver, engines
    e1 = engines.MABDeployEngine(mab_hp=(0.5, 0.3, 0.3, 0.1))
    e2 = engines.MABDeployEngine(mab_hp=(0.5, 0.3, 0.3, 0.1))
    e3 = engines.MABDeployEngine(mab_hp=(1.0, 0.3, 0.3, 0.1))
    assert e1 == e2 and hash(e1) == hash(e2)
    assert e1 != e3
    assert e1 != engines.StaticEngine()
    key = (e1, 4, 8, 32, 3, 50, 4, 300.0, 0.5)
    r1 = driver._get_runner(key, batched=False)
    r2 = driver._get_runner((e2,) + key[1:], batched=False)
    r3 = driver._get_runner((e3,) + key[1:], batched=False)
    assert r1 is r2
    assert r1 is not r3


def test_driver_has_single_program_family():
    """The acceptance pin: no per-mode `_learned_*` / `_trained_*`
    program/cache/key/summary duplicates survive in driver.py."""
    import inspect

    from repro.env.jaxsim import driver
    src = inspect.getsource(driver)
    for banned in ("_learned_trace_program", "_trained_trace_program",
                   "_get_learned_runner", "_get_trained_runner",
                   "_learned_static_key", "_trained_static_key",
                   "_learned_summary", "_trained_summary",
                   "_LEARNED_CACHE", "_TRAINED_CACHE"):
        assert banned not in src, f"driver still defines {banned}"
    assert src.count("def _trace_program") == 1
    assert src.count("def _get_runner") == 1
    # one process-wide cache (now an LRU OrderedDict, still singular)
    assert src.count("_RUNNER_CACHE: \"OrderedDict\" = OrderedDict()") == 1
    assert src.count("_RUNNER_CACHE = {}") == 0


def test_gillis_continues_from_state():
    """`gillis_state` threads a previous run's (Q, ε) into the carry —
    the handoff run must differ from a fresh one and report the carried
    ε decayed from the handoff value."""
    from repro.env import jaxsim
    from repro.env.workload import COMPRESSED, LAYER
    tr = jaxsim.compile_trace_dual(lam=5.0, seed=3, n_intervals=6,
                                   substeps=3,
                                   variants=(LAYER, COMPRESSED))
    r = jaxsim.resolve("gillis")
    fresh = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed))
    st = {"Q": fresh["gillis_q"], "eps": fresh["gillis_eps"]}
    r = jaxsim.resolve("gillis", gillis_state=st)
    cont = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed))
    decay = jaxsim.GILLIS_HP[2]
    assert np.isclose(cont["gillis_eps"],
                      fresh["gillis_eps"] * decay ** tr.n_intervals,
                      rtol=1e-9)
    assert not np.allclose(cont["gillis_q"], fresh["gillis_q"])
