"""Property-based differential fuzzing: ``backend="jax"`` vs EdgeSim.

Every generated case draws one configuration — worker fleet, arrival
rate λ, RAM/MIPS capacity scales, mobility/workload seed, handcrafted
MAB state (incl. ε/UCB hyperparameters) and DASO surrogate — runs it
through the jitted backend AND the host ``EdgeSim`` replay oracle, and
asserts the cross-backend allclose(rtol=1e-4) contract on every summary
metric (plus the final MAB scalars and, in train mode, the finetuned
DASO theta).  Three oracle pairs are covered:

Each jitted run is ``jaxsim.resolve`` → ``run_trace_engine``:

  * **static** — a static policy vs ``replay_trace_edgesim``;
  * **deploy** — ``"mab"`` / ``"splitplace"`` vs
    ``replay_trace_edgesim_learned`` (online UCB MAB ± frozen DASO);
  * **train**  — the same in ``mode="train"`` vs
    ``replay_trace_edgesim_trained`` (ε-greedy MAB + in-kernel DASO
    finetuning);
  * **gobi**   — ``"mab+gobi"`` vs the deploy replay with
    ``decision_aware=False`` (the decision-blind GOBI surrogate
    ablation; DASO always on);
  * **gillis** — ``"gillis"`` vs
    ``replay_trace_edgesim_gillis`` (in-kernel contextual ε-greedy
    Q-learning over (LAYER, COMPRESSED) dual traces, incl. the final
    Q-table/ε).

Shape-determining parameters (intervals, substeps, cluster, DASO config,
MAB hyperparameters, slot capacity) are drawn from small *quantized*
pools so the fuzz run reuses a bounded set of compiled executables —
the point is to fuzz the physics/learning data space, not to pay an XLA
compile per example.

Two harnesses share one case-check:

  * a seeded self-contained generator (``test_differential_fuzz``) that
    always runs — ``DIFF_FUZZ_CASES`` (default 30; CI pins it) selects
    how many generated cases, e.g. ``DIFF_FUZZ_CASES=200`` for the full
    local sweep;
  * a `hypothesis` wrapper (``test_differential_hypothesis``) drawing
    from the same quantized space with shrinking, skipped when
    hypothesis isn't installed (see requirements-dev.txt).

Plus shrunk regression cases distilled from fuzz findings: RAM-pressure
repair parity (incl. train mode), ε-boundary decisions, and
capacity-overflow drop counting.
"""
import os

import numpy as np
import pytest

RTOL, ATOL = 1e-4, 1e-9

#: fixed slot capacity — big enough that no quantized config ever drops
#: (a dropped arrival would make the replay oracle incomparable); the
#: drop-counting contract is pinned separately below
MAX_ACTIVE = 160

#: quantized pools for every shape-/compile-relevant parameter
N_INTERVALS = (4, 6)
SUBSTEPS = (3, 4)
CLUSTERS = ("table3", "ram_squeeze", "slow_small")
MAB_HPS = ((0.5, 0.3, 0.3, 0.1),      # host MABDecider defaults
           (1.0, 0.3, 0.3, 0.1),      # exploratory UCB
           (0.05, 0.9, 0.5, 0.2),     # paper-φ, aggressive RBED
           (0.5, 0.3, 0.3, 0.0))      # k=0: RBED never decays ε
#: (alpha, beta, train_steps, place_min, train_min) — the lowered
#: cold-start gates make the short fuzz horizons exercise the
#: finetuned-surrogate ascent + train_epoch_weighted paths that the
#: host-default gates (32/8) reserve for long traces
TRAIN_HPS = ((0.5, 0.5, 4, 32, 8),    # host SurrogatePlacer defaults
             (0.5, 0.5, 2, 2, 1),     # gates open almost immediately
             (0.3, 0.7, 4, 4, 2))     # different eq.-10 weights
DASO_CFGS = ("small", "wide")
#: (eps0, lr, decay) pools for the Gillis arm — the boundary rows pin
#: pure-greedy (ε=0) and undecayed-coin (ε=1, decay=1) corners
GILLIS_HPS = ((0.5, 0.3, 0.995),      # host GillisDecider defaults
              (1.0, 0.5, 0.9),        # explore-heavy, fast decay
              (0.0, 0.3, 1.0),        # pure greedy forever
              (1.0, 1.0, 1.0))        # pure coin, lr=1 overwrites


def _cluster(name):
    from repro.env.cluster import make_cluster
    if name == "table3":
        return make_cluster()
    if name == "ram_squeeze":
        return make_cluster(ram_scale=0.45)
    # a smaller, slower, mobile-heavy fleet: different n AND physics
    return make_cluster(fleet=[("B2ms", 8), ("E2asv4", 4), ("B4ms", 4)],
                        compute_scale=0.7)


def _daso(name, n_workers, rng):
    import jax

    from repro.core import daso
    hidden, C = (16, 8) if name == "small" else (32, 16)
    cfg = daso.DASOConfig(num_workers=n_workers, max_containers=C,
                          state_features=4, hidden=hidden, depth=2,
                          place_iters=8)
    theta = daso.init_surrogate(jax.random.PRNGKey(int(rng.randint(2**31))),
                                cfg)
    return theta, cfg


def _mab_state(rng):
    """A random-but-plausible MABState: both contexts/arms reachable."""
    import jax.numpy as jnp

    from repro.core import mab
    return mab.init_state(3)._replace(
        R=jnp.asarray(rng.uniform(300.0, 4000.0, 3).astype(np.float32)),
        Q=jnp.asarray(rng.uniform(0.0, 1.0, (2, 2)).astype(np.float32)),
        N=jnp.asarray(rng.uniform(1.0, 40.0, (2, 2)).astype(np.float32)),
        eps=jnp.asarray(np.float32(rng.uniform(0.0, 1.0))),
        rho=jnp.asarray(np.float32(rng.uniform(0.02, 0.2))),
        t=jnp.asarray(int(rng.randint(1, 80)), jnp.int32))


def draw_case(case_seed: int) -> dict:
    """One fuzz configuration, fully determined by ``case_seed``."""
    rng = np.random.RandomState(case_seed)
    mode = ("static", "deploy", "train", "gillis", "gobi")[rng.randint(5)]
    case = {
        "mode": mode,
        "lam": float(np.round(rng.uniform(2.0, 9.0), 2)),
        "seed": int(rng.randint(10_000)),        # workload + mobility
        "n_intervals": int(N_INTERVALS[rng.randint(len(N_INTERVALS))]),
        "substeps": int(SUBSTEPS[rng.randint(len(SUBSTEPS))]),
        "cluster": CLUSTERS[rng.randint(len(CLUSTERS))],
        "mab_hp": MAB_HPS[rng.randint(len(MAB_HPS))],
        "mab_rng": int(rng.randint(2**31)),
        # the gobi ablation IS a surrogate config, so its daso draw
        # never lands on None
        "daso": (((None,) if mode != "gobi" else ())
                 + DASO_CFGS)[rng.randint(
                     (1 if mode != "gobi" else 0) + len(DASO_CFGS))],
    }
    if mode == "train":
        case["train_hp"] = TRAIN_HPS[rng.randint(len(TRAIN_HPS))]
    if mode == "static":
        case["policy"] = ("mc", "bestfit-rr", "bestfit-layer",
                          "bestfit-semantic",
                          "bestfit-threshold")[rng.randint(5)]
    if mode == "gillis":
        case["gillis_hp"] = GILLIS_HPS[rng.randint(len(GILLIS_HPS))]
    # drawn LAST so every earlier field matches the pre-telemetry
    # generator for the same case_seed (regression cases stay stable)
    case["telemetry"] = ("summary", "interval")[rng.randint(2)]
    return case


#: percentile estimates are compared at the documented binning error
#: bound, not rtol: the host oracle is exact while the kernel path bins
#: per-interval (see ``repro.env.metrics.series_percentiles``)
PCT_KEYS = tuple(f"p{q}_{m}_s" for q in (50, 95, 99)
                 for m in ("response", "wait"))


def assert_close(ref, jx, ctx):
    assert set(ref) == set(jx), f"{ctx}: key sets differ"
    for k in ref:
        if k in ("daso_theta", "gillis_q"):
            import jax
            for a, b in zip(jax.tree_util.tree_leaves(ref[k]),
                            jax.tree_util.tree_leaves(jx[k])):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=RTOL, atol=ATOL,
                    err_msg=f"{ctx}: {k}")
            continue
        if k == "telemetry":
            assert ref[k]["cols"] == jx[k]["cols"], \
                f"{ctx}: telemetry cols: {ref[k]['cols']} vs {jx[k]['cols']}"
            np.testing.assert_allclose(
                np.asarray(ref[k]["series"]), np.asarray(jx[k]["series"]),
                rtol=RTOL, atol=ATOL, err_msg=f"{ctx}: telemetry series")
            continue
        if k == "percentile_err_s":
            assert ref[k] >= 0.0 and jx[k] >= 0.0, f"{ctx}: {k}"
            continue
        if k in PCT_KEYS:
            bound = max(ref["percentile_err_s"], jx["percentile_err_s"])
            assert abs(ref[k] - jx[k]) <= bound + ATOL + RTOL * abs(ref[k]), \
                f"{ctx}: {k}: host={ref[k]!r} jax={jx[k]!r} bound={bound!r}"
            continue
        assert np.isclose(ref[k], jx[k], rtol=RTOL, atol=ATOL), \
            f"{ctx}: {k}: host={ref[k]!r} jax={jx[k]!r}"


def _gillis_state(rng):
    """A random-but-plausible Gillis carry: non-trivial Q, live ε."""
    return {"Q": rng.uniform(0.0, 1.0, (3, 2, 2)).astype(np.float64),
            "eps": np.float64(rng.uniform(0.0, 1.0))}


def check_case(case: dict):
    """Run one configuration through both backends and compare."""
    from repro.env import jaxsim
    cl = _cluster(case["cluster"])
    tel = case.get("telemetry", "summary")
    ctx = f"case={case!r}"
    if case["mode"] == "static":
        r = jaxsim.resolve(case["policy"])
        tr = jaxsim.compile_trace(
            r.decider, lam=case["lam"], seed=case["seed"],
            n_intervals=case["n_intervals"], substeps=case["substeps"],
            cluster=cl, max_arrivals=48)
        ref = jaxsim.replay_trace_edgesim(tr, cluster=cl, telemetry=tel)
        jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                     cluster=cl, max_active=MAX_ACTIVE,
                                     telemetry=tel)
        assert jx["dropped_tasks"] == 0, ctx
        assert_close(ref, jx, ctx)
        return
    rng = np.random.RandomState(case["mab_rng"])
    if case["mode"] == "gillis":
        from repro.env.workload import COMPRESSED, LAYER
        st = _gillis_state(rng)
        tr = jaxsim.compile_trace_dual(
            lam=case["lam"], seed=case["seed"],
            n_intervals=case["n_intervals"], substeps=case["substeps"],
            cluster=cl, max_arrivals=48, variants=(LAYER, COMPRESSED))
        ref = jaxsim.replay_trace_edgesim_gillis(
            tr, gillis_state=st, cluster=cl, gillis_hp=case["gillis_hp"],
            telemetry=tel)
        r = jaxsim.resolve("gillis", gillis_state=st,
                           gillis_hp=case["gillis_hp"])
        jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                     cluster=cl, max_active=MAX_ACTIVE,
                                     telemetry=tel)
        assert jx["dropped_tasks"] == 0, ctx
        assert_close(ref, jx, ctx)
        return
    st = _mab_state(rng)
    theta = cfg = None
    if case["daso"] is not None:
        theta, cfg = _daso(case["daso"], cl.n, rng)
    policy = "mab" if cfg is None else "splitplace"
    r = jaxsim.resolve(
        "mab+gobi" if case["mode"] == "gobi" else policy,
        mode="train" if case["mode"] == "train" else "deploy", cluster=cl,
        mab_state=st, daso_theta=theta, daso_cfg=cfg,
        mab_hp=case["mab_hp"], train_hp=case.get("train_hp"))
    if case["mode"] == "gobi":
        cfg = cfg._replace(decision_aware=False)
    tr = jaxsim.compile_trace_dual(
        lam=case["lam"], seed=case["seed"],
        n_intervals=case["n_intervals"], substeps=case["substeps"],
        cluster=cl, max_arrivals=48)
    if case["mode"] in ("deploy", "gobi"):
        ref = jaxsim.replay_trace_edgesim_learned(
            tr, st, daso_theta=theta, daso_cfg=cfg, cluster=cl,
            mab_hp=case["mab_hp"], telemetry=tel)
        jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                     cluster=cl, max_active=MAX_ACTIVE,
                                     telemetry=tel)
    else:
        ref = jaxsim.replay_trace_edgesim_trained(
            tr, st, daso_theta=theta, daso_cfg=cfg, cluster=cl,
            mab_hp=case["mab_hp"], train_hp=case["train_hp"],
            telemetry=tel)
        jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                     cluster=cl, max_active=MAX_ACTIVE,
                                     telemetry=tel)
    assert jx["dropped_tasks"] == 0, ctx
    assert_close(ref, jx, ctx)


# ------------------------------------------------------------ fuzz drivers

N_CASES = int(os.environ.get("DIFF_FUZZ_CASES", "30"))


@pytest.mark.parametrize("case_seed", range(N_CASES))
def test_differential_fuzz(case_seed):
    check_case(draw_case(case_seed))


try:
    import hypothesis
    from hypothesis import strategies as hst
    HAVE_HYPOTHESIS = True
    hypothesis.settings.register_profile(
        "ci", max_examples=20, deadline=None, derandomize=False,
        print_blob=True)
    hypothesis.settings.register_profile(
        "full", max_examples=200, deadline=None)
    hypothesis.settings.load_profile(
        os.environ.get("HYPOTHESIS_PROFILE", "ci"))
except ImportError:                                  # pragma: no cover
    HAVE_HYPOTHESIS = False


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_differential_hypothesis():
    """The same differential property under hypothesis shrinking: any
    failing case minimizes to a single integer seed whose full drawn
    configuration prints via ``draw_case``.  CI runs this with the
    bounded "ci" profile and a fixed ``--hypothesis-seed``."""
    @hypothesis.given(hst.integers(min_value=0, max_value=2**20))
    def prop(case_seed):
        check_case(draw_case(case_seed))

    prop()


# ------------------------------------------- shrunk regression fixtures
#
# Distilled corner cases the random sweep found or the kernels' fast
# paths make easy to get wrong; pinned here so they run in every tier-1
# invocation regardless of the fuzz budget.


def test_regression_ram_pressure_repair_static():
    """Squeezed RAM + high λ forces the sequential feasibility repair,
    placement failure (waiting tasks) and swap slowdown on both
    backends."""
    from repro.env import jaxsim
    from repro.env.cluster import make_cluster
    cl = make_cluster(ram_scale=0.3)
    r = jaxsim.resolve("mc")
    tr = jaxsim.compile_trace(r.decider, lam=14.0, seed=5, n_intervals=12,
                              substeps=4, cluster=cl)
    ref = jaxsim.replay_trace_edgesim(tr, cluster=cl)
    jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed), cluster=cl)
    assert ref["wait_intervals"] > 0      # repair actually failed tasks
    assert_close(ref, jx, "ram-pressure static")


def test_regression_ram_pressure_repair_train():
    """RAM pressure under the TRAIN pipeline: the repair must rewrite
    the finetuned surrogate's requests identically on both backends
    (the learned stage's fallback path), while the training carry keeps
    advancing through the repaired placements."""
    from repro.env import jaxsim
    from repro.env.cluster import make_cluster
    rng = np.random.RandomState(11)
    cl = make_cluster(ram_scale=0.45)
    st = _mab_state(rng)
    theta, cfg = _daso("small", cl.n, rng)
    tr = jaxsim.compile_trace_dual(lam=11.0, seed=5, n_intervals=10,
                                   substeps=4, cluster=cl)
    hp = (0.5, 0.5, 2, 2, 1)      # gates open: repair sees ascended reqs
    ref = jaxsim.replay_trace_edgesim_trained(tr, st, daso_theta=theta,
                                              daso_cfg=cfg, cluster=cl,
                                              train_hp=hp)
    r = jaxsim.resolve("splitplace", mode="train", cluster=cl,
                       mab_state=st, daso_theta=theta, daso_cfg=cfg,
                       train_hp=hp)
    jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed), cluster=cl)
    assert ref["wait_intervals"] > 0 or ref["response_intervals"] > 1.0
    assert_close(ref, jx, "ram-pressure train")


def test_regression_eps_boundary_decisions():
    """ε=0 (pure greedy) and ε=1 (pure coin) train decisions both hold
    the parity contract — the boundary where a bernoulli tie could
    silently diverge between kernel and replay."""
    import jax.numpy as jnp

    from repro.env import jaxsim
    rng = np.random.RandomState(3)
    tr = jaxsim.compile_trace_dual(lam=5.0, seed=2, n_intervals=6,
                                   substeps=3)
    for eps in (0.0, 1.0):
        st = _mab_state(rng)._replace(eps=jnp.asarray(eps, jnp.float32))
        ref = jaxsim.replay_trace_edgesim_trained(tr, st)
        r = jaxsim.resolve("mab", mode="train", mab_state=st)
        jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed))
        assert_close(ref, jx, f"eps={eps}")


def test_regression_gillis_eps_boundaries():
    """Gillis ε=0 (pure greedy over a tied all-zero Q) and ε=1 with
    decay=1 (pure coin forever) both hold the parity contract incl. the
    final Q-table — the argmax-tie and bernoulli-boundary corners."""
    from repro.env import jaxsim
    from repro.env.workload import COMPRESSED, LAYER
    tr = jaxsim.compile_trace_dual(lam=5.0, seed=2, n_intervals=6,
                                   substeps=3,
                                   variants=(LAYER, COMPRESSED))
    for hp in ((0.0, 0.3, 0.995), (1.0, 1.0, 1.0)):
        ref = jaxsim.replay_trace_edgesim_gillis(tr, gillis_hp=hp)
        r = jaxsim.resolve("gillis", gillis_hp=hp)
        jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed))
        assert_close(ref, jx, f"gillis hp={hp}")


def test_regression_gillis_ram_pressure():
    """RAM pressure under the Gillis pipeline: compressed-arm tasks have
    the largest single-container footprints, so the feasibility repair
    rewrites BestFit requests while the Q-carry keeps updating."""
    from repro.env import jaxsim
    from repro.env.cluster import make_cluster
    from repro.env.workload import COMPRESSED, LAYER
    rng = np.random.RandomState(7)
    cl = make_cluster(ram_scale=0.4)
    st = _gillis_state(rng)
    tr = jaxsim.compile_trace_dual(lam=11.0, seed=5, n_intervals=10,
                                   substeps=4, cluster=cl,
                                   variants=(LAYER, COMPRESSED))
    ref = jaxsim.replay_trace_edgesim_gillis(tr, gillis_state=st,
                                             cluster=cl)
    r = jaxsim.resolve("gillis", gillis_state=st)
    jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed), cluster=cl)
    assert ref["wait_intervals"] > 0 or ref["response_intervals"] > 1.0
    assert_close(ref, jx, "gillis ram pressure")


def test_regression_capacity_drop_counting():
    """Arrivals beyond ``max_active`` are dropped and *counted*, the
    count is deterministic, and batched grid rows agree with solo runs
    even while dropping."""
    from repro.env import jaxsim
    r = jaxsim.resolve("mc")
    tr = jaxsim.compile_trace(r.decider, lam=10.0, seed=1, n_intervals=8,
                              substeps=3)
    jx1 = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                  max_active=8)
    jx2 = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                  max_active=8)
    assert jx1["dropped_tasks"] > 0
    assert jx1 == jx2                      # drop accounting deterministic
    grid = jaxsim.run_grid_engine(r.engine, [tr, tr], r.state, max_active=8,
                                  threads=1)
    for row in grid:
        for k in jx1:
            assert np.isclose(jx1[k], row[k], rtol=1e-12, atol=1e-12)
