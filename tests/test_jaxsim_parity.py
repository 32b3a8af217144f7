"""Jitted fixed-capacity simulator ≙ host EdgeSim, allclose on metrics.

The SoA↔legacy contract is bit-exact (``test_soa_equivalence``); the
jitted backend relaxes it to ``allclose(rtol=1e-4)`` on per-trace summary
metrics — reduction orders differ between its censuses and the host's
sequential ``bincount`` accumulation, but every elementwise float64
physics op matches.  Both sides consume the same compiled trace
(``repro.env.jaxsim.arrays.compile_trace``), replayed through the real
``EdgeSim`` by ``reference.replay_trace_edgesim``.
"""
import numpy as np
import pytest

from repro.env.cluster import make_cluster
from repro.env.jaxsim import (compile_trace, replay_trace_edgesim, resolve,
                              run_grid_engine, run_trace_engine)

RTOL, ATOL = 1e-4, 1e-9


def assert_summaries_close(ref, jx, rtol=RTOL, atol=ATOL):
    assert set(ref) == set(jx)
    for k in ref:
        assert np.isclose(ref[k], jx[k], rtol=rtol, atol=atol), \
            f"{k}: host={ref[k]!r} jax={jx[k]!r}"


@pytest.mark.parametrize("lam", [4.0, 9.0])
def test_bestfit_trace_parity_two_lams(lam):
    """20-interval mixed-decision BestFit trace at two arrival rates."""
    r = resolve("bestfit-rr")
    tr = compile_trace(r.decider, lam=lam, seed=0, n_intervals=20,
                       substeps=10)
    ref = replay_trace_edgesim(tr)
    jx = run_trace_engine(r.engine, tr, r.state(tr.seed))
    assert ref["tasks_completed"] > 0
    assert jx["dropped_tasks"] == 0
    assert_summaries_close(ref, jx)


def test_parity_under_ram_pressure():
    """Squeezed RAM exercises the repair fallback, placement failure
    (waiting tasks) and swap-slowdown paths on both backends."""
    cl = make_cluster(ram_scale=0.35)
    r = resolve("mc")
    tr = compile_trace(r.decider, lam=14.0, seed=2, n_intervals=12,
                       substeps=8, cluster=cl)
    ref = replay_trace_edgesim(tr, cluster=cl)
    jx = run_trace_engine(r.engine, tr, r.state(tr.seed), cluster=cl)
    assert ref["wait_intervals"] > 0        # repair actually failed tasks
    assert_summaries_close(ref, jx)


def test_layer_chain_parity():
    """Pure layer-split load: stage precedence + activation transfers."""
    r = resolve("bestfit-layer")
    tr = compile_trace(r.decider, lam=8.0, seed=3, n_intervals=15,
                       substeps=10)
    ref = replay_trace_edgesim(tr)
    jx = run_trace_engine(r.engine, tr, r.state(tr.seed))
    assert ref["layer_fraction"] == 1.0
    assert_summaries_close(ref, jx)


def test_vmap_grid_rows_match_solo_runs():
    """Batched grid row i must equal the solo run of trace i (vmap and
    chunked-thread dispatch change nothing numerically)."""
    r = resolve("bestfit-rr")
    traces = [compile_trace(r.decider, lam=lam, seed=s, n_intervals=10,
                            substeps=6)
              for lam in (4.0, 8.0) for s in (0, 1)]
    grid = run_grid_engine(r.engine, traces, r.state, threads=2)
    for i, tr in enumerate(traces):
        solo = run_trace_engine(r.engine, tr, r.state(tr.seed))
        for k in solo:
            assert np.isclose(solo[k], grid[i][k], rtol=1e-12, atol=1e-12), \
                f"row {i} {k}: solo={solo[k]!r} grid={grid[i][k]!r}"


def test_capacity_overflow_is_counted_not_silent():
    """Arrivals beyond ``max_active`` must surface in ``dropped_tasks``."""
    r = resolve("mc")
    tr = compile_trace(r.decider, lam=10.0, seed=0, n_intervals=8,
                       substeps=4)
    jx = run_trace_engine(r.engine, tr, r.state(tr.seed), max_active=8)
    assert jx["dropped_tasks"] > 0


def test_experiments_backend_jax_matches_batched():
    """`run_trace(backend='jax')` and `run_grid(backend='jax')` route
    through the same kernels and agree with run_grid_batched."""
    from repro.launch.experiments import run_grid, run_grid_batched, run_trace
    r1 = run_trace("mc", n_intervals=6, lam=4.0, seed=1, substeps=5,
                   backend="jax")
    recs = run_grid_batched("mc", seeds=(1,), lams=(4.0,), n_intervals=6,
                            substeps=5)
    assert np.isclose(r1["reward"], recs[0]["reward"], rtol=1e-12)
    grid = run_grid(("mc",), seeds=(1,), lams=(4.0,), n_intervals=6,
                    substeps=5, backend="jax")
    assert grid[0]["seed"] == 1 and grid[0]["lam"] == 4.0
    assert np.isclose(grid[0]["reward"], r1["reward"], rtol=1e-12)


# ------------------------------------------------- in-kernel learned policies
#
# The learned policies thread MABState (and the DASO surrogate) through
# the jitted interval carry; the reference is the same EdgeSim replay
# driven by the identical shared pure functions
# (reference.replay_trace_edgesim_learned).  States are handcrafted so
# the traces are deterministic and exercise both arms/contexts.


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


def _daso():
    import jax

    from repro.core import daso
    cfg = daso.DASOConfig(num_workers=50, max_containers=16,
                          state_features=4, hidden=32, depth=2,
                          place_iters=12)
    return daso.init_surrogate(jax.random.PRNGKey(0), cfg), cfg


def test_inkernel_mab_trace_parity():
    """Online UCB decisions + Algorithm-1 feedback in the kernel carry
    must reproduce the host replay: decisions, both split variants, and
    the final MAB scalars (eps/rho/t fingerprint the RBED trajectory)."""
    from repro.env.jaxsim import (compile_trace_dual,
                                  replay_trace_edgesim_learned)
    st = _mab_state()
    tr = compile_trace_dual(lam=5.0, seed=1, n_intervals=10, substeps=6)
    ref = replay_trace_edgesim_learned(tr, st)
    r = resolve("mab", mab_state=st)
    jx = run_trace_engine(r.engine, tr, r.state(tr.seed))
    assert ref["tasks_completed"] > 0
    assert 0.0 < ref["layer_fraction"] < 1.0   # both arms actually taken
    assert jx["mab_t"] == tr.n_intervals + int(st.t)
    assert_summaries_close(ref, jx)


def test_inkernel_splitplace_parity():
    """MAB decider + array-form DASO placer (surrogate ascent, BestFit
    warm start, feasibility-repair fallback) vs the host replay."""
    from repro.env.jaxsim import (compile_trace_dual,
                                  replay_trace_edgesim_learned)
    st = _mab_state()
    theta, cfg = _daso()
    tr = compile_trace_dual(lam=5.0, seed=1, n_intervals=10, substeps=6)
    ref = replay_trace_edgesim_learned(tr, st, daso_theta=theta,
                                       daso_cfg=cfg)
    r = resolve("splitplace", mab_state=st, daso_theta=theta, daso_cfg=cfg)
    jx = run_trace_engine(r.engine, tr, r.state(tr.seed))
    assert ref["tasks_completed"] > 0
    assert_summaries_close(ref, jx)


def test_learned_vmap_rows_match_solo():
    """Each grid row carries its own MABState copy: batched rows must be
    bit-close to solo runs, including the final carried-state scalars."""
    from repro.env.jaxsim import compile_trace_dual
    st = _mab_state()
    theta, cfg = _daso()
    traces = [compile_trace_dual(lam=lam, seed=s, n_intervals=6, substeps=4)
              for lam in (4.0, 7.0) for s in (0, 1)]
    r = resolve("splitplace", mab_state=st, daso_theta=theta, daso_cfg=cfg)
    grid = run_grid_engine(r.engine, traces, r.state, threads=2)
    eps = {g["mab_eps"] for g in grid}
    assert len(eps) > 1          # per-row online trajectories diverged
    for i, tr in enumerate(traces):
        solo = run_trace_engine(r.engine, tr, r.state(tr.seed))
        for k in solo:
            assert np.isclose(solo[k], grid[i][k], rtol=1e-12,
                              atol=1e-12), \
                f"row {i} {k}: solo={solo[k]!r} grid={grid[i][k]!r}"


def _theta_allclose(a, b, rtol=1e-4, atol=1e-9):
    import jax
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


def test_inkernel_mab_train_parity():
    """ε-greedy training decisions (eq. 6) + Algorithm-1 feedback in the
    kernel carry must reproduce the host replay: decisions drawn from
    the shared fold-in key choreography, both arms taken, and the final
    MAB scalars fingerprinting the RBED trajectory."""
    from repro.env.jaxsim import (compile_trace_dual,
                                  replay_trace_edgesim_trained)
    st = _mab_state()
    tr = compile_trace_dual(lam=5.0, seed=1, n_intervals=10, substeps=6)
    ref = replay_trace_edgesim_trained(tr, st)
    r = resolve("mab", mode="train", mab_state=st)
    jx = run_trace_engine(r.engine, tr, r.state(tr.seed))
    assert ref["tasks_completed"] > 0
    assert 0.0 < ref["layer_fraction"] < 1.0   # both arms actually taken
    assert jx["mab_t"] == tr.n_intervals + int(st.t)
    assert jx["mab_eps"] < float(st.eps)       # RBED ε-decay actually ran
    assert_summaries_close(ref, jx)


def test_inkernel_splitplace_train_parity():
    """The full §6.3 loop in-kernel — ε-greedy decisions + online DASO
    finetuning (replay-window appends, weighted train epochs, cold-start
    gates) — vs the host replay, incl. the finetuned theta pytree.  The
    trace is long enough that PLACE_MIN opens and the *finetuned*
    surrogate's ascended placements are actually deployed."""
    from repro.core import daso
    from repro.env.jaxsim import (compile_trace_dual,
                                  replay_trace_edgesim_trained)
    st = _mab_state()
    theta0, cfg = _daso()
    tr = compile_trace_dual(lam=5.0, seed=3, n_intervals=40, substeps=5)
    assert tr.n_intervals > daso.PLACE_MIN     # ascended placements used
    ref = replay_trace_edgesim_trained(tr, st, daso_theta=theta0,
                                       daso_cfg=cfg)
    r = resolve("splitplace", mode="train", mab_state=st,
                daso_theta=theta0, daso_cfg=cfg)
    jx = run_trace_engine(r.engine, tr, r.state(tr.seed))
    assert ref["tasks_completed"] > 0
    theta_ref = ref.pop("daso_theta")
    theta_jx = jx.pop("daso_theta")
    assert_summaries_close(ref, jx)
    _theta_allclose(theta_ref, theta_jx)
    # finetuning really moved the surrogate off the pretrain snapshot
    import jax
    moved = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                for a, b in zip(jax.tree_util.tree_leaves(theta_jx),
                                jax.tree_util.tree_leaves(theta0)))
    assert moved > 1e-4


def test_trained_vmap_rows_match_solo():
    """Each grid cell carries its own (MABState, theta, opt, window):
    batched rows must be bit-close to solo runs, incl. the finetuned
    theta, with per-cell ε-greedy keys diverging the trajectories."""
    from repro.env.jaxsim import compile_trace_dual
    st = _mab_state()
    theta, cfg = _daso()
    traces = [compile_trace_dual(lam=lam, seed=s, n_intervals=6, substeps=4)
              for lam in (4.0, 7.0) for s in (0, 1)]
    r = resolve("splitplace", mode="train", mab_state=st, daso_theta=theta,
                daso_cfg=cfg)
    grid = run_grid_engine(r.engine, traces, r.state, threads=2)
    eps = {g["mab_eps"] for g in grid}
    assert len(eps) > 1          # per-cell online trajectories diverged
    for i, tr in enumerate(traces):
        solo = run_trace_engine(r.engine, tr, r.state(tr.seed))
        _theta_allclose(solo.pop("daso_theta"), grid[i].pop("daso_theta"),
                        rtol=1e-12, atol=1e-12)
        for k in solo:
            assert np.isclose(solo[k], grid[i][k], rtol=1e-12,
                              atol=1e-12), \
                f"row {i} {k}: solo={solo[k]!r} grid={grid[i][k]!r}"


def test_experiments_train_mode_backend_jax():
    """`run_grid_batched(mode='train')` routes the pretrain state into
    the training kernel and agrees with `run_trace(mode='train')`;
    static policies reject mode='train'."""
    from repro.launch.experiments import (PretrainState, run_grid_batched,
                                          run_trace)
    st = _mab_state()
    theta, cfg = _daso()
    pre = PretrainState(mab_state=st, daso_theta=theta, daso_cfg=cfg)
    recs = run_grid_batched("splitplace", seeds=(1,), lams=(5.0,),
                            n_intervals=6, substeps=4, pretrain_state=pre,
                            mode="train")
    r1 = run_trace("splitplace", n_intervals=6, lam=5.0, seed=1,
                   substeps=4, backend="jax", mode="train", mab_state=st,
                   daso_theta=theta, daso_cfg=cfg)
    assert np.isclose(r1["reward"], recs[0]["reward"], rtol=1e-12)
    # train-mode ε-greedy decisions differ from deploy-mode UCB ones
    r_dep = run_trace("splitplace", n_intervals=6, lam=5.0, seed=1,
                      substeps=4, backend="jax", mab_state=st,
                      daso_theta=theta, daso_cfg=cfg)
    assert r_dep["mab_eps"] != r1["mab_eps"] \
        or r_dep["layer_fraction"] != r1["layer_fraction"]
    with pytest.raises(ValueError):
        run_grid_batched("mc", seeds=(1,), lams=(5.0,), n_intervals=6,
                         substeps=4, mode="train")
    with pytest.raises(ValueError):
        run_trace("mc", n_intervals=6, lam=5.0, seed=1, substeps=4,
                  backend="jax", mode="train")
    with pytest.raises(ValueError, match="is static"):
        resolve("mc", mode="train")


def test_inkernel_gillis_parity():
    """The Gillis baseline in the carry — contextual ε-greedy Q-learning
    between layer and compressed arms, per-interval ε-decay, sequential
    TD(0) updates — must reproduce the host replay, incl. the final
    Q-table and ε (the Q-trajectory fingerprint)."""
    from repro.env.jaxsim import (compile_trace_dual,
                                  replay_trace_edgesim_gillis)
    from repro.env.workload import COMPRESSED, LAYER
    tr = compile_trace_dual(lam=5.0, seed=1, n_intervals=10, substeps=6,
                            variants=(LAYER, COMPRESSED))
    ref = replay_trace_edgesim_gillis(tr)
    r = resolve("gillis")
    jx = run_trace_engine(r.engine, tr, r.state(tr.seed))
    assert ref["tasks_completed"] > 0
    assert 0.0 < ref["layer_fraction"] < 1.0   # both arms actually taken
    q_ref = ref.pop("gillis_q")
    q_jx = jx.pop("gillis_q")
    np.testing.assert_allclose(q_jx, q_ref, rtol=RTOL, atol=ATOL)
    assert np.abs(q_jx).sum() > 0              # Q-updates actually ran
    assert jx["gillis_eps"] < 0.5              # ε-decay actually ran
    assert_summaries_close(ref, jx)


def test_gillis_vmap_rows_match_solo():
    """Each grid cell carries its own (Q, ε) copy: batched rows must be
    bit-close to solo runs, incl. the final Q-table."""
    from repro.env.jaxsim import compile_trace_dual
    from repro.env.workload import COMPRESSED, LAYER
    traces = [compile_trace_dual(lam=lam, seed=s, n_intervals=6,
                                 substeps=4, variants=(LAYER, COMPRESSED))
              for lam in (4.0, 7.0) for s in (0, 1)]
    r = resolve("gillis")
    grid = run_grid_engine(r.engine, traces, r.state, threads=2)
    assert len({tuple(np.ravel(g["gillis_q"])) for g in grid}) > 1
    for i, tr in enumerate(traces):
        solo = run_trace_engine(r.engine, tr, r.state(tr.seed))
        np.testing.assert_allclose(grid[i].pop("gillis_q"),
                                   solo.pop("gillis_q"),
                                   rtol=1e-12, atol=1e-12)
        for k in solo:
            assert np.isclose(solo[k], grid[i][k], rtol=1e-12,
                              atol=1e-12), \
                f"row {i} {k}: solo={solo[k]!r} grid={grid[i][k]!r}"


def test_inkernel_gobi_parity():
    """The decision-blind GOBI ablation (surrogate input's decision
    one-hot zeroed) vs the host replay under the SAME blind config —
    the ascent trajectories must coincide exactly like decision-aware
    DASO's."""
    from repro.env.jaxsim import (compile_trace_dual,
                                  replay_trace_edgesim_learned)
    st = _mab_state()
    theta, cfg = _daso()
    blind = cfg._replace(decision_aware=False)
    tr = compile_trace_dual(lam=5.0, seed=1, n_intervals=10, substeps=6)
    ref = replay_trace_edgesim_learned(tr, st, daso_theta=theta,
                                       daso_cfg=blind)
    # the table applies the GOBI rule to the decision-aware cfg
    r = resolve("mab+gobi", mab_state=st, daso_theta=theta, daso_cfg=cfg)
    assert r.engine.daso_cfg == blind
    jx = run_trace_engine(r.engine, tr, r.state(tr.seed))
    assert ref["tasks_completed"] > 0
    assert_summaries_close(ref, jx)


def test_experiments_gillis_gobi_backend_jax():
    """`run_grid_batched(policy='gillis'|'mab+gobi')` routes through the
    in-kernel engines and agrees with `run_trace(backend='jax')`;
    mab+gobi still demands the pretrained surrogate."""
    from repro.launch.experiments import (PretrainState, run_grid_batched,
                                          run_trace)
    recs = run_grid_batched("gillis", seeds=(1,), lams=(5.0,),
                            n_intervals=6, substeps=4)
    r1 = run_trace("gillis", n_intervals=6, lam=5.0, seed=1, substeps=4,
                   backend="jax")
    assert np.isclose(r1["reward"], recs[0]["reward"], rtol=1e-12)
    assert recs[0]["policy"] == "gillis"
    assert "gillis_eps" in recs[0]
    st = _mab_state()
    theta, cfg = _daso()
    pre = PretrainState(mab_state=st, daso_theta=theta, daso_cfg=cfg)
    recs_g = run_grid_batched("mab+gobi", seeds=(1,), lams=(5.0,),
                              n_intervals=6, substeps=4,
                              pretrain_state=pre)
    r2 = run_trace("mab+gobi", n_intervals=6, lam=5.0, seed=1, substeps=4,
                   backend="jax", mab_state=st, daso_theta=theta,
                   daso_cfg=cfg)
    assert np.isclose(r2["reward"], recs_g[0]["reward"], rtol=1e-12)
    with pytest.raises(ValueError):
        run_grid_batched("mab+gobi", seeds=(1,), lams=(5.0,),
                         n_intervals=6, substeps=4, mab_state=st)
    with pytest.raises(ValueError, match="daso_cfg/daso_theta"):
        resolve("mab+gobi", mab_state=st)


def test_experiments_learned_backend_jax():
    """`run_grid_batched(policy='splitplace'|'mab')` routes the pretrain
    state into the kernel and agrees with `run_trace(backend='jax')`."""
    from repro.launch.experiments import (PretrainState, run_grid_batched,
                                          run_trace)
    st = _mab_state()
    theta, cfg = _daso()
    pre = PretrainState(mab_state=st, daso_theta=theta, daso_cfg=cfg)
    recs = run_grid_batched("splitplace", seeds=(1,), lams=(5.0,),
                            n_intervals=6, substeps=4, pretrain_state=pre)
    r1 = run_trace("splitplace", n_intervals=6, lam=5.0, seed=1,
                   substeps=4, backend="jax", mab_state=st,
                   daso_theta=theta, daso_cfg=cfg)
    assert np.isclose(r1["reward"], recs[0]["reward"], rtol=1e-12)
    recs_mab = run_grid_batched("mab", seeds=(1,), lams=(5.0,),
                                n_intervals=6, substeps=4,
                                pretrain_state=pre)
    assert recs_mab[0]["policy"] == "mab"
    with pytest.raises(ValueError):
        run_grid_batched("splitplace", seeds=(1,), lams=(5.0,),
                         n_intervals=6, substeps=4, mab_state=st)
    with pytest.raises(ValueError, match="daso_cfg/daso_theta"):
        resolve("splitplace", mab_state=st, daso_cfg=cfg)


def test_static_daso_arms_parity():
    """The three static-decider surrogate arms — one dual-trace engine:
    fixed LAYER/SEMANTIC decisions (or the prefix-stable fold-in random
    decider) feeding the frozen DASO placer, decision-blind for the GOBI
    arms — vs the host replay with the identical shared pure functions."""
    from repro.env.jaxsim import (compile_trace_dual,
                                  replay_trace_edgesim_static_daso)
    theta, cfg = _daso()
    tr = compile_trace_dual(lam=5.0, seed=1, n_intervals=6, substeps=4)
    fractions = {}
    for pol in ("layer+gobi", "semantic+gobi", "random+daso"):
        ref = replay_trace_edgesim_static_daso(tr, pol, daso_theta=theta,
                                               daso_cfg=cfg)
        r = resolve(pol, daso_theta=theta, daso_cfg=cfg)
        jx = run_trace_engine(r.engine, tr, r.state(tr.seed))
        assert ref["tasks_completed"] > 0, pol
        assert_summaries_close(ref, jx)
        fractions[pol] = ref["layer_fraction"]
    assert fractions["layer+gobi"] == 1.0      # fixed-arm deciders decide
    assert fractions["semantic+gobi"] == 0.0
    assert 0.0 <= fractions["random+daso"] <= 1.0


def test_experiments_static_daso_backend_jax():
    """`run_grid_batched`/`run_trace(backend='jax')` route the
    STATIC_DASO_ARMS names through the in-kernel engine; missing
    surrogate products are rejected."""
    import pytest

    from repro.launch.experiments import run_grid_batched, run_trace
    theta, cfg = _daso()
    recs = run_grid_batched("semantic+gobi", seeds=(1,), lams=(5.0,),
                            n_intervals=6, substeps=4, daso_theta=theta,
                            daso_cfg=cfg)
    r1 = run_trace("semantic+gobi", n_intervals=6, lam=5.0, seed=1,
                   substeps=4, backend="jax", daso_theta=theta,
                   daso_cfg=cfg)
    assert np.isclose(r1["reward"], recs[0]["reward"], rtol=1e-12)
    assert recs[0]["policy"] == "semantic+gobi"
    with pytest.raises(ValueError):
        run_grid_batched("random+daso", seeds=(1,), lams=(5.0,),
                         n_intervals=6, substeps=4)
    with pytest.raises(ValueError, match="daso_cfg/daso_theta"):
        resolve("random+daso", daso_theta=theta)
