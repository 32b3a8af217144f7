"""The policy table (``jaxsim.resolve``): one place turns a policy name
into its engine, starting engine state and trace variants, and every
entry point by name asks it.

Pinned per policy name the table knows:

  * the batch path (``experiments.run_trace(backend="jax")``) and the
    serving path (``stream.make_stream_policy``) build EQUAL engines, so
    they share one ``driver._static_key`` and one compiled executable;
  * a static policy resolves to a compile-time decider and no variants;
  * the GOBI rule: exactly the GOBI names place with the decision-blind
    surrogate (``daso_cfg.decision_aware=False``).
"""
import numpy as np
import pytest

from repro.env.jaxsim import engines, policies


def _products():
    import jax

    from repro.core import daso, mab
    cfg = daso.DASOConfig(num_workers=50, max_containers=8,
                          state_features=4, hidden=16, depth=2,
                          place_iters=4)
    return (mab.init_state(3), daso.init_surrogate(jax.random.PRNGKey(0),
                                                   cfg), cfg)


@pytest.mark.parametrize("policy", sorted(policies.TABLE))
def test_table_row(policy, monkeypatch):
    from repro.env import jaxsim
    from repro.env.jaxsim import stream
    from repro.launch import experiments
    st, theta, cfg = _products()
    seen = {}

    def capture(engine, trace, es0, **kw):
        seen.update(engine=engine, trace=trace)
        return {}

    monkeypatch.setattr(jaxsim, "run_trace_engine", capture)
    experiments.run_trace(policy, n_intervals=2, substeps=2, seed=5,
                          backend="jax", mab_state=st, daso_theta=theta,
                          daso_cfg=cfg)
    engine, es0, feeder_kw = stream.make_stream_policy(
        policy, seed=5, mab_state=st, daso_theta=theta, daso_cfg=cfg)
    assert seen["engine"] == engine and hash(seen["engine"]) == hash(engine)

    r = policies.resolve(policy, mab_state=st, daso_theta=theta,
                         daso_cfg=cfg)
    assert r.engine == engine
    assert r.variants == engine.variants
    row = policies.TABLE[policy]
    assert (r.needs_mab, r.needs_daso) == (row.needs_mab, row.needs_daso)
    if row.family == "static":
        assert r.variants is None and r.decider is not None
        assert feeder_kw == {"decider": feeder_kw["decider"]}
        assert not hasattr(seen["trace"], "variants")
    else:
        assert r.variants in (engines.MAB_VARIANTS, engines.GILLIS_VARIANTS)
        assert feeder_kw == {"variants": r.variants}
        assert tuple(seen["trace"].variants) == r.variants
    cfg_used = getattr(engine, "daso_cfg", None)
    assert (cfg_used is not None) == row.needs_daso
    if cfg_used is not None:
        assert cfg_used.decision_aware == \
            (policy not in ("mab+gobi", "layer+gobi", "semantic+gobi"))
    if policy == "mab+gobi":
        assert cfg_used == cfg._replace(decision_aware=False)


def test_state_stacks_keys_per_grid_cell():
    """``state(seed)`` gives one trace its key; ``state(seeds)`` stacks
    one per grid cell, in order, from the same ``trace_train_key``."""
    import jax
    r = policies.resolve("gillis")
    one = r.state(7)
    many = r.state([3, 7])
    assert many["key"].shape == (2,) + one["key"].shape
    np.testing.assert_array_equal(many["key"][1], one["key"])
    np.testing.assert_array_equal(
        one["key"], jax.random.PRNGKey(7))
    assert one["eps"] == engines.GILLIS_HP[0]


def test_resolve_refuses():
    """Unknown names and modes, a surrogate sized for another fleet, and
    train mode on a static policy are refused by the table itself."""
    from repro.env.cluster import make_cluster
    st, theta, cfg = _products()
    with pytest.raises(ValueError, match="unknown policy"):
        policies.resolve("no-such-policy")
    with pytest.raises(ValueError, match="unknown mode"):
        policies.resolve("mab", mode="serve", mab_state=st)
    with pytest.raises(ValueError, match="num_workers"):
        policies.resolve("splitplace", mab_state=st, daso_theta=theta,
                         daso_cfg=cfg, cluster=make_cluster(
                             fleet=[("B2ms", 2), ("E2asv4", 2)]))
    with pytest.raises(ValueError, match="is static"):
        policies.resolve("layer+gobi", mode="train", daso_theta=theta,
                         daso_cfg=cfg)
