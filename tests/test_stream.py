"""Streaming serve driver contracts (``repro.env.jaxsim.stream``).

Five pin groups, mirroring docs/ARCHITECTURE.md's "Streaming serve"
section:

  * **chunked-replay parity** — splitting a frozen compiled trace into
    chunk tapes and threading the carry through consecutive jitted
    chunk calls reproduces the one-shot ``run_trace_engine`` episode at
    the standard rtol=1e-4 summary contract, for the static, learned
    (deploy) and Gillis engine families — including a non-dividing
    chunk size (remainder chunk) and the fold_in(key, t) engines, which
    only pass if hooks see the ABSOLUTE interval index;
  * **counted-not-silent admission** — arrivals beyond the feeder tape
    width are dropped host-side into ``feeder_overflow``, arrivals
    beyond free ring capacity are dropped in-kernel into ``dropped``,
    and the serving report's ledger balances exactly:
    offered == fed + feeder_overflow, admitted == fed − dropped,
    admitted == finished + live;
  * **one compile per chunk shape** — a multi-chunk soak costs exactly
    one runner-cache miss; every later equal-size chunk is a hit
    (``driver.cache_stats()`` deltas);
  * **LRU-bounded cache** — the runner cache evicts beyond
    ``set_cache_limit``, ``cache_stats()`` reports evictions,
    re-compiling an evicted key raises the eviction ledger warning, and
    ``clear_cache()`` resets everything;
  * **donated carry** — on backends that pass the donation probe the
    chunk-to-chunk carry is donated (the previous chunk's buffers die
    in place; asserted inside ``run_chunk``) and stays device-resident
    between chunks — no host round-trip mid-stream.
"""
import numpy as np
import pytest

RTOL, ATOL = 1e-4, 1e-9


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


def _summaries_close(ref, got, ctx):
    assert set(ref) == set(got), ctx
    for k in ref:
        rv, gv = ref[k], got[k]
        if isinstance(rv, np.ndarray):
            np.testing.assert_allclose(gv, rv, rtol=RTOL, atol=ATOL,
                                       err_msg=f"{ctx}: {k}")
        elif isinstance(rv, float):
            assert np.isclose(gv, rv, rtol=RTOL, atol=ATOL), \
                f"{ctx}: {k} one-shot={rv!r} chunked={gv!r}"
        else:
            assert rv == gv, f"{ctx}: {k} one-shot={rv!r} chunked={gv!r}"


# ------------------------------------------------ chunked-replay parity


def test_replay_parity_static():
    from repro.env import jaxsim
    from repro.env.jaxsim import stream
    dec = jaxsim.make_static_decider("mc")
    tr = jaxsim.compile_trace(dec, lam=4.0, seed=0, n_intervals=12,
                              substeps=4)
    eng = jaxsim.engines.StaticEngine()
    ref = jaxsim.run_trace_engine(eng, tr, ())
    # 12 intervals in chunks of 5: two full chunks + a remainder chunk,
    # so the carry crosses two boundaries and one odd shape
    got = stream.replay_stream(eng, tr, (), chunk_intervals=5)
    _summaries_close(ref, got, "static")


def test_replay_parity_learned():
    """MABDeployEngine's UCB counters ride the carry across chunk
    boundaries; decision parity requires the global interval index."""
    from repro.env import jaxsim
    from repro.env.jaxsim import stream
    st = _mab_state()
    tr = jaxsim.compile_trace_dual(lam=4.0, seed=3, n_intervals=12,
                                   substeps=4)
    r = jaxsim.resolve("mab", mab_state=st)
    ref = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed))
    got = stream.replay_stream(r.engine, tr, r.state(tr.seed),
                               chunk_intervals=5)
    _summaries_close(ref, got, "learned")


def test_replay_parity_gillis():
    """GillisEngine draws its ε-greedy bits from fold_in(key, t) — the
    strictest chunk-boundary contract: any chunk-local t would pass
    static parity but desync every decision here."""
    from repro.env import jaxsim
    from repro.env.jaxsim import stream
    from repro.env.workload import COMPRESSED, LAYER
    tr = jaxsim.compile_trace_dual(lam=4.0, seed=2, n_intervals=12,
                                   substeps=4,
                                   variants=(LAYER, COMPRESSED))
    r = jaxsim.resolve("gillis")
    ref = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed))
    got = stream.replay_stream(r.engine, tr, r.state(tr.seed),
                               chunk_intervals=5)
    _summaries_close(ref, got, "gillis")


def test_replay_series_matches_episode_series():
    """The concatenated chunk telemetry series equals the one-shot
    interval-mode series row for row."""
    from repro.env import jaxsim
    from repro.env.jaxsim import stream
    dec = jaxsim.make_static_decider("bestfit-rr")
    tr = jaxsim.compile_trace(dec, lam=4.0, seed=1, n_intervals=9,
                              substeps=3)
    eng = jaxsim.engines.StaticEngine()
    ref = jaxsim.run_trace_engine(eng, tr, (), telemetry="interval")
    got = stream.replay_stream(eng, tr, (), chunk_intervals=4,
                               collect_series=True)
    assert got["telemetry"]["cols"] == ref["telemetry"]["cols"]
    np.testing.assert_allclose(got["telemetry"]["series"],
                               ref["telemetry"]["series"],
                               rtol=RTOL, atol=ATOL)


# ------------------------------------------- counted-not-silent admission


def _serve(policy="mc", **kw):
    from repro.env.jaxsim import stream
    eng, es0, fkw = stream.make_stream_policy(policy)
    feeder_kw = {k: kw.pop(k) for k in ("max_arrivals",) if k in kw}
    feeder = stream.StreamFeeder(lam=kw.pop("lam", 6.0), seed=0,
                                 interval_s=300.0, substeps=3,
                                 **feeder_kw, **fkw)
    rep = stream.serve(eng, es0, feeder, **kw)
    return rep


def _check_ledger(rep):
    assert rep["offered"] == rep["fed"] + rep["feeder_overflow"], rep
    assert rep["admitted"] == rep["fed"] - rep["dropped"], rep
    assert rep["admitted"] == rep["finished"] + rep["live"], rep


def test_serve_accounting_balances():
    rep = _serve(chunk_intervals=6, max_active=128, target_tasks=150,
                 window_intervals=24)
    _check_ledger(rep)
    assert rep["feeder_overflow"] == 0 and rep["dropped"] == 0
    assert rep["finished"] > 0
    assert rep["rolling"]["qps"] > 0
    assert 0 <= rep["rolling"]["violation_rate"] <= 1


def test_feeder_overflow_counted():
    """A tape too narrow for the burst drops host-side — counted, and
    the ledger still balances (nothing silently vanishes)."""
    rep = _serve(chunk_intervals=6, max_active=128, target_tasks=150,
                 window_intervals=24, max_arrivals=3)
    _check_ledger(rep)
    assert rep["feeder_overflow"] > 0


def test_ring_capacity_drops_counted():
    """A ring smaller than the live-task population drops in-kernel —
    counted in ``dropped``, and the ledger still balances."""
    rep = _serve(chunk_intervals=6, max_active=8, target_tasks=150,
                 window_intervals=24)
    _check_ledger(rep)
    assert rep["dropped"] > 0
    assert rep["max_occupancy"] <= 8


# ------------------------------------------ one compile per chunk shape


def test_soak_compiles_once_per_chunk_shape():
    from repro.env import jaxsim
    from repro.env.jaxsim import stream
    eng, es0, fkw = stream.make_stream_policy("mc")
    feeder = stream.StreamFeeder(lam=5.0, seed=1, interval_s=300.0,
                                 substeps=3, **fkw)
    before = jaxsim.cache_stats()
    rep = stream.serve(eng, es0, feeder, chunk_intervals=4,
                       max_active=128, target_tasks=400,
                       window_intervals=16)
    after = jaxsim.cache_stats()
    assert rep["n_chunks"] >= 3
    # serve emits fixed-size chunks only → exactly one stream compile,
    # every subsequent chunk a cache hit
    assert after["misses"] == before["misses"] + 1
    assert after["hits"] == before["hits"] + rep["n_chunks"] - 1


# ---------------------------------------------------- LRU-bounded cache


def test_cache_lru_eviction_and_clear():
    from repro.env import jaxsim
    from repro.obs import RunLedger, use_ledger
    dec = jaxsim.make_static_decider("mc")
    eng = jaxsim.engines.StaticEngine(name="stream-lru-test")
    trs = [jaxsim.compile_trace(dec, lam=3.0, seed=0, n_intervals=n,
                                substeps=3) for n in (3, 4, 5)]
    jaxsim.clear_cache()
    old = jaxsim.set_cache_limit(2)
    led = RunLedger("lru-test")
    try:
        with use_ledger(led):
            for tr in trs:                    # 3 keys into a 2-slot cache
                jaxsim.run_trace_engine(eng, tr, ())
            stats = jaxsim.cache_stats()
            assert stats["limit"] == 2
            assert stats["size"] <= 2
            assert stats["evictions"] >= 1
            # the oldest key was evicted; re-running it recompiles and
            # raises the eviction-specific ledger warning
            before = jaxsim.cache_stats()
            jaxsim.run_trace_engine(eng, trs[0], ())
            assert jaxsim.cache_stats()["misses"] == before["misses"] + 1
        warns = [ln for ln in led.to_lines() if ln["kind"] == "warning"]
        assert any("evicted" in w["message"] for w in warns), warns
        counts = [ln for ln in led.to_lines() if ln["kind"] == "counters"]
        assert any(c["counters"].get("runner_cache.eviction")
                   for c in counts), counts
    finally:
        jaxsim.set_cache_limit(old)
    jaxsim.clear_cache()
    stats = jaxsim.cache_stats()
    assert stats == {"hits": 0, "misses": 0, "evictions": 0, "size": 0,
                     "limit": old, "keys": {}}


def test_cache_limit_validation():
    from repro.env import jaxsim
    with pytest.raises(ValueError, match="cache limit"):
        jaxsim.set_cache_limit(0)


def test_lru_recency_order():
    """A hit refreshes recency: touching the oldest key makes the
    middle key the eviction victim."""
    from repro.env import jaxsim
    dec = jaxsim.make_static_decider("mc")
    eng = jaxsim.engines.StaticEngine(name="stream-lru-order-test")
    trs = [jaxsim.compile_trace(dec, lam=3.0, seed=0, n_intervals=n,
                                substeps=3) for n in (3, 4, 5)]
    jaxsim.clear_cache()
    old = jaxsim.set_cache_limit(2)
    try:
        jaxsim.run_trace_engine(eng, trs[0], ())    # A
        jaxsim.run_trace_engine(eng, trs[1], ())    # B
        jaxsim.run_trace_engine(eng, trs[0], ())    # hit A → B is LRU
        jaxsim.run_trace_engine(eng, trs[2], ())    # C evicts B
        before = jaxsim.cache_stats()
        jaxsim.run_trace_engine(eng, trs[0], ())    # A still cached
        after = jaxsim.cache_stats()
        assert after["hits"] == before["hits"] + 1
        assert after["misses"] == before["misses"]
    finally:
        jaxsim.set_cache_limit(old)
        jaxsim.clear_cache()


# -------------------------------------------------------- donated carry


def test_carry_donated_and_device_resident():
    """On a donation-capable backend (the CPU backend passes the probe
    on current jax) the previous carry dies in place after each chunk —
    ``run_chunk`` itself asserts that — and the live carry never leaves
    the device between chunks."""
    import jax

    from repro.env import jaxsim
    from repro.env.jaxsim import driver, stream
    dec = jaxsim.make_static_decider("mc")
    tr = jaxsim.compile_trace(dec, lam=4.0, seed=0, n_intervals=8,
                              substeps=3)
    eng = jaxsim.engines.StaticEngine()
    r = stream.StreamRunner(eng, (), interval_s=tr.interval_s,
                            substeps=tr.substeps, max_active=64)
    assert r.donated == driver._donation_ok()
    for _, tape in jaxsim.chunk_tapes(tr, 4):
        r.run_chunk(tape)                 # donation asserted inside
    for leaf in jax.tree_util.tree_leaves(r.carry):
        assert isinstance(leaf, jax.Array) and not leaf.is_deleted()
    s = r.summary(tr.n_intervals)
    assert s["tasks_completed"] >= 0


def test_chunk_tapes_validation():
    from repro.env import jaxsim
    dec = jaxsim.make_static_decider("mc")
    tr = jaxsim.compile_trace(dec, lam=3.0, seed=0, n_intervals=4,
                              substeps=3)
    with pytest.raises(ValueError, match="chunk_intervals"):
        list(jaxsim.chunk_tapes(tr, 0))
    chunks = list(jaxsim.chunk_tapes(tr, 3))
    assert [t0 for t0, _ in chunks] == [0, 3]
    assert chunks[-1][1]["valid"].shape[0] == 1   # remainder chunk


def test_feeder_requires_exactly_one_mode():
    from repro.env import jaxsim
    from repro.env.jaxsim import stream
    with pytest.raises(ValueError, match="exactly one"):
        stream.StreamFeeder(lam=3.0)
    with pytest.raises(ValueError, match="exactly one"):
        stream.StreamFeeder(lam=3.0,
                            decider=jaxsim.make_static_decider("mc"),
                            variants=jaxsim.engines.MAB_VARIANTS)


@pytest.mark.parametrize("policy,dots", [("mc", 0), ("splitplace", 8)])
def test_chunk_program_counts_sliced_dots(policy, dots):
    """Tracing one chunk program under a recording ledger counts the
    DASO ascent's sliced dots once per traced ascent: 8 for SplitPlace
    (one ascent in the interval body; four layers, forward and
    backward), none for ``mc``, which runs no ascent."""
    import jax
    import jax.numpy as jnp

    from repro.core import daso
    from repro.env.cluster import make_cluster
    from repro.env.jaxsim import driver, kernels, stream
    from repro.env.jaxsim.arrays import ClusterArrays
    from repro.launch.experiments import seeded_surrogate
    from repro.obs import RunLedger, use_ledger
    cluster = make_cluster()
    theta, cfg = seeded_surrogate(cluster.n, seed=0)
    engine, es0, feeder_kw = stream.make_stream_policy(
        policy, cluster=cluster, daso_theta=theta, daso_cfg=cfg)
    feeder = stream.StreamFeeder(lam=6.0, seed=0, cluster=cluster,
                                 **feeder_kw)
    tape = feeder.next_chunk(2)
    frag = tape["vinstr" if "vinstr" in tape else "instr"]
    K = 48
    ledger = RunLedger("trace")
    daso.optimize_placement.clear_cache()      # trace the ascent afresh
    with jax.enable_x64(True), use_ledger(ledger):
        cld = ClusterArrays.from_cluster(cluster).as_dict()
        carry = (kernels.init_state(K, frag.shape[-1], cluster.n),
                 driver._init_acc(cluster.n),
                 jax.tree_util.tree_map(jnp.asarray, es0))
        key = driver._static_key(engine, tape, K, cluster.n, feeder.substeps,
                                 feeder.interval_s, 0.5, "xla", "stream")
        jax.jit(driver._stream_program(*key[:-1])).trace(
            tape, cld, carry, jnp.asarray(0, jnp.int64))
    assert ledger.counters.get("daso.sliced_dot", 0) == dots
