"""Interval telemetry + run-ledger observability contracts.

Six pins, mirroring docs/ARCHITECTURE.md's "Observability" section:

  * **series parity** — the kernel's in-carry ``(T, C)`` telemetry
    series (``telemetry="interval"``) matches the host replay oracles
    column-for-column at the standard rtol=1e-4 contract, for the
    static, learned (deploy), trained and Gillis engine families, and
    the per-engine column layout agrees with the engine's
    ``telemetry_cols()`` declaration;
  * **zero-perturbation** — a ``telemetry="interval"`` run's summary
    scalars are identical (rtol=1e-12) to the ``"summary"`` run of the
    same trace: recording the series must not perturb the physics or
    the learning carries (both modes run the one hook sequence,
    ``driver._interval``, so this is near-bitwise);
  * **percentile bound** — kernel-path binned p50/p95/p99 estimates sit
    within the reported ``percentile_err_s`` of the host's exact
    percentiles, and the host's own error is exactly 0;
  * **runner-cache stats** — ``driver.cache_stats()`` counts hits and
    misses, and a same-engine recompile (same engine value, different
    static shapes) raises a ledger warning;
  * **RunLedger round-trip** — spans nest, JSONL dump/load round-trips,
    and ``tools/obs_report.py`` renders the cache and span sections the
    CI smoke step greps for;
  * **program spans** — spans keep their start, the process default
    ledger records nothing over a long stream, ``annotate=True`` writes
    ``repro.`` profiler annotations, and ``StreamRunner.run_chunk``
    records its put, dispatch, sync and fetch steps.
"""
import json
import os
import sys

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
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


def _series_close(ref, jx, ctx):
    assert ref["telemetry"]["cols"] == jx["telemetry"]["cols"], ctx
    rs = np.asarray(ref["telemetry"]["series"])
    js = np.asarray(jx["telemetry"]["series"])
    assert rs.shape == js.shape, f"{ctx}: {rs.shape} vs {js.shape}"
    for i, col in enumerate(ref["telemetry"]["cols"]):
        np.testing.assert_allclose(js[:, i], rs[:, i], rtol=RTOL,
                                   atol=ATOL, err_msg=f"{ctx}: col={col}")


# ------------------------------------------------- series parity oracles


def test_series_parity_static():
    from repro.env import jaxsim
    from repro.env.metrics import TELEMETRY_COLS
    r = jaxsim.resolve("bestfit-rr")
    tr = jaxsim.compile_trace(r.decider, lam=5.0, seed=0, n_intervals=8,
                              substeps=4)
    ref = jaxsim.replay_trace_edgesim(tr, telemetry="interval")
    jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                 telemetry="interval")
    assert jx["telemetry"]["cols"] == list(TELEMETRY_COLS)
    assert np.asarray(jx["telemetry"]["series"]).shape == (8, 18)
    _series_close(ref, jx, "static")


def test_series_parity_learned():
    """Deploy-mode series carry the four MAB learning-signal columns,
    sampled at end-of-interval *after* the UCB feedback update."""
    from repro.env import jaxsim
    from repro.env.jaxsim.engines import MAB_TELEMETRY_COLS
    st = _mab_state()
    tr = jaxsim.compile_trace_dual(lam=5.0, seed=3, n_intervals=6,
                                   substeps=3)
    ref = jaxsim.replay_trace_edgesim_learned(tr, st, telemetry="interval")
    r = jaxsim.resolve("mab", mab_state=st)
    jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                 telemetry="interval")
    assert tuple(jx["telemetry"]["cols"][-4:]) == MAB_TELEMETRY_COLS
    _series_close(ref, jx, "learned")
    # the MAB decision counter actually advanced over the trace
    s = np.asarray(jx["telemetry"]["series"])
    n_dec = s[:, -2] + s[:, -1]            # mab_n_layer + mab_n_semantic
    assert n_dec[-1] > n_dec[0]


def test_series_parity_trained():
    """Train mode adds the DASO replay-window fill and window loss on
    top of the MAB columns; the loss column tracks the finetuned theta,
    so parity here pins the whole in-kernel training carry."""
    import jax

    from repro.core import daso
    from repro.env import jaxsim
    from repro.env.cluster import make_cluster
    cfg = daso.DASOConfig(num_workers=make_cluster().n, max_containers=8,
                          state_features=4, hidden=16, depth=2,
                          place_iters=8)
    theta = daso.init_surrogate(jax.random.PRNGKey(7), cfg)
    st = _mab_state()
    tr = jaxsim.compile_trace_dual(lam=5.0, seed=3, n_intervals=6,
                                   substeps=3)
    hp = (0.5, 0.5, 2, 2, 1)              # gates open on short horizons
    ref = jaxsim.replay_trace_edgesim_trained(
        tr, st, daso_theta=theta, daso_cfg=cfg, train_hp=hp,
        telemetry="interval")
    r = jaxsim.resolve("splitplace", mode="train", mab_state=st,
                       daso_theta=theta, daso_cfg=cfg, train_hp=hp)
    jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                 telemetry="interval")
    cols = jx["telemetry"]["cols"]
    assert cols[-2:] == ["daso_win_fill", "daso_last_loss"]
    _series_close(ref, jx, "trained")
    s = np.asarray(jx["telemetry"]["series"])
    fill = s[:, cols.index("daso_win_fill")]
    assert fill[-1] > 0 and np.all(np.diff(fill) >= 0)


def test_series_parity_gillis():
    from repro.env import jaxsim
    from repro.env.workload import COMPRESSED, LAYER
    tr = jaxsim.compile_trace_dual(lam=5.0, seed=2, n_intervals=6,
                                   substeps=3,
                                   variants=(LAYER, COMPRESSED))
    ref = jaxsim.replay_trace_edgesim_gillis(tr, telemetry="interval")
    r = jaxsim.resolve("gillis")
    jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                 telemetry="interval")
    cols = jx["telemetry"]["cols"]
    assert cols[-3:] == ["gillis_eps", "gillis_q_min", "gillis_q_max"]
    _series_close(ref, jx, "gillis")
    # ε decays every interval (default decay < 1)
    eps = np.asarray(jx["telemetry"]["series"])[:, cols.index("gillis_eps")]
    assert np.all(np.diff(eps) < 0)


# --------------------------------------------- zero-perturbation + bound


def test_interval_mode_preserves_summary():
    """Turning the series on must not move any summary scalar: the
    interval-mode body runs the summary-mode hooks (``driver._interval``)
    and adds the row, so everything the ``"summary"`` run reports is
    reproduced at 1e-12."""
    from repro.env import jaxsim
    r = jaxsim.resolve("bestfit-rr")
    tr = jaxsim.compile_trace(r.decider, lam=5.0, seed=0, n_intervals=8,
                              substeps=4)
    off = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed))
    on = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                 telemetry="interval")
    for k, v in off.items():
        assert np.isclose(on[k], v, rtol=1e-12, atol=1e-12), \
            f"{k}: summary={v!r} interval={on[k]!r}"


def test_percentiles_within_reported_bound():
    from repro.env import jaxsim
    r = jaxsim.resolve("bestfit-rr")
    tr = jaxsim.compile_trace(r.decider, lam=5.0, seed=0, n_intervals=8,
                              substeps=4)
    ref = jaxsim.replay_trace_edgesim(tr, telemetry="interval")
    jx = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                 telemetry="interval")
    assert ref["percentile_err_s"] == 0.0      # host path is exact
    assert jx["percentile_err_s"] >= 0.0
    for q in (50, 95, 99):
        for m in ("response", "wait"):
            k = f"p{q}_{m}_s"
            assert abs(ref[k] - jx[k]) <= jx["percentile_err_s"] + ATOL, \
                f"{k}: exact={ref[k]!r} binned={jx[k]!r} " \
                f"bound={jx['percentile_err_s']!r}"


def test_telemetry_knob_validation():
    import pytest

    from repro.env import jaxsim
    r = jaxsim.resolve("mc")
    tr = jaxsim.compile_trace(r.decider, lam=3.0, seed=0, n_intervals=4,
                              substeps=3)
    with pytest.raises(ValueError, match="telemetry"):
        jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                telemetry="everything")


# ------------------------------------------------- cache + ledger layer


def test_cache_stats_hits_and_misses():
    from repro.env import jaxsim
    r = jaxsim.resolve("mc")
    tr = jaxsim.compile_trace(r.decider, lam=3.0, seed=0, n_intervals=4,
                              substeps=3)
    jaxsim.run_trace_engine(r.engine, tr, ())      # warm (maybe a miss)
    before = jaxsim.cache_stats()
    jaxsim.run_trace_engine(r.engine, tr, ())      # definitely a hit
    after = jaxsim.cache_stats()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]
    assert after["size"] >= 1 and after["keys"]


def test_recompile_warning_on_ledger():
    """The same engine value compiled under two different static shapes
    is legitimate but worth flagging: the ledger records a warning and
    the per-engine key map shows both compilations."""
    from repro.env import jaxsim
    from repro.obs import RunLedger, use_ledger
    eng = jaxsim.engines.StaticEngine(name="telemetry-recompile-test")
    dec = jaxsim.make_static_decider("mc")
    tr1 = jaxsim.compile_trace(dec, lam=3.0, seed=0, n_intervals=4,
                               substeps=3)
    tr2 = jaxsim.compile_trace(dec, lam=3.0, seed=0, n_intervals=5,
                               substeps=3)
    led = RunLedger("recompile-test")
    with use_ledger(led):
        jaxsim.run_trace_engine(eng, tr1, ())
        jaxsim.run_trace_engine(eng, tr2, ())      # same engine, new key
    warns = [ln for ln in led.to_lines() if ln["kind"] == "warning"]
    assert any("recompile" in w["message"] for w in warns), warns


def test_ledger_round_trip_and_report(tmp_path):
    from repro.env import jaxsim
    from repro.obs import RunLedger, load_ledger_lines, use_ledger
    r = jaxsim.resolve("mc")
    tr = jaxsim.compile_trace(r.decider, lam=3.0, seed=0, n_intervals=4,
                              substeps=3)
    led = RunLedger("round-trip")
    led.stamp(telemetry="interval")
    with use_ledger(led):
        out = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                      telemetry="interval")
        led.add_series("trace", out["telemetry"]["cols"],
                       out["telemetry"]["series"])
        led.add_cache_stats(jaxsim.cache_stats())
        led.count("unit_runs")
    path = tmp_path / "ledger.jsonl"
    led.dump(path)
    lines = load_ledger_lines(path)
    kinds = {ln["kind"] for ln in lines}
    assert {"meta", "span", "counters", "cache_stats",
            "series"} <= kinds
    spans = [ln for ln in lines if ln["kind"] == "span"]
    names = {s["name"] for s in spans}
    assert "dispatch" in names and "summarize" in names
    # every non-root span's parent is a recorded span id
    ids = {s["id"] for s in spans}
    assert all(s["parent"] in ids for s in spans
               if s["parent"] is not None)
    # the report renders the sections the CI smoke step greps for
    sys.path.insert(0, os.path.join(_HERE, os.pardir, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    text = obs_report.render(lines)
    assert "== Span tree ==" in text
    assert "== Runner cache ==" in text
    assert "== Series: trace ==" in text
    assert "percentiles (binned" in text


def test_provenance_stamp_keys():
    from repro.obs import provenance_stamp
    st = provenance_stamp(telemetry="interval")
    for k in ("jax_version", "backend", "device_count", "device_kind",
              "cpu_count", "substep_impl", "devices"):
        assert k in st, st
    assert st["telemetry"] == "interval"
    assert json.dumps(st)                  # JSON-serializable


# ------------------------------------------- program spans on the trace


def test_spans_carry_starts():
    """Every span keeps its ``perf_counter`` start beside its duration,
    so spans can be laid on a profiler trace's clock."""
    import time

    from repro.obs import RunLedger
    led = RunLedger("starts")
    t_before = time.perf_counter()
    with led.span("outer"):
        with led.span("inner", k=1):
            time.sleep(0.002)
    t_after = time.perf_counter()
    outer, = led.spans("outer")
    inner, = led.spans("inner")
    assert t_before <= outer["start_s"] <= inner["start_s"]
    assert inner["start_s"] + inner["dur_s"] \
        <= outer["start_s"] + outer["dur_s"] <= t_after
    assert inner["dur_s"] >= 0.002 and inner["attrs"] == {"k": 1}
    assert inner["parent"] == outer["id"]


def test_default_ledger_records_nothing_over_a_long_stream():
    """The process-global ledger keeps nothing: a 200-chunk stream and
    a short ``serve`` leave it empty, so serving memory stays flat."""
    from repro.env import jaxsim
    from repro.env.jaxsim import stream
    from repro.obs import get_ledger
    led = get_ledger()
    assert not led.record
    dec = jaxsim.make_static_decider("mc")
    tr = jaxsim.compile_trace(dec, lam=3.0, seed=0, n_intervals=200,
                              substeps=2)
    eng = jaxsim.engines.StaticEngine()
    stream.replay_stream(eng, tr, (), chunk_intervals=1)
    eng, es0, fkw = stream.make_stream_policy("mc")
    feeder = stream.StreamFeeder(lam=3.0, seed=0, substeps=2, **fkw)
    stream.serve(eng, es0, feeder, chunk_intervals=2, max_active=32,
                 target_tasks=60)
    assert get_ledger() is led
    assert led.events == [] and led.counters == {} and led.series == []
    assert led.cache_stats is None


def test_annotated_ledger_writes_repro_annotations(tmp_path):
    """``annotate=True`` opens a ``repro.<span>`` profiler annotation
    around each span; a ledger without it writes none."""
    import jax
    from jax.profiler import ProfileData

    from repro.obs import RunLedger
    from repro.obs.ledger import ANNOTATION_PREFIX
    on, off = RunLedger("on", annotate=True), RunLedger("off")
    jax.profiler.start_trace(str(tmp_path))
    with on.span("stream_put"):
        with on.span("inner"):
            pass
    with off.span("unannotated"):
        pass
    jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    names = [e.name for plane in ProfileData.from_file(str(path)).planes
             for ln in plane.lines for e in ln.events]
    got = sorted(n for n in names if n.startswith(ANNOTATION_PREFIX))
    assert got == ["repro.inner", "repro.stream_put"], got
    assert {e["name"] for e in off.events} == {"unannotated"}


def test_run_chunk_records_its_four_steps():
    """Under a recording ledger each ``StreamRunner.run_chunk`` call
    records put, dispatch, sync and fetch once, in that order and
    without overlap; the put span names the tape's leaves and bytes."""
    from repro.env import jaxsim
    from repro.env.jaxsim import arrays, stream
    from repro.obs import RunLedger, use_ledger
    steps = ("stream_put", "stream_dispatch", "stream_sync",
             "stream_fetch")
    dec = jaxsim.make_static_decider("mc")
    tr = jaxsim.compile_trace(dec, lam=3.0, seed=0, n_intervals=6,
                              substeps=2)
    tapes = [tape for _, tape in arrays.chunk_tapes(tr, 2)]
    r = stream.StreamRunner(jaxsim.engines.StaticEngine(), (),
                            interval_s=tr.interval_s, substeps=tr.substeps,
                            max_active=jaxsim.default_capacity([tr]))
    led = RunLedger("chunks")
    with use_ledger(led):
        for tape in tapes:
            r.run_chunk(tape)
    spans = [e for e in led.events
             if e["kind"] == "span" and e["name"] in steps]
    assert len(spans) == 4 * len(tapes)
    for i in range(len(tapes)):
        chunk = sorted(spans[4 * i:4 * i + 4], key=lambda e: e["start_s"])
        assert tuple(e["name"] for e in chunk) == steps
        for a, b in zip(chunk, chunk[1:]):
            assert a["start_s"] + a["dur_s"] <= b["start_s"]
        put = chunk[0]["attrs"]
        assert put["leaves"] == len(tapes[i])
        assert put["bytes"] == sum(v.nbytes for v in tapes[i].values())
    assert not any(k.startswith("runner_cache.h")
                   or k.startswith("runner_cache.m") for k in led.counters)
