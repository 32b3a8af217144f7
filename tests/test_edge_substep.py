"""Fuzzed state parity for the fused substep-physics kernel.

Three implementations of one interval of substep physics must agree:

  * ``repro.kernels.ref.edge_substep_ref`` — the pure-jnp scan
    reference;
  * ``repro.kernels.edge_substep.edge_substep`` — the Pallas kernel
    (interpret mode on CPU), same formulas expressed as an in-kernel
    ``fori_loop`` over VMEM-resident refs;
  * the driver's inline XLA path (``kernels.run_substeps``) — checked
    end-to-end through ``run_trace_engine(substep_impl=...)``.

The fuzz draws synthetic-but-consistent slot states from a seeded
``numpy.random.RandomState`` (no hypothesis dependency): padding
fragment columns are born done with ``worker=-1``, stages are within
``[0, F]``, and every physical quantity is positive — the same
invariants ``arrays.init_state`` guarantees.  float64 carries must
match at ``rtol=1e-12`` (they are the same operations in the same
order, so in practice they match bitwise).
"""
from __future__ import annotations

import numpy as np
import pytest

K, F, N = 12, 4, 6
SUBSTEPS, DT = 7, 1.5
KW = dict(substeps=SUBSTEPS, dt=DT, swap_slowdown=0.5, nic_cap=50.0)


def _rand_inputs(rng: np.random.RandomState):
    """One consistent fuzzed (carries + statics) input set."""
    nfrag = rng.randint(1, F + 1, K).astype(np.int32)
    colpad = np.arange(F)[None, :] >= nfrag[:, None]     # padding columns
    done = rng.rand(K, F) < 0.35
    done |= colpad
    worker = rng.randint(0, N, (K, F)).astype(np.int32)
    worker[colpad] = -1
    placed = rng.rand(K) < 0.8
    worker[~placed] = -1
    task_done = done.all(axis=1) & (rng.rand(K) < 0.5)
    stage = np.minimum(done.argmin(axis=1).astype(np.int32), nfrag - 1)
    stage[done.all(axis=1)] = nfrag[done.all(axis=1)]
    args = dict(
        instr=np.where(done, 0.0, rng.uniform(1e3, 5e4, (K, F))),
        done=done,
        transfer=np.where(done, 0.0, rng.uniform(0.0, 30.0, (K, F))),
        stage=stage,
        task_done=task_done,
        resp=np.where(task_done, rng.uniform(1.0, 50.0, K), 0.0),
        now=np.asarray([rng.uniform(0.0, 900.0)]),
        metrics=rng.uniform(0.0, 10.0, 9),
        worker=worker,
        ram_task=rng.uniform(0.5, 8.0, K),
        out_bytes=rng.uniform(0.1, 40.0, (K, F)),
        nfrag=nfrag,
        chain=rng.rand(K) < 0.5,
        placed=placed,
        sla=rng.uniform(5.0, 60.0, K),
        arrival=rng.uniform(0.0, 600.0, K),
        acc_t=rng.uniform(0.5, 1.0, K),
        wait_s=rng.uniform(0.0, 10.0, K),
        decision=rng.randint(0, 3, K).astype(np.int32),
        bw_mult=rng.uniform(0.3, 1.0, N),
        mips=rng.uniform(2e3, 8e3, N),
        cap=rng.uniform(4.0, 16.0, N),
        net_bw=rng.uniform(100.0, 1000.0, N),
    )
    from repro.kernels.edge_substep import CARRY_NAMES, STATIC_NAMES
    return [args[k] for k in CARRY_NAMES + STATIC_NAMES]


@pytest.mark.parametrize("seed", range(8))
def test_pallas_matches_ref_fuzzed(seed):
    import jax

    from repro.kernels.edge_substep import OUT_NAMES, edge_substep
    from repro.kernels.ref import edge_substep_ref
    args = _rand_inputs(np.random.RandomState(seed))
    with jax.enable_x64(True):  # f64 carries, the driver's execution regime
        outs_p = edge_substep(*args, **KW, interpret=True)
        outs_r = edge_substep_ref(*args, **KW)
    for name, p, r in zip(OUT_NAMES, outs_p, outs_r):
        p, r = np.asarray(p), np.asarray(r)
        assert p.shape == r.shape and p.dtype == r.dtype, name
        if p.dtype == bool:
            assert (p == r).all(), name
        else:
            np.testing.assert_allclose(p, r, rtol=1e-12, atol=0,
                                       err_msg=name)


def test_pallas_under_vmap_matches_per_row():
    """The grid driver runs the kernel under vmap — the batching rule
    must agree with stacking per-row calls."""
    import jax

    from repro.kernels.edge_substep import edge_substep

    rows = [_rand_inputs(np.random.RandomState(100 + i)) for i in range(3)]
    stacked = [np.stack(cols) for cols in zip(*rows)]
    f = lambda *a: edge_substep(*a, **KW, interpret=True)
    with jax.enable_x64(True):
        outs_v = jax.vmap(f)(*stacked)
        for i, row in enumerate(rows):
            outs_1 = f(*row)
            for v, o in zip(outs_v, outs_1):
                np.testing.assert_allclose(np.asarray(v)[i],
                                           np.asarray(o), rtol=1e-12,
                                           atol=0)


def test_driver_impls_agree_end_to_end():
    """substep_impl="xla" / "ref" / "pallas" must produce the same trace
    summaries through the real driver (dense vs incremental census is
    exact: the counts are small integers)."""
    from repro.env.jaxsim import compile_trace, resolve, run_trace_engine
    r = resolve("mc")
    tr = compile_trace(r.decider, lam=5.0, seed=3, n_intervals=4,
                       substeps=4)
    outs = {impl: run_trace_engine(r.engine, tr, r.state(tr.seed),
                                   substep_impl=impl)
            for impl in ("xla", "ref", "pallas")}
    base = outs["xla"]
    for impl in ("ref", "pallas"):
        for k in base:
            assert np.isclose(base[k], outs[impl][k], rtol=1e-9,
                              atol=1e-12), \
                f"{impl} {k}: xla={base[k]!r} {impl}={outs[impl][k]!r}"


def test_substep_impl_env_var(monkeypatch):
    """JAXSIM_SUBSTEP_IMPL is the process-wide default; an explicit
    argument wins over it; junk values raise."""
    from repro.env.jaxsim.driver import _resolve_substep_impl
    monkeypatch.delenv("JAXSIM_SUBSTEP_IMPL", raising=False)
    assert _resolve_substep_impl(None) == "xla"
    monkeypatch.setenv("JAXSIM_SUBSTEP_IMPL", "pallas")
    assert _resolve_substep_impl(None) == "pallas"
    assert _resolve_substep_impl("ref") == "ref"
    with pytest.raises(ValueError):
        _resolve_substep_impl("vulkan")
    monkeypatch.setenv("JAXSIM_SUBSTEP_IMPL", "vulkan")
    with pytest.raises(ValueError):
        _resolve_substep_impl(None)
