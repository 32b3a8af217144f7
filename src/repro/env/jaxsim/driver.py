"""Jitted trace/grid drivers: one compiled call per (seed × λ) grid.

ONE interval program for every policy.  ``_trace_program(engine, ...)``
threads the unified carry ``(state, acc, engine_state)`` through a
``lax.fori_loop`` over intervals and calls the engine's
``decide / place / feedback`` hooks around the shared physics
(``repro.env.jaxsim.engines`` documents the protocol and implements the
zoo: static, MAB deploy ± DASO/GOBI, full §6.3 training, Gillis).  One
runner cache, one static key, one chunk dispatcher and one summary path
serve every engine — adding a policy adds an engine + a host parity
oracle, never another driver copy.

The driver names no policy: ``run_trace_engine`` / ``run_grid_engine``
run whatever engine and starting state they are handed, and
``policies.resolve`` turns a policy name into both.

Executables are cached on ``(engine, T, A, K, F, n, substeps,
interval_s, swap)`` — engines are frozen hashable dataclasses — so a
whole λ-sweep with common shapes compiles exactly once per engine.
Everything runs under ``jax.enable_x64(True)`` so the float64
elementwise physics matches ``env/soa.py``.  That scope is a
thread-local context: the global x64 flag is left untouched for the rest
of the process (models/optimizers stay float32), and each dispatch
thread enters it itself.
"""
from __future__ import annotations

import os
import warnings
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.env.cluster import Cluster, make_cluster
from repro.env.jaxsim import kernels
from repro.env.jaxsim.arrays import (ClusterArrays, default_capacity,
                                     stack_traces)
from repro.env.metrics import TELEMETRY_COLS, series_percentiles
from repro.obs import get_ledger

#: LRU-bounded executable cache.  A long-lived serving process sweeps
#: many configs over its lifetime; an unbounded dict of compiled
#: executables is a real leak there, so insertion beyond the cap evicts
#: the least-recently-used runner (XLA frees the executable once the
#: last reference drops).
_RUNNER_CACHE: "OrderedDict" = OrderedDict()
_CACHE_LIMIT = [max(1, int(os.environ.get("JAXSIM_RUNNER_CACHE_MAX",
                                          "64")))]
_EVICTED = set()          # evicted keys, to flag eviction-induced recompiles

#: runner-cache observability: misses were silent recompiles before —
#: every ``_get_runner``/``_get_sharded_runner`` consult now counts, and
#: an engine config that compiles under a SECOND distinct static key
#: logs a ledger warning (the classic symptom of an accidentally
#: shape-polymorphic sweep).
_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}
_CACHE_KEYS = {}          # static-key repr -> compile count
_ENGINE_KEYS = {}         # engine repr -> set of distinct compiled keys


def cache_stats() -> dict:
    """Snapshot of the runner-cache counters: hits/misses/evictions,
    resident executable count, the LRU cap, and the per-key static-key
    reprs with their compile counts (feed it to
    ``RunLedger.add_cache_stats``)."""
    return {"hits": _CACHE_STATS["hits"], "misses": _CACHE_STATS["misses"],
            "evictions": _CACHE_STATS["evictions"],
            "size": len(_RUNNER_CACHE), "limit": _CACHE_LIMIT[0],
            "keys": dict(_CACHE_KEYS)}


def set_cache_limit(limit: int) -> int:
    """Set the LRU cap of the runner cache (also settable process-wide
    via ``JAXSIM_RUNNER_CACHE_MAX``); returns the previous cap.  Shrinking
    below the resident count evicts immediately."""
    if limit < 1:
        raise ValueError(f"cache limit must be >= 1, got {limit}")
    old = _CACHE_LIMIT[0]
    _CACHE_LIMIT[0] = int(limit)
    _evict_to_limit()
    return old


def clear_cache() -> None:
    """Drop every cached executable and reset the cache counters — the
    long-lived-process escape hatch (a serving loop that has moved on to
    a new config can release the old executables' memory at once)."""
    _RUNNER_CACHE.clear()
    _EVICTED.clear()
    _CACHE_KEYS.clear()
    _ENGINE_KEYS.clear()
    _CACHE_STATS.update(hits=0, misses=0, evictions=0)


def _evict_to_limit():
    while len(_RUNNER_CACHE) > _CACHE_LIMIT[0]:
        ck, _ = _RUNNER_CACHE.popitem(last=False)
        _EVICTED.add(ck)
        _CACHE_STATS["evictions"] += 1
        get_ledger().count("runner_cache.eviction")


def _cache_put(ck, runner):
    _RUNNER_CACHE[ck] = runner
    _evict_to_limit()


def _cache_get(ck):
    _RUNNER_CACHE.move_to_end(ck)      # LRU touch
    return _RUNNER_CACHE[ck]


def _note_cache(ck, hit: bool):
    if hit:
        _CACHE_STATS["hits"] += 1
        return
    _CACHE_STATS["misses"] += 1
    led = get_ledger()
    kr = repr(ck)
    _CACHE_KEYS[kr] = _CACHE_KEYS.get(kr, 0) + 1
    er = repr(ck[0])
    keys = _ENGINE_KEYS.setdefault(er, set())
    keys.add(kr)
    if ck in _EVICTED:
        _EVICTED.discard(ck)
        led.warn("eviction-induced recompile: this static key was evicted "
                 f"by the LRU cap ({_CACHE_LIMIT[0]}) and is compiling "
                 "again — raise the cap (set_cache_limit / "
                 "JAXSIM_RUNNER_CACHE_MAX) if this config is hot",
                 engine=er, limit=_CACHE_LIMIT[0])
    elif len(keys) > 1:
        led.warn(f"engine config recompiled: {len(keys)} distinct static "
                 f"keys compiled for {er} — check for shape-polymorphic "
                 "sweeps (T/A/K/F/n or dispatch knobs varying per call)",
                 engine=er, n_keys=len(keys))


_DONATION_OK = {}         # backend name -> probed donation support


def _donation_ok() -> bool:
    """Probe (once per backend) whether jit buffer donation actually
    releases the argument buffer.  XLA:CPU gained donation support only
    recently, so instead of hard-coding a backend list the driver donates
    wherever the probe shows the buffer really dies — and keeps the old
    no-donation behavior (plus no spurious warnings) everywhere else."""
    backend = jax.default_backend()
    ok = _DONATION_OK.get(backend)
    if ok is None:
        probe = jnp.zeros((8,), jnp.float32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            jax.block_until_ready(
                jax.jit(lambda v: v + 1.0, donate_argnums=0)(probe))
        ok = _DONATION_OK[backend] = bool(probe.is_deleted())
    return ok

#: layout of the packed per-substep metric accumulator (one dot per
#: substep): [n_fin, Σresp, n_viol, Σacc, Σreward, Σwait, fin_dec·3]
METRIC_COLS = ("n_fin", "sum_resp", "n_viol", "sum_acc", "sum_reward",
               "sum_wait", "fin_layer", "fin_semantic", "fin_compressed")


def _init_acc(n: int):
    f8 = jnp.float64
    return {
        "now": jnp.zeros((), f8),
        "energy": jnp.zeros((), f8),
        "pwt": jnp.zeros((n,), f8),
        "metrics": jnp.zeros((len(METRIC_COLS),), f8),
    }


def _resolve_substep_impl(substep_impl):
    """Resolve the substep execution strategy: an explicit argument wins,
    then the ``JAXSIM_SUBSTEP_IMPL`` environment variable (how the CI
    Pallas leg flips the whole suite), then the byte-stable ``"xla"``
    default.  ``"pallas"`` runs in interpret mode and so only on the
    CPU: anywhere else it raises rather than quietly interpreting."""
    impl = substep_impl or os.environ.get("JAXSIM_SUBSTEP_IMPL", "xla")
    if impl not in ("xla", "pallas", "ref"):
        raise ValueError(f"substep_impl={impl!r} "
                         "(want 'xla', 'pallas' or 'ref')")
    if impl == "pallas" and jax.default_backend() != "cpu":
        raise ValueError(
            f"substep_impl='pallas' is refused on the "
            f"{jax.default_backend()!r} backend: the edge-substep kernel "
            "computes in float64, which Mosaic does not lower, so it "
            "cannot compile for this device (use 'xla')")
    return impl


def _interval_physics(state, acc, bw_row, cl, substeps, dt, interval_s,
                      swap_slowdown, substep_impl):
    """Shared interval tail for every engine: waiting-time accounting,
    the substep physics, and the utilization → power → energy
    accumulation.  Engines differ only in their decide/place/feedback
    hooks around this.  Also returns the per-worker interval utilization
    (the AEC ingredient of the DASO training target, eq. 10)."""
    state = dict(state)
    state["wait_s"] = state["wait_s"] + jnp.where(
        state["alive"] & ~state["placed"], interval_s, 0.0)
    state, acc, busy = kernels.run_substeps(
        state, acc, bw_row, cl, substeps=substeps, dt=dt,
        swap_slowdown=swap_slowdown, impl=substep_impl)
    util = busy / interval_s
    power = cl["power_idle"] + (cl["power_peak"] - cl["power_idle"]) \
        * jnp.clip(util, 0.0, 1.0)
    acc = dict(acc)
    acc["energy"] = acc["energy"] + jnp.sum(power) * interval_s
    return state, acc, util


def _telemetry_base_row(state, acc, m0, e0, d0, util, fin):
    """One float64 row of the per-interval telemetry series (the
    ``metrics.TELEMETRY_COLS`` layout): interval deltas of the packed
    metric dot / drop counter / energy, finisher response & wait
    extremes, the per-worker utilization summary, and end-of-interval
    slot occupancy.  ``m0``/``e0``/``d0`` are the interval-entry
    snapshots the deltas subtract."""
    f8 = jnp.float64
    md = (acc["metrics"] - m0).astype(f8)
    have = md[0] > 0
    inf = jnp.asarray(jnp.inf, f8)
    resp, wait = state["resp"], state["wait_s"]
    rmin = jnp.where(have, jnp.min(jnp.where(fin, resp, inf)), 0.0)
    rmax = jnp.where(have, jnp.max(jnp.where(fin, resp, -inf)), 0.0)
    wmin = jnp.where(have, jnp.min(jnp.where(fin, wait, inf)), 0.0)
    wmax = jnp.where(have, jnp.max(jnp.where(fin, wait, -inf)), 0.0)
    extras = jnp.stack([
        (state["dropped"] - d0).astype(f8),
        (acc["energy"] - e0).astype(f8),
        rmin, rmax, wmin, wmax,
        jnp.mean(util).astype(f8), jnp.max(util).astype(f8),
        jnp.sum(state["alive"]).astype(f8),
    ])
    return jnp.concatenate([md, extras])


#: the phases of one interval, each a stable ``jax.named_scope`` that
#: names every operation of its hook in the compiled program's metadata
#: (``op_name``) and so in a device profile of it
PHASES = ("decide", "admit", "place", "apply", "substeps", "feedback",
          "telemetry")


def _interval(engine, trace, cl, t, state, acc, es, substeps, dt,
              interval_s, swap_slowdown, substep_impl):
    """THE hook sequence of one interval, shared by every program: the
    engine's decide, admission, the engine's place, request apply (with
    its RAM repair), the physics and the engine's feedback, each under
    its phase scope.  Returns ``(state, acc, es, util, fin)``, ``fin``
    the tasks that finished in this interval."""
    with jax.named_scope("decide"):
        arr, es = engine.decide(es, trace, t)
    with jax.named_scope("admit"):
        state = kernels.admit(state, arr)
    with jax.named_scope("place"):
        req, es, aux = engine.place(es, state, cl, trace, t, interval_s)
    with jax.named_scope("apply"):
        state = kernels.apply_requests(state, cl, req)
    prev_done = state["task_done"]
    with jax.named_scope("substeps"):
        state, acc, util = _interval_physics(
            state, acc, trace["bw_mult"][t], cl, substeps, dt, interval_s,
            swap_slowdown, substep_impl)
    with jax.named_scope("feedback"):
        fin = state["task_done"] & ~prev_done
        es = engine.feedback(es, state, fin, util, aux, t, interval_s)
        state["alive"] = state["alive"] & ~state["task_done"]
    return state, acc, es, util, fin


def _interval_tel(engine, trace, cl, t, row_t, carry, *args):
    """``_interval`` plus the telemetry row of the interval, written to
    row ``row_t`` of the carried ``(T, C)`` series: the interval-entry
    snapshots the deltas subtract, then the base
    ``metrics.TELEMETRY_COLS`` columns and the engine's
    ``telemetry_cols()``."""
    state, acc, es, series = carry
    m0, e0, d0 = acc["metrics"], acc["energy"], state["dropped"]
    state, acc, es, util, fin = _interval(engine, trace, cl, t, state, acc,
                                          es, *args)
    with jax.named_scope("telemetry"):
        row = _telemetry_base_row(state, acc, m0, e0, d0, util, fin)
        erow = engine.telemetry_row(es)
        if erow is not None:
            row = jnp.concatenate([row, erow.astype(jnp.float64)])
        series = lax.dynamic_update_slice(series, row[None, :], (row_t, 0))
    return state, acc, es, series


def _trace_program(engine, T, A, K, F, n, substeps, interval_s,
                   swap_slowdown, substep_impl="xla", telemetry="summary"):
    """THE interval program: one carry layout, one hook sequence
    (``_interval``), every policy.  ``engine`` is compile-time static
    (part of the cache key); its dynamic state rides the carry as
    ``es``.

    ``telemetry="interval"`` appends a preallocated ``(T, C)`` float64
    series to the fori_loop carry and writes one row per interval via
    ``dynamic_update_slice`` (``_interval_tel``).  The default
    ``"summary"`` path computes no row and takes no interval-entry
    snapshot, which is what keeps the golden fixtures valid
    unregenerated."""
    dt = interval_s / substeps
    tel = telemetry == "interval"
    if tel:
        n_cols = len(TELEMETRY_COLS) + len(tuple(engine.telemetry_cols()))
    hp = (substeps, dt, interval_s, swap_slowdown, substep_impl)

    def run_one(trace, cl, es0):
        state = kernels.init_state(K, F, n)
        acc = _init_acc(n)

        def interval(t, carry):
            return _interval(engine, trace, cl, t, *carry, *hp)[:3]

        def interval_tel(t, carry):
            return _interval_tel(engine, trace, cl, t, t, carry, *hp)

        if tel:
            series0 = jnp.zeros((T, n_cols), jnp.float64)
            state, acc, es, series = lax.fori_loop(
                0, T, interval_tel, (state, acc, es0, series0))
        else:
            state, acc, es = lax.fori_loop(0, T, interval, (state, acc, es0))
        out = {"metrics": acc["metrics"], "energy": acc["energy"],
               "pwt": acc["pwt"], "dropped": state["dropped"]}
        if tel:
            out["telemetry"] = series
        out.update(engine.outputs(es))
        return out

    return run_one


def _static_key(engine, trace_leaves, K, n, substeps, interval_s,
                swap_slowdown, substep_impl, telemetry="summary"):
    """The runner-cache / compile key.  Shape-bearing dims are read off
    the fragment leaf (``vinstr`` for dual traces, ``instr`` for static
    ones); the engine itself carries every policy-side static.  The
    telemetry knob is compile-time static too: it changes the carry
    layout, so each mode is its own executable."""
    dual = "vinstr" in trace_leaves
    shp = trace_leaves["vinstr" if dual else "instr"].shape
    T, A, F = (shp[-4], shp[-3], shp[-1]) if dual else \
        (shp[-3], shp[-2], shp[-1])
    return (engine, T, A, K, F, n, substeps, interval_s, swap_slowdown,
            substep_impl, telemetry)


def _get_runner(key, batched: bool):
    ck = key + (batched,)
    hit = ck in _RUNNER_CACHE
    _note_cache(ck, hit)
    if not hit:
        engine = key[0]
        with get_ledger().span("compile", engine=engine.name,
                               batched=batched):
            prog = _trace_program(*key)
            if batched:
                prog = jax.vmap(prog,
                                in_axes=(0, None, engine.batch_axes()))
            _cache_put(ck, jax.jit(prog))
    return _cache_get(ck)


# ------------------------------------------------ streaming chunk program


class _ShiftedLeaf:
    """A chunk-local tape leaf indexed by the ABSOLUTE interval index.

    The streaming driver feeds the interval program fixed-size chunk
    tapes whose row 0 is absolute interval ``t0``, but engine hooks must
    see the global ``t`` — their ``fold_in(key, t)`` decision bits have
    to match the one-shot episode bit for bit.  Wrapping every leaf so
    ``leaf[t]`` reads row ``t - t0`` keeps the engine protocol unchanged
    (``trace[k][t]`` everywhere) while the tape stays chunk-sized."""

    __slots__ = ("arr", "t0")

    def __init__(self, arr, t0):
        self.arr = arr
        self.t0 = t0

    def __getitem__(self, t):
        return self.arr[t - self.t0]


def _stream_program(engine, T, A, K, F, n, substeps, interval_s,
                    swap_slowdown, substep_impl="xla"):
    """Carry-re-entrant chunk program for the streaming serve driver:
    ``_trace_program``'s telemetry body (``_interval_tel``), but the
    carry ``(state, acc, es)`` enters as an ARGUMENT and leaves as a
    result, so consecutive ``chunk_intervals``-sized calls continue one
    endless episode (``T`` here is the chunk length — one compile per
    chunk shape).  ``t0`` is the chunk's absolute start interval, traced
    (not static) so every chunk shares the executable; the fori_loop
    runs over absolute indices and tape rows are shifted back via
    ``_ShiftedLeaf``.  The per-interval telemetry series is always on —
    it is the substrate of the serving layer's rolling metrics."""
    dt = interval_s / substeps
    n_cols = len(TELEMETRY_COLS) + len(tuple(engine.telemetry_cols()))
    hp = (substeps, dt, interval_s, swap_slowdown, substep_impl)

    def run_chunk(trace, cl, carry, t0):
        tr = {k: _ShiftedLeaf(v, t0) for k, v in trace.items()}

        def interval_tel(t, c):
            return _interval_tel(engine, tr, cl, t, t - t0, c, *hp)

        state, acc, es = carry
        series0 = jnp.zeros((T, n_cols), jnp.float64)
        state, acc, es, series = lax.fori_loop(
            t0, t0 + T, interval_tel, (state, acc, es, series0))
        return (state, acc, es), series

    return run_chunk


def _get_stream_runner(key):
    """Compile-cached streaming chunk runner.  ``key`` is a
    ``_static_key(..., telemetry="stream")`` tuple — ``T`` in it is the
    chunk length, so a steady stream of equal-size chunks hits one
    executable forever.  The chunk-to-chunk carry (argument 2) is
    donated wherever the backend supports it: the slot/accumulator/
    engine-state arrays are updated in place instead of holding two
    copies across a 16k-interval soak."""
    hit = key in _RUNNER_CACHE
    _note_cache(key, hit)
    if not hit:
        engine = key[0]
        with get_ledger().span("compile", engine=engine.name, stream=True):
            prog = _stream_program(*key[:-1])
            donate = (2,) if _donation_ok() else ()
            _cache_put(key, jax.jit(prog, donate_argnums=donate))
    return _cache_get(key)


def _check_telemetry(engine, telemetry):
    """Validate the knob and resolve the full column tuple (base +
    engine learning-signal columns); None in summary mode."""
    if telemetry not in ("summary", "interval"):
        raise ValueError(f"telemetry={telemetry!r} "
                         "(want 'summary' or 'interval')")
    if telemetry == "summary":
        return None
    return tuple(TELEMETRY_COLS) + tuple(engine.telemetry_cols())


def _summarize(out, interval_s: float, n_intervals: int,
               cost_hr_total: float, telemetry_cols=None) -> dict:
    """Assemble the §6.4 summary dict (``MetricsAccumulator.summary``
    schema) from kernel accumulators.  With ``telemetry_cols`` (interval
    mode) the summary additionally carries the sliced per-interval
    series under ``"telemetry"`` plus host-side percentile estimates
    from it (see ``metrics.series_percentiles`` for the binning error
    bound reported as ``percentile_err_s``)."""
    m = dict(zip(METRIC_COLS, np.asarray(out["metrics"], np.float64)))
    n_fin = m["n_fin"]
    d = max(n_fin, 1.0)
    mean_resp = m["sum_resp"] / d
    mean_wait = m["sum_wait"] / d
    pwt = np.asarray(out["pwt"], np.float64)
    tot = pwt.sum()
    fair = float(tot ** 2 / (len(pwt) * np.sum(pwt ** 2) + 1e-12)) \
        if tot > 0 else 1.0
    cost = cost_hr_total * interval_s / 3600.0 * n_intervals
    s = {
        "accuracy": float(m["sum_acc"] / d),
        "sla_violations": float(m["n_viol"] / d),
        "reward": float(m["sum_reward"] / d),
        "response_intervals": float(mean_resp / interval_s),
        "wait_intervals": float(mean_wait / interval_s),
        "exec_intervals": float((mean_resp - mean_wait) / interval_s),
        "energy_mwhr": float(out["energy"]) / 3.6e9,
        "fairness": fair,
        "cost_per_container": float(cost / max(1, int(tot))),
        "layer_fraction": float(m["fin_layer"] / d),
        "tasks_completed": int(n_fin),
        "dropped_tasks": int(out["dropped"]),
    }
    if telemetry_cols is not None:
        # slice to the valid interval cells (padded grid rows were
        # already dropped by the caller's row loop)
        series = np.asarray(out["telemetry"], np.float64)[:n_intervals]
        s.update(series_percentiles(series, telemetry_cols))
        s["telemetry"] = {"cols": list(telemetry_cols), "series": series}
    return s


def _run_chunks(prepped):
    """Execute (runner, stacked-leaves) chunks, one thread per chunk:
    jitted XLA executions release the GIL, so chunks run on separate
    cores — parallelism the GIL-bound host interval loop cannot have.
    Results are independent per trace, so chunking changes nothing
    numerically."""
    led = get_ledger()
    # the span stack is thread-local, so pool threads attach their chunk
    # spans to the dispatch span via an explicit parent id
    parent = led.current_span()

    def run_chunk(irl):
        i, rl = irl
        with led.span("chunk", parent=parent, idx=i,
                      n_traces=int(rl[1]["valid"].shape[0])):
            with jax.enable_x64(True):   # config contexts are thread-local
                return rl[0](rl[1])

    if len(prepped) == 1:
        outs = [run_chunk((0, prepped[0]))]
    else:
        with ThreadPoolExecutor(max_workers=len(prepped)) as ex:
            outs = list(ex.map(run_chunk, enumerate(prepped)))
    return [jax.tree_util.tree_map(np.asarray, o) for o in outs]


def _check_grid_homogeneous(traces):
    """Every grid cell must share the compile-time statics; the error
    names each offending cell so a mixed sweep is debuggable from the
    message alone."""
    sig = lambda t: (t.n_intervals, t.interval_s, t.substeps,
                     getattr(t, "variants", None))
    s0 = sig(traces[0])
    bad = [(i, sig(t)) for i, t in enumerate(traces) if sig(t) != s0]
    if bad:
        lines = "; ".join(
            f"trace[{i}] has (n_intervals, interval_s, substeps, "
            f"variants)={s}" for i, s in bad)
        raise ValueError(
            "grid cells must share n_intervals/interval_s/substeps/"
            "variants (shapes and decision codes are compile-time "
            f"static): trace[0] has {s0}, but {lines}")


def _grid_chunks(traces, threads):
    """Validate grid homogeneity and split it into thread chunks."""
    # checked here, not just inside per-chunk stack_traces: chunking
    # could otherwise split mismatched traces into separate chunks
    # and silently run them under traces[0]'s compiled physics (or,
    # for variants, the wrong decision codes)
    _check_grid_homogeneous(traces)
    if threads is None:
        threads = max(1, min(os.cpu_count() or 1, len(traces) // 2))
    threads = max(1, min(threads, len(traces)))
    per = -(-len(traces) // threads)
    return [list(traces[i:i + per]) for i in range(0, len(traces), per)]


# ------------------------------------------------ sharded grid dispatch


def _es_shard_spec(axes):
    """shard_map spec prefix for the engine-state pytree, derived from
    the same ``batch_axes()`` prefix vmap consumes: per-cell leaves
    (axis 0) shard over the grid mesh axis, shared starting state
    replicates."""
    from jax.sharding import PartitionSpec as P
    if axes is None:
        return P()
    if axes == 0:
        return P("grid")
    if isinstance(axes, dict):
        return {k: _es_shard_spec(v) for k, v in axes.items()}
    raise ValueError(f"unsupported engine batch axis {axes!r}")


def _sharded_program(key, mesh, donate: bool):
    """``jit(shard_map(vmap(program)))`` over the 1-D grid mesh: every
    device runs the vmapped interval program on its contiguous slice of
    the stacked-trace axis.  Trace leaves and per-cell engine-state
    leaves shard over ``"grid"``; cluster rows and shared engine state
    replicate.  ``donate`` donates the trace-leaf and engine-state
    arguments."""
    from jax.sharding import PartitionSpec as P
    engine = key[0]
    prog = jax.vmap(_trace_program(*key),
                    in_axes=(0, None, engine.batch_axes()))
    # cells are independent, so there is nothing cross-device to
    # validate: skip the varying-manual-axes check, which the interval
    # program's fori/while loops do not pass
    sharded = jax.shard_map(
        prog, mesh=mesh,
        in_specs=(P("grid"), P(), _es_shard_spec(engine.batch_axes())),
        out_specs=P("grid"), check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 2) if donate else ())


def _get_sharded_runner(key, mesh):
    """Compile-cached ``_sharded_program``, donating wherever the
    backend's donation probe passes (``_donation_ok`` — accelerators
    always, XLA:CPU on the jaxlib builds that actually support
    donation)."""
    d = int(np.prod(mesh.devices.shape))
    ck = key + ("smap", d)
    hit = ck in _RUNNER_CACHE
    _note_cache(ck, hit)
    if not hit:
        with get_ledger().span("compile", engine=key[0].name,
                               sharded=True, mesh=d):
            _cache_put(ck, _sharded_program(key, mesh, _donation_ok()))
    return _cache_get(ck)


def _run_grid_sharded(engine, traces, es_builder, cl, cld, K,
                      swap_slowdown, substep_impl, devices,
                      telemetry="summary"):
    """One shard_map call over the whole grid (no thread chunking).

    The grid is padded up to a multiple of the mesh size by replicating
    the last trace and masking its arrivals invalid — dead cells admit
    no tasks, so their interval program runs an empty system and their
    output rows are discarded.  Returns the stacked (padded) output
    tree as NumPy; the caller slices the first ``len(traces)`` rows."""
    from repro.launch.mesh import make_grid_mesh
    mesh = make_grid_mesh(devices)
    d = int(np.prod(mesh.devices.shape))
    t0, G = traces[0], len(traces)
    pad = (-G) % d
    padded = list(traces) + [traces[-1]] * pad
    A = max(t.max_arrivals for t in traces)
    F = max(t.max_frags for t in traces)
    leaves = {k: jnp.asarray(v)
              for k, v in stack_traces(padded, max_arrivals=A,
                                       max_frags=F).items()}
    if pad:
        leaves["valid"] = leaves["valid"].at[G:].set(False)
    # the sharded runner donates the engine-state argument; es_builder
    # may hand back device arrays the caller still holds (shared
    # pretrained theta, carried MAB scalars), so copy instead of
    # aliasing — donation must only consume buffers this call owns
    es0 = jax.tree_util.tree_map(lambda v: jnp.array(v, copy=True),
                                 es_builder([t.seed for t in padded]))
    key = _static_key(engine, leaves, K, cl.n, t0.substeps, t0.interval_s,
                      swap_slowdown, substep_impl, telemetry)
    runner = _get_sharded_runner(key, mesh)
    led = get_ledger()
    with led.span("dispatch", engine=engine.name, sharded=True,
                  n_traces=G, mesh=d):
        out = runner(leaves, cld, es0)
        # where the (padded) cells' outputs live: one counter per device
        for shard in out["metrics"].addressable_shards:
            led.count(f"grid.cells_on_device.{shard.device.id}",
                      int(shard.data.shape[0]))
        return jax.tree_util.tree_map(np.asarray, out)


# ------------------------------------------------- generic engine runners


def _check_variants(engine, traces):
    """A dual trace's V axis must realize the decision codes the engine
    decides between (``engine.variants``) — an MAB trace fed to the
    Gillis engine (or vice versa) would mislabel fragments as the wrong
    split."""
    if engine.variants is None:
        return
    for t in traces:
        got = tuple(getattr(t, "variants", (0, 1)))
        if got != tuple(engine.variants):
            raise ValueError(
                f"trace realizes variants {got}, engine needs "
                f"{tuple(engine.variants)} (compile_trace_dual(variants=...))")


def run_trace_engine(engine, trace, es0, cluster: Optional[Cluster] = None,
                     max_active: Optional[int] = None,
                     swap_slowdown: float = 0.5,
                     substep_impl: Optional[str] = None,
                     telemetry: str = "summary") -> dict:
    """Run one compiled trace through the unified interval program under
    ``engine``, starting its carried state from ``es0``.

    ``telemetry="interval"`` additionally records the per-interval
    telemetry series in the carry and attaches it (plus percentile
    estimates) to the summary; ``"summary"`` compiles the exact program
    this driver has always run."""
    _check_variants(engine, [trace])
    tcols = _check_telemetry(engine, telemetry)
    led = get_ledger()
    cluster = cluster or make_cluster()
    cl = ClusterArrays.from_cluster(cluster)
    K = max_active or default_capacity([trace])
    impl = _resolve_substep_impl(substep_impl)
    with jax.enable_x64(True):
        leaves = {k: jnp.asarray(v) for k, v in trace.kernel_dict().items()}
        cld = {k: jnp.asarray(v) for k, v in cl.as_dict().items()}
        es0 = jax.tree_util.tree_map(jnp.asarray, es0)
        key = _static_key(engine, leaves, K, cl.n, trace.substeps,
                          trace.interval_s, swap_slowdown, impl, telemetry)
        runner = _get_runner(key, batched=False)
        with led.span("dispatch", engine=engine.name, n_traces=1,
                      telemetry=telemetry):
            out = jax.tree_util.tree_map(np.asarray,
                                         runner(leaves, cld, es0))
    with led.span("summarize", engine=engine.name, n_traces=1):
        return engine.summarize(out, _summarize(
            out, trace.interval_s, trace.n_intervals,
            float(cl.cost_hr.sum()), telemetry_cols=tcols))


def run_grid_engine(engine, traces, es_builder: Callable,
                    cluster: Optional[Cluster] = None,
                    max_active: Optional[int] = None,
                    swap_slowdown: float = 0.5,
                    threads: Optional[int] = None,
                    devices=None,
                    substep_impl: Optional[str] = None,
                    telemetry: str = "summary") -> list:
    """Run a whole grid of compiled traces through the jitted vmapped
    engine program; returns one summary dict per trace (same order).

    ``es_builder(seeds)`` produces the engine-state pytree for one trace
    chunk from its traces' seeds (shared leaves + any per-cell leaves
    like PRNG keys, marked by ``engine.batch_axes()``) —
    ``policies.resolve(...).state``; it runs inside the driver's
    ``enable_x64`` scope so float64 state construction is safe.

    Dispatch is two-mode.  Default (``devices=None``): the grid is split
    into ``threads`` equal vmap chunks dispatched from a thread pool —
    jitted XLA executions release the GIL, so chunks run on separate
    cores; ``threads`` defaults to the core count (capped by the grid
    size); pass 1 to force a single call.  ``devices="auto"`` (or an
    int): one ``shard_map`` call over a 1-D device mesh instead — the
    grid is padded to a mesh multiple with masked dead cells and every
    device runs its contiguous slice (``_run_grid_sharded``).  Results
    are independent per trace, so neither chunking nor sharding changes
    anything numerically.
    """
    _check_variants(engine, traces)
    tcols = _check_telemetry(engine, telemetry)
    led = get_ledger()
    cluster = cluster or make_cluster()
    cl = ClusterArrays.from_cluster(cluster)
    K = max_active or default_capacity(traces)
    t0 = traces[0]
    impl = _resolve_substep_impl(substep_impl)
    if devices is not None:
        _check_grid_homogeneous(traces)
        with jax.enable_x64(True):
            cld = {k: jnp.asarray(v) for k, v in cl.as_dict().items()}
            out = _run_grid_sharded(engine, traces, es_builder, cl, cld,
                                    K, swap_slowdown, impl, devices,
                                    telemetry)
        # one padded output tree; the summary loop below walks only the
        # first len(traces) rows, dropping the dead padding cells
        chunks, outs = [list(traces)], [out]
    else:
        chunks = _grid_chunks(traces, threads)
        with jax.enable_x64(True):
            cld = {k: jnp.asarray(v) for k, v in cl.as_dict().items()}
            A = max(t.max_arrivals for t in traces)
            F = max(t.max_frags for t in traces)

            def prep(chunk):
                leaves = {k: jnp.asarray(v)
                          for k, v in stack_traces(chunk, max_arrivals=A,
                                                   max_frags=F).items()}
                es0 = jax.tree_util.tree_map(
                    jnp.asarray, es_builder([t.seed for t in chunk]))
                key = _static_key(engine, leaves, K, cl.n, t0.substeps,
                                  t0.interval_s, swap_slowdown, impl,
                                  telemetry)
                runner = _get_runner(key, batched=True)
                # bind the per-chunk engine state so _run_chunks' (runner,
                # leaves) calling convention is engine-agnostic
                return (lambda l, r_=runner, e_=es0: r_(l, cld, e_)), leaves

            # compile (cached) before parallel dispatch so threads only
            # race on execution, never on tracing
            prepped = [prep(c) for c in chunks]
            with led.span("dispatch", engine=engine.name,
                          n_traces=len(traces), n_chunks=len(chunks),
                          telemetry=telemetry):
                outs = _run_chunks(prepped)
    cost_total = float(cl.cost_hr.sum())
    results = []
    with led.span("summarize", engine=engine.name, n_traces=len(traces)):
        for chunk, out in zip(chunks, outs):
            for i, _ in enumerate(chunk):
                row = jax.tree_util.tree_map(
                    lambda v: v[i] if np.ndim(v) > 0 else v, out)
                results.append(engine.summarize(row, _summarize(
                    row, t0.interval_s, t0.n_intervals, cost_total,
                    telemetry_cols=tcols)))
    return results
