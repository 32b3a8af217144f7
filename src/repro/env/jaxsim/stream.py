"""Streaming serve driver: continuous arrivals through chunked,
carry-re-entrant interval programs.

Everything else in ``repro.env.jaxsim`` runs fixed-Γ episodes compiled
up front; this module is the always-on serving mode the paper's setting
implies — tasks arrive continuously and the policy engine must keep
deciding and placing under a live deadline stream:

  * a host **feeder** (``StreamFeeder``) generates Poisson arrivals
    incrementally — the same ``WorkloadGenerator``/``MobilityModel``
    choreography as ``arrays.compile_trace(_dual)``, but stateful, so
    the sim clock, mobility walk and task ids continue forever — and
    emits fixed-shape *chunk tapes* of ``chunk_intervals`` intervals;
  * the **ring buffer** is the fixed-capacity slot store itself
    (``kernels.init_state``): ``max_active`` device-resident task slots
    that arrivals scatter into and finished tasks vacate.  Admission is
    counted-not-silent twice over: arrivals beyond the tape's
    ``max_arrivals`` rows are dropped host-side and counted
    (``feeder_overflow``), arrivals beyond free slot capacity are
    dropped in-kernel and counted (``state["dropped"]``);
  * the jitted chunk program (``driver._stream_program``) takes the
    carry ``(state, acc, engine_state)`` as an argument and returns it,
    so consecutive chunks continue ONE endless episode.  The chunk
    length is the only new static — one compile per chunk shape — and
    the carry is **donated** chunk-to-chunk wherever the backend
    supports it, so a 16k-interval soak never holds two copies of the
    slot arrays.  The carry never round-trips to host mid-stream
    (``StreamRunner`` asserts the donated previous carry actually died);
  * ``serve`` overlaps the two: a feeder thread fills chunk N+1's
    arrival tape into a small queue while the device executes chunk N
    (double buffering — jitted executions release the GIL), with ledger
    spans for both sides and for each host step of a chunk
    (``StreamRunner.run_chunk``), so the overlap is visible in a
    recording run ledger;
  * rolling metrics (``RollingMetrics``) replace end-of-episode
    summaries: QPS, p50/p99 response, deadline-violation rate and ring
    occupancy over a sliding window of the per-interval telemetry rows
    the chunk program always records (``metrics.TELEMETRY_COLS`` + the
    engine's learning-signal columns).

``replay_stream`` drives the same machinery over a frozen compiled
trace (``arrays.chunk_tapes``); because engine hooks see the absolute
interval index (``driver._ShiftedLeaf``), the chunked replay equals the
one-shot ``run_trace_engine`` episode to float tolerance — the parity
contract ``tests/test_stream.py`` pins at rtol=1e-4.
"""
from __future__ import annotations

import queue
import threading
from collections import deque
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.env.cluster import Cluster, make_cluster
from repro.env.jaxsim import driver, kernels, policies
from repro.env.jaxsim.arrays import (ClusterArrays, chunk_tapes,
                                     default_capacity)
from repro.env.metrics import TELEMETRY_COLS, series_percentiles
from repro.env.mobility import MobilityModel
from repro.env.workload import (APP_PROFILES, WorkloadGenerator,
                                accuracy_from_noise)
from repro.obs import get_ledger


def _default_max_arrivals(lam: float) -> int:
    """Arrival-row capacity of one tape interval: the Poisson mean plus
    an 8-sigma margin, so overflow is astronomically rare at steady
    state yet still *counted* when a burst exceeds it."""
    return int(np.ceil(lam + 8.0 * np.sqrt(max(lam, 1.0)) + 4.0))


def _max_frags(apps) -> int:
    """Fragment-column capacity covering any split decision of the
    selected apps (layer chains and semantic branches both)."""
    return max(max(APP_PROFILES[a].n_frag, APP_PROFILES[a].n_branch, 1)
               for a in apps)


class StreamFeeder:
    """Incremental host-side tape compiler for the serving loop.

    Carries the ``WorkloadGenerator``, ``MobilityModel`` and sim clock
    across calls, so consecutive ``next_chunk`` tapes continue one
    endless workload — the streaming analogue of
    ``arrays.compile_trace`` (pass ``decider``) or ``compile_trace_dual``
    (pass ``variants``), with identical per-task RNG choreography.

    Shapes are fixed for the stream's lifetime (``max_arrivals`` rows
    per interval, ``max_frags`` fragment columns), so every chunk shares
    one compiled executable.  Arrivals beyond ``max_arrivals`` in a
    burst interval are dropped host-side and counted in ``overflow`` —
    never silently truncated; the running totals satisfy
    ``offered == fed + overflow``.
    """

    def __init__(self, lam: float = 6.0, seed: int = 0,
                 interval_s: float = 300.0, substeps: int = 30,
                 cluster: Optional[Cluster] = None, apps=None,
                 max_arrivals: Optional[int] = None,
                 decider=None, variants=None):
        if (decider is None) == (variants is None):
            raise ValueError("pass exactly one of decider= (static "
                             "single-variant tapes) or variants= (dual "
                             "tapes for in-kernel deciders)")
        self.lam = lam
        self.seed = seed
        self.interval_s = interval_s
        self.substeps = substeps
        self.cluster = cluster or make_cluster()
        self.apps = list(apps) if apps is not None else [0, 1, 2]
        self.decider = decider
        self.variants = tuple(variants) if variants is not None else None
        self.max_arrivals = max_arrivals if max_arrivals is not None \
            else _default_max_arrivals(lam)
        self.max_frags = _max_frags(self.apps)
        self.gen = WorkloadGenerator(lam=lam, seed=seed, apps=self.apps)
        self.mob = MobilityModel(self.cluster.n,
                                 self.cluster.mobile_mask(), seed=seed + 1)
        self.now = 0.0
        self.n_intervals = 0
        # counted-not-silent admission ledger (host half)
        self.offered = 0       # tasks the Poisson process generated
        self.fed = 0           # tasks written into tapes
        self.overflow = 0      # tasks dropped for exceeding max_arrivals
        # the placer sees the PREVIOUS interval's mobility latency draw
        # (compile_trace_dual's lat_prev row-0-ones convention, continued
        # across chunks)
        self._lat_prev = np.ones(self.cluster.n, np.float64)

    # ------------------------------------------------------------ tapes

    def _arrivals(self):
        """One interval's admitted tasks, with overflow counted."""
        tasks = self.gen.arrivals(self.now)
        self.offered += len(tasks)
        if len(tasks) > self.max_arrivals:
            self.overflow += len(tasks) - self.max_arrivals
            tasks = tasks[:self.max_arrivals]
        self.fed += len(tasks)
        return tasks

    def next_chunk(self, n_intervals: int) -> dict:
        """Generate the next ``n_intervals`` intervals as a chunk tape
        (the ``kernel_dict`` layout of ``TraceArrays`` /
        ``DualTraceArrays``, chunk-local T axis)."""
        T, A, F = n_intervals, self.max_arrivals, self.max_frags
        dt = self.interval_s / self.substeps
        if self.variants is None:
            tape = self._next_chunk_static(T, A, F, dt)
        else:
            tape = self._next_chunk_dual(T, A, F, dt)
        self.n_intervals += T
        return tape

    def _next_chunk_static(self, T, A, F, dt):
        tape = {
            "bw_mult": np.ones((T, self.cluster.n), np.float64),
            "valid": np.zeros((T, A), bool),
            "sla": np.zeros((T, A), np.float64),
            "arrival_s": np.zeros((T, A), np.float64),
            "app": np.zeros((T, A), np.int32),
            "batch": np.zeros((T, A), np.int64),
            "acc": np.zeros((T, A), np.float64),
            "decision": np.full((T, A), -1, np.int32),
            "chain": np.zeros((T, A), bool),
            "nfrag": np.zeros((T, A), np.int32),
            "instr": np.zeros((T, A, F), np.float64),
            "ram": np.zeros((T, A, F), np.float64),
            "out_bytes": np.zeros((T, A, F), np.float64),
        }
        for t in range(T):
            tasks = self._arrivals()
            decisions = self.decider.decide(tasks)
            for a, (task, d) in enumerate(zip(tasks, decisions)):
                self.gen.realize(task, int(d))
                acc = self.gen.accuracy_of(task)
                tape["valid"][t, a] = True
                tape["sla"][t, a] = task.sla_s
                tape["arrival_s"][t, a] = task.arrival_s
                tape["app"][t, a] = task.app
                tape["batch"][t, a] = task.batch
                tape["acc"][t, a] = acc
                tape["decision"][t, a] = task.decision
                tape["chain"][t, a] = task.chain
                tape["nfrag"][t, a] = len(task.fragments)
                for i, f in enumerate(task.fragments):
                    tape["instr"][t, a, i] = f.instr_left
                    tape["ram"][t, a, i] = f.ram_mb
                    tape["out_bytes"][t, a, i] = f.out_bytes
            _, bw = self.mob.step()
            tape["bw_mult"][t] = bw
            for _ in range(self.substeps):
                self.now += dt
        return tape

    def _next_chunk_dual(self, T, A, F, dt):
        n = self.cluster.n
        tape = {
            "bw_mult": np.ones((T, n), np.float64),
            "lat_prev": np.ones((T, n), np.float64),
            "valid": np.zeros((T, A), bool),
            "sla": np.zeros((T, A), np.float64),
            "arrival_s": np.zeros((T, A), np.float64),
            "app": np.zeros((T, A), np.int32),
            "batch": np.zeros((T, A), np.int64),
            "vacc": np.zeros((T, A, 2), np.float64),
            "vchain": np.zeros((T, A, 2), bool),
            "vnfrag": np.zeros((T, A, 2), np.int32),
            "vinstr": np.zeros((T, A, 2, F), np.float64),
            "vram": np.zeros((T, A, 2, F), np.float64),
            "vout": np.zeros((T, A, 2, F), np.float64),
        }
        for t in range(T):
            tasks = self._arrivals()
            for a, task in enumerate(tasks):
                img_mb = self.gen.rng.uniform(
                    *APP_PROFILES[task.app].model_mb)
                tape["valid"][t, a] = True
                tape["sla"][t, a] = task.sla_s
                tape["arrival_s"][t, a] = task.arrival_s
                tape["app"][t, a] = task.app
                tape["batch"][t, a] = task.batch
                for v, d in enumerate(self.variants):
                    self.gen.realize(task, d, img_mb=img_mb)
                    tape["vchain"][t, a, v] = task.chain
                    tape["vnfrag"][t, a, v] = len(task.fragments)
                    for i, f in enumerate(task.fragments):
                        tape["vinstr"][t, a, v, i] = f.instr_left
                        tape["vram"][t, a, v, i] = f.ram_mb
                        tape["vout"][t, a, v, i] = f.out_bytes
                noise = self.gen.rng.normal(0, 0.003)
                for v, d in enumerate(self.variants):
                    tape["vacc"][t, a, v] = accuracy_from_noise(
                        task.app, d, noise)
            tape["lat_prev"][t] = self._lat_prev
            lat, bw = self.mob.step()
            tape["bw_mult"][t] = bw
            self._lat_prev = lat
            for _ in range(self.substeps):
                self.now += dt
        return tape


class StreamRunner:
    """Chunked executor of the carry-re-entrant interval program.

    Holds the device-resident carry ``(slot state, accumulators,
    engine_state)`` between ``run_chunk`` calls; each call advances the
    stream by one chunk tape and returns that chunk's per-interval
    telemetry rows (the only per-chunk device→host transfer).  The carry
    itself NEVER round-trips mid-stream: it stays a committed jax.Array
    pytree, and with backend donation support the previous chunk's
    buffers are reused in place — ``run_chunk`` asserts the donated
    carry actually died, which doubles as the no-copy proof."""

    def __init__(self, engine, es0, *, interval_s: float, substeps: int,
                 max_active: int, cluster: Optional[Cluster] = None,
                 swap_slowdown: float = 0.5,
                 substep_impl: Optional[str] = None):
        self.engine = engine
        self.cluster = cluster or make_cluster()
        self.cl = ClusterArrays.from_cluster(self.cluster)
        self.interval_s = float(interval_s)
        self.substeps = int(substeps)
        self.K = int(max_active)
        self.swap_slowdown = swap_slowdown
        self.impl = driver._resolve_substep_impl(substep_impl)
        self.tcols = tuple(TELEMETRY_COLS) + tuple(engine.telemetry_cols())
        self.t0 = 0
        self.n_chunks = 0
        self.donated = driver._donation_ok()
        self._es0 = es0
        self.carry = None          # built on the first chunk (needs F)
        with jax.enable_x64(True):
            self._cld = {k: jnp.asarray(v)
                         for k, v in self.cl.as_dict().items()}

    def _ensure_carry(self, F: int):
        if self.carry is not None:
            return
        with jax.enable_x64(True):
            state = kernels.init_state(self.K, F, self.cl.n)
            acc = driver._init_acc(self.cl.n)
            es = jax.tree_util.tree_map(jnp.asarray, self._es0)
        self.carry = (state, acc, es)

    def run_chunk(self, tape: dict) -> np.ndarray:
        """Advance the stream by one chunk tape; returns the chunk's
        ``(T, C)`` float64 telemetry series as NumPy.

        Each host step is a span of the active ledger: ``stream_put``
        (the tape's leaves, the first chunk's carry and ``t0`` onto the
        device), ``stream_dispatch`` (the cache lookup and the call until
        it returns), ``stream_sync`` (waiting for the chunk and the
        donation check) and ``stream_fetch`` (the series back to the
        host)."""
        led = get_ledger()
        with jax.enable_x64(True):
            attrs = {"leaves": len(tape),
                     "bytes": sum(int(v.nbytes) for v in tape.values())} \
                if led.record else {}
            with led.span("stream_put", **attrs):
                leaves = {k: jnp.asarray(v) for k, v in tape.items()}
                frag = leaves["vinstr"] if "vinstr" in leaves \
                    else leaves["instr"]
                self._ensure_carry(int(frag.shape[-1]))
                t0 = jnp.asarray(self.t0, jnp.int64)
            with led.span("stream_dispatch"):
                key = driver._static_key(self.engine, leaves, self.K,
                                         self.cl.n, self.substeps,
                                         self.interval_s,
                                         self.swap_slowdown, self.impl,
                                         "stream")
                runner = driver._get_stream_runner(key)
                prev = self.carry
                carry, series = runner(leaves, self._cld, prev, t0)
        leaf = jax.tree_util.tree_leaves(carry)[0]
        if not isinstance(leaf, jax.Array):
            raise RuntimeError("streaming carry left the device")
        with led.span("stream_sync"):
            if self.donated:
                jax.block_until_ready(leaf)
                prev_leaf = jax.tree_util.tree_leaves(prev)[0]
                # the donated input dying in place is the proof that the
                # chunk-to-chunk carry is updated without a second copy
                # of the slot arrays (and never round-trips through the
                # host)
                if not prev_leaf.is_deleted():
                    raise RuntimeError(
                        "streaming carry was copied instead of donated")
        self.carry = carry
        self.t0 += int(tape["valid"].shape[0])
        self.n_chunks += 1
        with led.span("stream_fetch"):
            return np.asarray(series)

    # --------------------------------------------------------- summary

    def raw_outputs(self) -> dict:
        """Pull the final accumulators to host (the stream's ONLY carry
        round-trip — call it once, after the last chunk)."""
        state, acc, es = self.carry
        out = {"metrics": acc["metrics"], "energy": acc["energy"],
               "pwt": acc["pwt"], "dropped": state["dropped"],
               "live": jnp.sum(state["alive"])}
        out.update(self.engine.outputs(es))
        return jax.tree_util.tree_map(np.asarray, out)

    def summary(self, n_intervals: Optional[int] = None) -> dict:
        """Assemble the §6.4 summary over everything streamed so far."""
        out = self.raw_outputs()
        s = driver._summarize(out, self.interval_s,
                              n_intervals or self.t0,
                              float(self.cl.cost_hr.sum()))
        return self.engine.summarize(out, s)


class RollingMetrics:
    """Sliding-window serving metrics over interval-telemetry rows:
    QPS (completions per sim-second), binned p50/p95/p99 response and
    wait percentiles (``metrics.series_percentiles`` with its
    ``percentile_err_s`` bound), deadline-violation rate and mean ring
    occupancy — all over the trailing ``window_intervals`` intervals."""

    def __init__(self, cols, window_intervals: int, interval_s: float):
        self.cols = list(cols)
        self.interval_s = float(interval_s)
        self.window = deque(maxlen=int(window_intervals))
        self._i = {c: i for i, c in enumerate(self.cols)}

    def update(self, series) -> None:
        for row in np.asarray(series, np.float64):
            self.window.append(row)

    def snapshot(self) -> dict:
        if not self.window:
            return {"window_intervals": 0, "qps": 0.0,
                    "violation_rate": 0.0, "occupancy_mean": 0.0}
        w = np.stack(self.window)
        n_fin = float(w[:, self._i["n_fin"]].sum())
        snap = {
            "window_intervals": len(self.window),
            "qps": n_fin / (len(self.window) * self.interval_s),
            "violation_rate":
                float(w[:, self._i["n_viol"]].sum()) / max(n_fin, 1.0),
            "occupancy_mean": float(w[:, self._i["n_active"]].mean()),
            "dropped": float(w[:, self._i["n_dropped"]].sum()),
        }
        snap.update(series_percentiles(w, self.cols))
        return snap


def replay_stream(engine, trace, es0, *, chunk_intervals: int,
                  cluster: Optional[Cluster] = None,
                  max_active: Optional[int] = None,
                  swap_slowdown: float = 0.5,
                  substep_impl: Optional[str] = None,
                  collect_series: bool = False) -> dict:
    """Chunked streaming replay of a frozen compiled trace.

    Splits ``trace`` into ``chunk_intervals``-sized tapes and threads
    the carry through consecutive chunk calls; the resulting summary
    equals the one-shot ``driver.run_trace_engine`` episode within the
    standard rtol=1e-4 summary-metric contract (the per-interval math is
    identical — only the fori_loop boundaries move).  With
    ``collect_series`` the summary also carries the concatenated
    telemetry series + percentile estimates, mirroring
    ``telemetry="interval"`` episodes."""
    cluster = cluster or make_cluster()
    K = max_active or default_capacity([trace])
    r = StreamRunner(engine, es0, interval_s=trace.interval_s,
                     substeps=trace.substeps, max_active=K,
                     cluster=cluster, swap_slowdown=swap_slowdown,
                     substep_impl=substep_impl)
    led = get_ledger()
    chunks = []
    for t0, tape in chunk_tapes(trace, chunk_intervals):
        with led.span("stream_chunk", engine=engine.name, idx=r.n_chunks,
                      t0=t0, n_intervals=int(tape["valid"].shape[0])):
            chunks.append(r.run_chunk(tape))
    s = r.summary(trace.n_intervals)
    if collect_series:
        series = np.concatenate(chunks, axis=0)
        s.update(series_percentiles(series, r.tcols))
        s["telemetry"] = {"cols": list(r.tcols), "series": series}
    return s


def serve(engine, es0, feeder: StreamFeeder, *, chunk_intervals: int = 64,
          max_active: int = 512, target_tasks: int = 10_000,
          window_intervals: int = 256, prefetch: int = 2,
          swap_slowdown: float = 0.5,
          substep_impl: Optional[str] = None, on_chunk=None) -> dict:
    """The always-on serving loop: stream Poisson arrivals through the
    chunked interval program until the feeder has offered at least
    ``target_tasks`` tasks, overlapping host tape generation with device
    compute.

    A daemon feeder thread fills a ``prefetch``-deep queue with chunk
    tapes (``prefetch=2`` is classic double buffering: chunk N+1's tape
    is generated while chunk N executes — jitted executions release the
    GIL); the main thread drains it through a ``StreamRunner`` whose
    carry is donated chunk to chunk.  ``on_chunk(i, runner, rolling)``
    fires after every chunk (progress printing, RSS sampling).

    Returns the serving report: admission ledger (``offered == fed +
    feeder_overflow``, ``admitted == fed - dropped``, ``admitted ==
    finished + live``), ring-occupancy stats (first-half vs second-half
    means — the flat-memory soak criterion), the rolling-window
    snapshot, and the cumulative §6.4 summary."""
    runner = StreamRunner(engine, es0, interval_s=feeder.interval_s,
                          substeps=feeder.substeps, max_active=max_active,
                          cluster=feeder.cluster,
                          swap_slowdown=swap_slowdown,
                          substep_impl=substep_impl)
    rolling = RollingMetrics(runner.tcols, window_intervals,
                             feeder.interval_s)
    led = get_ledger()
    parent = led.current_span()
    q: "queue.Queue" = queue.Queue(maxsize=max(1, int(prefetch)))
    stop = threading.Event()
    feed_err = []

    def _feed():
        try:
            while not stop.is_set() and feeder.offered < target_tasks:
                t0 = feeder.n_intervals
                with led.span("feed", parent=parent, t0=t0,
                              n_intervals=chunk_intervals):
                    tape = feeder.next_chunk(chunk_intervals)
                q.put(tape)
        except BaseException as e:  # surfaced to the caller below
            feed_err.append(e)
        finally:
            q.put(None)

    occupancy = []
    i_active = runner.tcols.index("n_active")
    with led.span("serve", engine=engine.name, capacity=max_active,
                  chunk_intervals=chunk_intervals,
                  target_tasks=target_tasks):
        th = threading.Thread(target=_feed, name="stream-feeder",
                              daemon=True)
        th.start()
        try:
            while True:
                tape = q.get()
                if tape is None:
                    break
                with led.span("stream_chunk", engine=engine.name,
                              idx=runner.n_chunks, t0=runner.t0,
                              n_intervals=int(tape["valid"].shape[0]),
                              n_tasks=int(tape["valid"].sum())):
                    series = runner.run_chunk(tape)
                rolling.update(series)
                occupancy.append(series[:, i_active])
                if on_chunk is not None:
                    on_chunk(runner.n_chunks, runner, rolling)
        finally:
            stop.set()
            th.join()
    if feed_err:
        raise feed_err[0]
    summary = runner.summary()
    out = runner.raw_outputs()
    occ = np.concatenate(occupancy) if occupancy else np.zeros(1)
    h = len(occ) // 2
    dropped = int(out["dropped"])
    return {
        "engine": engine.name,
        "chunk_intervals": chunk_intervals,
        "window_intervals": window_intervals,
        "capacity": max_active,
        "n_chunks": runner.n_chunks,
        "n_intervals": runner.t0,
        "offered": feeder.offered,
        "fed": feeder.fed,
        "feeder_overflow": feeder.overflow,
        "dropped": dropped,
        "admitted": feeder.fed - dropped,
        "finished": int(summary["tasks_completed"]),
        "live": int(out["live"]),
        "max_occupancy": float(occ.max()),
        "occupancy_mean_first_half": float(occ[:h].mean()) if h else 0.0,
        "occupancy_mean_second_half": float(occ[h:].mean()),
        "rolling": rolling.snapshot(),
        "summary": summary,
    }


def make_stream_policy(policy: str, *, cluster: Optional[Cluster] = None,
                       seed: int = 0, mab_state=None, daso_theta=None,
                       daso_cfg=None, gillis_state=None, num_apps: int = 3):
    """Resolve a policy name into ``(engine, es0, feeder_kwargs)`` for
    the serving loop, in deploy mode, through ``policies.resolve``.

    Static BestFit policies get a host decider feeder; every other
    policy a dual-variant feeder with its engine state.  The MAB family
    continues a pretrained ``mab_state`` (fresh ``mab.init_state`` when
    None — cold-start serving); the surrogate placers (``"splitplace"``,
    ``"mab+gobi"`` and the static-decider arms) need
    ``daso_cfg``/``daso_theta``, since without a surrogate the engine
    would not be their placer; ``"gillis"`` carries its Q-table/ε."""
    if mab_state is None and policy in policies.MAB_LEARNED_POLICIES:
        from repro.core import mab
        mab_state = mab.init_state(num_apps)
    r = policies.resolve(policy, cluster=cluster, mab_state=mab_state,
                         daso_theta=daso_theta, daso_cfg=daso_cfg,
                         gillis_state=gillis_state, num_apps=num_apps)
    feeder_kw = {"decider": r.decider} if r.variants is None \
        else {"variants": r.variants}
    return r.engine, r.state(seed), feeder_kw
