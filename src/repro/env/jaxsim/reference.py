"""Replay a compiled trace through the host ``EdgeSim`` — the parity
reference for the jitted backend.

The compiled trace carries pre-realized fragments and pre-sampled
accuracies, so the replay swaps the simulator's workload generator for a
scripted source that deals the identical tasks interval by interval.
Mobility needs no scripting: ``EdgeSim`` seeds its own ``MobilityModel``
with ``seed + 1`` exactly as ``compile_trace`` did, so the bandwidth
multipliers line up by construction.

``tests/test_jaxsim_parity.py`` pins ``run_trace_engine`` ≈ this replay
(allclose on summary metrics) — the relaxed successor of the SoA↔legacy
bit-exactness contract.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.env.cluster import Cluster
from repro.env.jaxsim.arrays import TraceArrays
from repro.env.metrics import TELEMETRY_COLS, MetricsAccumulator
from repro.env.simulator import EdgeSim
from repro.env.workload import Fragment, Task


def _attach_telemetry(out, acc, eng_cols=(), eng_rows=None):
    """Host-side twin of the driver's interval-mode summary extras:
    EXACT percentiles (the host keeps full sample lists, so the binning
    error bound is 0), plus the per-interval series — base
    ``TELEMETRY_COLS`` rows from the accumulator with the engine's
    learning-signal columns appended."""
    out.update(acc.percentiles())
    out["percentile_err_s"] = 0.0
    series = acc.telemetry_series()
    if eng_cols:
        series = np.concatenate(
            [series, np.asarray(eng_rows, np.float64).reshape(
                series.shape[0], len(eng_cols))], axis=1)
    out["telemetry"] = {"cols": list(TELEMETRY_COLS) + list(eng_cols),
                        "series": series}
    return out


class _ScriptedSource:
    """Stands in for ``WorkloadGenerator``: deals the compiled trace's
    tasks per interval and replays its pre-sampled accuracies."""

    def __init__(self, trace: TraceArrays):
        self._acc = {}
        self._queues = []
        for t in range(trace.n_intervals):
            tasks = []
            for a in range(trace.max_arrivals):
                if not trace.arr_valid[t, a]:
                    continue
                tid = int(trace.arr_id[t, a])
                task = Task(id=tid, app=int(trace.arr_app[t, a]),
                            batch=int(trace.arr_batch[t, a]),
                            sla_s=float(trace.arr_sla[t, a]),
                            arrival_s=float(trace.arr_arrival_s[t, a]),
                            decision=int(trace.arr_decision[t, a]),
                            chain=bool(trace.arr_chain[t, a]))
                for i in range(int(trace.arr_nfrag[t, a])):
                    task.fragments.append(Fragment(
                        tid, i, float(trace.frag_instr[t, a, i]),
                        float(trace.frag_ram[t, a, i]),
                        float(trace.frag_out[t, a, i])))
                self._acc[tid] = float(trace.arr_acc[t, a])
                tasks.append(task)
            self._queues.append(tasks)
        self._t = 0

    def arrivals(self, now_s: float):
        if self._t >= len(self._queues):
            return []
        tasks = self._queues[self._t]
        self._t += 1
        return tasks

    def accuracy_of(self, task) -> float:
        return self._acc[task.id]


def replay_trace_edgesim(trace: TraceArrays,
                         cluster: Optional[Cluster] = None,
                         placer=None, telemetry: str = "summary") -> dict:
    """Drive ``EdgeSim`` + BestFit through the compiled trace; returns the
    same summary schema as a static policy's ``driver.run_trace_engine``."""
    from repro.core.splitplace import BestFitPlacer
    tel = telemetry == "interval"
    sim = EdgeSim(cluster=cluster, lam=trace.lam, seed=trace.seed,
                  interval_s=trace.interval_s, substeps=trace.substeps)
    sim.gen = _ScriptedSource(trace)
    placer = placer or BestFitPlacer()
    acc = MetricsAccumulator(interval_s=trace.interval_s, telemetry=tel)
    for _ in range(trace.n_intervals):
        tasks = sim.new_interval_tasks()
        sim.admit(tasks, [0] * len(tasks))   # decisions pre-realized
        sim.apply_placement(placer.place(sim))
        acc.update(sim.advance())
    out = acc.summary()
    out["dropped_tasks"] = 0
    if tel:
        _attach_telemetry(out, acc)
    return out


# ---------------------------------------------- learned-policy reference
#
# The in-kernel learned policies (online MAB decider, array-form DASO
# placer) are pinned against the same host simulator: the replay below
# drives ``EdgeSim`` through a *dual* compiled trace, taking the split
# decisions / placements with the identical shared pure functions
# (``repro.core.mab`` masked feedback, ``repro.core.daso`` surrogate
# ascent) in the identical order, so the two backends see the same
# decision/placement trajectory and the metric contract stays
# allclose(rtol=1e-4).


class _AccuracyMap:
    """Minimal ``WorkloadGenerator`` stand-in for a learned replay: only
    ``accuracy_of`` is consulted (tasks are constructed pre-realized)."""

    def __init__(self):
        self._acc = {}

    def accuracy_of(self, task) -> float:
        return self._acc[task.id]


def _tasks_of_interval(trace, t, decisions, acc_map):
    """Materialize interval ``t``'s arrivals under the given per-row
    split *arm* indices (the V axis of the dual trace arrays); each
    task's recorded decision code comes from ``trace.variants`` —
    (LAYER, SEMANTIC) for MAB traces, (LAYER, COMPRESSED) for Gillis."""
    variants = getattr(trace, "variants", (0, 1))
    tasks = []
    rows = np.nonzero(trace.arr_valid[t])[0]
    for a, d in zip(rows, decisions):
        tid = int(trace.arr_id[t, a])
        task = Task(id=tid, app=int(trace.arr_app[t, a]),
                    batch=int(trace.arr_batch[t, a]),
                    sla_s=float(trace.arr_sla[t, a]),
                    arrival_s=float(trace.arr_arrival_s[t, a]),
                    decision=int(variants[d]),
                    chain=bool(trace.var_chain[t, a, d]))
        for i in range(int(trace.var_nfrag[t, a, d])):
            task.fragments.append(Fragment(
                tid, i, float(trace.var_instr[t, a, d, i]),
                float(trace.var_ram[t, a, d, i]),
                float(trace.var_out[t, a, d, i])))
        acc_map._acc[tid] = float(trace.var_acc[t, a, d])
        tasks.append(task)
    return tasks


def _daso_assignment(sim, cfg, theta, warm):
    """Host mirror of ``kernels.daso_requests``: same container
    enumeration (admission order, ``max_containers`` head), same
    warm-start logits, same float64 ``optimize_placement`` — so both
    backends feed the feasibility repair identical requests."""
    import jax
    import jax.numpy as jnp

    from repro.core import daso as daso_mod

    conts = sim.containers()
    C = cfg.max_containers
    head = conts[:C]
    feat = sim.state_features()
    warm_w = np.zeros(C, np.int32)
    rowvalid = np.zeros(C, bool)
    dec = np.zeros(C, np.int32)
    for i, (task, f) in enumerate(head):
        rowvalid[i] = True
        dec[i] = min(task.decision, 1)
        w = f.worker if f.worker >= 0 else warm[(task.id, f.idx)]
        warm_w[i] = w
    with jax.enable_x64(True):
        logits = daso_mod.warm_start_logits(cfg, jnp.asarray(warm_w),
                                            jnp.asarray(rowvalid))
        p_opt, _, _ = daso_mod.optimize_placement(
            cfg, theta, jnp.asarray(feat), logits, jnp.asarray(dec),
            jnp.asarray(rowvalid, jnp.float64))
        assign = np.asarray(jnp.argmax(p_opt, axis=-1))
    out = dict(warm)
    for i, (task, f) in enumerate(head):
        out[(task.id, f.idx)] = int(assign[i])
    return out


def _daso_rows_host(sim, cfg, warm):
    """Host mirror of ``kernels._daso_rows``: the first ``max_containers``
    live fragments in ``EdgeSim.containers`` (admission) order with their
    warm-start workers and clipped decisions."""
    conts = sim.containers()
    C = cfg.max_containers
    head = conts[:C]
    warm_w = np.zeros(C, np.int32)
    rowvalid = np.zeros(C, bool)
    dec = np.zeros(C, np.int32)
    for i, (task, f) in enumerate(head):
        rowvalid[i] = True
        dec[i] = min(task.decision, 1)
        w = f.worker if f.worker >= 0 else warm[(task.id, f.idx)]
        warm_w[i] = w
    return head, warm_w, rowvalid, dec


def replay_trace_edgesim_trained(trace, mab_state, daso_theta=None,
                                 daso_cfg=None, daso_opt_state=None,
                                 cluster: Optional[Cluster] = None,
                                 mab_hp=None, train_hp=None,
                                 telemetry: str = "summary") -> dict:
    """Drive ``EdgeSim`` through a dual compiled trace under the FULL
    training loop — ε-greedy MAB decisions (eq. 6) from the shared
    fold-in key choreography, Algorithm-1 feedback with RBED ε-decay,
    and (when ``daso_cfg`` is given) online DASO finetuning: per-interval
    (packed placement features, O^P) replay-window appends and
    ``train_epoch_weighted`` steps through the identical shared pure
    functions.  The parity oracle for ``resolve(..., mode="train")``;
    returns the same summary schema including the final MAB scalars and
    (DASO runs) the finetuned ``theta`` under ``"daso_theta"``."""
    import jax
    import jax.numpy as jnp

    from repro.core import daso as daso_mod
    from repro.core import mab as mab_mod
    from repro.core.splitplace import BestFitPlacer
    from repro.env.jaxsim.engines import MAB_HP, TRAIN_HP, trace_train_key
    from repro.optim.optimizers import adamw_init

    _, phi, gamma, k_rbed = mab_hp or MAB_HP
    alpha, beta, train_steps, place_min, train_min = train_hp or TRAIN_HP
    tel = telemetry == "interval"
    eng_rows = []
    sim = EdgeSim(cluster=cluster, lam=trace.lam, seed=trace.seed,
                  interval_s=trace.interval_s, substeps=trace.substeps)
    acc_map = _AccuracyMap()
    sim.gen = acc_map
    bestfit = BestFitPlacer()
    acc = MetricsAccumulator(interval_s=trace.interval_s, telemetry=tel)
    with jax.enable_x64(True):
        mab = jax.tree_util.tree_map(jnp.asarray, mab_state)
        theta = jax.tree_util.tree_map(jnp.asarray, daso_theta) \
            if daso_theta is not None else None
        if daso_cfg is not None:
            opt = jax.tree_util.tree_map(
                jnp.asarray, daso_opt_state if daso_opt_state is not None
                else adamw_init(theta))
            win = daso_mod.window_init(daso_cfg)
        key = trace_train_key(trace.seed)
    for t in range(trace.n_intervals):
        rows = np.nonzero(trace.arr_valid[t])[0]
        sla_n = (trace.arr_sla[t, rows] * 40000.0
                 / np.maximum(trace.arr_batch[t, rows].astype(np.float64),
                              1.0)).astype(np.float32)
        with jax.enable_x64(True):
            key_t = jax.random.fold_in(key, t)
            d, _ = mab_mod.decide_train_rows(
                mab, key_t, jnp.asarray(sla_n),
                jnp.asarray(trace.arr_app[t, rows]))
        decisions = np.asarray(d)
        tasks = _tasks_of_interval(trace, t, decisions, acc_map)
        sim.admit(tasks, decisions)
        warm = bestfit.place(sim)
        if daso_cfg is not None:
            head, warm_w, rowvalid, dec = _daso_rows_host(sim, daso_cfg,
                                                          warm)
            feat = sim.state_features()
            with jax.enable_x64(True):
                logits = daso_mod.warm_start_logits(
                    daso_cfg, jnp.asarray(warm_w), jnp.asarray(rowvalid))
                mask = jnp.asarray(rowvalid, jnp.float64)
                # cold-start gate: warm logits verbatim until place_min
                # records exist.  One record lands per interval, so the
                # pre-append count equals t — the same interval-indexed
                # gate the kernel's lax.cond branches on, skipping the
                # ascent entirely during cold start on both backends
                if t >= place_min:
                    p_used, _, _ = daso_mod.optimize_placement(
                        daso_cfg, theta, jnp.asarray(feat), logits,
                        jnp.asarray(dec), mask)
                else:
                    p_used = logits
                assign = np.asarray(jnp.argmax(p_used, axis=-1))
                x = daso_mod.pack_input(daso_cfg, jnp.asarray(feat),
                                        p_used, jnp.asarray(dec), mask)
            out_asg = dict(warm)
            for i, (task, f) in enumerate(head):
                out_asg[(task.id, f.idx)] = int(assign[i])
            warm = out_asg
        sim.apply_placement(warm)
        stats = sim.advance()
        fin = sorted(stats.finished, key=lambda task: task.id)
        with jax.enable_x64(True):
            batch = np.maximum(np.array([task.batch for task in fin],
                                        np.float64), 1.0)
            mab = mab_mod.end_of_interval_masked(
                mab,
                jnp.asarray(np.array([task.app for task in fin], np.int32)),
                jnp.asarray((np.array([task.sla_s for task in fin])
                             * 40000.0 / batch).astype(np.float32)),
                jnp.asarray((np.array([task.response_s for task in fin])
                             * 40000.0 / batch).astype(np.float32)),
                jnp.asarray(np.array([task.accuracy for task in fin],
                                     np.float32)),
                jnp.asarray(np.array([min(task.decision, 1) for task in fin],
                                     np.int32)),
                jnp.ones((len(fin),), bool), phi, gamma, k_rbed)
            if daso_cfg is not None:
                y = daso_mod.op_objective(
                    jnp.asarray(np.array([task.response_s for task in fin],
                                         np.float64)),
                    jnp.asarray(np.array([task.sla_s for task in fin],
                                         np.float64)),
                    jnp.asarray(np.array([task.accuracy for task in fin],
                                         np.float64)),
                    jnp.ones((len(fin),), bool),
                    jnp.asarray(stats.cpu_util), trace.interval_s,
                    alpha, beta)
                win = daso_mod.window_append(win, x, y)
                theta, opt = daso_mod.finetune_window(daso_cfg, theta, opt,
                                                      win, train_steps,
                                                      train_min)
            if tel:
                # sampled at the same point as the kernel engine's
                # telemetry_row: end of feedback, post-finetune
                row = [float(mab.eps), float(mab.rho),
                       float(mab.N[:, 0].sum()), float(mab.N[:, 1].sum())]
                if daso_cfg is not None:
                    row += [float(win["count"]),
                            float(daso_mod.window_loss(daso_cfg, theta,
                                                       win))]
                eng_rows.append(row)
        acc.update(stats)
    out = acc.summary()
    out["dropped_tasks"] = 0
    out["mab_eps"] = float(mab.eps)
    out["mab_rho"] = float(mab.rho)
    out["mab_t"] = int(mab.t)
    if daso_cfg is not None:
        out["daso_theta"] = jax.tree_util.tree_map(np.asarray, theta)
    if tel:
        from repro.env.jaxsim.engines import MAB_TELEMETRY_COLS
        cols = MAB_TELEMETRY_COLS if daso_cfg is None else \
            MAB_TELEMETRY_COLS + ("daso_win_fill", "daso_last_loss")
        _attach_telemetry(out, acc, cols, eng_rows)
    return out


def replay_trace_edgesim_learned(trace, mab_state, daso_theta=None,
                                 daso_cfg=None,
                                 cluster: Optional[Cluster] = None,
                                 mab_hp=None,
                                 telemetry: str = "summary") -> dict:
    """Drive ``EdgeSim`` through a dual compiled trace under the learned
    policy (online UCB MAB decider; DASO placer when ``daso_cfg`` is
    given, BestFit otherwise) — the parity reference for the deploy-mode
    MAB engines of ``policies.resolve``.  Returns the same summary
    schema, including the final MAB scalars."""
    import jax
    import jax.numpy as jnp

    from repro.core import mab as mab_mod
    from repro.core.splitplace import BestFitPlacer
    from repro.env.jaxsim.engines import MAB_HP

    ucb_c, phi, gamma, k_rbed = mab_hp or MAB_HP
    tel = telemetry == "interval"
    eng_rows = []
    sim = EdgeSim(cluster=cluster, lam=trace.lam, seed=trace.seed,
                  interval_s=trace.interval_s, substeps=trace.substeps)
    acc_map = _AccuracyMap()
    sim.gen = acc_map
    bestfit = BestFitPlacer()
    acc = MetricsAccumulator(interval_s=trace.interval_s, telemetry=tel)
    with jax.enable_x64(True):
        mab = jax.tree_util.tree_map(jnp.asarray, mab_state)
        theta = jax.tree_util.tree_map(jnp.asarray, daso_theta) \
            if daso_theta is not None else None
    for t in range(trace.n_intervals):
        rows = np.nonzero(trace.arr_valid[t])[0]
        sla_n = (trace.arr_sla[t, rows] * 40000.0
                 / np.maximum(trace.arr_batch[t, rows].astype(np.float64),
                              1.0)).astype(np.float32)
        with jax.enable_x64(True):
            d, _ = mab_mod.decide_ucb_batch(
                mab, jnp.asarray(sla_n),
                jnp.asarray(trace.arr_app[t, rows]), ucb_c)
        decisions = np.asarray(d)
        tasks = _tasks_of_interval(trace, t, decisions, acc_map)
        sim.admit(tasks, decisions)
        warm = bestfit.place(sim)
        if daso_cfg is not None:
            warm = _daso_assignment(sim, daso_cfg, theta, warm)
        sim.apply_placement(warm)
        stats = sim.advance()
        fin = sorted(stats.finished, key=lambda task: task.id)
        with jax.enable_x64(True):
            batch = np.maximum(np.array([task.batch for task in fin],
                                        np.float64), 1.0)
            mab = mab_mod.end_of_interval_masked(
                mab,
                jnp.asarray(np.array([task.app for task in fin], np.int32)),
                jnp.asarray((np.array([task.sla_s for task in fin])
                             * 40000.0 / batch).astype(np.float32)),
                jnp.asarray((np.array([task.response_s for task in fin])
                             * 40000.0 / batch).astype(np.float32)),
                jnp.asarray(np.array([task.accuracy for task in fin],
                                     np.float32)),
                jnp.asarray(np.array([min(task.decision, 1) for task in fin],
                                     np.int32)),
                jnp.ones((len(fin),), bool), phi, gamma, k_rbed)
            if tel:
                eng_rows.append([float(mab.eps), float(mab.rho),
                                 float(mab.N[:, 0].sum()),
                                 float(mab.N[:, 1].sum())])
        acc.update(stats)
    out = acc.summary()
    out["dropped_tasks"] = 0
    out["mab_eps"] = float(mab.eps)
    out["mab_rho"] = float(mab.rho)
    out["mab_t"] = int(mab.t)
    if tel:
        from repro.env.jaxsim.engines import MAB_TELEMETRY_COLS
        _attach_telemetry(out, acc, MAB_TELEMETRY_COLS, eng_rows)
    return out


def replay_trace_edgesim_static_daso(trace, policy: str, daso_theta=None,
                                     daso_cfg=None,
                                     cluster: Optional[Cluster] = None,
                                     telemetry: str = "summary") -> dict:
    """Drive ``EdgeSim`` through a dual compiled trace under one of the
    static-decider Table-4 baseline arms — fixed ``layer+gobi`` /
    ``semantic+gobi`` splits with decision-blind surrogate placement, or
    ``random+daso`` uniform-random splits (the kernel engine's per-row
    fold-in bitstream, so both backends realize identical decisions)
    with decision-aware placement.  The parity oracle for
    ``policies.resolve``'s ``STATIC_DASO_ARMS``; returns the plain §6.4
    summary schema.

    Note the random arm pins the *in-kernel* decider (JAX PRNG), not the
    object-loop ``splitplace.RandomDecider`` (NumPy ``RandomState``) —
    same algorithm, different bitstreams."""
    import jax
    import jax.numpy as jnp

    from repro.core.splitplace import BestFitPlacer
    from repro.env.jaxsim.engines import trace_train_key
    from repro.env.jaxsim.policies import STATIC_DASO_ARMS

    arm = STATIC_DASO_ARMS[policy]
    if arm >= 0:
        daso_cfg = daso_cfg._replace(decision_aware=False)
    tel = telemetry == "interval"
    sim = EdgeSim(cluster=cluster, lam=trace.lam, seed=trace.seed,
                  interval_s=trace.interval_s, substeps=trace.substeps)
    acc_map = _AccuracyMap()
    sim.gen = acc_map
    bestfit = BestFitPlacer()
    acc = MetricsAccumulator(interval_s=trace.interval_s, telemetry=tel)
    with jax.enable_x64(True):
        theta = jax.tree_util.tree_map(jnp.asarray, daso_theta)
        key = trace_train_key(trace.seed)
    for t in range(trace.n_intervals):
        rows = np.nonzero(trace.arr_valid[t])[0]
        if arm < 0:
            with jax.enable_x64(True):
                key_t = jax.random.fold_in(key, t)
                decisions = np.array(
                    [int(jax.random.bernoulli(jax.random.fold_in(key_t, r)))
                     for r in range(len(rows))], np.int32)
        else:
            decisions = np.full(len(rows), arm, np.int32)
        tasks = _tasks_of_interval(trace, t, decisions, acc_map)
        sim.admit(tasks, decisions)
        warm = bestfit.place(sim)
        warm = _daso_assignment(sim, daso_cfg, theta, warm)
        sim.apply_placement(warm)
        acc.update(sim.advance())
    out = acc.summary()
    out["dropped_tasks"] = 0
    if tel:
        _attach_telemetry(out, acc)
    return out


def replay_trace_edgesim_gillis(trace, gillis_state=None,
                                cluster: Optional[Cluster] = None,
                                gillis_hp=None, num_apps: int = 3,
                                telemetry: str = "summary") -> dict:
    """Drive ``EdgeSim`` through a (LAYER, COMPRESSED) dual compiled
    trace under the in-kernel Gillis baseline — contextual ε-greedy
    Q-learning decisions from the shared fold-in key choreography,
    per-interval ε-decay, and sequential per-leaving-task TD(0) updates
    through the identical shared pure functions (``mab.gillis_*``).  The
    parity oracle for ``resolve("gillis")``; returns the same
    summary schema including the final ``gillis_eps`` scalar and
    ``gillis_q`` table.

    Note this pins the *in-kernel* Gillis arm (JAX PRNG), not the
    object-loop ``splitplace.GillisDecider`` (NumPy ``RandomState``) —
    same algorithm, different bitstreams."""
    import jax
    import jax.numpy as jnp

    from repro.core import mab as mab_mod
    from repro.core.splitplace import BestFitPlacer
    from repro.env.jaxsim.engines import (GILLIS_HP, gillis_layer_ref,
                                          trace_train_key)
    from repro.env.workload import LAYER

    eps0, lr, decay = gillis_hp or GILLIS_HP
    tel = telemetry == "interval"
    eng_rows = []
    sim = EdgeSim(cluster=cluster, lam=trace.lam, seed=trace.seed,
                  interval_s=trace.interval_s, substeps=trace.substeps)
    acc_map = _AccuracyMap()
    sim.gen = acc_map
    bestfit = BestFitPlacer()
    acc = MetricsAccumulator(interval_s=trace.interval_s, telemetry=tel)
    with jax.enable_x64(True):
        layer_ref = jnp.asarray(gillis_layer_ref(num_apps))
        if gillis_state is None:
            Q = mab_mod.gillis_init(num_apps)
            eps = jnp.asarray(eps0, jnp.float64)
        else:
            Q = jnp.asarray(np.asarray(gillis_state["Q"], np.float64))
            eps = jnp.asarray(np.float64(gillis_state["eps"]))
        key = trace_train_key(trace.seed)
    for t in range(trace.n_intervals):
        rows = np.nonzero(trace.arr_valid[t])[0]
        with jax.enable_x64(True):
            key_t = jax.random.fold_in(key, t)
            arms, _ = mab_mod.gillis_decide_rows(
                Q, eps, key_t, jnp.asarray(trace.arr_sla[t, rows]),
                jnp.asarray(trace.arr_batch[t, rows].astype(np.float64)),
                jnp.asarray(trace.arr_app[t, rows]), layer_ref)
            eps = eps * decay
        arms = np.asarray(arms)
        tasks = _tasks_of_interval(trace, t, arms, acc_map)
        sim.admit(tasks, arms)
        sim.apply_placement(bestfit.place(sim))
        stats = sim.advance()
        fin = sorted(stats.finished, key=lambda task: task.id)
        with jax.enable_x64(True):
            sla = jnp.asarray(np.array([task.sla_s for task in fin],
                                       np.float64))
            batch = jnp.asarray(np.array([task.batch for task in fin],
                                         np.float64))
            apps = jnp.asarray(np.array([task.app for task in fin],
                                        np.int32))
            buckets = mab_mod.gillis_bucket(sla, batch, apps, layer_ref)
            fin_arms = jnp.asarray(np.array(
                [0 if task.decision == LAYER else 1 for task in fin],
                np.int32))
            rewards = jnp.asarray(np.array(
                [((task.response_s <= task.sla_s) + task.accuracy) / 2.0
                 for task in fin], np.float64))
            Q = mab_mod.gillis_update_masked(
                Q, apps, buckets, fin_arms, rewards,
                jnp.ones((len(fin),), bool), lr)
            if tel:
                # eps already carries this interval's decay (it decays in
                # decide, before feedback — same point the kernel samples)
                eng_rows.append([float(eps), float(Q.min()),
                                 float(Q.max())])
        acc.update(stats)
    out = acc.summary()
    out["dropped_tasks"] = 0
    out["gillis_eps"] = float(eps)
    out["gillis_q"] = np.asarray(Q, np.float64)
    if tel:
        _attach_telemetry(out, acc,
                          ("gillis_eps", "gillis_q_min", "gillis_q_max"),
                          eng_rows)
    return out
