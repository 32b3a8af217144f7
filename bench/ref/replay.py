"""Replay a served stream or a grid cell through the plain reference,
from the seed alone: the reference draws its own tasks, decides and
places them, and returns what the system reports for the same run (the
per-interval telemetry rows of a stream, the summary of a grid cell)."""
from __future__ import annotations

import jax
import numpy as np

from bench.ref import policy
from bench.ref.sim import Sim
from bench.ref.traffic import COMPRESSED, Tape, fleet_arrays

MAB_COLS = ("mab_eps", "mab_rho", "mab_n_layer", "mab_n_semantic")


def _intervals(cfg, engine, seed, lam, n_intervals, max_arrivals, inputs,
               dtype):
    """Yield (sim, interval output, engine telemetry) per interval."""
    sim = Sim(fleet_arrays(cfg), cfg["interval_s"], cfg["substeps"],
              cfg.get("swap_slowdown", 0.5), dtype)
    if engine == "mc":
        tape = Tape(cfg, seed, lam, COMPRESSED, max_arrivals)
        for _ in range(n_intervals):
            tasks, bw, lat = tape.next()
            sim.admit(tasks, [0] * len(tasks))
            sim.apply(sim.bestfit())
            yield sim, sim.advance(bw, lat), []
        return
    if engine != "splitplace":
        raise ValueError(f"the reference has no engine {engine!r}")
    mab_state, theta, dcfg = inputs
    ucb_c, phi, gamma, k = policy.MAB_HP
    tape = Tape(cfg, seed, lam, None, max_arrivals)
    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True), jax.default_device(cpu):
        mab = policy.MABState(*[jax.numpy.asarray(x) for x in mab_state])
        theta = jax.tree_util.tree_map(jax.numpy.asarray, theta)
        C = dcfg.max_containers
        jnp_dtype = jax.numpy.dtype(dtype)
        for _ in range(n_intervals):
            tasks, bw, lat = tape.next()
            k_ = len(tasks)
            sla_n = np.array([t["sla"] * 40000.0 / max(t["batch"], 1.0)
                              for t in tasks], np.float64).astype(np.float32)
            apps = np.array([t["app"] for t in tasks], np.int32)
            w = policy.width(k_)
            d = np.asarray(policy.mab_decide(
                mab, policy.pad_to(sla_n, w), policy.pad_to(apps, w),
                ucb_c))[:k_]
            sim.admit(tasks, d.tolist())
            req = sim.bestfit()
            rows = sim.containers()[:C]
            cur = sim.f["worker"][rows]
            warm = np.where(cur >= 0, cur, req[rows])
            dec = np.minimum(sim.t["decision"][sim.f["task_of"][rows]], 1)
            valid = policy.pad_to(np.ones(len(rows), bool), C)
            got = np.asarray(policy.daso_assign(
                dcfg, theta, sim.features(),
                policy.pad_to(warm.astype(np.int32), C), valid,
                policy.pad_to(dec.astype(np.int32), C), jnp_dtype))
            req[rows] = got[:len(rows)]
            sim.apply(req)
            out = sim.advance(bw, lat)
            order = np.argsort(out["tid"], kind="stable")
            nf = len(order)
            w = policy.width(nf)
            bt = np.maximum(out["batch"][order].astype(np.float64), 1.0)
            mab = policy.mab_feedback(
                mab, policy.pad_to(out["app"][order].astype(np.int32), w),
                policy.pad_to((out["sla"][order] * 40000.0 / bt)
                              .astype(np.float32), w),
                policy.pad_to((out["resp"][order] * 40000.0 / bt)
                              .astype(np.float32), w),
                policy.pad_to(out["acc"][order].astype(np.float32), w),
                policy.pad_to(np.minimum(out["decision"][order], 1)
                              .astype(np.int32), w),
                policy.pad_to(np.ones(nf, bool), w), phi, gamma, k)
            yield sim, out, [float(mab.eps), float(mab.rho),
                        float(mab.N[:, 0].sum()), float(mab.N[:, 1].sum())]


def stream_series(cfg, engine, seed, lam, n_intervals, max_arrivals,
                  inputs=None, dtype=np.float64):
    """(n_intervals, C) telemetry rows of a served stream from interval 0."""
    rows = [out["row"] + extra for _, out, extra in _intervals(
        cfg, engine, seed, lam, n_intervals, max_arrivals, inputs, dtype)]
    return np.asarray(rows, np.float64)


def grid_summary(cfg, engine, seed, lam, n_intervals, dtype=np.float64):
    """The summary of one grid cell (§6.4, eqs. 13-16)."""
    keys = ("resp", "sla", "acc", "wait", "decision")
    got = {k: [] for k in keys}
    energy, sim = dtype(0.0), None
    for sim, out, _ in _intervals(cfg, engine, seed, lam, n_intervals,
                                  1 << 30, None, dtype):
        for k in keys:
            got[k].extend(out[k].tolist())
        energy += dtype(out["energy"])
    r, s, a, w, d = (np.asarray(got[k], dtype) for k in keys)
    n = len(r)
    if not n:
        raise ValueError("no task finished in the grid cell")
    pwt = sim.per_worker_tasks
    cost = float(sim.cost_hr.sum()) * cfg["interval_s"] / 3600.0 \
        * n_intervals
    return {"accuracy": a.mean(), "sla_violations": (r > s).mean(),
            "reward": (((r <= s).astype(dtype) + a) / dtype(2)).mean(),
            "response_intervals": r.mean() / cfg["interval_s"],
            "wait_intervals": w.mean() / cfg["interval_s"],
            "exec_intervals": (r.mean() - w.mean()) / cfg["interval_s"],
            "energy_mwhr": energy / dtype(3.6e9),
            "fairness": pwt.sum() ** 2 / (len(pwt) * np.sum(pwt ** 2)
                                          + 1e-12),
            "cost_per_container": cost / max(1, int(pwt.sum())),
            "layer_fraction": (d == 0).mean(),
            "tasks_completed": float(n), "dropped_tasks": 0.0}
