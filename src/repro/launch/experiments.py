"""Batched experiment runner — (policy × seed × λ) grids over the edge sim.

The paper's headline results need hundreds of interval traces (Table 4:
7 policies × seeds × Γ=100 intervals on top of 200 MAB-pretraining
intervals; §6.4/A.3-A.5 sweeps more).  This module owns the canonical
interval loop (``run_trace``, Algorithm 1) and a grid driver
(``run_grid``) so every benchmark shares:

  * one MAB pretraining trace (§6.3) and one Gillis Q-pretraining trace
    per grid, instead of per-call copies;
  * the process-wide DASO jit cache — ``SurrogatePlacer`` training is
    shape-stable (fixed 64-row replay window, see
    ``daso.train_epoch_weighted``), so every surrogate policy in the grid
    reuses the same compiled ``optimize_placement`` / ``train_epoch``
    executables rather than re-tracing per instance;
  * two simulator backends: ``backend="soa"`` — the vectorized NumPy
    ``EdgeSim`` host loop (the §6.3 pretraining substrate and the
    object-level reference for every policy) — and ``backend="jax"`` —
    the fixed-capacity jitted simulator (``repro.env.jaxsim``), where
    ``run_grid_batched`` runs a whole (seed × λ) grid as one compiled
    vmapped call under any policy of ``jaxsim.resolve``'s table,
    deploying — or in ``mode="train"`` finetuning — the states
    ``pretrain`` produced.

``repro.core.splitplace.run_experiment`` and the Table 4 / sensitivity
benchmarks are thin wrappers over these entry points.
"""
from __future__ import annotations

import itertools
from typing import (Callable, Dict, Iterable, List, NamedTuple, Optional,
                    Sequence)

import numpy as np

from repro.core import splitplace as sp
from repro.core.policies import Policy
from repro.env.cluster import FLEET_SPEC, make_cluster
from repro.env.metrics import TELEMETRY_COLS, MetricsAccumulator
from repro.env.jaxsim.policies import (GILLIS_POLICIES, LEARNED_POLICIES,
                                       MAB_LEARNED_POLICIES, spec)
from repro.env.simulator import EdgeSim


class PretrainState(NamedTuple):
    """Everything the §6.3 pretraining pass produces.

    ``mab_state`` seeds both the host deciders and the in-kernel carried
    MAB; ``daso_theta``/``daso_cfg`` are the trained placement surrogate
    the jitted backend's array-form DASO stage consumes
    (``run_grid_batched(policy="splitplace", ...)``);
    ``daso_opt_state`` is the AdamW moment state the pretraining pass
    ended on, so ``mode="train"`` grids continue finetuning in-kernel
    from the exact pretrain optimizer trajectory; ``gillis_policy`` is
    the continued Gillis baseline object (host backend only).  Fields
    are ``None`` when the requested policy set doesn't need them.
    """
    mab_state: Optional[object] = None
    gillis_policy: Optional[object] = None
    daso_theta: Optional[object] = None
    daso_cfg: Optional[object] = None
    daso_opt_state: Optional[object] = None


def _products(pretrain_state, **given):
    """The pretraining products an entry runs with: each one passed in
    wins over ``pretrain_state``'s field of the same name."""
    if pretrain_state is None:
        return given
    return {k: getattr(pretrain_state, k) if v is None else v
            for k, v in given.items()}


def _jax_traces(policy, cells, trace_kw, **resolve_kw):
    """The jitted backend's entries by name: resolve ``policy`` through
    ``jaxsim.resolve`` (refusing a missing pretrained ``mab_state``), then
    compile one trace per ``(lam, seed)`` cell — both split variants
    when the engine decides in-kernel, else through the static decider.
    Returns ``(resolved, traces)``."""
    from repro.env import jaxsim
    r = jaxsim.resolve(policy, **resolve_kw)
    if r.needs_mab and resolve_kw.get("mab_state") is None:
        raise ValueError(f"policy {policy!r} needs a pretrained "
                         "mab_state (see pretrain())")
    if r.variants is None:
        return r, [jaxsim.compile_trace(r.decider, lam=lam, seed=seed,
                                        **trace_kw) for lam, seed in cells]
    return r, [jaxsim.compile_trace_dual(lam=lam, seed=seed,
                                         variants=r.variants, **trace_kw)
               for lam, seed in cells]


def run_trace(policy_name: Optional[str] = None, n_intervals: int = 100,
              lam: float = 6.0, seed: int = 0, mab_state=None,
              train: bool = False, cluster=None, apps=None,
              interval_s: float = 300.0, substeps: int = 30,
              policy: Optional[Policy] = None,
              backend: str = "soa", daso_theta=None, daso_cfg=None,
              daso_opt_state=None, mode: str = "deploy",
              substep_impl: Optional[str] = None,
              telemetry: str = "summary") -> dict:
    """Run one execution trace; returns the §6.4 metric summary.

    Pass ``policy`` to continue a pre-trained policy object (used to
    pretrain the Gillis baseline's Q-learner, mirroring the MAB's
    pretraining phase).  ``backend="jax"`` compiles the workload and runs
    the jitted fixed-capacity simulator under any policy name of the
    table (``jaxsim.resolve``: engine, starting state, trace variants),
    with the ``pretrain()`` products it needs.  ``mode`` selects the
    learned policies' in-kernel loop: ``"deploy"`` (UCB decisions,
    frozen surrogate) or ``"train"`` (ε-greedy decisions + in-kernel
    DASO finetuning; pass ``daso_opt_state`` to continue the pretrain
    optimizer trajectory).  On the host backend ``mode="train"`` is the
    ε-greedy training flag (same as ``train=True``).  ``substep_impl``
    selects the jitted backend's substep physics implementation
    (``"xla"``/``"pallas"``/``"ref"``; None → env/default).
    ``telemetry="interval"`` records the per-interval telemetry series
    on either backend and adds response/wait percentiles to the summary
    (exact on the host; binned with a reported error bound on the
    jitted backend)."""
    if mode not in ("deploy", "train"):
        raise ValueError(f"unknown mode {mode!r}")
    if backend == "jax":
        if policy is not None or train:
            raise ValueError("backend='jax' takes policy names only "
                             "(no policy objects; ε-greedy training is "
                             "mode='train' on the learned policies)")
        from repro.env import jaxsim
        r, (tr,) = _jax_traces(
            policy_name, [(lam, seed)],
            dict(n_intervals=n_intervals, interval_s=interval_s,
                 substeps=substeps, apps=apps, cluster=cluster),
            mode=mode, cluster=cluster, mab_state=mab_state,
            daso_theta=daso_theta, daso_cfg=daso_cfg,
            daso_opt_state=daso_opt_state)
        out = jaxsim.run_trace_engine(r.engine, tr, r.state(tr.seed),
                                      cluster=cluster,
                                      substep_impl=substep_impl,
                                      telemetry=telemetry)
        out["policy"] = policy_name
        return out
    if backend != "soa":
        raise ValueError(f"unknown backend {backend!r}")
    if telemetry not in ("summary", "interval"):
        raise ValueError(f"telemetry={telemetry!r} "
                         "(want 'summary' or 'interval')")
    tel = telemetry == "interval"
    train = train or mode == "train"
    sim = EdgeSim(cluster=cluster, lam=lam, seed=seed, apps=apps,
                  interval_s=interval_s, substeps=substeps)
    policy = policy or sp.make_policy(policy_name, sim.cluster.n, seed=seed,
                                      mab_state=mab_state, train=train)
    acc = MetricsAccumulator(interval_s=interval_s, telemetry=tel)
    for _ in range(n_intervals):
        tasks = sim.new_interval_tasks()
        decisions = policy.decider.decide(tasks)
        sim.admit(tasks, decisions)
        assignment = policy.placer.place(sim)
        sim.apply_placement(assignment)
        stats = sim.advance()
        policy.decider.feedback(stats.finished)
        if isinstance(policy.placer, sp.SurrogatePlacer):
            o_mab = (policy.decider.interval_reward(stats.finished)
                     if isinstance(policy.decider, sp.MABDecider)
                     else sp.MABDecider().interval_reward(stats.finished))
            policy.placer.feedback(o_mab, stats, sim)
        acc.update(stats)
    out = acc.summary()
    if tel:
        # object-loop policies have no kernel engine, so the series
        # carries the base columns only; percentiles are exact
        out.update(acc.percentiles())
        out["percentile_err_s"] = 0.0
        out["telemetry"] = {"cols": list(TELEMETRY_COLS),
                            "series": acc.telemetry_series()}
    out["policy"] = policy.name
    out["policy_obj"] = policy
    if isinstance(policy.decider, sp.MABDecider):
        out["mab_state"] = policy.decider.state
    return out


def pretrain(n_intervals: int, lam: float = 6.0, seed: int = 7,
             substeps: int = 30, interval_s: float = 300.0,
             policies: Sequence[str] = ("splitplace",)) -> PretrainState:
    """§6.3 pretraining pass: feedback-based ε-greedy MAB training with
    DASO online finetuning (and, when 'gillis' is requested, the Gillis
    Q-learner on the same budget).  Returns a ``PretrainState`` whose
    fields are None when not requested.

    The training trace runs on the host backend (ε-greedy exploration and
    surrogate finetuning are inherently sequential); the resulting
    ``mab_state`` and DASO ``theta`` then flow into either backend —
    host deciders/placers or the jitted in-kernel learned policies."""
    out = PretrainState()
    if any(p in MAB_LEARNED_POLICIES for p in policies):
        r = run_trace("splitplace", n_intervals=n_intervals, lam=lam,
                      seed=seed, train=True, substeps=substeps,
                      interval_s=interval_s)
        placer = r["policy_obj"].placer
        out = out._replace(mab_state=r["mab_state"],
                           daso_theta=placer.theta, daso_cfg=placer.cfg,
                           daso_opt_state=placer.opt_state)
    if any(p in GILLIS_POLICIES for p in policies):
        r = run_trace("gillis", n_intervals=n_intervals, lam=lam, seed=seed,
                      substeps=substeps, interval_s=interval_s)
        out = out._replace(gillis_policy=r["policy_obj"])
    return out


_SCALARS = (int, float)


def _record(pol: str, seed: int, lam: float, summary: dict) -> dict:
    rec = {"policy": pol, "seed": seed, "lam": lam}
    rec.update({k: float(v) for k, v in summary.items()
                if isinstance(v, _SCALARS) and not isinstance(v, bool)})
    return rec


def run_grid_batched(policy: str = "mc", seeds: Sequence[int] = (0,),
                     lams: Sequence[float] = (6.0,), n_intervals: int = 100,
                     substeps: int = 30, interval_s: float = 300.0,
                     apps=None, cluster=None, mab_state=None, seed_offset=0,
                     max_active: Optional[int] = None,
                     threads: Optional[int] = None,
                     pretrain_state: Optional[PretrainState] = None,
                     daso_theta=None, daso_cfg=None, daso_opt_state=None,
                     gillis_state=None, mab_hp=None, train_hp=None,
                     mode: str = "deploy", devices=None,
                     substep_impl: Optional[str] = None,
                     telemetry: str = "summary") -> List[dict]:
    """Run a whole (seed × λ) grid for one policy as ONE compiled vmapped
    call on the jitted backend; one record per trace, in
    ``itertools.product(lams, seeds)`` order (matching ``run_grid``).

    Every policy name of the table (``jaxsim.resolve``) is accepted:
    the static BestFit policies, the in-kernel learned engines (one
    carried state copy per grid cell, online decisions and per-interval
    feedback inside the kernel), and the static-decider surrogate arms.
    ``mode="train"`` switches the MAB policies to the full §6.3
    in-kernel training loop; ``mab_hp`` / ``train_hp`` override the
    engine defaults (the α×λ sensitivity sweep drives eq. 10's α/β
    through ``train_hp``).  Pass the pretraining products either as
    ``pretrain_state`` (the ``pretrain()`` result) or as the individual
    ``mab_state``/``daso_theta``/``daso_cfg``/``daso_opt_state``
    fields; ``gillis_state={"Q":..., "eps":...}`` continues a Gillis
    Q-table (records keep only scalar metrics: the final ``"gillis_q"``
    comes from running the resolved engine with
    ``jaxsim.run_grid_engine``).

    ``devices`` routes the grid through the shard_map dispatcher (1-D
    ``"grid"`` device mesh; ``"auto"`` = every visible device) instead of
    the host thread-chunk pool; ``substep_impl`` selects the substep
    physics implementation (``"xla"``/``"pallas"``/``"ref"``, None →
    ``JAXSIM_SUBSTEP_IMPL`` env or ``"xla"``).

    ``telemetry="interval"`` threads the driver's per-interval telemetry
    knob through every arm; records keep only the scalar percentile
    fields (``_record`` drops the non-scalar series payload) — run the
    ``jaxsim.resolve`` engine with ``jaxsim.run_grid_engine`` for the
    full series.

    Workload compilation is host-side and cheap; the interval dynamics
    (decisions + placement + substep physics + metric accumulators) run
    batched, so every sequential greedy placement iteration is shared by
    all grid cells.  See ``repro.env.jaxsim`` for the capacity/padding
    contract — records report ``dropped_tasks`` (0 unless ``max_active``
    was forced too small)."""
    from repro.env import jaxsim
    cells = list(itertools.product(lams, seeds))
    r, traces = _jax_traces(
        policy, [(lam, seed + seed_offset) for lam, seed in cells],
        dict(n_intervals=n_intervals, interval_s=interval_s,
             substeps=substeps, apps=apps, cluster=cluster),
        mode=mode, cluster=cluster, gillis_state=gillis_state,
        mab_hp=mab_hp, train_hp=train_hp,
        **_products(pretrain_state, mab_state=mab_state,
                    daso_theta=daso_theta, daso_cfg=daso_cfg,
                    daso_opt_state=daso_opt_state))
    outs = jaxsim.run_grid_engine(r.engine, traces, r.state, cluster=cluster,
                                  max_active=max_active, threads=threads,
                                  devices=devices, substep_impl=substep_impl,
                                  telemetry=telemetry)
    return [_record(policy, seed, lam, out)
            for (lam, seed), out in zip(cells, outs)]


def seeded_surrogate(num_workers: int, seed: int = 0):
    """SplitPlace's DASO placer without pretraining: a surrogate with
    random weights drawn from ``seed``, at the sizes of the host
    ``SurrogatePlacer`` (64 containers, 4 state features, the
    ``DASOConfig`` network defaults).  Returns ``(theta, cfg)``."""
    import jax

    from repro.core import daso
    cfg = daso.DASOConfig(num_workers=num_workers, max_containers=64,
                          state_features=4)
    return daso.init_surrogate(jax.random.PRNGKey(seed), cfg), cfg


def run_stream(policy: str = "mc", lam: float = 6.0, seed: int = 0,
               target_tasks: int = 10_000, chunk_intervals: int = 64,
               max_active: int = 512, interval_s: float = 300.0,
               substeps: int = 30, window_intervals: int = 256,
               apps=None, cluster=None,
               pretrain_state: Optional[PretrainState] = None,
               mab_state=None, daso_theta=None, daso_cfg=None,
               gillis_state=None, max_arrivals: Optional[int] = None,
               prefetch: int = 2, substep_impl: Optional[str] = None,
               on_chunk: Optional[Callable] = None) -> dict:
    """Always-on serving run: stream Poisson arrivals through the
    chunked jitted interval program until ``target_tasks`` tasks have
    been offered (``repro.env.jaxsim.stream.serve``); a host feeder
    thread fills the next chunk's arrival tape while the device executes
    the current one.

    Accepts every policy name of the table (``jaxsim.resolve``, deploy
    mode) and the same pretraining products as ``run_grid_batched``;
    the MAB policies cold-start when no ``mab_state`` is given.
    Returns the serving report —
    admission ledger, ring occupancy, rolling-window QPS / percentile /
    violation metrics, and the cumulative §6.4 summary — annotated with
    the grid coordinates."""
    from repro.env.jaxsim import stream
    cluster = cluster or make_cluster()
    engine, es0, feeder_kw = stream.make_stream_policy(
        policy, cluster=cluster, seed=seed, gillis_state=gillis_state,
        **_products(pretrain_state, mab_state=mab_state,
                    daso_theta=daso_theta, daso_cfg=daso_cfg))
    feeder = stream.StreamFeeder(lam=lam, seed=seed, interval_s=interval_s,
                                 substeps=substeps, cluster=cluster,
                                 apps=apps, max_arrivals=max_arrivals,
                                 **feeder_kw)
    rep = stream.serve(engine, es0, feeder, chunk_intervals=chunk_intervals,
                       max_active=max_active, target_tasks=target_tasks,
                       window_intervals=window_intervals, prefetch=prefetch,
                       substep_impl=substep_impl, on_chunk=on_chunk)
    rep.update(policy=policy, lam=lam, seed=seed)
    return rep


def run_grid(policies: Sequence[str], seeds: Sequence[int] = (0,),
             lams: Sequence[float] = (6.0,), n_intervals: int = 100,
             substeps: int = 30, interval_s: float = 300.0, apps=None,
             cluster_factory: Optional[Callable[[], object]] = None,
             pretrain_intervals: int = 0, pretrain_lam: Optional[float] = None,
             pretrain_seed: int = 7, mab_state=None, gillis_policy=None,
             progress: Optional[Callable[[str], None]] = None,
             backend: str = "soa", daso_theta=None,
             daso_cfg=None, daso_opt_state=None,
             mode: str = "deploy") -> List[dict]:
    """Run the full (λ × policy × seed) grid; one record per trace.

    ``pretrain_intervals > 0`` runs the shared §6.3 pretraining pass once
    for the whole grid (skipped for strategies that don't consume it).
    The Gillis policy object is continued across its grid cells, matching
    the sequential-evaluation protocol of the seed benchmarks.  A fresh
    cluster comes from ``cluster_factory`` per trace (default: the Table 3
    50-worker fleet).

    ``backend="jax"`` routes every policy through ``run_grid_batched`` —
    one compiled call per policy instead of a Python loop per cell;
    record order matches the host backend.  Every policy name of
    ``jaxsim.resolve``'s table is accepted; the pretraining pass
    (host-side, shared) runs when a policy needs states (the table's
    ``needs_mab`` / ``needs_daso``) that weren't passed in.  ``mode="train"``
    selects the in-kernel §6.3 training loop for the learned policies on
    the jitted backend (ε-greedy decisions + DASO finetuning in the
    carry) and the host training flag on ``backend="soa"``."""
    if mode not in ("deploy", "train"):
        raise ValueError(f"unknown mode {mode!r}")
    if backend == "jax":
        # pretrain only for what the requested policies actually consume
        # (the table's needs_mab / needs_daso; the in-kernel Gillis
        # baseline needs nothing — fresh Q/ε per grid).  The pass is a
        # full host-loop trace — the most expensive step in the pipeline.
        rows = [spec(p) for p in policies]
        needs_mab = any(r.needs_mab for r in rows) and mab_state is None
        needs_daso = any(r.needs_daso for r in rows) and daso_theta is None
        pre = None
        if pretrain_intervals and (needs_mab or needs_daso):
            pre = pretrain(pretrain_intervals,
                           lam=pretrain_lam if pretrain_lam is not None
                           else lams[0],
                           seed=pretrain_seed, substeps=substeps,
                           interval_s=interval_s)
        products = _products(pre, mab_state=mab_state,
                             daso_theta=daso_theta, daso_cfg=daso_cfg,
                             daso_opt_state=daso_opt_state)
        records = []
        for pol in policies:
            # mab_state passes through untouched to static policies: only
            # the frozen-UCB decider ("bestfit-mab") consumes it there;
            # learned policies thread it through the kernel carry.  mode
            # only applies to learned policies — static ones have no
            # training loop, so a mixed list runs them in deploy form
            # (mirroring backend="soa", where train=True is a no-op for
            # policies without a learning decider)
            records += run_grid_batched(
                pol, seeds=seeds, lams=lams, n_intervals=n_intervals,
                substeps=substeps, interval_s=interval_s, apps=apps,
                cluster=cluster_factory() if cluster_factory else None,
                mode=mode if pol in LEARNED_POLICIES else "deploy",
                **products)
        # run_grid order is (lam, policy, seed); per-policy batches are
        # (lam, seed) — reorder to match the host backend exactly
        by_cell = {(r["lam"], r["policy"], r["seed"]): r for r in records}
        records = [by_cell[(lam, pol, seed)]
                   for lam, pol, seed in itertools.product(lams, policies,
                                                           seeds)]
        if progress:
            for rec in records:
                progress(f"lam={rec['lam']:g} {rec['policy']:15s} "
                         f"seed={rec['seed']} reward={rec['reward']:.4f} "
                         f"viol={rec['sla_violations']:.2f}")
        return records
    if pretrain_intervals:
        pre = pretrain(pretrain_intervals,
                       lam=pretrain_lam if pretrain_lam is not None
                       else lams[0],
                       seed=pretrain_seed, substeps=substeps,
                       interval_s=interval_s,
                       policies=[p for p in policies
                                 if (p in MAB_LEARNED_POLICIES
                                     and mab_state is None)
                                 or (p in GILLIS_POLICIES
                                     and gillis_policy is None)])
        mab_state = mab_state if mab_state is not None else pre.mab_state
        gillis_policy = gillis_policy if gillis_policy is not None \
            else pre.gillis_policy
    records = []
    for lam, pol, seed in itertools.product(lams, policies, seeds):
        ms = mab_state if pol in MAB_LEARNED_POLICIES else None
        r = run_trace(pol, n_intervals=n_intervals, lam=lam, seed=seed,
                      mab_state=ms, train=mode == "train",
                      substeps=substeps,
                      interval_s=interval_s, apps=apps,
                      cluster=cluster_factory() if cluster_factory else None,
                      policy=gillis_policy if pol in GILLIS_POLICIES
                      else None)
        records.append(_record(pol, seed, lam, r))
        if progress:
            rec = records[-1]
            progress(f"lam={lam:g} {pol:15s} seed={seed} "
                     f"reward={rec['reward']:.4f} "
                     f"viol={rec['sla_violations']:.2f}")
    return records


def aggregate(records: Iterable[dict],
              by: Sequence[str] = ("policy",)) -> Dict:
    """Group records and average every numeric metric; adds
    ``reward_std`` and ``n_runs``.  Keys are the ``by`` values (a scalar
    for a single key, else a tuple)."""
    groups: Dict = {}
    for rec in records:
        key = tuple(rec[k] for k in by)
        groups.setdefault(key[0] if len(by) == 1 else key, []).append(rec)
    out = {}
    # grid coordinates are labels, not metrics — never average them in
    skip = set(by) | {"policy", "seed", "lam"}
    for key, rs in groups.items():
        agg = {k: float(np.mean([r[k] for r in rs]))
               for k in rs[0] if k not in skip
               and isinstance(rs[0][k], _SCALARS)}
        agg["reward_std"] = float(np.std([r["reward"] for r in rs]))
        agg["n_runs"] = len(rs)
        out[key] = agg
    return out


def scaled_fleet(factor: int):
    """Scale the Table 3 fleet spec by an integer factor (2 → a
    100-worker cluster) — the SoA simulator makes these affordable."""
    return [(name, qty * factor) for name, qty in FLEET_SPEC]


def make_scaled_cluster(factor: int, **kw):
    return make_cluster(fleet=scaled_fleet(factor), **kw)
