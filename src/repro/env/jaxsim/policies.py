"""The policy table of the jitted backend: policy name → engine,
starting engine state and trace variants (``resolve``), plus the static
deciders.

Every device-side entry point by name (``launch.experiments.run_trace``
/ ``run_grid_batched`` and ``stream.make_stream_policy``) asks
``resolve`` and decides nothing for itself; the driver
(``driver.run_trace_engine`` / ``run_grid_engine``) only runs the
engine it is handed.

Static deciders realize fragments at trace-compile time, so they must be
a pure function of the task (and optionally a frozen learned state),
with no interval-feedback loop:

  * fixed LAYER / SEMANTIC / COMPRESSED (the paper's L+*, S+*, MC arms);
  * ``roundrobin`` — the i % 3 mixed-decision trace the throughput and
    equivalence suites use;
  * ``threshold``  — deadline-vs-reference heuristic (layer when the SLA
    clears 1.6× the unloaded layer-chain reference, else semantic —
    the Gillis-style context split without the Q-loop);
  * ``mab-static`` — UCB deployment decisions (eq. 9) from a *frozen*
    pretrained ``MABState``; the ε-greedy training loop stays on the
    host backend.

Placement for the static deciders is the vectorized BestFit kernel; the
learned policies run their full loop — including ``mode="train"``
ε-greedy exploration and DASO finetuning — inside the kernel.  Every
decider here also satisfies the host ``Decider`` protocol
(``decide``/``feedback``), so the same object can drive ``run_trace`` on
the SoA backend for apples-to-apples benchmarking.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.env.cluster import Cluster, make_cluster
from repro.env.jaxsim import engines
from repro.env.workload import (COMPRESSED, LAYER, SEMANTIC,
                                layer_ref_response_s)


class StaticFixedDecider:
    def __init__(self, decision: int, name: str):
        self.decision = decision
        self.name = name

    def decide(self, tasks) -> List[int]:
        return [self.decision] * len(tasks)

    def feedback(self, finished):
        pass


class RoundRobinDecider:
    """i % 3 over each interval's arrivals (the sim_throughput trace)."""
    name = "bestfit-rr"

    def decide(self, tasks) -> List[int]:
        return [i % 3 for i in range(len(tasks))]

    def feedback(self, finished):
        pass


class ThresholdDecider:
    """LAYER when the deadline clears ``margin``× the unloaded layer-split
    reference time (batch-scaled), else SEMANTIC."""
    name = "bestfit-threshold"

    def __init__(self, margin: float = 1.6):
        self.margin = margin

    def decide(self, tasks) -> List[int]:
        out = []
        for t in tasks:
            ref = layer_ref_response_s(t.app) * t.batch / 40000.0
            out.append(LAYER if t.sla_s >= self.margin * ref else SEMANTIC)
        return out

    def feedback(self, finished):
        pass


class StaticMABDecider:
    """Frozen-state UCB decisions (deploy-mode MAB without the feedback
    loop — the state never changes, so decisions are trace-compilable)."""
    name = "bestfit-mab"

    def __init__(self, state, ucb_c: float = 0.5):
        if state is None:
            raise ValueError("bestfit-mab needs a pretrained mab_state")
        from repro.core import mab as mab_mod
        self._mab = mab_mod
        self.state = state
        self.ucb_c = ucb_c

    def decide(self, tasks) -> List[int]:
        out = []
        for t in tasks:
            sla = jnp.float32(t.sla_s * 40000.0 / max(t.batch, 1))
            d, _ = self._mab.decide_ucb(self.state, sla, t.app, self.ucb_c)
            out.append(int(d))
        return out

    def feedback(self, finished):
        pass


class _Row(NamedTuple):
    family: str                  # "static" | "static-daso" | "mab" | "gillis"
    needs_mab: bool = False      # takes a pretrained MABState
    needs_daso: bool = False     # places with the pretrained DASO surrogate
    blind: bool = False          # GOBI: decision-blind surrogate input
    arm: int = 0                 # static-daso: variant index (−1 random)
    decider: Optional[Callable] = None   # static: mab_state -> decider


#: THE table: every policy name the jitted backend knows.
#:
#: * ``static`` — a compile-time decider + BestFit placement;
#: * ``static-daso`` — the three static-decider baseline arms of Table 4
#:   over dual traces, placed by the frozen surrogate;
#: * ``mab`` — in-kernel MAB decisions with Algorithm-1 feedback, in
#:   ``mode="deploy"`` (UCB, frozen surrogate) or ``"train"`` (ε-greedy +
#:   in-kernel DASO finetuning);
#: * ``gillis`` — the baseline's contextual ε-greedy Q-table over
#:   (LAYER, COMPRESSED) dual traces; inherently online, so ``mode`` is
#:   ignored.
#:
#: ``blind`` is the GOBI rule: the surrogate input's decision one-hot
#: slice is zeroed (``daso_cfg.decision_aware=False``, the host
#: ``SurrogatePlacer(decision_aware=False)`` ablation).
TABLE = {
    "mc": _Row("static", decider=lambda st: StaticFixedDecider(
        COMPRESSED, "mc")),
    "bestfit-layer": _Row("static", decider=lambda st: StaticFixedDecider(
        LAYER, "bestfit-layer")),
    "bestfit-semantic": _Row("static", decider=lambda st: StaticFixedDecider(
        SEMANTIC, "bestfit-semantic")),
    "bestfit-rr": _Row("static", decider=lambda st: RoundRobinDecider()),
    "bestfit-threshold": _Row("static",
                              decider=lambda st: ThresholdDecider()),
    "bestfit-mab": _Row("static", needs_mab=True, decider=StaticMABDecider),
    "mab": _Row("mab", needs_mab=True),
    "splitplace": _Row("mab", needs_mab=True, needs_daso=True),
    "mab+gobi": _Row("mab", needs_mab=True, needs_daso=True, blind=True),
    "gillis": _Row("gillis"),
    "layer+gobi": _Row("static-daso", needs_daso=True, blind=True, arm=0),
    "semantic+gobi": _Row("static-daso", needs_daso=True, blind=True, arm=1),
    "random+daso": _Row("static-daso", needs_daso=True, arm=-1),
}

#: policy names with a compile-time decider (all BestFit-placed)
STATIC_POLICIES = tuple(p for p, r in TABLE.items() if r.family == "static")
#: policies whose learning loop runs *inside* the jitted kernel
LEARNED_POLICIES = tuple(p for p, r in TABLE.items()
                         if r.family in ("mab", "gillis"))
#: the subset that consumes a pretrained ``MABState``
MAB_LEARNED_POLICIES = tuple(p for p, r in TABLE.items()
                             if r.family == "mab")
#: the subset that consumes the pretrained DASO surrogate (theta + cfg)
DASO_LEARNED_POLICIES = tuple(p for p in MAB_LEARNED_POLICIES
                              if TABLE[p].needs_daso)
#: the static-decider baseline arms and the ``engines.MAB_VARIANTS``
#: index each realizes every row (−1 = uniform random per row)
STATIC_DASO_ARMS = {p: r.arm for p, r in TABLE.items()
                    if r.family == "static-daso"}
#: the Gillis Q-learner (the host backend pretrains its policy object)
GILLIS_POLICIES = tuple(p for p, r in TABLE.items() if r.family == "gillis")


def spec(policy: str) -> _Row:
    """The table row of ``policy``: its engine family and what it takes
    from pretraining."""
    if policy not in TABLE:
        raise ValueError(f"unknown policy {policy!r} (want one of "
                         f"{tuple(TABLE)})")
    return TABLE[policy]


def make_static_decider(policy: str, mab_state=None,
                        seed: int = 0):
    """Resolve a jitted-backend policy name to its compile-time decider."""
    del seed  # static deciders are deterministic
    row = TABLE.get(policy)
    if row is None or row.decider is None:
        raise ValueError(
            f"policy {policy!r} is not static (jit backend supports "
            f"{STATIC_POLICIES}; learning deciders/placers need "
            f"backend='soa')")
    return row.decider(mab_state)


def host_policy(policy: str, mab_state=None, seed: int = 0):
    """The same (static decider, BestFit) pair as a host ``Policy`` object
    for the SoA interval loop — used by benchmarks to compare backends on
    identical policy behaviour."""
    from repro.core.splitplace import BestFitPlacer, Policy
    return Policy(policy, make_static_decider(policy, mab_state, seed),
                  BestFitPlacer())


@dataclasses.dataclass(frozen=True)
class Resolved:
    """Everything the device path needs to run one policy.

    ``engine`` is the frozen engine (the runner-cache key); ``variants``
    the decision codes a dual trace must realize (``None``: a static
    trace compiled from ``decider``); ``state(seeds)`` the starting
    engine state for one seed, or for a list of seeds (one grid chunk:
    per-cell PRNG keys are stacked); ``needs_mab`` / ``needs_daso`` what
    the policy takes from pretraining."""

    engine: object
    variants: Optional[tuple]
    decider: object
    state: Callable
    needs_mab: bool
    needs_daso: bool


def _keys(seeds):
    """``engines.trace_train_key`` of one seed, or stacked over a list."""
    if np.ndim(seeds):
        return jnp.stack([engines.trace_train_key(s) for s in seeds])
    return engines.trace_train_key(seeds)


def _train_state(daso_cfg, mab_state, theta, daso_opt_state, keys):
    """Training-carry starting state; built under ``enable_x64`` so the
    replay window is float64 like the in-carry appends.  The AdamW state
    is fresh zeros when the caller didn't hand over the pretraining
    optimizer moments."""
    win, opt = {}, ()
    if daso_cfg is not None:
        from repro.core import daso
        from repro.optim.optimizers import adamw_init
        with jax.enable_x64(True):
            win = daso.window_init(daso_cfg)
            opt = adamw_init(theta) if daso_opt_state is None \
                else daso_opt_state
    return {"mab": mab_state, "theta": theta, "opt": opt, "win": win,
            "key": keys}


def resolve(policy: str, *, mode: str = "deploy",
            cluster: Optional[Cluster] = None, mab_state=None,
            daso_theta=None, daso_cfg=None, daso_opt_state=None,
            gillis_state=None, mab_hp=None, train_hp=None, gillis_hp=None,
            num_apps: int = 3) -> Resolved:
    """Turn a policy name into its engine, starting state and trace
    variants.  Hyper-parameters default to ``engines.MAB_HP`` /
    ``TRAIN_HP`` / ``GILLIS_HP``; ``gillis_state`` continues a Gillis
    Q-table (fresh zeros/ε₀ when None); the DASO products are checked
    against ``cluster`` (the Table-3 fleet when None).  ``mab_state`` is
    taken as given (``None`` for a policy that needs one is the caller's
    to refuse or to cold-start)."""
    if mode not in ("deploy", "train"):
        raise ValueError(f"unknown mode {mode!r}")
    row = spec(policy)
    if mode == "train" and row.family in ("static", "static-daso"):
        raise ValueError(f"policy {policy!r} is static — mode='train' "
                         f"needs a learned policy ({LEARNED_POLICIES})")

    def resolved(engine, state, decider=None):
        return Resolved(engine, engine.variants, decider, state,
                        row.needs_mab, row.needs_daso)

    if row.family == "static":
        return resolved(engines.StaticEngine(), lambda seeds: (),
                        make_static_decider(policy, mab_state))
    if row.family == "gillis":
        hp = tuple(gillis_hp or engines.GILLIS_HP)
        st = gillis_state or engines.gillis_init_state(num_apps, hp[0])
        return resolved(engines.GillisEngine(gillis_hp=hp), lambda seeds: {
            "Q": np.asarray(st["Q"], np.float64),
            "eps": np.float64(st["eps"]), "key": _keys(seeds),
            "layer_ref": engines.gillis_layer_ref(num_apps)})
    cfg, theta = None, ()                 # BestFit placement: no surrogate
    if row.needs_daso:
        if daso_cfg is None or daso_theta is None:
            raise ValueError(f"policy {policy!r} places with the DASO "
                             "surrogate: pass daso_cfg/daso_theta (from "
                             "launch.experiments.pretrain or "
                             "seeded_surrogate)")
        n = (cluster or make_cluster()).n
        if daso_cfg.num_workers != n:
            raise ValueError(f"daso_cfg.num_workers={daso_cfg.num_workers} "
                             f"!= cluster size {n}")
        cfg = daso_cfg._replace(decision_aware=False) if row.blind \
            else daso_cfg
        theta = daso_theta
    if row.family == "static-daso":
        def static_daso_es(seeds):        # the random arm draws bits
            if row.arm < 0:
                return {"theta": theta, "key": _keys(seeds)}
            return {"theta": theta}
        return resolved(
            engines.StaticDeciderDASOEngine(arm=row.arm, daso_cfg=cfg,
                                            name=policy), static_daso_es)
    mab_hp = tuple(mab_hp or engines.MAB_HP)
    if mode == "deploy":
        return resolved(engines.MABDeployEngine(mab_hp=mab_hp, daso_cfg=cfg),
                        lambda seeds: {"mab": mab_state, "theta": theta})
    return resolved(
        engines.MABTrainEngine(mab_hp=mab_hp,
                               train_hp=tuple(train_hp or engines.TRAIN_HP),
                               daso_cfg=cfg),
        lambda seeds: _train_state(cfg, mab_state, theta, daso_opt_state,
                                   _keys(seeds)))
