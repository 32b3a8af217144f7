"""PolicyEngine zoo — the composable policy layer of the unified
interval program.

``driver._trace_program`` runs ONE interval pipeline for every policy:

    arr, es  = engine.decide(es, trace, t)          # split decisions
    state    = kernels.admit(state, arr)
    req, es, aux = engine.place(es, state, cl, trace, t, interval_s)
    state    = kernels.apply_requests(state, cl, req)
    ... physics (kernels.run_substeps) ...
    es       = engine.feedback(es, state, fin, util, aux, t, interval_s)

with a single carry layout ``(state, acc, engine_state)``.  An engine is
a **frozen, hashable** dataclass of static configuration — it is part of
the runner-cache key, so two calls with equal engines share one compiled
executable — and its ``engine_state`` (``es``) is an ordinary dynamic
pytree threaded through the ``fori_loop`` carry (MAB state, surrogate
theta, optimizer moments, replay window, PRNG key, Gillis Q-table…).

Protocol (duck-typed; every engine below implements it):

  * ``variants`` — a class constant, not a field (so it stays out of the
    hash and the cache key): the decision codes of the dual trace's V
    axis the engine decides between, or ``None`` for a static
    (pre-realized) trace; the driver checks every trace against it;
  * ``batch_axes()``  — vmap ``in_axes`` prefix for ``es`` under the
    batched grid runner (``0`` for per-cell leaves like PRNG keys,
    ``None`` for shared starting state);
  * ``decide(es, trace, t) -> (arr, es)`` — the admit-ready arrival
    dict for interval ``t`` (static engines slice the pre-realized
    trace; learned engines decide + ``select_variant`` a dual trace);
  * ``place(es, state, cl, trace, t, interval_s) -> (req, es, aux)`` —
    the (K, F) worker-request matrix ``apply_requests`` repairs;
    ``aux`` carries intra-interval data from place to feedback (the
    train engine's packed surrogate input);
  * ``feedback(es, state, fin, util, aux, t, interval_s) -> es`` —
    end-of-interval learning over the finished-slot mask + per-worker
    utilization;
  * ``outputs(es) -> dict`` — extra kernel outputs appended to the raw
    result (final MAB scalars, finetuned theta, Gillis Q/ε);
  * ``summarize(out, summary) -> summary`` — host-side: lift those
    extras into the §6.4 summary dict;
  * ``telemetry_cols() -> tuple[str, ...]`` / ``telemetry_row(es) ->
    jnp.ndarray | None`` — the engine's per-interval learning-signal
    columns for the driver's ``telemetry="interval"`` series (appended
    after ``metrics.TELEMETRY_COLS``); ``telemetry_row`` returns a
    float64 vector matching ``telemetry_cols`` evaluated on the
    END-of-interval ``es`` (after feedback), or ``None`` when the
    engine has no columns.

Adding a policy = adding one engine here (plus its row in
``policies.resolve`` and its host parity oracle in ``reference.py``);
the driver, runner cache, chunk dispatcher and summary path are shared
and untouched.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import daso as daso_mod
from repro.core.daso import DASOConfig
from repro.env.jaxsim import kernels
from repro.env.workload import COMPRESSED, LAYER, SEMANTIC

#: arrival keys of a single-variant (static) compiled trace
STATIC_ARR_KEYS = ("valid", "sla", "arrival_s", "app", "batch", "acc",
                   "decision", "chain", "nfrag", "instr", "ram",
                   "out_bytes")
#: variant-independent / per-variant keys of a dual compiled trace
SHARED_KEYS = ("valid", "sla", "arrival_s", "app", "batch")
VAR_KEYS = ("vacc", "vchain", "vnfrag", "vinstr", "vram", "vout")

#: the dual-trace variant codes each engine family decides between
MAB_VARIANTS = (LAYER, SEMANTIC)
GILLIS_VARIANTS = (LAYER, COMPRESSED)

#: MAB hyperparameters of the in-kernel learned policies, matching the
#: host ``MABDecider`` defaults: (ucb_c, phi, gamma, k)
MAB_HP = (0.5, 0.3, 0.3, 0.1)

#: DASO finetuning hyperparameters, matching the host ``SurrogatePlacer``
#: defaults: (alpha, beta, train_steps, place_min, train_min) — the last
#: two are the cold-start gates (ascend the surrogate only after
#: ``place_min`` replay records, train only after ``train_min``);
#: lowering them lets short test/benchmark horizons exercise the
#: finetuned-ascent path the defaults reserve for long traces
TRAIN_HP = (0.5, 0.5, 4, 32, 8)

#: Gillis baseline hyperparameters, matching the host ``GillisDecider``
#: defaults: (eps0, lr, decay)
GILLIS_HP = (0.5, 0.3, 0.995)


def trace_train_key(seed: int):
    """The per-trace decision PRNG key of the in-kernel training, Gillis
    and ``random+daso`` loops — shared with the host parity oracles in
    ``reference`` so both backends draw identical ε-greedy bits."""
    return jax.random.PRNGKey(seed)


def gillis_layer_ref(num_apps: int = 3):
    """The (num_apps,) unloaded layer-chain reference table the Gillis
    context bucket divides deadlines by (``mab.gillis_bucket``) — built
    once here so the kernel engine and the host parity oracle consume
    the identical float64 values."""
    from repro.env.workload import layer_ref_response_s
    return np.array([layer_ref_response_s(a) for a in range(num_apps)],
                    np.float64)


def gillis_init_state(num_apps: int = 3, eps0: float = GILLIS_HP[0]):
    """Fresh host-side Gillis carry pieces (Q-table + ε) — NumPy float64
    so the driver's ``enable_x64`` asarray keeps full precision.  Pass a
    previous run's ``{"Q": gillis_q, "eps": gillis_eps}`` instead to
    continue a pretrained baseline."""
    return {"Q": np.zeros((num_apps, 2, 2), np.float64),
            "eps": np.float64(eps0)}


def _interval_rows(trace, t):
    shared = {k: trace[k][t] for k in SHARED_KEYS}
    var = {k: trace[k][t] for k in VAR_KEYS}
    return shared, var


def _mab_scalars(out, s):
    s["mab_eps"] = float(out["mab_eps"])
    s["mab_rho"] = float(out["mab_rho"])
    s["mab_t"] = int(out["mab_t"])
    return s


#: per-interval learning-signal columns shared by both MAB engines:
#: exploration/threshold scalars plus cumulative per-arm decision counts
#: (summed over the two SLA contexts)
MAB_TELEMETRY_COLS = ("mab_eps", "mab_rho", "mab_n_layer",
                      "mab_n_semantic")


def _mab_telemetry_row(mab):
    f8 = jnp.float64
    return jnp.stack([mab.eps.astype(f8), mab.rho.astype(f8),
                      mab.N[:, 0].sum().astype(f8),
                      mab.N[:, 1].sum().astype(f8)])


@dataclasses.dataclass(frozen=True)
class StaticEngine:
    """Pre-realized split decisions + BestFit placement; ``es`` is
    empty.  The trace carries one realized variant per task, so decide
    is a pure slice of the compiled arrays."""

    name: str = "static"
    variants = None

    def batch_axes(self):
        return None

    def decide(self, es, trace, t):
        return {k: trace[k][t] for k in STATIC_ARR_KEYS}, es

    def place(self, es, state, cl, trace, t, interval_s):
        return kernels.bestfit_requests(state, cl), es, None

    def feedback(self, es, state, fin, util, aux, t, interval_s):
        return es

    def outputs(self, es):
        return {}

    def summarize(self, out, s):
        return s

    def telemetry_cols(self):
        return ()

    def telemetry_row(self, es):
        return None


@dataclasses.dataclass(frozen=True)
class StaticDeciderDASOEngine:
    """The three remaining trivial Table-4 baseline arms as ONE engine:
    a static split decider over a dual (LAYER, SEMANTIC) trace plus the
    array-form DASO placement stage ascending a *frozen* pretrained
    surrogate.  ``arm`` picks the variant index every row — 0 for
    ``layer+gobi``, 1 for ``semantic+gobi``, or −1 for uniform-random
    rows (``random+daso``, per-interval fold-in bits like the train/
    Gillis engines; same algorithm as the host ``RandomDecider``,
    different bitstream).  The GOBI arms pass a
    ``decision_aware=False`` cfg — the surrogate input's decision
    one-hot slice is zeroed (``daso.pack_input``), mirroring the host
    ``SurrogatePlacer(decision_aware=False)``.  ``es = {"theta":
    pytree}`` (+ per-cell ``"key"`` for the random arm)."""

    arm: int
    daso_cfg: DASOConfig
    name: str = "static-daso"
    variants = MAB_VARIANTS

    def batch_axes(self):
        if self.arm < 0:
            return {"theta": None, "key": 0}
        return None

    def decide(self, es, trace, t):
        shared, var = _interval_rows(trace, t)
        A = shared["sla"].shape[0]
        if self.arm < 0:
            # per-row fold-in (not one batched draw): row r's bit depends
            # only on (key, t, r), so the host replay walking the dense
            # valid prefix draws identical bits regardless of A padding
            key_t = jax.random.fold_in(es["key"], t)
            d = jax.vmap(lambda r: jax.random.bernoulli(
                jax.random.fold_in(key_t, r)))(
                    jnp.arange(A, dtype=jnp.int32)).astype(jnp.int32)
        else:
            d = jnp.full((A,), self.arm, jnp.int32)
        return kernels.select_variant(shared, var, d), es

    def place(self, es, state, cl, trace, t, interval_s):
        req = kernels.bestfit_requests(state, cl)
        feat = kernels.state_features_k(state, cl, trace["lat_prev"][t],
                                        interval_s)
        req = kernels.daso_requests(self.daso_cfg, es["theta"], state,
                                    feat, req)
        return req, es, None

    def feedback(self, es, state, fin, util, aux, t, interval_s):
        return es

    def outputs(self, es):
        return {}

    def summarize(self, out, s):
        return s

    def telemetry_cols(self):
        return ()

    def telemetry_row(self, es):
        return None


@dataclasses.dataclass(frozen=True)
class MABDeployEngine:
    """Online UCB MAB decisions (eq. 9) + Algorithm-1 feedback against
    the carried ``MABState``; optional array-form DASO placement stage
    ascending a *frozen* pretrained surrogate.  ``decision_aware=False``
    in ``daso_cfg`` is the GOBI ablation — the surrogate input's
    decision one-hot slice is zeroed (``daso.pack_input``), everything
    else identical.  ``es = {"mab": MABState, "theta": pytree | ()}``."""

    mab_hp: Tuple[float, float, float, float]
    daso_cfg: Optional[DASOConfig] = None
    name: str = "mab-deploy"
    variants = MAB_VARIANTS

    def batch_axes(self):
        return None

    def decide(self, es, trace, t):
        shared, var = _interval_rows(trace, t)
        d = kernels.mab_decide_arrivals(es["mab"], shared, self.mab_hp[0])
        return kernels.select_variant(shared, var, d), es

    def place(self, es, state, cl, trace, t, interval_s):
        req = kernels.bestfit_requests(state, cl)
        if self.daso_cfg is not None:
            feat = kernels.state_features_k(state, cl, trace["lat_prev"][t],
                                            interval_s)
            req = kernels.daso_requests(self.daso_cfg, es["theta"], state,
                                        feat, req)
        return req, es, None

    def feedback(self, es, state, fin, util, aux, t, interval_s):
        _, phi, gamma, k_rbed = self.mab_hp
        es = dict(es)
        es["mab"] = kernels.mab_feedback(es["mab"], state, fin, phi, gamma,
                                         k_rbed)
        return es

    def outputs(self, es):
        mab = es["mab"]
        return {"mab_eps": mab.eps, "mab_rho": mab.rho, "mab_t": mab.t}

    def summarize(self, out, s):
        return _mab_scalars(out, s)

    def telemetry_cols(self):
        return MAB_TELEMETRY_COLS

    def telemetry_row(self, es):
        return _mab_telemetry_row(es["mab"])


@dataclasses.dataclass(frozen=True)
class MABTrainEngine:
    """The full §6.3 training loop in the carry: ε-greedy MAB decisions
    (eq. 6, prefix-stable fold-in keys), Algorithm-1 feedback, and —
    with a ``daso_cfg`` — online DASO finetuning (cold-start-gated
    ascent of the CARRIED theta, replay-window appends, weighted train
    epochs).  ``es = {"mab", "theta", "opt", "win", "key"}``; only the
    per-trace PRNG key is batched per grid cell."""

    mab_hp: Tuple[float, float, float, float]
    train_hp: Tuple[float, float, int, int, int]
    daso_cfg: Optional[DASOConfig] = None
    name: str = "mab-train"
    variants = MAB_VARIANTS

    def batch_axes(self):
        return {"mab": None, "theta": None, "opt": None, "win": None,
                "key": 0}

    def decide(self, es, trace, t):
        shared, var = _interval_rows(trace, t)
        key_t = jax.random.fold_in(es["key"], t)
        d = kernels.mab_decide_arrivals_train(es["mab"], shared, key_t)
        return kernels.select_variant(shared, var, d), es

    def place(self, es, state, cl, trace, t, interval_s):
        req = kernels.bestfit_requests(state, cl)
        aux = None
        if self.daso_cfg is not None:
            feat = kernels.state_features_k(state, cl, trace["lat_prev"][t],
                                            interval_s)
            # cold-start gate reads the PRE-interval record count — place
            # happens before this interval's (x, y) append, and exactly
            # one record lands per interval, so the count equals the
            # (unbatched) interval index: gating on t keeps lax.cond a
            # real branch under vmap and lets it skip the ascent during
            # cold start
            use_opt = t >= self.train_hp[3]
            req, aux = kernels.daso_requests_train(
                self.daso_cfg, es["theta"], state, feat, req, use_opt)
        return req, es, aux

    def feedback(self, es, state, fin, util, aux, t, interval_s):
        _, phi, gamma, k_rbed = self.mab_hp
        alpha, beta, train_steps, _, train_min = self.train_hp
        es = dict(es)
        es["mab"] = kernels.mab_feedback(es["mab"], state, fin, phi, gamma,
                                         k_rbed)
        if self.daso_cfg is not None:
            y = daso_mod.op_objective(
                state["resp"], state["sla"], state["acc"], fin, util,
                interval_s, alpha, beta)
            es["win"] = daso_mod.window_append(es["win"], aux, y)
            es["theta"], es["opt"] = daso_mod.finetune_window(
                self.daso_cfg, es["theta"], es["opt"], es["win"],
                train_steps, train_min)
        return es

    def outputs(self, es):
        mab = es["mab"]
        out = {"mab_eps": mab.eps, "mab_rho": mab.rho, "mab_t": mab.t}
        if self.daso_cfg is not None:
            out["daso_theta"] = es["theta"]
        return out

    def summarize(self, out, s):
        s = _mab_scalars(out, s)
        if "daso_theta" in out:
            s["daso_theta"] = out["daso_theta"]
        return s

    def telemetry_cols(self):
        if self.daso_cfg is None:
            return MAB_TELEMETRY_COLS
        return MAB_TELEMETRY_COLS + ("daso_win_fill", "daso_last_loss")

    def telemetry_row(self, es):
        row = _mab_telemetry_row(es["mab"])
        if self.daso_cfg is None:
            return row
        f8 = jnp.float64
        loss = daso_mod.window_loss(self.daso_cfg, es["theta"], es["win"])
        return jnp.concatenate(
            [row, jnp.stack([es["win"]["count"].astype(f8),
                             loss.astype(f8)])])


@dataclasses.dataclass(frozen=True)
class GillisEngine:
    """Gillis baseline in the carry: contextual ε-greedy Q-learning
    between the layer split (arm 0) and model compression (arm 1), with
    multiplicative ε-decay per interval and sequential per-leaving-task
    TD(0) updates — the array form of ``splitplace.GillisDecider``
    against dual traces compiled with ``variants=(LAYER, COMPRESSED)``.
    ``gillis_hp = (eps0, lr, decay)``; eps0 seeds ``es["eps"]``
    (``policies.resolve`` owns state construction).  Placement is plain
    BestFit.
    ``es = {"Q", "eps", "key", "layer_ref"}``."""

    gillis_hp: Tuple[float, float, float]
    name: str = "gillis"
    variants = GILLIS_VARIANTS

    def batch_axes(self):
        return {"Q": None, "eps": None, "key": 0, "layer_ref": None}

    def decide(self, es, trace, t):
        shared, var = _interval_rows(trace, t)
        key_t = jax.random.fold_in(es["key"], t)
        arms = kernels.gillis_decide_arrivals(es["Q"], es["eps"], shared,
                                              key_t, es["layer_ref"])
        arr = kernels.select_variant(shared, var, arms,
                                     arm_decisions=GILLIS_VARIANTS)
        # ε decays once per scheduling interval, after the interval's
        # decisions (GillisDecider.decide's trailing `eps *= decay`)
        es = dict(es)
        es["eps"] = es["eps"] * self.gillis_hp[2]
        return arr, es

    def place(self, es, state, cl, trace, t, interval_s):
        return kernels.bestfit_requests(state, cl), es, None

    def feedback(self, es, state, fin, util, aux, t, interval_s):
        es = dict(es)
        es["Q"] = kernels.gillis_feedback(es["Q"], state, fin,
                                          es["layer_ref"],
                                          self.gillis_hp[1])
        return es

    def outputs(self, es):
        return {"gillis_eps": es["eps"], "gillis_q": es["Q"]}

    def summarize(self, out, s):
        s["gillis_eps"] = float(out["gillis_eps"])
        s["gillis_q"] = np.asarray(out["gillis_q"], np.float64)
        return s

    def telemetry_cols(self):
        return ("gillis_eps", "gillis_q_min", "gillis_q_max")

    def telemetry_row(self, es):
        f8 = jnp.float64
        return jnp.stack([es["eps"].astype(f8),
                          jnp.min(es["Q"]).astype(f8),
                          jnp.max(es["Q"]).astype(f8)])
