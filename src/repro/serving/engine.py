"""SLA-aware serving engine: SplitPlace's MAB policy driving real plan
selection over batched requests (the TPU-native integration, DESIGN §2.2).

Per request batch:
  1. context = deadline vs EMA estimate of the layer-pipeline latency
     (eq. 2 semantics, measured wall-clock here);
  2. the MAB (UCB at serve time) picks layer_pipeline or semantic_branch;
  3. DASO places the plan's fragments on mesh slices;
  4. the plan executes (really — pipeline_forward / branch_forward, the
     branches batched in one program) and its wall time is measured;
  5. reward couples deadline satisfaction with fidelity (agreement of the
     plan's argmax tokens vs the monolithic forward), eqs. 3–5.

The deadline is judged on a simulated latency: the measured one times
``1 + 0.25 * queue cost`` of the slices DASO placed the fragments on
(``ServeResult.sim_latency_s``; the slices are not real devices).  Each
step is a span of the active ``RunLedger`` (``plan.decide``,
``plan.place``, ``plan.run``, ``plan.fidelity``, ``plan.feedback``);
counters ``plan.layer`` / ``plan.semantic`` (requests by plan),
``plan.tokens``; for the dropless MoE layers of the plan and the
fidelity forward, ``moe.tokens`` and ``moe.routed_pairs`` (token-expert
pairs computed on the held experts); and for their MLA layers, one per
attention call by the core it lowered to (``attention.mla_core``, known
per compiled program): ``mla.fused``, ``mla.blockwise``, ``mla.full``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import daso as daso_mod
from repro.core import mab as mab_mod
from repro.models.attention import mla_core
from repro.models.model import MOE_KINDS, forward
from repro.obs import get_ledger
from repro.serving.plans import (LAYER_PLAN, SEMANTIC_PLAN, PlanSpec,
                                 branch_forward, pipeline_forward, unrolled)


@dataclasses.dataclass
class Request:
    tokens: np.ndarray          # (b, s)
    deadline_s: float
    app: int = 0
    probe: Optional[np.ndarray] = None  # (P,) flat token positions at which
                                # the plan's dropless MoE layers report
                                # their input and held-expert output


@dataclasses.dataclass
class ServeResult:
    plan: int
    latency_s: float            # measured wall of the plan's program
    sim_latency_s: float        # simulated: latency_s * (1 + 0.25 * queue
                                # cost of the DASO-placed slices)
    fidelity: float             # argmax agreement with monolithic forward
    met_deadline: bool          # sim_latency_s <= deadline
    reward: float
    logits: object = None       # the plan's logits (device array)
    routes: object = None       # its dropless MoE routes (stack_routes),
                                # probed at Request.probe


class SplitPlaceEngine:
    def __init__(self, params, cfg, num_stages=2, num_branches=2,
                 phi=0.9, gamma=0.3, ucb_c=0.5, seed=0, num_slices=4):
        # one weight tree per layer: the plans read each layer's weights
        # in place (slicing a layer out of a stacked body copies it), and
        # the layer plan and the monolithic forward unroll alike, so
        # that they are one computation
        params, cfg = unrolled(params, cfg)
        self.params = params
        self.cfg = cfg
        self.layer_plan = PlanSpec(LAYER_PLAN, num_stages=num_stages)
        self.sem_plan = PlanSpec(SEMANTIC_PLAN, num_branches=num_branches)
        self.state = mab_mod.init_state(num_apps=1)
        self.phi, self.gamma, self.ucb_c = phi, gamma, ucb_c
        from repro.serving.plans import optimal_stage_bounds
        self._stage_bounds = optimal_stage_bounds(cfg, seq=256, batch=1,
                                                  num_stages=num_stages)

        # named programs: a device profile tells them apart by name
        def layer_plan(p, b):
            return pipeline_forward(p, b, cfg, num_stages,
                                    bounds=self._stage_bounds,
                                    with_routes=True)

        def semantic_plan(p, b):
            return branch_forward(p, b, cfg, num_branches, with_routes=True)

        def monolithic(p, b):
            return forward(p, b, cfg, with_routes=True)[::2]

        def fidelity(a, b):
            return (jnp.argmax(a, -1) == jnp.argmax(b, -1)).mean()

        self._pipe, self._branch, self._mono, self._fid = map(
            jax.jit, (layer_plan, semantic_plan, monolithic, fidelity))
        # the MAB's decision and Algorithm-1 bookkeeping as programs
        # (op by op, the bookkeeping's scan would compile on every call)
        self._decide = jax.jit(mab_mod.decide_ucb, static_argnames=("c",))
        self._end = jax.jit(mab_mod.end_of_interval,
                            static_argnames=("phi", "gamma"))
        self._moe_layers = sum(k in MOE_KINDS for k in cfg.layer_kinds) \
            if cfg.moe is not None and cfg.moe.dispatch == "dropless" else 0
        self._mla_layers = sum(k in ("mla", "mla_moe")
                               for k in cfg.layer_kinds)
        # DASO fragment->mesh-slice placement (the paper's placement
        # sub-problem): per-slice queue depth is the state; fragments are
        # pipeline stages or semantic branches
        self.num_slices = num_slices
        max_frag = max(num_stages, num_branches)
        self._daso_cfg = daso_mod.DASOConfig(
            num_workers=num_slices, max_containers=max_frag,
            state_features=1, hidden=32, depth=2, place_iters=25,
            lr_place=0.2)
        self._theta, self._daso_opt = daso_mod.make_trainer(
            self._daso_cfg, jax.random.PRNGKey(seed))
        self.slice_load = np.zeros(num_slices)
        self._replay = []
        self._placer_warm = False

    def place_fragments(self, plan: int):
        """DASO placement of the plan's fragments onto mesh slices given
        current per-slice queue depths; returns (assignment, queue_cost)."""
        n = (self.layer_plan.num_stages if plan == LAYER_PLAN
             else self.sem_plan.num_branches)
        C = self._daso_cfg.max_containers
        mask = np.zeros(C, np.float32)
        mask[:n] = 1.0
        decisions = np.full(C, plan, np.int32)
        logits = np.zeros((C, self.num_slices), np.float32)
        # warm start: least-loaded slices
        order = np.argsort(self.slice_load)
        for i in range(n):
            logits[i, order[i % self.num_slices]] = 2.0
        state = jnp.asarray(self.slice_load[:, None] / 4.0, jnp.float32)
        if len(self._replay) >= 16:
            p_opt, _, _ = daso_mod.optimize_placement(
                self._daso_cfg, self._theta, state, jnp.asarray(logits),
                jnp.asarray(decisions), jnp.asarray(mask))
        else:
            p_opt = jnp.asarray(logits)
        assign = np.asarray(daso_mod.placement_to_assignment(
            p_opt, jnp.asarray(mask)))[:n]
        if plan == LAYER_PLAN:
            # sequential stages: queue cost = sum of per-stage waits
            qcost = float(sum(self.slice_load[a] for a in assign))
        else:
            # parallel branches: straggler = max wait
            qcost = float(max(self.slice_load[a] for a in assign))
        for a in assign:
            self.slice_load[a] += 1.0
        self.slice_load *= 0.8                     # queues drain
        x = np.asarray(daso_mod.pack_input(
            self._daso_cfg, state, p_opt, jnp.asarray(decisions),
            jnp.asarray(mask)))
        return assign, qcost, x

    def _daso_feedback(self, x, reward):
        self._replay.append((x, reward))
        if len(self._replay) >= 16 and len(self._replay) % 4 == 0:
            xs = jnp.asarray(np.stack([r[0] for r in self._replay[-64:]]))
            ys = jnp.asarray(np.array([r[1] for r in self._replay[-64:]],
                                      np.float32))
            for _ in range(2):
                self._theta, self._daso_opt, _ = daso_mod.train_epoch(
                    self._daso_cfg, self._theta, self._daso_opt, xs, ys)

    def warmup(self, batch, probe=None):
        """Compile the three programs (and the fidelity score) for this
        batch shape (and probe size) and, once, the DASO ascent and every
        trainer batch the replay will reach, so that serving compiles
        nothing."""
        b = self._batch(batch, probe)
        logits = None
        for fn in (self._pipe, self._branch, self._mono):
            logits = jax.block_until_ready(fn(self.params, b))[0]
        self._fid(logits, logits).block_until_ready()
        if not self._placer_warm:
            self._warm_placer()
            self._placer_warm = True

    def _warm_placer(self):
        saved = (self.slice_load.copy(), self._replay, self._theta,
                 self._daso_opt)
        self._replay = [None] * 16
        for plan in (LAYER_PLAN, SEMANTIC_PLAN):
            _, _, x = self.place_fragments(plan)
        for n in range(16, 65, 4):
            jax.block_until_ready(daso_mod.train_epoch(
                self._daso_cfg, self._theta, self._daso_opt,
                jnp.zeros((n,) + x.shape, jnp.float32),
                jnp.zeros((n,), jnp.float32)))
        self.slice_load, self._replay, self._theta, self._daso_opt = saved

    @staticmethod
    def _batch(tokens, probe=None):
        b = {"tokens": jnp.asarray(tokens)}
        if probe is not None:
            b["probe"] = jnp.asarray(probe, jnp.int32)
        return b

    def _run(self, plan_kind: int, batch) -> tuple:
        """(logits, routes, measured wall seconds) of one plan's program."""
        fn = self._pipe if plan_kind == LAYER_PLAN else self._branch
        t0 = time.perf_counter()
        logits, routes = jax.block_until_ready(fn(self.params, batch))
        return logits, routes, time.perf_counter() - t0

    def _count(self, led, plan, shape, routes, ref_routes):
        tokens = int(np.prod(shape))
        led.count("plan.semantic" if plan else "plan.layer")
        led.count("plan.tokens", tokens)
        if self._mla_layers:
            # the plan's program (a branch holds 1/B of the heads; the
            # branches are one vmapped call) and the fidelity forward
            b, s = shape[:2]
            h = self.cfg.num_heads
            for heads in (h // self.sem_plan.num_branches if plan else h, h):
                core = mla_core(self.cfg, b, heads, s, jax.default_backend())
                led.count(f"mla.{core}", self._mla_layers)
        if self._moe_layers:
            branches = self.sem_plan.num_branches if plan else 1
            led.count("moe.tokens", tokens * self._moe_layers * (branches + 1))
            led.count("moe.routed_pairs", int(routes["pairs"])
                      + int(ref_routes["pairs"]))

    def serve(self, req: Request) -> ServeResult:
        led = get_ledger()
        batch = self._batch(req.tokens, req.probe)
        with led.span("plan.decide"):
            d, ctx = self._decide(self.state, jnp.float32(req.deadline_s),
                                  req.app, c=self.ucb_c)
            plan = int(d)                 # 0=LAYER(pipeline) 1=SEMANTIC(branch)
        with led.span("plan.place"):
            assign, qcost, daso_x = self.place_fragments(plan)
        with led.span("plan.run"):
            logits, routes, latency = self._run(plan, batch)
        # simulated queueing on the busy slices DASO placed the fragments on
        sim_latency = latency * (1.0 + 0.25 * qcost)
        with led.span("plan.fidelity"):
            # the same batch, probe included: the fidelity forward is
            # then the layer plan's computation, bit for bit
            ref, ref_routes = self._mono(self.params, batch)
            fid = float(self._fid(logits, ref))
        met = sim_latency <= req.deadline_s
        reward = 0.5 * (float(met) + fid)
        with led.span("plan.feedback"):
            # Algorithm-1 bookkeeping (single leaving task)
            self.state = self._end(
                self.state,
                np.array([req.app], np.int32),
                np.array([req.deadline_s], np.float32),
                np.array([sim_latency], np.float32),
                np.array([fid], np.float32),
                np.array([plan], np.int32),
                phi=self.phi, gamma=self.gamma)
            self._daso_feedback(daso_x, reward)
        if led.record:
            self._count(led, plan, req.tokens.shape, routes, ref_routes)
        return ServeResult(plan, latency, sim_latency, fid, met, reward,
                           logits, routes)

    def serve_many(self, reqs: List[Request]) -> List[ServeResult]:
        return [self.serve(r) for r in reqs]
