"""JAX-native edge simulator: whole (seed × λ) grids in one compiled call.

``repro.env.jaxsim`` is the accelerator-resident successor of the SoA
NumPy simulator (``repro.env.soa`` / ``repro.env.simulator``): the same
interval physics — MIPS sharing, layer-chain activation transfer under
mobility-modulated NIC bandwidth, RAM over-subscription swap slowdown,
and the eq. 13–16 metric accumulators — expressed as a jitted
``lax.fori_loop`` over substeps, so an entire experiment grid runs as a
single ``vmap``-over-traces XLA executable.

Fixed-capacity array layout
---------------------------
The growable object/SoA store becomes a *fixed-capacity slot store* so
every shape is compile-time static:

  * ``K = max_active`` task slots, each with ``F = max_frags`` fragment
    columns.  Per-fragment state is dense ``(K, F)`` (``instr``, ``ram``,
    ``out_bytes``, ``worker``, ``done``, ``transfer``); per-task state is
    ``(K,)`` (``chain``, ``stage``, ``placed``, ``alive``, ``task_done``,
    ``sla``, ``arrival_s``, ``wait_s``, ``seq``…).
  * Liveness is mask-based: a free slot has ``alive=False`` and all
    fragment columns ``done=True``; fragment columns beyond a task's
    ``nfrag`` are born ``done=True`` with ``worker=-1``, so every physics
    mask excludes padding with no special cases.
  * Admission scatters each interval's (padded, ``valid``-masked) arrival
    rows into free slots; slot *identity* is arbitrary but admission
    *order* is preserved in ``seq``, and the sequential greedy placement
    passes iterate in ``argsort(seq)`` order — the same order the host
    simulator's feasibility repair walks its active list.
  * Arrivals beyond free capacity are dropped and *counted*
    (``dropped_tasks`` in every summary); size ``max_active`` so it stays
    zero (``arrays.default_capacity`` never drops).

Workloads are compiled host-side (``arrays.compile_trace``) — Poisson
arrivals, split decisions from a *static* decider
(``policies.make_static_decider``), realized fragments, pre-sampled
accuracies, and mobility multipliers — then ``driver.run_grid_engine``
runs the whole grid batched.  Equivalence vs the host ``EdgeSim`` is
``allclose`` on per-trace summary metrics (response times, energy, cost,
utilization-derived quantities) against ``reference.replay_trace_edgesim``,
relaxing the SoA↔legacy bit-exactness contract (reduction orders differ
between ``segment_sum`` and sequential ``bincount``).

Every policy runs through ONE interval program driven by a
**PolicyEngine** (``engines``): the carry is ``(slot state, metric
accumulators, engine_state)`` and engines supply the
``decide/place/feedback`` hooks — one runner cache, chunk dispatcher
and summary path for all of them.  ``policies.resolve`` is the one
table from a policy name to its engine, starting engine state and trace
variants.  Learned policies run **in-kernel**
(``policies.LEARNED_POLICIES``): the SplitPlace MAB decider threads its
``MABState`` through the interval carry — online UCB decisions realized
against dual-variant traces (``arrays.compile_trace_dual``),
per-interval reward feedback and RBED ε-decay
(``kernels.mab_feedback``) — and the array-form DASO stage
(``kernels.daso_requests``) gradient-ascends the pretrained placement
surrogate between the BestFit request and feasibility-repair stages
(``"mab+gobi"`` is the decision-blind GOBI ablation of the same
machinery).  ``mode="train"`` (``resolve(..., mode="train")``) moves
the full §6.3 *training* loop in-kernel too: ε-greedy decisions (eq. 6)
from a fold-in key threaded through the carry, and online DASO
finetuning —
each interval appends its (packed placement features, O^P) pair into a
carried fixed 64-row replay window and advances (theta, opt_state)
with ``daso.train_epoch_weighted`` epochs, so the surrogate the placer
ascends is the finetuned one.  The Gillis baseline
(``resolve("gillis")``) carries its contextual ε-greedy Q-table over
(LAYER, COMPRESSED) dual traces with per-interval ε-decay and
sequential TD(0) updates.  The parity references are
``reference.replay_trace_edgesim_learned`` /
``replay_trace_edgesim_trained`` / ``replay_trace_edgesim_gillis``,
which drive ``EdgeSim`` with the identical shared pure functions; see
``docs/POLICIES.md``.
"""
from repro.env.jaxsim import engines
from repro.env.jaxsim.arrays import (ClusterArrays, DualTraceArrays,
                                     TraceArrays, chunk_tapes, compile_trace,
                                     compile_trace_dual, default_capacity,
                                     stack_traces)
from repro.env.jaxsim.driver import (cache_stats, clear_cache,
                                     run_grid_engine, run_trace_engine,
                                     set_cache_limit)
from repro.env.jaxsim.engines import (GILLIS_HP, MAB_HP, TRAIN_HP,
                                      gillis_init_state, trace_train_key)
from repro.env.jaxsim.policies import (DASO_LEARNED_POLICIES,
                                       LEARNED_POLICIES,
                                       MAB_LEARNED_POLICIES,
                                       STATIC_DASO_ARMS, STATIC_POLICIES,
                                       host_policy, make_static_decider,
                                       resolve)
from repro.env.jaxsim.stream import (RollingMetrics, StreamFeeder,
                                     StreamRunner, make_stream_policy,
                                     replay_stream, serve)
from repro.env.jaxsim.reference import (replay_trace_edgesim,
                                        replay_trace_edgesim_gillis,
                                        replay_trace_edgesim_learned,
                                        replay_trace_edgesim_static_daso,
                                        replay_trace_edgesim_trained)

__all__ = [
    "ClusterArrays", "DualTraceArrays", "TraceArrays", "chunk_tapes",
    "compile_trace",
    "compile_trace_dual", "default_capacity", "stack_traces", "GILLIS_HP",
    "MAB_HP", "STATIC_DASO_ARMS", "TRAIN_HP", "cache_stats", "clear_cache",
    "set_cache_limit", "engines", "gillis_init_state",
    "RollingMetrics", "StreamFeeder", "StreamRunner", "make_stream_policy",
    "replay_stream", "serve",
    "run_grid_engine", "run_trace_engine", "resolve", "trace_train_key",
    "DASO_LEARNED_POLICIES", "LEARNED_POLICIES", "MAB_LEARNED_POLICIES",
    "STATIC_POLICIES", "host_policy", "make_static_decider",
    "replay_trace_edgesim", "replay_trace_edgesim_gillis",
    "replay_trace_edgesim_learned", "replay_trace_edgesim_static_daso",
    "replay_trace_edgesim_trained",
]
