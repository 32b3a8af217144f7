"""Jit-able interval kernels over the fixed-capacity slot arrays.

Three pieces, mirroring one ``EdgeSim`` interval:

  * ``admit``       — scatter this interval's (padded) arrivals into free
                      task slots;
  * ``place``       — vectorized BestFit for unplaced fragments + the
                      RAM feasibility repair of ``EdgeSim.apply_placement``,
                      both as ``lax.fori_loop`` sequential greedy passes in
                      admission order (the greedy admit order is part of
                      the physics contract, so it cannot be parallelized —
                      but under ``vmap`` the whole grid shares each loop
                      iteration, which is where the batching win comes
                      from);
  * ``run_substeps``— the substep physics of ``repro.env.soa.run_interval``
                      (MIPS sharing, swap slowdown, chain activation
                      transfers under mobility-modulated NIC bandwidth,
                      eqs. 13–16 accumulators) on dense ``(K, F)`` arrays.

Every elementwise float op matches ``env/soa.py`` in float64; only
reduction orders/groupings differ (one-hot matmul and count-matrix
censuses vs sequential ``bincount``), which is why the cross-backend
contract is ``allclose`` on summary metrics rather than the SoA↔legacy
bit-exactness.

Unsupported relative to the host repair: the ``w < 0 → argmin`` rescue in
``apply_placement`` is unreachable here (every live unplaced fragment
receives a BestFit target in the same interval), so it is omitted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import daso as daso_mod
from repro.core import mab as mab_mod
from repro.env.soa import NIC_CAP_MB

_SEQ_DEAD = jnp.iinfo(jnp.int64).max


def init_state(K: int, F: int, n: int):
    """Empty slot store: all slots free, padding-done, worker −1."""
    f8 = jnp.float64
    return {
        # per-fragment (K, F)
        "instr": jnp.zeros((K, F), f8),
        "ram": jnp.zeros((K, F), f8),
        "out_bytes": jnp.zeros((K, F), f8),
        "worker": jnp.full((K, F), -1, jnp.int32),
        "done": jnp.ones((K, F), bool),
        "transfer": jnp.zeros((K, F), f8),
        # per-task (K,)
        "nfrag": jnp.zeros((K,), jnp.int32),
        "chain": jnp.zeros((K,), bool),
        "stage": jnp.zeros((K,), jnp.int32),
        "placed": jnp.zeros((K,), bool),
        "alive": jnp.zeros((K,), bool),
        "task_done": jnp.ones((K,), bool),
        "sla": jnp.zeros((K,), f8),
        "arrival_s": jnp.zeros((K,), f8),
        "wait_s": jnp.zeros((K,), f8),
        "acc": jnp.zeros((K,), f8),
        "decision": jnp.zeros((K,), jnp.int32),
        # learned-policy feedback channels: app/batch identify the MAB
        # context of a slot, resp records its response time at the substep
        # it finished (batch is 1.0 on dead slots so norms never divide
        # by zero)
        "app": jnp.zeros((K,), jnp.int32),
        "batch": jnp.ones((K,), f8),
        "resp": jnp.zeros((K,), f8),
        "seq": jnp.full((K,), _SEQ_DEAD, jnp.int64),
        "seq_counter": jnp.zeros((), jnp.int64),
        "dropped": jnp.zeros((), jnp.int64),
    }


def admit(state, arr):
    """Scatter the interval's arrival rows into free slots.

    ``arr`` holds one interval's slices of the compiled trace (leading
    axis A).  Valid arrivals are a prefix; arrival *j* takes the *j*-th
    free slot (slot identity is irrelevant to the physics — admission
    *order* is preserved via ``seq``).  Arrivals beyond capacity are
    dropped and counted, never silently lost.
    """
    K, F = state["worker"].shape
    A = arr["valid"].shape[0]
    # j-th free slot via binary search on the running free count (cheaper
    # than `nonzero`, which XLA:CPU lowers to a per-row scatter)
    fcum = jnp.cumsum((~state["alive"]).astype(jnp.int32))
    slots = jnp.searchsorted(fcum, jnp.arange(1, A + 1), side="left")
    slots = jnp.where(slots >= K, K, slots)
    valid = arr["valid"]
    tgt = jnp.where(valid, slots, K)          # K == out-of-bounds → drop
    s = dict(state)
    s["dropped"] = state["dropped"] + jnp.sum(valid & (tgt >= K))

    fcols = jnp.arange(F, dtype=jnp.int32)[None, :]
    pad_done = fcols >= arr["nfrag"][:, None]
    st = lambda name, val: s[name].at[tgt].set(val, mode="drop")
    s["instr"] = st("instr", arr["instr"])
    s["ram"] = st("ram", arr["ram"])
    s["out_bytes"] = st("out_bytes", arr["out_bytes"])
    s["worker"] = st("worker", jnp.full((A, F), -1, jnp.int32))
    s["done"] = st("done", pad_done)
    s["transfer"] = st("transfer", jnp.zeros((A, F)))
    s["nfrag"] = st("nfrag", arr["nfrag"])
    s["chain"] = st("chain", arr["chain"])
    s["stage"] = st("stage", jnp.zeros((A,), jnp.int32))
    s["placed"] = st("placed", jnp.zeros((A,), bool))
    s["alive"] = st("alive", jnp.ones((A,), bool))
    s["task_done"] = st("task_done", jnp.zeros((A,), bool))
    s["sla"] = st("sla", arr["sla"])
    s["arrival_s"] = st("arrival_s", arr["arrival_s"])
    s["wait_s"] = st("wait_s", jnp.zeros((A,)))
    s["acc"] = st("acc", arr["acc"])
    s["decision"] = st("decision", arr["decision"])
    s["app"] = st("app", arr["app"])
    s["batch"] = st("batch", jnp.maximum(
        arr["batch"].astype(jnp.float64), 1.0))
    s["resp"] = st("resp", jnp.zeros((A,)))
    s["seq"] = st("seq", state["seq_counter"]
                  + jnp.arange(A, dtype=jnp.int64))
    s["seq_counter"] = state["seq_counter"] + jnp.sum(valid)
    return s


def _admission_order(state):
    """Slot indices sorted by admission sequence (dead slots last)."""
    return jnp.argsort(jnp.where(state["alive"], state["seq"], _SEQ_DEAD))


def _onehot(idx, n, dtype=jnp.float64):
    """(…, n) one-hot.  XLA:CPU scatter (what ``segment_sum`` lowers to)
    costs ~µs *per update row*, so the hot kernels do their per-worker
    censuses as one-hot matmuls instead — dense FLOPs on (K·F, n) tiles
    are orders of magnitude cheaper here.  Integer counts use float32
    one-hots (exact below 2²⁴ and half the memory traffic); anything
    entering float64 physics sums stays float64."""
    return (idx[..., None] == jnp.arange(n)).astype(dtype)


def bestfit_requests(state, cl):
    """Phase A: greedy BestFit worker requests for unplaced fragments —
    semantics-equal to ``BestFitPlacer.place`` (already-placed fragments
    keep their current worker in the returned request matrix).

    Cost shaping (the greedy admit order is part of the physics contract,
    so the loop cannot be parallelized — but its *trip count* can
    shrink): the scan walks only the compacted admission-ordered list of
    fragments that need a worker (``n_new`` iterations, not ``K·F``);
    positions come from one vectorized binary search over the running
    count (XLA:CPU lowers `nonzero` to a ~ms scatter; this is
    ~log₂(K·F) fused gather rounds).  Under ``vmap`` every grid cell
    shares each iteration.
    """
    K, F = state["worker"].shape
    n = cl["ram"].shape[0]
    cap, mips = cl["ram"], cl["mips"]
    worker, done, ram = state["worker"], state["done"], state["ram"]
    wsafe = jnp.clip(worker, 0, n - 1)
    live = (~done) & (worker >= 0)
    # census via the f32 fragment-count einsum + per-task RAM (fragments
    # of one task share one footprint; see run_substeps docstring)
    kfn32 = _onehot(wsafe, n, jnp.float32)
    cnt_live = jnp.einsum("kf,kfn->kn", live.astype(jnp.float32), kfn32)
    ram_task = ram[:, 0]
    lr0 = jnp.stack([jnp.ones((K,)), ram_task]) @ cnt_live.astype(jnp.float64)
    load0, ram_used0 = lr0[0], lr0[1]
    static = 0.3 * mips / mips.max()
    order = _admission_order(state)
    arange_n = jnp.arange(n)

    new_mask = (~done) & (worker < 0)
    flat_ord = new_mask[order].ravel()
    ncum = jnp.cumsum(flat_ord.astype(jnp.int32))
    n_new = ncum[-1]
    pos = jnp.minimum(jnp.searchsorted(
        ncum, jnp.arange(1, K * F + 1, dtype=jnp.int32), side="left"),
        K * F - 1)
    slot_of = order[pos // F]
    f_of = (pos % F).astype(jnp.int32)

    def bodyA(i, carry):
        req, ram_free, load, score = carry
        slot, f = slot_of[i], f_of[i]
        rm = ram[slot, f]
        buf = jnp.where(ram_free < rm, -1e9, score)
        w = jnp.argmax(buf)
        hot = arange_n == w
        nf = ram_free[w] - rm
        nl = load[w] + 1.0
        ns = -nl + static[w] + 0.1 * nf / cap[w]
        req = req.at[slot, f].set(w.astype(jnp.int32))
        ram_free = jnp.where(hot, nf, ram_free)
        load = jnp.where(hot, nl, load)
        score = jnp.where(hot, ns, score)
        return req, ram_free, load, score

    score0 = -load0 + static + 0.1 * (cap - ram_used0) / cap
    req, _, _, _ = lax.fori_loop(
        0, n_new, bodyA, (worker, cap - ram_used0, load0, score0))
    return req


def apply_requests(state, cl, req):
    """Phase B: the RAM feasibility repair of ``EdgeSim.apply_placement``
    over an arbitrary worker-request matrix ``req`` (K, F).

    Fast path: when every requested placement fits its worker outright,
    the sequential repair provably admits everything verbatim (RAM
    prefix sums are bounded by the final totals), so its loop runs zero
    iterations.  Requests must cover every live unplaced fragment with a
    valid worker index (BestFit and the array-form DASO stage both
    guarantee this), which keeps the host repair's ``w < 0 → argmin``
    rescue unreachable.
    """
    K, F = state["worker"].shape
    n = cl["ram"].shape[0]
    cap = cl["ram"]
    worker, done, ram = state["worker"], state["done"], state["ram"]
    ram_task = ram[:, 0]
    order = _admission_order(state)
    alive, chain, stage = state["alive"], state["chain"], state["stage"]

    # fast path: when every requested placement fits its worker outright,
    # the sequential repair is the identity on the requests
    live_und = ~done                     # dead/padding columns are done
    holds_f = jnp.where(chain[:, None],
                        jnp.arange(F, dtype=jnp.int32)[None, :]
                        == stage[:, None], True)
    req_safe = jnp.clip(req, 0, n - 1)
    cnt_dem = jnp.einsum("kf,kfn->kn",
                         (live_und & holds_f).astype(jnp.float32),
                         _onehot(req_safe, n, jnp.float32))
    demand = ram_task @ cnt_dem.astype(jnp.float64)
    feasible = jnp.all(demand <= cap)
    worker_fast = jnp.where(live_und, req, worker)
    placed_fast = state["placed"] | alive

    def bodyB(i, carry):
        ram_used, worker2, placed = carry
        slot = order[i]
        pb = alive[slot]
        ok = jnp.bool_(True)
        for f in range(F):
            act = pb & (~done[slot, f]) & ok
            holds = (~chain[slot]) | (f == stage[slot])
            w = jnp.clip(req[slot, f], 0, n - 1)
            rm = ram[slot, f]
            infeas = act & holds & (ram_used[w] + rm > cap[w])
            headroom = cap - ram_used
            cand = jnp.argmax(headroom).astype(jnp.int32)
            fb_ok = headroom[cand] >= rm
            w2 = jnp.where(infeas & fb_ok, cand, w)
            admit_f = act & (~infeas | fb_ok)
            ok = ok & ~(infeas & ~fb_ok)
            worker2 = worker2.at[slot, f].set(
                jnp.where(admit_f, w2, worker2[slot, f]))
            ram_used = ram_used.at[w2].add(
                jnp.where(admit_f & holds, rm, 0.0))
        fail = pb & ~ok
        worker2 = worker2.at[slot].set(
            jnp.where(fail, jnp.full((F,), -1, jnp.int32), worker2[slot]))
        placed = placed.at[slot].set(jnp.where(pb, ok, placed[slot]))
        return ram_used, worker2, placed

    n_alive = jnp.sum(alive)
    trip = jnp.where(feasible, 0, n_alive)
    _, worker2, placed = lax.fori_loop(
        0, trip, bodyB, (jnp.zeros((n,)), worker_fast, placed_fast))
    s = dict(state)
    s["worker"] = worker2
    s["placed"] = placed
    return s


def place(state, cl):
    """BestFit targets for unplaced fragments, then the feasibility
    repair — semantics-equal to ``BestFitPlacer.place`` +
    ``EdgeSim.apply_placement``.  Learned placers reuse the same two
    stages with a policy step in between (``daso_requests``)."""
    return apply_requests(state, cl, bestfit_requests(state, cl))


def _run_substeps_fused(state, acc, bw_mult, cl, *, substeps: int,
                        dt: float, swap_slowdown: float, impl: str):
    """Route one interval of substep physics through the fused kernels
    under ``src/repro/kernels/`` — ``impl="pallas"`` is the Pallas
    edge-substep kernel (interpret mode on CPU), ``impl="ref"`` its
    pure-jnp oracle.  Both consume/produce the same carry slices as the
    inline XLA path below; ``ram`` collapses to its per-task column
    (fragments of one task share one RAM footprint by construction)."""
    if impl == "pallas":
        from repro.kernels.edge_substep import edge_substep
        # interpret mode on the CPU only; elsewhere the kernel would have
        # to compile (``driver._resolve_substep_impl`` refuses it there)
        fn = functools.partial(edge_substep,
                               interpret=jax.default_backend() == "cpu")
    elif impl == "ref":
        from repro.kernels.ref import edge_substep_ref as fn
    else:
        raise ValueError(f"unknown substep impl {impl!r} "
                         "(want 'xla', 'pallas' or 'ref')")
    (instr, done, transfer, stage, task_done, resp, now, metrics, busy,
     pwt_delta) = fn(
        state["instr"], state["done"], state["transfer"], state["stage"],
        state["task_done"], state["resp"], acc["now"][None],
        acc["metrics"], state["worker"], state["ram"][:, 0],
        state["out_bytes"], state["nfrag"], state["chain"],
        state["placed"], state["sla"], state["arrival_s"], state["acc"],
        state["wait_s"], state["decision"], bw_mult, cl["mips"],
        cl["ram"], cl["net_bw"], substeps=substeps, dt=dt,
        swap_slowdown=swap_slowdown, nic_cap=NIC_CAP_MB)
    s = dict(state)
    s.update(instr=instr, done=done, transfer=transfer, stage=stage,
             task_done=task_done, resp=resp)
    a = dict(acc)
    a.update(now=now[0], pwt=acc["pwt"] + pwt_delta, metrics=metrics)
    return s, a, busy


def run_substeps(state, acc, bw_mult, cl, *, substeps: int, dt: float,
                 swap_slowdown: float, impl: str = "xla"):
    """One interval of substep physics; returns (state, acc, busy_time).

    ``impl`` selects the execution strategy: ``"xla"`` (default) is the
    inline incremental-census formulation below, tuned op by op for
    XLA:CPU; ``"pallas"`` routes through the fused
    ``repro.kernels.edge_substep`` kernel (one VMEM-resident loop,
    interpret mode on CPU) and ``"ref"`` through its pure-jnp oracle —
    all three agree to float64 rounding (the fuzzed parity suite and
    the differential/golden fences pin it).

    Mask structure and op order follow ``soa.run_interval``: the
    placed/chain masks are interval-static, ``done``/``transfer``/
    ``stage`` evolve per substep, execution precedes transfers, and the
    clock advances by repeated ``+= dt`` so finish timestamps carry the
    same accumulated rounding.

    Census cost shaping — a per-substep (K·F, n) float64 census whose
    operand depends on the loop carry is an un-hoistable dot XLA:CPU runs
    slowly every substep.  Instead the kernel carries ``cnt``, the
    per-(task, worker) count of undone placed fragments of *non-chain*
    tasks (float32 — exact, these are small integers), updated
    incrementally from each substep's completions.  Then

      * non-chain load = column sum of ``cnt``;
      * non-chain RAM  = ``ram_task @ cnt`` — fragments of one task share
        one RAM footprint by construction (``compile_trace`` asserts it);
      * chain load/RAM = a (K, n) one-hot census of each chain's single
        active-stage fragment;

    and the only full-width per-substep contraction left is the float32
    completion-delta reduce, exact for counts.
    """
    if impl != "xla":
        return _run_substeps_fused(state, acc, bw_mult, cl,
                                   substeps=substeps, dt=dt,
                                   swap_slowdown=swap_slowdown, impl=impl)
    K, F = state["worker"].shape
    n = cl["ram"].shape[0]
    mips, cap, net_bw = cl["mips"], cl["ram"], cl["net_bw"]
    worker, ram, out_bytes = state["worker"], state["ram"], state["out_bytes"]
    nfrag, chain = state["nfrag"], state["chain"]
    sla, arrival, acc_t = state["sla"], state["arrival_s"], state["acc"]
    wait_s, decision = state["wait_s"], state["decision"]
    fidx = jnp.arange(F, dtype=jnp.int32)[None, :]
    wsafe = jnp.clip(worker, 0, n - 1)
    chain_f = chain[:, None]
    placed_f = state["placed"][:, None] & (worker >= 0)
    holdable = worker >= 0
    chactive = chain & state["placed"] & ~state["task_done"]
    # interval-static hoists: worker assignments cannot change mid-interval
    kfn32 = _onehot(wsafe, n, jnp.float32)               # (K, F, n)
    ram_task = ram[:, 0]                                 # uniform per task
    mips_f = mips[wsafe]
    doh = _onehot(jnp.clip(decision, 0, 2), 3)           # (K, 3)
    not_chain_f = ~chain_f
    arange_n = jnp.arange(n)
    ones_k = jnp.ones((K,))
    dual_idx = jnp.concatenate([wsafe.ravel(), wsafe.ravel() + n])
    hand_static = chain_f & (fidx < nfrag[:, None] - 1)
    out_r = jnp.concatenate(                              # shifted handoffs
        [jnp.zeros((K, 1)), out_bytes[:, :-1]], axis=1)
    # bandwidth between consecutive chain stages is also interval-static
    # (workers + mobility fixed): bw_pair[k, f] = rate into fragment f
    w_prev = jnp.clip(jnp.roll(worker, 1, axis=1), 0, n - 1)
    bw_pair = jnp.minimum(NIC_CAP_MB,
                          jnp.minimum(net_bw[w_prev] / 100.0,
                                      net_bw[wsafe] / 100.0))
    bw_pair = bw_pair * jnp.minimum(bw_mult[w_prev], bw_mult[wsafe])

    def census(mask_f):
        """Per-(task, worker) fragment counts of a (K, F) bool mask.
        (einsum, NOT broadcast-multiply+reduce: XLA:CPU runs the latter
        ~7× slower on these shapes.)"""
        return jnp.einsum("kf,kfn->kn", mask_f.astype(jnp.float32), kfn32)

    cnt0 = census((~state["done"]) & holdable & not_chain_f)

    def body(carry, _):
        (instr, done, transfer, stage, task_done, now, busy, cnt,
         m, resp_rec) = carry
        notdone = ~done
        is_stage = fidx == stage[:, None]
        tle = (transfer <= 0.0) & is_stage
        runnable = (not_chain_f | tle) & placed_f & notdone
        holds = (not_chain_f | is_stage) & holdable & notdone
        # one packed gather pulls every per-active-stage channel (scalar
        # reductions cost ~18µs *each* in this vmapped loop on XLA:CPU)
        stage_ch = jnp.take_along_axis(
            jnp.stack([wsafe.astype(jnp.float64), transfer, bw_pair,
                       runnable.astype(jnp.float64),
                       holds.astype(jnp.float64)]),
            stage[None, :, None].astype(jnp.int32), axis=2)[:, :, 0]
        w_stage = stage_ch[0].astype(jnp.int32)
        cur_tl, bw_s = stage_ch[1], stage_ch[2]
        r_ch = (stage_ch[3] > 0.5) & chain
        h_ch = (stage_ch[4] > 0.5) & chain
        # per-worker census: non-chain tasks from the carried cnt matrix,
        # chains from their single active-stage fragment — all four
        # contractions packed as two dots
        ohs = w_stage[:, None] == arange_n               # (K, n)
        nc_lr = jnp.stack([ones_k, ram_task]) @ cnt.astype(jnp.float64)
        ch_lr = jnp.stack([r_ch.astype(jnp.float64),
                           jnp.where(h_ch, ram_task, 0.0)]) \
            @ ohs.astype(jnp.float64)
        load = nc_lr[0] + ch_lr[0]
        ram_load = nc_lr[1] + ch_lr[1]
        swap = ram_load > cap
        busy = busy + (load > 0) * dt
        lf_sw = jnp.take(jnp.concatenate([load, swap.astype(jnp.float64)]),
                         dual_idx).reshape(2, K, F)
        load_f, swap_f = lf_sw[0], lf_sw[1] > 0.5
        rate = mips_f / jnp.maximum(load_f, 1.0)
        rate = jnp.where(swap_f, rate * swap_slowdown, rate)
        instr = instr - jnp.where(runnable, rate * dt, 0.0)
        newly = runnable & (instr <= 0.0)
        done = done | newly
        cnt = cnt - census(newly & not_chain_f)
        # chain handoff: a finished stage queues its activation onto the
        # next fragment
        hand = newly & hand_static
        hand_r = jnp.concatenate(
            [jnp.zeros((K, 1), bool), hand[:, :-1]], axis=1)
        transfer = jnp.where(hand_r, out_r, transfer)
        # task completion → metric accumulators (eqs. 13–16 ingredients),
        # all nine summed by a single (K,)·(K, 9) dot into the m vector
        newfin = jnp.all(done, axis=1) & ~task_done
        task_done = task_done | newfin
        resp = now - arrival
        # response recorded at the finish substep — the learned-policy
        # feedback (MAB end_of_interval) consumes it after the interval
        resp_rec = jnp.where(newfin, resp, resp_rec)
        finf = newfin.astype(jnp.float64)
        mcols = jnp.stack(
            [ones_k, resp, (resp > sla).astype(jnp.float64), acc_t,
             ((resp <= sla) + acc_t) / 2.0, wait_s,
             doh[:, 0], doh[:, 1], doh[:, 2]], axis=1)
        m = m + finf @ mcols
        # transfers: forward the active stage's inbound activation
        s = stage
        cond = chactive & (s > 0) & (cur_tl > 0.0)
        transfer = transfer - jnp.where(
            cond, bw_s * 1e6 * dt, 0.0)[:, None] * is_stage
        # stage advance checks done[stage] *after* this substep's execution
        done_s = jnp.take_along_axis(done, s[:, None], axis=1)[:, 0]
        adv = chactive & done_s & (s < nfrag - 1)
        stage = stage + adv.astype(jnp.int32)
        now = now + dt
        return (instr, done, transfer, stage, task_done, now, busy, cnt,
                m, resp_rec), None

    carry = (state["instr"], state["done"], state["transfer"],
             state["stage"], state["task_done"], acc["now"],
             jnp.zeros((n,)), cnt0, acc["metrics"], state["resp"])
    (instr, done, transfer, stage, task_done, now, busy, _cnt,
     metrics, resp_rec), _ = lax.scan(body, carry, None, length=substeps,
                                      unroll=min(substeps, 2))
    # per-worker completion census once per interval: the accumulator only
    # ever consumes interval sums, and workers are interval-static, so
    # counting done-transitions at the end is exact
    completed = done & ~state["done"]
    pwt = acc["pwt"] + jnp.sum(census(completed),
                               axis=0).astype(jnp.float64)
    s = dict(state)
    s.update(instr=instr, done=done, transfer=transfer, stage=stage,
             task_done=task_done, resp=resp_rec)
    a = dict(acc)
    a.update(now=now, pwt=pwt, metrics=metrics)
    return s, a, busy


# -------------------------------------------------- learned-policy stages
#
# The stages below move the SplitPlace learning loop *inside* the jitted
# interval program: UCB split decisions over each interval's arrival rows
# (realized by selecting between the dual trace's pre-compiled variants),
# an array-form DASO placement pass between ``bestfit_requests`` and
# ``apply_requests``, and the Algorithm-1 MAB bookkeeping over the slots
# that finished the interval.  Every learned computation is a shared pure
# function from ``repro.core.{mab,daso}`` so the host-side parity replay
# (``reference.replay_trace_edgesim_learned``) runs the identical math.


def select_variant(shared, var, decision, arm_decisions=(0, 1)):
    """Realize the in-kernel split decisions against a dual trace.

    ``shared``/``var`` hold one interval's arrival rows of a
    ``DualTraceArrays`` (variant axis V=2); ``decision`` is the (A,) arm
    index per row.  ``arm_decisions`` maps the arm index to the decision
    *code* recorded on the task — (LAYER, SEMANTIC) for the SplitPlace
    MAB, (LAYER, COMPRESSED) for the Gillis baseline's dual traces.
    Returns the one-variant ``arr`` dict ``admit`` consumes.
    """
    d = decision.astype(jnp.int32)[:, None]

    def pick(x):
        idx = d if x.ndim == 2 else d[:, :, None]
        return jnp.take_along_axis(x, idx, axis=1)[:, 0]

    return {"valid": shared["valid"], "sla": shared["sla"],
            "arrival_s": shared["arrival_s"], "app": shared["app"],
            "batch": shared["batch"], "acc": pick(var["vacc"]),
            "chain": pick(var["vchain"]), "nfrag": pick(var["vnfrag"]),
            "instr": pick(var["vinstr"]), "ram": pick(var["vram"]),
            "out_bytes": pick(var["vout"]),
            "decision": jnp.asarray(arm_decisions, jnp.int32)[
                decision.astype(jnp.int32)]}


def mab_decide_arrivals(mab_state, shared, ucb_c: float):
    """UCB deployment decisions (eq. 9) for one interval's arrival rows.

    SLAs are batch-normalized exactly as ``MABDecider._norm`` (float64
    math, float32 cast) so the in-kernel context classification matches
    the host decider bit for bit.  Padding rows get a (harmless)
    decision; ``admit`` masks them out.
    """
    sla_n = (shared["sla"] * 40000.0
             / jnp.maximum(shared["batch"].astype(jnp.float64), 1.0)) \
        .astype(jnp.float32)
    d, _ = mab_mod.decide_ucb_batch(mab_state, sla_n, shared["app"], ucb_c)
    return d


def mab_decide_arrivals_train(mab_state, shared, key_t):
    """ε-greedy training decisions (eq. 6) for one interval's arrival
    rows, against the carried ``MABState`` and the interval's fold-in
    key.  SLA normalization matches ``mab_decide_arrivals``; the per-row
    key choreography lives in ``mab.decide_train_rows`` (prefix-stable,
    so the host replay running on the dense valid prefix draws identical
    bits).  Padding rows get a (harmless) decision; ``admit`` masks them
    out.
    """
    sla_n = (shared["sla"] * 40000.0
             / jnp.maximum(shared["batch"].astype(jnp.float64), 1.0)) \
        .astype(jnp.float32)
    d, _ = mab_mod.decide_train_rows(mab_state, key_t, sla_n, shared["app"])
    return d


def mab_feedback(mab_state, state, fin, phi: float, gamma: float, k: float):
    """End-of-interval MAB bookkeeping over the slots that finished.

    Gathers the feedback channels in admission (``seq``) order — the
    canonical order the parity replay feeds the same shared masked
    functions — and applies ``end_of_interval_masked``.
    """
    ordr = jnp.argsort(jnp.where(fin, state["seq"], _SEQ_DEAD))
    batch = state["batch"]               # >= 1 by construction
    sla_n = (state["sla"] * 40000.0 / batch).astype(jnp.float32)
    resp_n = (state["resp"] * 40000.0 / batch).astype(jnp.float32)
    dec = jnp.clip(state["decision"], 0, 1)
    return mab_mod.end_of_interval_masked(
        mab_state, state["app"][ordr], sla_n[ordr], resp_n[ordr],
        state["acc"].astype(jnp.float32)[ordr], dec[ordr], fin[ordr],
        phi, gamma, k)


def gillis_decide_arrivals(Q, eps, shared, key_t, layer_ref):
    """Gillis ε-greedy arm decisions (layer vs compressed) for one
    interval's arrival rows, against the carried Q-table/ε and the
    interval's fold-in key.  Context buckets come straight from the raw
    SLA/batch via the shared ``mab.gillis_bucket`` — no normalization,
    matching the host ``GillisDecider._ctx``.  Padding rows get a
    (harmless) decision; ``admit`` masks them out.
    """
    arms, _ = mab_mod.gillis_decide_rows(
        Q, eps, key_t, shared["sla"],
        shared["batch"].astype(jnp.float64), shared["app"], layer_ref)
    return arms


def gillis_feedback(Q, state, fin, layer_ref, lr: float):
    """End-of-interval Gillis Q-updates over the slots that finished.

    Gathers the feedback channels in admission (``seq``) order — the
    order the host replay walks its finished list — recomputes each
    slot's context bucket from its stored SLA/batch/app, and applies the
    shared sequential ``mab.gillis_update_masked``.
    """
    ordr = jnp.argsort(jnp.where(fin, state["seq"], _SEQ_DEAD))
    bucket = mab_mod.gillis_bucket(state["sla"], state["batch"],
                                   state["app"], layer_ref)
    arm = (state["decision"] != 0).astype(jnp.int32)   # LAYER → arm 0
    reward = ((state["resp"] <= state["sla"]).astype(jnp.float64)
              + state["acc"]) / 2.0
    return mab_mod.gillis_update_masked(
        Q, state["app"][ordr], bucket[ordr], arm[ordr], reward[ordr],
        fin[ordr], lr)


def state_features_k(state, cl, lat_mult, interval_s: float):
    """(n, 4) worker utilization features — the array mirror of
    ``repro.env.soa.state_features`` (cpu load, ram load, net quality,
    placed count), computed post-admit so new fragments (worker −1) are
    excluded exactly as on the host.  float64 censuses; the float32 cast
    happens inside the surrogate input packing.
    """
    n = cl["mips"].shape[0]
    worker, done = state["worker"], state["done"]
    K, F = worker.shape
    wsafe = jnp.clip(worker, 0, n - 1)
    live = (~done) & (worker >= 0)
    oh = _onehot(wsafe, n)
    mips_f = jnp.maximum(cl["mips"][wsafe], 1)
    cpu_v = jnp.where(live, state["instr"] / mips_f / interval_s, 0.0)
    is_stage = jnp.arange(F, dtype=jnp.int32)[None, :] \
        == state["stage"][:, None]
    holds = live & ((~state["chain"][:, None]) | is_stage)
    ram_v = jnp.where(holds, state["ram"] / cl["ram"][wsafe], 0.0)
    stacked = jnp.stack([cpu_v, ram_v, live.astype(jnp.float64)])
    sums = jnp.einsum("ckf,kfn->cn", stacked, oh)
    cpu, ram_load, cnt = sums[0], sums[1], sums[2]
    return jnp.stack([jnp.clip(cpu, 0, 4) / 4.0,
                      jnp.clip(ram_load, 0, 2) / 2.0,
                      1.0 / lat_mult,
                      jnp.clip(cnt, 0, 8) / 8.0], axis=-1)


def _daso_rows(cfg, state, req):
    """Container-row packing shared by the DASO deploy/train stages: the
    first ``cfg.max_containers`` live fragments in admission order (the
    same container enumeration as ``EdgeSim.containers``), each with its
    warm-start worker (current worker or BestFit target from ``req``)
    and clipped split decision."""
    K, F = state["worker"].shape
    n, C = cfg.num_workers, cfg.max_containers
    order = _admission_order(state)
    live = ~state["done"]
    flat_ord = live[order].ravel()
    ncum = jnp.cumsum(flat_ord.astype(jnp.int32))
    n_live = ncum[-1]
    pos = jnp.minimum(jnp.searchsorted(
        ncum, jnp.arange(1, C + 1, dtype=jnp.int32), side="left"),
        K * F - 1)
    slot_i = order[pos // F]
    f_i = (pos % F).astype(jnp.int32)
    rowvalid = jnp.arange(C) < n_live
    warm = jnp.clip(req[slot_i, f_i], 0, n - 1)
    dec_i = jnp.where(rowvalid, jnp.clip(state["decision"][slot_i], 0, 1), 0)
    return slot_i, f_i, rowvalid, warm, dec_i


def daso_requests(cfg, theta, state, feat, req):
    """Array-form DASO placement stage (§5.3 / eqs. 10–12).

    Packs the first ``cfg.max_containers`` live fragments (admission
    order — the same container enumeration as ``EdgeSim.containers``)
    into placement-logit rows warm-started from ``req`` (current worker
    or BestFit target), gradient-ascends the surrogate with
    ``optimize_placement``, and writes each row's argmax worker back into
    the request matrix.  Fragments beyond the container budget keep their
    BestFit request, and ``apply_requests`` feasibility-repairs the
    result — the fallback for infeasible surrogate outputs.
    """
    K, _ = state["worker"].shape
    slot_i, f_i, rowvalid, warm, dec_i = _daso_rows(cfg, state, req)
    logits = daso_mod.warm_start_logits(cfg, warm, rowvalid)
    mask = rowvalid.astype(feat.dtype)
    p_opt, _, _ = daso_mod.optimize_placement(cfg, theta, feat, logits,
                                              dec_i, mask)
    assign = jnp.argmax(p_opt, axis=-1).astype(jnp.int32)
    tgt = jnp.where(rowvalid, slot_i, K)     # K == out of bounds -> drop
    return req.at[tgt, f_i].set(assign, mode="drop")


def daso_requests_train(cfg, theta, state, feat, req, use_opt):
    """Train-mode DASO stage: same row packing/ascent as
    ``daso_requests``, but (a) cold-start gated — until ``use_opt`` the
    warm (BestFit/current-worker) logits are used verbatim, matching the
    host placer before ``place_min`` replay records exist — and (b) it
    also returns this interval's packed surrogate input
    (``daso.pack_input`` of the logits actually used), the features half
    of the (x, O^P) pair the training carry appends to the replay
    window after the physics run.

    ``use_opt`` must be an UNBATCHED scalar (the driver derives it from
    the fori_loop interval index, which the one-record-per-interval
    append invariant makes equivalent to the replay-count gate): the
    ``lax.cond`` then genuinely skips the ascent while-loop — the
    dominant per-interval cost — during cold start, instead of
    computing and discarding it, and stays a real conditional under
    ``vmap``."""
    K, _ = state["worker"].shape
    slot_i, f_i, rowvalid, warm, dec_i = _daso_rows(cfg, state, req)
    logits = daso_mod.warm_start_logits(cfg, warm, rowvalid)
    mask = rowvalid.astype(feat.dtype)
    p_used = lax.cond(
        use_opt,
        lambda _: daso_mod.optimize_placement(cfg, theta, feat, logits,
                                              dec_i, mask)[0],
        lambda _: logits, None)
    assign = jnp.argmax(p_used, axis=-1).astype(jnp.int32)
    tgt = jnp.where(rowvalid, slot_i, K)     # K == out of bounds -> drop
    x = daso_mod.pack_input(cfg, feat, p_used, dec_i, mask)
    return req.at[tgt, f_i].set(assign, mode="drop"), x
