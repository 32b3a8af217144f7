"""Pallas edge-substep physics kernel (SplitPlace interval program).

Fuses one scheduling interval's substep loop — execute/advance physics
under MIPS sharing and swap slowdown, chain activation transfers under
mobility-modulated NIC bandwidth, and the eq. 13–16 metric accumulation
over padded slots — into a single grid-free kernel.  The (K, F) slot
store plus the (n,) cluster rows total a few hundred KB, so every
operand fits in VMEM as one full-array block: the interval-static
hoists (placement one-hots, pairwise chain bandwidth, decision one-hot)
are computed once on loaded values, and the substep loop is a
``fori_loop`` over VMEM-resident data with zero HBM traffic between
substeps — on XLA:CPU the same fusion runs via ``interpret=True``
(the driver's ``substep_impl="pallas"`` switch), where the kernel
traces into the surrounding jit instead of bouncing ~10 small tuned
ops per substep through the scheduler.  The kernel computes in float64,
which Mosaic does not lower, so it does not compile for the TPU yet and
the driver refuses ``"pallas"`` on any backend but the CPU.

Validated against the pure-jnp oracle ``repro.kernels.ref
.edge_substep_ref`` (rtol=1e-12 on the float64 carries) and — through
the driver switch — against the incremental-census XLA formulation,
the EdgeSim differential fuzzer, and the golden fixtures.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: input/output operand order of the fused kernel (carries first, then
#: interval-static per-task/per-fragment channels, then cluster rows)
CARRY_NAMES = ("instr", "done", "transfer", "stage", "task_done", "resp",
               "now", "metrics")
STATIC_NAMES = ("worker", "ram_task", "out_bytes", "nfrag", "chain",
                "placed", "sla", "arrival", "acc_t", "wait_s", "decision",
                "bw_mult", "mips", "cap", "net_bw")
OUT_NAMES = CARRY_NAMES + ("busy", "pwt_delta")


def _kernel(instr_ref, done_ref, transfer_ref, stage_ref, task_done_ref,
            resp_ref, now_ref, metrics_ref, worker_ref, ram_task_ref,
            out_bytes_ref, nfrag_ref, chain_ref, placed_ref, sla_ref,
            arrival_ref, acc_t_ref, wait_s_ref, decision_ref, bw_mult_ref,
            mips_ref, cap_ref, net_bw_ref, o_instr, o_done, o_transfer,
            o_stage, o_task_done, o_resp, o_now, o_metrics, o_busy,
            o_pwt, *, substeps, dt, swap_slowdown, nic_cap):
    worker = worker_ref[...]
    ram_task = ram_task_ref[...]
    out_bytes = out_bytes_ref[...]
    nfrag = nfrag_ref[...]
    chain = chain_ref[...]
    placed = placed_ref[...]
    sla = sla_ref[...]
    arrival = arrival_ref[...]
    acc_t = acc_t_ref[...]
    wait_s = wait_s_ref[...]
    mips, cap = mips_ref[...], cap_ref[...]
    net_bw, bw_mult = net_bw_ref[...], bw_mult_ref[...]

    K, F = worker.shape
    n = mips.shape[0]
    f8 = jnp.float64

    # ---- interval-static hoists (once per kernel, VMEM-resident)
    fidx = jnp.arange(F, dtype=jnp.int32)[None, :]
    wsafe = jnp.clip(worker, 0, n - 1)
    chain_f = chain[:, None]
    placed_f = placed[:, None] & (worker >= 0)
    holdable = worker >= 0
    chactive = chain & placed & ~task_done_ref[...]
    kfn32 = (wsafe[:, :, None] == jnp.arange(n)).astype(jnp.float32)
    mips_f = mips[wsafe]
    doh = (jnp.clip(decision_ref[...], 0, 2)[:, None]
           == jnp.arange(3)).astype(f8)
    not_chain_f = ~chain_f
    arange_n = jnp.arange(n)
    ones_k = jnp.ones((K,))
    dual_idx = jnp.concatenate([wsafe.ravel(), wsafe.ravel() + n])
    hand_static = chain_f & (fidx < nfrag[:, None] - 1)
    out_r = jnp.concatenate([jnp.zeros((K, 1)), out_bytes[:, :-1]], axis=1)
    w_prev = jnp.clip(jnp.roll(worker, 1, axis=1), 0, n - 1)
    bw_pair = jnp.minimum(nic_cap, jnp.minimum(net_bw[w_prev] / 100.0,
                                               net_bw[wsafe] / 100.0))
    bw_pair = bw_pair * jnp.minimum(bw_mult[w_prev], bw_mult[wsafe])

    def census(mask_f):
        return jnp.einsum("kf,kfn->kn", mask_f.astype(jnp.float32), kfn32)

    # ---- the substep loop: pure VPU work on the VMEM-resident carry
    def body(_, carry):
        instr, done, transfer, stage, task_done, now_s, busy, m, resp_rec \
            = carry
        notdone = ~done
        cnt = census(notdone & holdable & not_chain_f)
        is_stage = fidx == stage[:, None]
        tle = (transfer <= 0.0) & is_stage
        runnable = (not_chain_f | tle) & placed_f & notdone
        holds = (not_chain_f | is_stage) & holdable & notdone
        stage_ch = jnp.take_along_axis(
            jnp.stack([wsafe.astype(f8), transfer, bw_pair,
                       runnable.astype(f8), holds.astype(f8)]),
            stage[None, :, None].astype(jnp.int32), axis=2)[:, :, 0]
        w_stage = stage_ch[0].astype(jnp.int32)
        cur_tl, bw_s = stage_ch[1], stage_ch[2]
        r_ch = (stage_ch[3] > 0.5) & chain
        h_ch = (stage_ch[4] > 0.5) & chain
        ohs = w_stage[:, None] == arange_n
        nc_lr = jnp.stack([ones_k, ram_task]) @ cnt.astype(f8)
        ch_lr = jnp.stack([r_ch.astype(f8),
                           jnp.where(h_ch, ram_task, 0.0)]) \
            @ ohs.astype(f8)
        load = nc_lr[0] + ch_lr[0]
        ram_load = nc_lr[1] + ch_lr[1]
        swap = ram_load > cap
        busy = busy + (load > 0) * dt
        lf_sw = jnp.take(jnp.concatenate([load, swap.astype(f8)]),
                         dual_idx).reshape(2, K, F)
        load_f, swap_f = lf_sw[0], lf_sw[1] > 0.5
        rate = mips_f / jnp.maximum(load_f, 1.0)
        rate = jnp.where(swap_f, rate * swap_slowdown, rate)
        instr = instr - jnp.where(runnable, rate * dt, 0.0)
        newly = runnable & (instr <= 0.0)
        done = done | newly
        hand = newly & hand_static
        hand_r = jnp.concatenate(
            [jnp.zeros((K, 1), bool), hand[:, :-1]], axis=1)
        transfer = jnp.where(hand_r, out_r, transfer)
        newfin = jnp.all(done, axis=1) & ~task_done
        task_done = task_done | newfin
        resp_t = now_s - arrival
        resp_rec = jnp.where(newfin, resp_t, resp_rec)
        finf = newfin.astype(f8)
        mcols = jnp.stack(
            [ones_k, resp_t, (resp_t > sla).astype(f8), acc_t,
             ((resp_t <= sla) + acc_t) / 2.0, wait_s,
             doh[:, 0], doh[:, 1], doh[:, 2]], axis=1)
        m = m + finf @ mcols
        s = stage
        cond = chactive & (s > 0) & (cur_tl > 0.0)
        transfer = transfer - jnp.where(
            cond, bw_s * 1e6 * dt, 0.0)[:, None] * is_stage
        done_s = jnp.take_along_axis(done, s[:, None], axis=1)[:, 0]
        adv = chactive & done_s & (s < nfrag - 1)
        stage = stage + adv.astype(jnp.int32)
        now_s = now_s + dt
        return (instr, done, transfer, stage, task_done, now_s, busy, m,
                resp_rec)

    done0 = done_ref[...]
    carry = (instr_ref[...], done0, transfer_ref[...], stage_ref[...],
             task_done_ref[...], now_ref[0], jnp.zeros((n,)),
             metrics_ref[...], resp_ref[...])
    (instr, done, transfer, stage, task_done, now_s, busy, m, resp_rec) \
        = jax.lax.fori_loop(0, substeps, body, carry)
    o_instr[...] = instr
    o_done[...] = done
    o_transfer[...] = transfer
    o_stage[...] = stage
    o_task_done[...] = task_done
    o_resp[...] = resp_rec
    o_now[0] = now_s
    o_metrics[...] = m
    o_busy[...] = busy
    o_pwt[...] = jnp.sum(census(done & ~done0), axis=0).astype(f8)


def edge_substep(instr, done, transfer, stage, task_done, resp, now,
                 metrics, worker, ram_task, out_bytes, nfrag, chain,
                 placed, sla, arrival, acc_t, wait_s, decision, bw_mult,
                 mips, cap, net_bw, *, substeps, dt, swap_slowdown,
                 nic_cap, interpret):
    """One interval of fused substep physics; see ``_kernel`` and the
    module docstring.  Argument order is ``CARRY_NAMES + STATIC_NAMES``;
    returns the ``OUT_NAMES`` tuple (updated carries + per-worker busy
    seconds and completion census).  ``interpret`` has no default: the
    caller chooses it, ``True`` on the CPU only.  The call batches
    transparently under ``vmap`` (the
    batching rule prepends a grid axis), which is how the grid driver
    runs one kernel instance per trace cell."""
    n = mips.shape[0]
    out_shape = (
        jax.ShapeDtypeStruct(instr.shape, instr.dtype),
        jax.ShapeDtypeStruct(done.shape, done.dtype),
        jax.ShapeDtypeStruct(transfer.shape, transfer.dtype),
        jax.ShapeDtypeStruct(stage.shape, stage.dtype),
        jax.ShapeDtypeStruct(task_done.shape, task_done.dtype),
        jax.ShapeDtypeStruct(resp.shape, resp.dtype),
        jax.ShapeDtypeStruct(now.shape, now.dtype),
        jax.ShapeDtypeStruct(metrics.shape, metrics.dtype),
        jax.ShapeDtypeStruct((n,), jnp.float64),
        jax.ShapeDtypeStruct((n,), jnp.float64),
    )
    return pl.pallas_call(
        functools.partial(_kernel, substeps=substeps, dt=dt,
                          swap_slowdown=swap_slowdown, nic_cap=nic_cap),
        out_shape=out_shape,
        interpret=interpret,
    )(instr, done, transfer, stage, task_done, resp, now, metrics,
      worker, ram_task, out_bytes, nfrag, chain, placed, sla, arrival,
      acc_t, wait_s, decision, bw_mult, mips, cap, net_bw)
