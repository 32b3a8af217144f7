"""Plain reference of the edge-cluster interval semantics (arXiv
2205.10635 §6), on flat NumPy arrays in admission order.

One interval: admit the new tasks, place every unplaced fragment
(BestFit, optionally overridden by a placer such as DASO), repair the
placement against worker RAM, advance ``substeps`` sub-steps of
MIPS sharing, swap slowdown and layer-chain transfers, and account what
finished.  The numbers follow the system's documented host simulator
operation for operation, so a sound device run agrees with it to
rounding; ``dtype`` computes everything (clock, instructions,
transfers, worker constants, energy and the telemetry sums) in another
float type, which is the control the comparison has to reject.
"""
from __future__ import annotations

import numpy as np

NIC_CAP_MB = 10.0

_F = ("task_of", "frag_idx", "instr_left", "ram_mb", "out_bytes",
      "worker", "done", "transfer_left")
_T = ("tid", "chain", "placed", "stage", "frag_start", "frag_count",
      "task_done", "app", "batch", "sla", "arrival", "decision", "acc",
      "wait")

#: per-interval telemetry row (the system's telemetry layout)
TELEMETRY_COLS = (
    "n_fin", "sum_resp", "n_viol", "sum_acc", "sum_reward", "sum_wait",
    "fin_layer", "fin_semantic", "fin_compressed",
    "n_dropped", "energy_j", "resp_min", "resp_max", "wait_min",
    "wait_max", "util_mean", "util_max", "n_active",
)


class Sim:
    def __init__(self, fleet, interval_s, substeps, swap_slowdown=0.5,
                 dtype=np.float64):
        self.dtype = dtype
        self.mips = fleet["mips"].astype(dtype)
        self.ram = fleet["ram"].astype(dtype)
        self.net_bw = fleet["net_bw"].astype(dtype)
        self.p_idle = fleet["power_idle"].astype(dtype)
        self.p_peak = fleet["power_peak"].astype(dtype)
        self.cost_hr = fleet["cost_hr"]
        self.n = len(self.mips)
        self.interval_s = float(interval_s)
        self.substeps = int(substeps)
        self.swap = swap_slowdown
        self.now = dtype(0.0)
        self.lat_mult = np.ones(self.n)
        ft = dict(task_of=np.int64, frag_idx=np.int64, worker=np.int64,
                  done=bool)
        tt = dict(tid=np.int64, chain=bool, placed=bool, stage=np.int64,
                  frag_start=np.int64, frag_count=np.int64, task_done=bool,
                  app=np.int64, batch=np.int64, decision=np.int64)
        self.f = {k: np.zeros(0, ft.get(k, dtype)) for k in _F}
        self.t = {k: np.zeros(0, tt.get(k, np.float64)) for k in _T}
        self.per_worker_tasks = np.zeros(self.n)

    @property
    def n_tasks(self):
        return len(self.t["tid"])

    # ---------------------------------------------------------- admit
    def admit(self, tasks, choice):
        """Append tasks in order; ``choice[i]`` picks task i's variant."""
        if not tasks:
            return
        T0, F0 = self.n_tasks, len(self.f["worker"])
        trow, frow, start = {k: [] for k in _T}, {k: [] for k in _F}, F0
        for i, (task, v) in enumerate(zip(tasks, choice)):
            chain, frags = task["variants"][v]
            for k, val in (("tid", task["id"]), ("chain", chain),
                           ("placed", False), ("stage", 0),
                           ("frag_start", start), ("frag_count", len(frags)),
                           ("task_done", False), ("app", task["app"]),
                           ("batch", task["batch"]), ("sla", task["sla"]),
                           ("arrival", task["arrival"]),
                           ("decision", task["codes"][v]),
                           ("acc", task["acc"][v]), ("wait", 0.0)):
                trow[k].append(val)
            for j, (instr, ram, out) in enumerate(frags):
                for k, val in (("task_of", T0 + i), ("frag_idx", j),
                               ("instr_left", instr), ("ram_mb", ram),
                               ("out_bytes", out), ("worker", -1),
                               ("done", False), ("transfer_left", 0.0)):
                    frow[k].append(val)
            start += len(frags)
        for k in _T:
            self.t[k] = np.concatenate(
                [self.t[k], np.asarray(trow[k], self.t[k].dtype)])
        for k in _F:
            self.f[k] = np.concatenate(
                [self.f[k], np.asarray(frow[k], self.f[k].dtype)])

    # ------------------------------------------------------ placement
    def bestfit(self):
        """Requested worker per fragment row (-1: keep the current one):
        each unplaced fragment, in order, goes to the RAM-feasible worker
        of best score -load + 0.3 mips/max + 0.1 free/cap."""
        f, n = self.f, self.n
        live = ~f["done"]
        placed = live & (f["worker"] >= 0)
        pw = f["worker"][placed]
        ram_used = np.bincount(pw, weights=f["ram_mb"][placed], minlength=n)
        load = np.bincount(pw, minlength=n).astype(np.float64)
        req = np.full(len(f["worker"]), -1, np.int64)
        new_rows = np.nonzero(live & (f["worker"] < 0))[0]
        if not new_rows.size:
            return req
        cap = self.ram.astype(np.float64)
        mips = self.mips.astype(np.float64)
        ram_free = cap - ram_used
        static = 0.3 * mips / mips.max()
        score = -load + static + 0.1 * ram_free / cap
        for r in new_rows:
            need = float(f["ram_mb"][r])
            buf = np.where(ram_free < need, -1e9, score)
            w = int(buf.argmax())
            req[r] = w
            ram_free[w] -= need
            load[w] += 1.0
            score[w] = -load[w] + static[w] + 0.1 * ram_free[w] / cap[w]
        return req

    def apply(self, req):
        """Admit requested placements against worker RAM: when every
        request fits outright all are taken; otherwise greedily in task
        order, moving a fragment that does not fit to the worker with the
        most free RAM, or leaving its whole task unplaced."""
        f, t, n = self.f, self.t, self.n
        want = np.where(req >= 0, req, f["worker"])
        live = ~f["done"]
        holds = live & (~t["chain"][f["task_of"]]
                        | (f["frag_idx"] == t["stage"][f["task_of"]]))
        valid = want[live]
        if valid.size == 0 or ((valid >= 0).all() and (valid < n).all()):
            demand = np.bincount(want[holds].clip(0),
                                 weights=f["ram_mb"][holds], minlength=n)
            if (demand <= self.ram).all():
                f["worker"] = np.where(f["done"], f["worker"], want)
                t["placed"] = np.where(t["task_done"], t["placed"], True)
                return
        worker = f["worker"].tolist()
        ram_cap = self.ram.tolist()
        ram_used = [0.0] * n
        ram_used_np = np.zeros(n)
        ram_np = self.ram.astype(np.float64)
        for ti in range(self.n_tasks):
            s, c = int(t["frag_start"][ti]), int(t["frag_count"][ti])
            chain, stage = bool(t["chain"][ti]), int(t["stage"][ti])
            ok = True
            for r in range(s, s + c):
                if f["done"][r]:
                    continue
                rm = float(f["ram_mb"][r])
                hold = (not chain) or int(f["frag_idx"][r]) == stage
                w = int(want[r])
                if w < 0 or w >= n:
                    w = int((ram_used_np / ram_np).argmin())
                if hold and ram_used[w] + rm > ram_cap[w]:
                    free = ram_np - ram_used_np
                    cand = int(free.argmax())
                    if free[cand] >= rm:
                        w = cand
                    else:
                        ok = False
                        break
                worker[r] = w
                if hold:
                    ram_used[w] += rm
                    ram_used_np[w] = ram_used[w]
            if not ok:
                for r in range(s, s + c):
                    worker[r] = -1
            t["placed"][ti] = ok
        f["worker"] = np.asarray(worker, np.int64)

    def features(self):
        """(n, 4) worker state the DASO surrogate reads: CPU load, RAM
        load, network quality, placed-fragment count."""
        f, t, n = self.f, self.t, self.n
        live = ~f["done"] & (f["worker"] >= 0)
        w = f["worker"][live]
        mips = self.mips.astype(np.float64)
        ram = self.ram.astype(np.float64)
        cpu = np.bincount(w, weights=f["instr_left"][live].astype(np.float64)
                          / np.maximum(mips[w], 1) / self.interval_s,
                          minlength=n)
        to = f["task_of"]
        holds = live & (~t["chain"][to] | (f["frag_idx"] == t["stage"][to]))
        hw = f["worker"][holds]
        ram_load = np.bincount(hw, weights=f["ram_mb"][holds] / ram[hw],
                               minlength=n)
        cnt = np.bincount(w, minlength=n).astype(np.float64)
        return np.stack([np.clip(cpu, 0, 4) / 4.0,
                         np.clip(ram_load, 0, 2) / 2.0, 1.0 / self.lat_mult,
                         np.clip(cnt, 0, 8) / 8.0], -1)

    def containers(self):
        """Rows of the live fragments in task order."""
        return np.nonzero(~self.f["done"])[0]

    # -------------------------------------------------------- advance
    def advance(self, bw_mult, lat_mult):
        """Run one interval; returns (finished task rows, telemetry row,
        per-task arrays of the finished)."""
        f, t, n = self.f, self.t, self.n
        dt = self.interval_s / self.substeps
        bw_mult = np.asarray(bw_mult, self.dtype)
        t["wait"] = np.where(t["placed"], t["wait"],
                             t["wait"] + self.interval_s)
        busy = np.zeros(n, self.dtype)
        T = self.n_tasks
        to, idx, worker = f["task_of"], f["frag_idx"], f["worker"]
        instr, done, trans = f["instr_left"], f["done"], f["transfer_left"]
        stage, count = t["stage"], t["frag_count"]
        chain_f = t["chain"][to]
        placeable = (worker >= 0) & t["placed"][to]
        holdable = worker >= 0
        undone = np.bincount(to[~done], minlength=T).astype(np.int64)
        chain_rows = np.nonzero(t["chain"] & t["placed"] & ~t["task_done"])[0]
        fin_rows, fin_now = [], []
        for _ in range(self.substeps):
            is_stage = idx == stage[to]
            runnable = placeable & ~done & (~chain_f
                                            | (is_stage & (trans <= 0.0)))
            holds = holdable & ~done & (~chain_f | is_stage)
            run_w = worker[runnable]
            load = np.bincount(run_w, minlength=n)
            ram_load = np.bincount(worker[holds], weights=f["ram_mb"][holds],
                                   minlength=n)
            swap = ram_load > self.ram
            busy += (load > 0) * self.dtype(dt)
            rate = self.mips[run_w] / np.maximum(load[run_w], 1)
            rate = np.where(swap[run_w], rate * self.swap, rate)
            rows = np.nonzero(runnable)[0]
            instr[rows] -= rate * self.dtype(dt)
            done_rows = rows[instr[rows] <= 0]
            if done_rows.size:
                done[done_rows] = True
                self.per_worker_tasks += np.bincount(worker[done_rows],
                                                     minlength=n)
                hand = chain_f[done_rows] & (idx[done_rows]
                                             < count[to[done_rows]] - 1)
                h = done_rows[hand]
                trans[h + 1] = f["out_bytes"][h]
                np.subtract.at(undone, to[done_rows], 1)
                for ti in np.unique(to[done_rows][undone[to[done_rows]] == 0]):
                    if not t["task_done"][ti]:
                        t["task_done"][ti] = True
                        fin_rows.append(int(ti))
                        fin_now.append(self.now)
            if chain_rows.size:
                srow = t["frag_start"][chain_rows] + stage[chain_rows]
                moving = (stage[chain_rows] > 0) & (trans[srow] > 0)
                if moving.any():
                    m = srow[moving]
                    src, dst = worker[m - 1], worker[m]
                    bw = np.minimum(NIC_CAP_MB,
                                    np.minimum(self.net_bw[src] / 100.0,
                                               self.net_bw[dst] / 100.0))
                    bw = bw * np.minimum(bw_mult[src], bw_mult[dst])
                    trans[m] -= bw * self.dtype(1e6 * dt)
                adv = done[srow] & (stage[chain_rows] < count[chain_rows] - 1)
                stage[chain_rows[adv]] += 1
            self.now = self.now + self.dtype(dt)
        self.lat_mult = np.asarray(lat_mult, np.float64)
        dt_ = self.dtype
        util = busy / dt_(self.interval_s)
        power = self.p_idle + (self.p_peak - self.p_idle) * np.clip(util, 0, 1)
        energy = float(np.sum(power * dt_(self.interval_s)))
        fin = np.asarray(fin_rows, np.int64)
        out = {"resp": np.asarray(fin_now, dt_) - t["arrival"][fin].astype(dt_),
               "sla": t["sla"][fin].astype(dt_),
               "acc": t["acc"][fin].astype(dt_),
               "wait": t["wait"][fin].astype(dt_),
               "decision": t["decision"][fin],
               "app": t["app"][fin], "batch": t["batch"][fin],
               "tid": t["tid"][fin], "util": util, "energy": energy}
        self._compact()
        out["row"] = telemetry_row(out, self.n_tasks)
        return out

    def _compact(self):
        """Drop finished tasks, keeping admission order."""
        keep_t = ~self.t["task_done"]
        if keep_t.all():
            return
        keep_f = keep_t[self.f["task_of"]]
        new_index = np.cumsum(keep_t) - 1
        for k in _F:
            self.f[k] = self.f[k][keep_f]
        self.f["task_of"] = new_index[self.f["task_of"]]
        for k in _T:
            self.t[k] = self.t[k][keep_t]
        counts = self.t["frag_count"]
        self.t["frag_start"] = np.cumsum(counts) - counts


def telemetry_row(o, n_active):
    """One interval's telemetry, summed in the dtype of its arrays."""
    r, s, a, w, d = o["resp"], o["sla"], o["acc"], o["wait"], o["decision"]
    u = o["util"]
    k = len(r)
    return [float(k), float(r.sum()), float((r > s).sum()), float(a.sum()),
            float((((r <= s).astype(r.dtype) + a) / r.dtype.type(2)).sum()),
            float(w.sum()), float((d == 0).sum()), float((d == 1).sum()),
            float((d == 2).sum()), 0.0, o["energy"],
            float(r.min()) if k else 0.0, float(r.max()) if k else 0.0,
            float(w.min()) if k else 0.0, float(w.max()) if k else 0.0,
            float(u.mean()), float(u.max()), float(n_active)]
