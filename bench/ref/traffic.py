"""The benchmark's own traffic: Poisson task arrivals, their split
realizations and the workers' mobility walk, drawn from the seed.

A plain copy of the paper's workload model (arXiv 2205.10635 §6:
AIoTBench-style apps, bimodal deadlines, SUMO-style mobility) with the
exact draw order of the system's host feeder, so the reference replays
the same tasks the feeder hands the device, without importing it.  The
app profiles and worker types come from the configuration file.
"""
from __future__ import annotations

import numpy as np

LAYER, SEMANTIC, COMPRESSED = 0, 1, 2
REF_MIPS = 4019.0
SEMANTIC_WORK = 0.85
COMPRESS_WORK = 1.00
ACC_COMPRESS_DROP = 0.032


def layer_ref_s(prof) -> float:
    return prof["minstr_per_sample"] * 40000 / REF_MIPS


def accuracy_from_noise(prof, decision: int, noise: float) -> float:
    base = {LAYER: prof["acc_layer"], SEMANTIC: prof["acc_semantic"],
            COMPRESSED: prof["acc_layer"] - ACC_COMPRESS_DROP}[decision]
    return float(np.clip(base + noise, 0, 1))


def realize(prof, batch: int, decision: int, img_mb: float):
    """(chain, [(instr, ram_mb, out_bytes), ...]) of one split."""
    total_mi = prof["minstr_per_sample"] * batch
    feat = prof["feat_kb_per_sample"] * 1024.0 * batch
    ram_batch = prof["base_ram_mb"] * batch / 40000.0
    nf, nb = prof["n_frag"], prof["n_branch"]
    if decision == LAYER:
        return True, [(total_mi / nf, img_mb / nf + ram_batch / 2.0,
                       feat if i < nf - 1 else feat * 0.05)
                      for i in range(nf)]
    if decision == SEMANTIC:
        return False, [(total_mi * SEMANTIC_WORK / nb,
                        img_mb / nb + ram_batch / 2.5, feat * 0.02)
                       for _ in range(nb)]
    return False, [(total_mi * COMPRESS_WORK, img_mb * 0.5 + ram_batch * 3.0,
                    feat * 0.02)]


class Arrivals:
    """Poisson arrivals with the Gillis-style bimodal deadline mix."""

    def __init__(self, profiles, lam, seed, apps, tight_frac=0.55,
                 tight=(0.35, 1.15), loose=(2.2, 3.5)):
        self.profiles, self.lam, self.apps = profiles, lam, list(apps)
        self.rng = np.random.RandomState(seed)
        self.tight_frac, self.tight, self.loose = tight_frac, tight, loose
        self.next_id = 0

    def draw(self, now_s):
        out = []
        for _ in range(self.rng.poisson(self.lam)):
            app = int(self.rng.choice(self.apps))
            batch = int(self.rng.randint(16000, 64001))
            ref = layer_ref_s(self.profiles[app]) * batch / 40000.0
            band = self.tight if self.rng.rand() < self.tight_frac \
                else self.loose
            out.append({"id": self.next_id, "app": app, "batch": batch,
                        "sla": ref * self.rng.uniform(*band),
                        "arrival": now_s})
            self.next_id += 1
        return out


class Mobility:
    """Bounded random-waypoint walk of the mobile workers."""

    def __init__(self, mobile, seed, speed=0.08, max_dist=1.0):
        self.mobile = np.asarray(mobile, bool)
        n = len(self.mobile)
        self.rng = np.random.RandomState(seed)
        self.dist = self.rng.uniform(0.1, 0.6, n)
        self.dist[~self.mobile] = 0.15
        self.target = self.rng.uniform(0.05, max_dist, n)
        self.speed, self.max_dist = speed, max_dist

    def step(self):
        n = len(self.mobile)
        move = np.clip(self.target - self.dist, -self.speed, self.speed)
        jitter = self.rng.normal(0, 0.01, n)
        self.dist = np.clip(self.dist + np.where(self.mobile, move + jitter,
                                                 0.0), 0.02, self.max_dist)
        reached = np.abs(self.target - self.dist) < 0.05
        fresh = self.rng.uniform(0.05, self.max_dist, n)
        self.target = np.where(reached & self.mobile, fresh, self.target)
        return 1.0 + 3.0 * self.dist, 1.0 / (1.0 + 1.5 * self.dist)


class Tape:
    """Interval-by-interval task stream for one seed: each ``next()``
    gives (tasks, bw_mult, lat_mult), every task carrying its realized
    split variants and pre-drawn accuracies.

    ``static`` is a fixed split decision (the tape then draws the image
    size in ``realize`` and the accuracy noise after it, one task at a
    time); ``static=None`` realizes both the LAYER and the SEMANTIC
    variant of every task from one image draw, for in-kernel deciders.
    ``max_arrivals`` trims a burst as the feeder does."""

    def __init__(self, cfg, seed, lam, static, max_arrivals):
        self.profiles = cfg["app_profiles"]
        self.gen = Arrivals(self.profiles, lam, seed, cfg["apps"])
        self.mob = Mobility(fleet_arrays(cfg)["mobile"], seed + 1)
        self.static = static
        self.max_arrivals = max_arrivals
        self.dt = cfg["interval_s"] / cfg["substeps"]
        self.substeps = cfg["substeps"]
        self.now = 0.0

    def next(self):
        tasks = self.gen.draw(self.now)[:self.max_arrivals]
        rng = self.gen.rng
        for t in tasks:
            prof = self.profiles[t["app"]]
            if self.static is not None:
                img = rng.uniform(*prof["model_mb"])
                t["variants"] = [realize(prof, t["batch"], self.static, img)]
                t["acc"] = [accuracy_from_noise(prof, self.static,
                                                rng.normal(0, 0.003))]
                t["codes"] = [self.static]
            else:
                img = rng.uniform(*prof["model_mb"])
                t["variants"] = [realize(prof, t["batch"], d, img)
                                 for d in (LAYER, SEMANTIC)]
                noise = rng.normal(0, 0.003)
                t["acc"] = [accuracy_from_noise(prof, d, noise)
                            for d in (LAYER, SEMANTIC)]
                t["codes"] = [LAYER, SEMANTIC]
        lat, bw = self.mob.step()
        for _ in range(self.substeps):
            self.now += self.dt
        return tasks, bw, lat


def fleet_arrays(cfg) -> dict:
    """Per-worker constants of the configuration's fleet, in fleet order,
    with the configuration's compute/RAM/network scales applied."""
    sc = cfg.get("scales", {})
    rows = [cfg["worker_types"][name] for name, qty in cfg["fleet"]
            for _ in range(qty)]
    f = lambda k: np.array([r[k] for r in rows], np.float64)
    return {"mips": f("mips") * sc.get("compute", 1.0),
            "ram": f("ram_mb") * sc.get("ram", 1.0),
            "net_bw": f("net_bw") * sc.get("net", 1.0),
            "power_idle": f("power_idle"), "power_peak": f("power_peak"),
            "cost_hr": f("cost_hr"),
            "mobile": np.array([r["mobile"] for r in rows], bool)}
