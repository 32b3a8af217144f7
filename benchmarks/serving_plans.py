"""TPU-native SplitPlace: MAB plan selection over real executions.

Measures the layer-pipeline vs semantic-branch latency/fidelity trade-off
on a reduced Kimi K2 (MLA, sigmoid-routed experts; ``.reduced()``) and
shows the engine's UCB converging to deadline-appropriate plans
(DESIGN.md §2.2).  The chip measures the published widths in the
benchmark cell ``kimi-k2-ep32.splitplace.plan``."""
from __future__ import annotations

import argparse
import json
import os

try:
    from benchmarks._provenance import provenance
except ImportError:       # run as a loose script from benchmarks/
    from _provenance import provenance

import jax
import numpy as np

from repro.configs import get_config
from repro.models import init_params
from repro.serving.engine import Request, SplitPlaceEngine
from repro.serving.plans import LAYER_PLAN


def run(n_requests=40, seed=0, out_json=None):
    cfg = get_config("kimi-k2-1t-a32b").reduced(max_d_model=512, max_layers=6)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = SplitPlaceEngine(params, cfg, num_stages=2, num_branches=2,
                           seed=seed)
    rng = np.random.RandomState(seed)
    tok = rng.randint(0, cfg.vocab_size, (4, 256)).astype(np.int32)
    eng.warmup(tok)
    # measure the plan latencies once for the report
    t_layer = eng._run(0, {"tokens": tok})[-1]
    t_sem = eng._run(1, {"tokens": tok})[-1]
    results = []
    for i in range(n_requests):
        tight = rng.rand() < 0.5
        # headroom covers the engine's simulated slice-queue penalty
        # (steady ~1.5x)
        ddl = (t_sem * 2.5) if tight else (t_layer * 4.0)
        results.append(eng.serve(Request(tokens=tok, deadline_s=float(ddl))))
    tail = results[n_requests // 2:]
    layer_frac_tail = float(np.mean([r.plan == LAYER_PLAN for r in tail]))
    met = float(np.mean([r.met_deadline for r in results]))
    fid_layer = [r.fidelity for r in results if r.plan == LAYER_PLAN]
    fid_sem = [r.fidelity for r in results if r.plan != LAYER_PLAN]
    summary = dict(
        latency_layer_ms=t_layer * 1e3, latency_semantic_ms=t_sem * 1e3,
        speedup=t_layer / max(t_sem, 1e-9),
        deadline_met_frac=met,
        layer_plan_frac_tail=layer_frac_tail,
        fidelity_layer=float(np.mean(fid_layer)) if fid_layer else 1.0,
        fidelity_semantic=float(np.mean(fid_sem)) if fid_sem else 0.0,
        reward=float(np.mean([r.reward for r in results])),
    )
    for k, v in summary.items():
        print(f"{k:24s} {v:.4f}")
    summary["provenance"] = provenance()
    if out_json:
        os.makedirs(os.path.dirname(out_json), exist_ok=True)
        json.dump(summary, open(out_json, "w"), indent=1)
    return summary


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/results/serving_plans.json")
    args = ap.parse_args()
    run(out_json=args.out)
