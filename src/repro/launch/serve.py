"""Serving drivers.

Two modes share this entrypoint:

  * default — SLA-aware SplitPlace plan selection over batched model
    requests (reduced model on CPU; mesh-slice plans on TPU):

        PYTHONPATH=src python -m repro.launch.serve --requests 20

  * ``--stream`` — the always-on edge-simulator serving loop
    (``repro.env.jaxsim.stream``): a host feeder thread streams Poisson
    task arrivals into the fixed-capacity device slot ring while the
    jitted interval program executes double-buffered chunks, printing
    rolling QPS / p50-p99 response / deadline-violation metrics:

        PYTHONPATH=src python -m repro.launch.serve --stream \\
            --policy mc --tasks 100000 --chunk 64
"""
from __future__ import annotations

import argparse

import numpy as np


def _stream_main(args):
    from repro.env.cluster import make_cluster
    from repro.env.jaxsim import policies
    from repro.launch import experiments

    pretrain_state = None
    if args.pretrain > 0:
        print(f"pretraining ({args.pretrain} intervals)...")
        wants = ("splitplace",) if args.policy != "gillis" else ("gillis",)
        pretrain_state = experiments.pretrain(args.pretrain, lam=args.lam,
                                              policies=wants)
    elif (row := policies.spec(args.policy)).needs_daso:
        theta, cfg = experiments.seeded_surrogate(make_cluster().n,
                                                  seed=args.seed)
        pretrain_state = experiments.PretrainState(daso_theta=theta,
                                                   daso_cfg=cfg)
        print(f"{args.policy}: DASO placer on an untrained surrogate "
              f"(random weights from seed {args.seed}; --pretrain N "
              f"trains one)" + (", cold-start MAB" if row.needs_mab
                                else ""))

    def progress(i, runner, rolling):
        if i % args.report_every:
            return
        s = rolling.snapshot()
        print(f"chunk {i:5d}  intervals={runner.t0:7d}  "
              f"qps={s['qps']:.4f}/s  p50={s.get('p50_response_s', 0):.0f}s "
              f"p99={s.get('p99_response_s', 0):.0f}s  "
              f"viol={s['violation_rate']:.3f}  "
              f"occ={s['occupancy_mean']:.1f}", flush=True)

    rep = experiments.run_stream(
        policy=args.policy, lam=args.lam, seed=args.seed,
        target_tasks=args.tasks, chunk_intervals=args.chunk,
        max_active=args.capacity, interval_s=args.interval,
        substeps=args.substeps, window_intervals=args.window,
        pretrain_state=pretrain_state, on_chunk=progress)
    s = rep["summary"]
    print(f"\nserved {rep['finished']} tasks over {rep['n_intervals']} "
          f"intervals ({rep['n_chunks']} chunks of {args.chunk}); "
          f"{rep['live']} still live")
    print(f"admission: offered={rep['offered']} "
          f"feeder_overflow={rep['feeder_overflow']} "
          f"ring_dropped={rep['dropped']}")
    print(f"occupancy: max={rep['max_occupancy']:.0f}/{args.capacity}, "
          f"halves {rep['occupancy_mean_first_half']:.1f} / "
          f"{rep['occupancy_mean_second_half']:.1f}")
    print(f"summary: reward={s['reward']:.3f} "
          f"sla_violations={s['sla_violations']:.3f} "
          f"accuracy={s['accuracy']:.3f} "
          f"energy_mwhr={s['energy_mwhr']:.3f}")


def _plan_main(args):
    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.engine import Request, SplitPlaceEngine

    cfg = get_config(args.arch).reduced(max_d_model=256, max_layers=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = SplitPlaceEngine(params, cfg, num_stages=args.stages,
                           num_branches=args.branches)
    rng = np.random.RandomState(0)
    tok = rng.randint(0, cfg.vocab_size,
                      (args.batch, args.seq)).astype(np.int32)
    eng.warmup(tok)
    t_layer = eng._run(0, {"tokens": jax.numpy.asarray(tok)})[-1]
    t_sem = eng._run(1, {"tokens": jax.numpy.asarray(tok)})[-1]
    print(f"plan latencies: layer-pipeline {t_layer*1e3:.1f}ms, "
          f"semantic-branch {t_sem*1e3:.1f}ms")
    for i in range(args.requests):
        tight = rng.rand() < 0.5
        ddl = t_sem * 2.5 if tight else t_layer * 4.0
        r = eng.serve(Request(tokens=tok, deadline_s=float(ddl)))
        print(f"req {i:3d} deadline={'tight' if tight else 'loose'} -> "
              f"plan={'layer' if r.plan == 0 else 'semantic'} "
              f"lat={r.latency_s*1e3:.1f}ms "
              f"simulated={r.sim_latency_s*1e3:.1f}ms fid={r.fidelity:.3f} "
              f"met={r.met_deadline} reward={r.reward:.3f}")
    print(f"final MAB Q:\n{np.asarray(eng.state.Q).round(3)}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="kimi-k2-1t-a32b",
                    help="plan mode: architecture, run .reduced()")
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--branches", type=int, default=2)
    ap.add_argument("--stream", action="store_true",
                    help="run the always-on edge-sim serving loop "
                         "instead of model-plan selection")
    ap.add_argument("--policy", default="mc",
                    help="stream mode: a policy name of "
                         "jaxsim.policies.TABLE")
    ap.add_argument("--lam", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tasks", type=int, default=10_000,
                    help="stream mode: stop after offering this many")
    ap.add_argument("--chunk", type=int, default=64,
                    help="stream mode: intervals per jitted chunk")
    ap.add_argument("--capacity", type=int, default=512,
                    help="stream mode: device ring slot capacity")
    ap.add_argument("--interval", type=float, default=300.0)
    ap.add_argument("--substeps", type=int, default=30)
    ap.add_argument("--window", type=int, default=256,
                    help="stream mode: rolling-metrics window intervals")
    ap.add_argument("--report-every", type=int, default=10,
                    help="stream mode: print rolling metrics every N "
                         "chunks")
    ap.add_argument("--pretrain", type=int, default=0,
                    help="stream mode: §6.3 pretraining intervals for "
                         "learned policies (0 = cold start)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.stream:
        _stream_main(args)
    else:
        _plan_main(args)


if __name__ == "__main__":
    main()
