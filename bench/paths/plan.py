"""The plan-engine window: a model configuration served request by
request through ``SplitPlaceEngine.serve``.

Set-up builds the cut model's weights on the device from the seed and
the engine with the cell's S stages and B branches, then, for each
request length of the traffic's pool, compiles every program (layer
plan, semantic plan, the monolithic fidelity forward) and times each
plan (the median of ``latency_runs``), which sets the deadlines: tight
= ``tight_x_semantic`` times the semantic plan's latency at that length,
loose = ``loose_x_layer`` times the layer plan's.  One request served
through ``serve`` warms the engine's own decision and placement steps.

The window serves the pool's requests back to back, one in flight, in
the seed's permutation, cycled, until the first that ends after
``seconds``: ``tasks_per_s`` is requests over the window's wall seconds,
``chunk_p95_ms`` the 95th percentile of a request's wall time from the
``serve`` call to its result on the host (a "chunk" of this path is one
served request).

The weights are the harness's own: ``bench/ref/checkpoint.py`` makes
them from the seed by the published checkpoint's names and layout, and
``load_params`` loads them into the program's parameter tree.  Every
request carries a seeded sample of ``compare.positions`` token
positions (``Request.probe``), at which the timed programs report each
dropless MoE layer's input and held-expert output.

``correct`` compares what the timed path returned with the plain
float32 reference (``bench/ref/model.py``) on the same checkpoint: for
every set-up request, and for the first ``window_per_pair`` window
requests of each (plan, length) pair, each routed as the program routed
it.  The checks: the widest relative RMS gap of a request's logits at
the sampled positions (``logits_rel_err``) and its widest single gap
over the reference's RMS (``logits_max_err``); the held experts' part of
every MoE layer and branch at those positions, the reference's computed
on the program's own layer input (``expert_rel_err``, widest request:
the experts are a small share of the logits, so that a lower precision
there shows); the tokens whose program routing falls more than
``route_eps`` below the reference's own top-k scores (``route_flips``);
the layer plan against the monolithic forward at every length
(``layer_plan_vs_forward``, 0: they are one computation); and the plans
that went uncompared (``plans_missing``).

A ``--trace 1`` run then serves the next cycle of the pool, with the
cell's deadlines, under a profile of its own, by length, and sums each
program's leaf operations by the model's scopes (``mla``, ``moe.route``,
``moe.experts``, ``moe.shared``, ``dense_mlp``, ``lm_head``), with the
engine's ledger counting the token-expert pairs the held experts
computed.
"""
from __future__ import annotations

import collections
import shutil
import tempfile
import time

import numpy as np

from bench import common, flops, trace_phases, trace_reduce
from bench.ref import checkpoint
from bench.ref import model as ref_model

SCOPES = ("mla", "moe.route", "moe.experts", "moe.shared", "dense_mlp",
          "lm_head")


def program_config(cfg: dict):
    """The system's ``ModelConfig`` for the configuration file."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, \
        YaRNConfig
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise common.CellError("group-limited routing is not implemented")
    if not cfg["norm_topk_prob"]:
        raise common.CellError("the router always renormalises its top-k")
    rs = cfg["rope_scaling"]
    first, count = cfg["experts_held"]
    if count != cfg["n_routed_experts"]:
        raise common.CellError("experts_held must count n_routed_experts")
    return ModelConfig(
        name=cfg["name"], arch_type="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        block_pattern=("mla_moe",), rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YaRNConfig(
            factor=rs["factor"],
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        activation=cfg["hidden_act"], mlp_gated=True,
        mla=MLAConfig(q_lora_rank=cfg["q_lora_rank"],
                      kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=MoEConfig(
            num_experts=cfg["router_experts"],
            top_k=cfg["num_experts_per_tok"],
            d_ff_expert=cfg["moe_intermediate_size"],
            num_shared_experts=cfg["n_shared_experts"],
            shared_d_ff=cfg["n_shared_experts"]
            * cfg["moe_intermediate_size"],
            first_k_dense=cfg["first_k_dense_replace"], dispatch="dropless",
            scoring_func=cfg["scoring_func"],
            correction_bias=cfg["topk_method"] == "noaux_tc",
            routed_scaling_factor=cfg["routed_scaling_factor"],
            shared_gate=False, held=(first, count)),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg.get("param_dtype", "bfloat16"),
        compute_dtype=cfg.get("compute_dtype", "bfloat16"),
        remat=False, scan_layers=False)   # load_params: a tree per layer


class Traffic:
    """The cell's requests: the pool's (length, deadline class) pairs in
    the seed's permutation, cycled; token ids uniform over the vocabulary
    slice."""

    def __init__(self, tr, vocab, seed):
        self.pool = list(zip(tr["pool"]["lengths"], tr["pool"]["tight"]))
        self.order = np.random.default_rng(
            common.sub_seed(seed, 1)).permutation(len(self.pool))
        self.batch = {int(L): b for L, b in tr["batch"].items()}
        self.vocab = vocab
        self.rng = np.random.default_rng(common.sub_seed(seed, 0))
        self.i = 0

    def tokens(self, L):
        return self.rng.integers(0, self.vocab, (self.batch[L], L),
                                 dtype=np.int32)

    def next(self):
        L, tight = self.pool[self.order[self.i % len(self.pool)]]
        self.i += 1
        return L, tight, self.tokens(L)


def load_params(cfg, mcfg, seed):
    """The program's parameter tree (``init_params``' layout, one tree per
    layer) holding the checkpoint of ``seed`` (``bench/ref/checkpoint``),
    made and moved a layer at a time on the device.  The program keeps a
    linear layer's weight (in, out), heads as an axis of their own, the
    held experts stacked, and RMSNorm weights as offsets from 1."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.models import init_params
    dt = mcfg.param_dtype
    H, d = cfg["num_attention_heads"], cfg["hidden_size"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    first, count = cfg["experts_held"]

    def offset(w):
        return (w.astype(jnp.float32) - 1.0).astype(dt)

    def heads(w, n):                       # (H n, r) -> (r, H, n)
        return w.reshape(H, n, w.shape[-1]).transpose(2, 0, 1)

    def mlp(w, pre):
        return {"w_gate": w[f"{pre}.gate_proj.weight"].T,
                "w_up": w[f"{pre}.up_proj.weight"].T,
                "w_down": w[f"{pre}.down_proj.weight"].T}

    def block(i):
        w = checkpoint.layer(cfg, seed, i, dt)
        out = {"norm1": offset(w["input_layernorm.weight"]),
               "attn": {"wq_a": w["self_attn.q_a_proj.weight"].T,
                        "q_norm": offset(w["self_attn.q_a_layernorm.weight"]),
                        "wq_b": heads(w["self_attn.q_b_proj.weight"],
                                      nope + rope),
                        "wkv_a": w["self_attn.kv_a_proj_with_mqa.weight"].T,
                        "kv_norm": offset(
                            w["self_attn.kv_a_layernorm.weight"]),
                        "wkv_b": heads(w["self_attn.kv_b_proj.weight"],
                                       nope + v),
                        "wo": w["self_attn.o_proj.weight"].reshape(
                            d, H, v).transpose(1, 2, 0)},
               "norm2": offset(w["post_attention_layernorm.weight"])}
        if i < cfg["first_k_dense_replace"]:
            out["mlp"] = mlp(w, "mlp")
            return out
        experts = [mlp(w, f"mlp.experts.{e}")
                   for e in range(first, first + count)]
        out["moe"] = dict(
            {n: jnp.stack([x[n] for x in experts]) for n in experts[0]},
            router=w["mlp.gate.weight"].T,
            bias=w["mlp.gate.e_score_correction_bias"],
            shared=mlp(w, "mlp.shared_experts"))
        return out

    top = {n: checkpoint.tensor(seed, n, shape, kind, dt)
           for n, (shape, kind) in checkpoint.top_specs(cfg).items()}
    params = {"embed": top["model.embed_tokens.weight"],
              "final_norm": offset(top["model.norm.weight"]),
              "head": top["lm_head.weight"].T,
              "prefix": [], "suffix": []}
    del top
    for i in range(cfg["num_hidden_layers"]):
        params["prefix"].append(jax.block_until_ready(block(i)))
    want = jax.eval_shape(functools.partial(init_params, cfg=mcfg),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if got != jax.tree.map(lambda a: (a.shape, a.dtype), want):
        raise common.CellError("the loaded checkpoint is not the program's "
                               "parameter layout")
    return params


def _sample(res_logits, routes, tokens, pos, plan):
    """What the comparison needs of one request, on the host."""
    flat = res_logits.reshape(-1, res_logits.shape[-1])
    return {"plan": plan, "tokens": tokens, "pos": pos,
            "logits": np.asarray(flat[pos], np.float32),
            "routes": {k: np.asarray(routes[k])
                       for k in ("topk", "probe_x", "probe_y")}}


def run(ctx) -> dict:
    import gc

    import jax
    import jax.numpy as jnp

    from repro.serving.engine import Request, SplitPlaceEngine
    from repro.serving.plans import LAYER_PLAN, SEMANTIC_PLAN
    cfg, tr, spans, watch = ctx["config"], ctx["traffic"], ctx["spans"], \
        ctx["watch"]
    mcfg = program_config(cfg)
    S, B = tr["stages"], tr["branches"]
    seed = ctx["seed"]
    wseed = common.sub_seed(seed, 2)
    params = load_params(cfg, mcfg, wseed)
    engine = SplitPlaceEngine(params, mcfg, num_stages=S, num_branches=B,
                              seed=common.sub_seed(seed, 4))
    traffic = Traffic(tr, cfg["vocab_size"], seed)
    pick = np.random.default_rng(common.sub_seed(seed, 3))
    npos = tr["compare"]["positions"]

    def probe(tok):
        return np.sort(pick.choice(tok.size, size=npos, replace=False))
    compared, exact, lat = [], 0.0, {}
    lengths = sorted(set(traffic.batch))
    for L in lengths:
        tok = traffic.tokens(L)
        pos = probe(tok)
        engine.warmup(tok, pos)
        batch = engine._batch(tok, pos)
        mono = engine._mono(params, batch)[0]
        for plan in (LAYER_PLAN, SEMANTIC_PLAN):
            walls = []
            for r in range(tr["latency_runs"]):
                logits, routes, wall = engine._run(plan, batch)
                walls.append(wall)
                if r == 0:
                    compared.append(_sample(logits, routes, tok, pos, plan))
                    if plan == LAYER_PLAN:
                        exact = max(exact, float(jnp.abs(logits - mono).max()))
                del logits, routes
            lat[plan, L] = float(np.median(walls))
        del mono
    deadline = lambda L, tight: (
        tr["deadline"]["tight_x_semantic"] * lat[SEMANTIC_PLAN, L] if tight
        else tr["deadline"]["loose_x_layer"] * lat[LAYER_PLAN, L])

    def request(L, tight, tok):
        return Request(tokens=tok, deadline_s=deadline(L, tight),
                       probe=probe(tok))
    tok = traffic.tokens(lengths[0])
    req = request(lengths[0], False, tok)
    res = engine.serve(req)
    compared.append(_sample(res.logits, res.routes, tok, req.probe, res.plan))
    del res

    ctx["start_window"]()
    programs = watch.programs
    per_pair = tr["compare"]["window_per_pair"]
    seen = collections.Counter()
    walls, reqs, plans = [], [], collections.Counter()
    t0 = time.perf_counter()
    while True:
        L, tight, tok = traffic.next()
        req = request(L, tight, tok)
        s = time.perf_counter()
        with spans.span("request"):
            res = engine.serve(req)
        e = time.perf_counter()
        walls.append(e - s)
        plans[res.plan] += 1
        reqs.append((s, e, flops.request_flops(
            cfg, *tok.shape, B if res.plan == SEMANTIC_PLAN else 1)))
        if seen[res.plan, L] < per_pair:
            seen[res.plan, L] += 1
            compared.append(_sample(res.logits, res.routes, tok, req.probe,
                                    res.plan))
        del res
        ctx["tick"]()
        if e - t0 >= ctx["seconds"]:
            break
    t1 = time.perf_counter()
    ctx["end_window"]()
    if watch.programs != programs:
        raise common.CellError(f"{watch.programs - programs} compiles "
                               "inside the window")
    device = ctx["device_record"]()
    counts = {"window_s": t1 - t0, "requests": reqs,
              "plans": {"layer": plans[LAYER_PLAN],
                        "semantic": plans[SEMANTIC_PLAN]}}
    if spans.trace:
        counts.update(scope_profile(jax, engine, traffic, cfg, request,
                                    watch))
        try:
            from bench.peaks import peaks
            pk = peaks(jax.devices()[0].device_kind)
            counts.update(peak_flops=pk["flops_bf16"], peak_bw=pk["hbm_bw"])
        except ValueError:
            pass
    del engine, params
    gc.collect()
    checks, gap = compare_all(checkpoint.load(cfg, wseed, mcfg.param_dtype),
                              cfg, tr, compared, exact, B)
    e2e = {"tasks_per_s": len(walls) / (t1 - t0),
           "chunk_p95_ms": float(np.percentile(walls, 95)) * 1e3}
    window = dict(common.spread("request", walls))
    if "scope_s" in counts:
        window["scope_ms_per_request"] = {
            k: v * 1e3 / counts["scope_requests"]
            for k, v in counts["scope_s"].items()}
        window["scope_compiles"] = counts["scope_compiles"]
    return {"e2e": e2e, "counts": counts, "checks": checks,
            "attempted": len(walls), "failed": 0, "device": device,
            "window": dict(window,
                           plans=counts["plans"], route_gap_max=gap,
                           setup_latency_ms={f"{'ls'[p]}{L}": v * 1e3
                                             for (p, L), v in lat.items()})}


def compare_all(ckpt, cfg, tr, compared, exact, B):
    """(the checks that decide ``correct``, each beside its limit; the
    widest gap of a program routing below the reference's top-k)."""
    rel = worst = expert = 0.0
    flips, gap = 0, -np.inf
    dense = cfg["first_k_dense_replace"]
    for c in compared:
        got, routes = c["logits"], c["routes"]
        branches = B if c["plan"] else 1
        want, routing = ref_model.logits_at(
            ckpt, cfg, c["tokens"], c["pos"], routes["topk"],
            semantic=branches, eps=tr["compare"]["route_eps"])
        rms = float(np.sqrt(np.mean(want.astype(np.float64) ** 2)))
        diff = np.abs(got.astype(np.float64) - want)
        if not np.isfinite(got).all():
            rel = worst = float("inf")
        else:
            rel = max(rel, float(np.sqrt(np.mean(diff ** 2))) / rms)
            worst = max(worst, float(diff.max()) / rms)
        flips += routing["flips"]
        gap = max(gap, routing["gap"])
        num = den = 0.0
        for li in range(len(routes["topk"])):
            for br in range(branches):
                part = ref_model.held_part(
                    ckpt, cfg, dense + li, routes["probe_x"][li, br],
                    routes["topk"][li, br][c["pos"]], br, branches)
                y = np.asarray(routes["probe_y"][li, br], np.float64)
                num += float(np.sum((y - part) ** 2))
                den += float(np.sum(part.astype(np.float64) ** 2))
        expert = max(expert, float(np.sqrt(num / den)) if den
                     and np.isfinite(num) else float("inf"))
    lim = tr["limits"]
    done = {c["plan"] for c in compared}
    return {"logits_rel_err": {"value": rel, "limit": lim["logits_rel_err"]},
            "logits_max_err": {"value": worst,
                               "limit": lim["logits_max_err"]},
            "route_flips": {"value": flips, "limit": lim["route_flips"]},
            "expert_rel_err": {"value": expert,
                               "limit": lim["expert_rel_err"]},
            "layer_plan_vs_forward": {"value": exact,
                                      "limit": lim["layer_plan_vs_forward"]},
            "plans_missing": {"value": 2 - len(done),
                              "limit": lim["plans_missing"]}}, gap


def scope_profile(jax, engine, traffic, cfg, request, watch,
                  load=None) -> dict:
    """Serve the next cycle of the pool (``request`` gives each its
    deadline and probe, as in the window) under a profile per request
    length; returns the device seconds by model scope (leaf operations,
    scope from each compiled program's HLO), the requests profiled, the
    held experts' token-expert pairs and MoE layer executions, and the
    programs compiled meanwhile (0: each program's HLO comes from
    ``lower().compile()`` of the warmed signature, which JAX's trace and
    executable caches answer)."""
    from repro.obs import RunLedger, use_ledger
    load = load or trace_phases.load
    by_len = collections.defaultdict(list)
    for i in range(len(traffic.pool)):
        L, tight, tok = traffic.next()
        by_len[L].append(request(L, tight, tok))
    led = RunLedger("plan-scopes")
    programs = watch.programs
    secs = collections.Counter()
    layer_reads = 0
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    for L, reqs in sorted(by_len.items()):
        batch = engine._batch(reqs[0].tokens, reqs[0].probe)
        scopes = {}
        for fn in (engine._pipe, engine._branch, engine._mono):
            scopes.update(trace_phases.op_scopes(
                fn.lower(engine.params, batch).compile().as_text()))
        tmp = tempfile.mkdtemp(prefix="bench-plan-scopes-")
        try:
            jax.profiler.start_trace(tmp)
            with use_ledger(led), \
                    jax.profiler.TraceAnnotation("bench.scopes"):
                for req in reqs:
                    engine.serve(req)
                    layer_reads += 2 * n_moe
            jax.profiler.stop_trace()
            devices, spans = load(trace_reduce.find_xplane(tmp))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        (lo, hi), _ = trace_reduce.window_of(spans, "scopes")
        for part, s in scope_seconds(devices, lo, hi, scopes).items():
            secs[part] += s
    pairs = led.counters.get("moe.routed_pairs", 0)
    return {"scope_s": dict(secs), "scope_requests": len(traffic.pool),
            "routed_pairs": pairs, "layer_reads": layer_reads,
            "expert_cost": flops.expert_cost(cfg, pairs, layer_reads),
            "scope_compiles": watch.programs - programs}


def scope_seconds(devices, lo, hi, scopes) -> dict:
    """Device seconds of leaf operations in [lo, hi] by the outermost
    model scope on their path (mean over the devices)."""
    out = collections.Counter()
    n = max(1, len(devices))
    for _, ops in devices.values():
        per_line = collections.defaultdict(list)
        for op in ops:
            per_line[op[4]].append(op)
        for evs in per_line.values():
            evs.sort(key=lambda o: (o[2], -o[3]))
            _, kids = trace_phases.nest([(o[2], o[3]) for o in evs])
            for i, (module, name, s, e, _) in enumerate(evs):
                part = trace_phases.phase_of(scopes.get((module, name)),
                                             SCOPES)
                if kids[i] or part is None:
                    continue
                out[part] += max(0, min(e, hi) - max(s, lo)) * 1e-9 / n
    return dict(out)
