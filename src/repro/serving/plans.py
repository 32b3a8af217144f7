"""Execution plans — the paper's split strategies as TPU serving plans.

* ``layer_pipeline``: the layer-split analog.  The layer stack is cut into
  S sequential stages (on hardware: one mesh sub-slice per stage,
  activations forwarded stage-to-stage over ICI).  Full fidelity, higher
  per-request latency, pipelined throughput.

* ``semantic_branch``: the semantic-split analog.  B disjoint branches,
  each using a 1/B head-group and 1/B ffn-channel slice of the weights,
  run in parallel (batched over a branch axis in one program on one
  chip) and their logits are combined.  Reduced fidelity (measurably —
  branches share no features).

Both are REAL executions of the same parameters (sliced views), so the
accuracy/latency trade-off the MAB consumes is measured, not assumed.
"""
from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models import moe as moe_mod
from repro.models.layers import rmsnorm

LAYER_PLAN, SEMANTIC_PLAN = 0, 1


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    kind: int                 # LAYER_PLAN | SEMANTIC_PLAN
    num_stages: int = 2       # pipeline stages (layer plan)
    num_branches: int = 2     # parallel branches (semantic plan)


def stage_bounds(num_layers: int, num_stages: int):
    import numpy as np
    b = np.linspace(0, num_layers, num_stages + 1).astype(int)
    return list(zip(b[:-1], b[1:]))


def optimal_stage_bounds(cfg, seq: int, batch: int, num_stages: int):
    """Gillis-DP stage boundaries from the analytic per-layer cost table
    (latency-balanced cuts instead of equal layer counts)."""
    from repro.core.partitioner import model_layer_costs, optimal_partition
    costs = model_layer_costs(cfg, seq, batch)
    cuts, _ = optimal_partition(costs, num_stages, [1.0], hop_bw=1e15,
                                exact=True)
    return list(zip(cuts[:-1], cuts[1:]))


def pipeline_forward(params, batch, cfg, num_stages: int, constrain=None,
                     bounds=None, with_routes=False):
    """Layer-split execution: identical math to ``forward`` but structured
    as sequential stages (the per-stage boundary is where activations move
    between mesh slices on hardware).  Must equal forward() exactly for
    ANY stage boundaries; ``bounds`` defaults to equal layer counts, the
    serving engine passes Gillis-DP latency-balanced cuts.  With
    ``with_routes`` also returns the dropless MoE layers' routing
    (``model.stack_routes``)."""
    ctx = M._make_ctx(batch, cfg, constrain,
                      cache_len=batch["tokens"].shape[1])
    x = M.embed_tokens(params, batch, cfg, ctx["positions"])
    kinds = cfg.layer_kinds
    blocks = _flat_blocks(params, cfg)
    routes = []
    for lo, hi in (bounds or stage_bounds(len(kinds), num_stages)):
        for i in range(lo, hi):
            x, _, _, r = M.apply_block(kinds[i], blocks[i], x, ctx, cfg)
            routes.append(r)
    logits = M.lm_head(params, x, cfg)
    return (logits, M.stack_routes(routes)) if with_routes else logits


def unrolled(params, cfg):
    """(params, cfg) with one parameter tree per layer (``scan_layers``
    off): a stacked body's periods cut into their layers once."""
    if not cfg.scan_layers:
        return params, cfg
    out = {k: v for k, v in params.items() if k != "body"}
    out.update(prefix=_flat_blocks(params, cfg), suffix=[])
    return out, dataclasses.replace(cfg, scan_layers=False)


def _flat_blocks(params, cfg) -> List:
    """Per-layer params in order (prefix, unstacked body periods, suffix)."""
    prefix, (pattern, periods), suffix = cfg.scan_segments
    blocks = list(params["prefix"])
    if periods:
        for i in range(periods):
            period = jax.tree.map(lambda a: a[i], params["body"])
            for j in range(len(pattern)):
                blocks.append(period[f"b{j}"])
    blocks.extend(params["suffix"])
    return blocks


#: per weight name, the axis a semantic branch slices: attention heads
#: (GQA and MLA), MLP channels (dense and the MoE's shared expert)
_BRANCH_AXIS = {"attn": {"wq": 1, "wk": 1, "wv": 1, "wo": 0, "bq": 0,
                         "bk": 0, "bv": 0, "wq_b": 1, "wkv_b": 1},
                "mlp": {"w_up": 1, "w_gate": 1, "w_down": 0}}


def _branch_params(block, cfg, num_branches):
    """(params, vmap axes) of one block for B batched branches: each
    sliced weight reshaped so that branch b's head group or channel block
    is row b of a leading axis (a view, or a transpose XLA fuses into the
    dot that reads it); shared weights (norms, latent down-projections,
    router, routed experts) unchanged, axis None.  The routed experts'
    channel blocks are taken by ``moe_routed`` (``_branch_block``)."""
    B = num_branches

    def cut(arr, axis):
        shape = arr.shape[:axis] + (B, arr.shape[axis] // B) \
            + arr.shape[axis + 1:]
        return jnp.moveaxis(arr.reshape(shape), axis, 0)

    def part(tree, rules):
        p, ax = {}, {}
        for name, a in tree.items():
            axis = rules.get(name)
            p[name], ax[name] = (a, None) if axis is None else (cut(a, axis), 0)
        return p, ax

    attn_ok = cfg.num_heads % B == 0 and (
        cfg.mla is not None or cfg.num_kv_heads % B == 0)
    params, axes = {}, {}
    for name, sub in block.items():
        if name == "attn" and attn_ok:
            params[name], axes[name] = part(sub, _BRANCH_AXIS["attn"])
        elif name == "mlp":
            params[name], axes[name] = part(sub, _BRANCH_AXIS["mlp"])
        elif name == "moe" and cfg.moe.dispatch == "dropless" \
                and "shared" in sub:
            sp, sa = part(sub["shared"], _BRANCH_AXIS["mlp"])
            params[name] = dict(sub, shared=sp)
            axes[name] = dict(jax.tree.map(lambda _: None, sub), shared=sa)
        else:
            params[name] = sub
            axes[name] = jax.tree.map(lambda _: None, sub)
    return params, axes


def _branch_block(kind, p, axes, x, ctx, cfg, B):
    """One block of the B branches, x (B, b, s, d) -> (x, route).  A
    dropless MoE block runs its attention per branch, then routes all
    branches together, branch b on channel block b of each held expert
    (``moe_routed``), and its shared expert per branch; any other block
    runs once per branch."""
    if not (kind in M.MOE_KINDS and cfg.moe.dispatch == "dropless"):
        x = jax.vmap(lambda p_, x_: M.apply_block(kind, p_, x_, ctx, cfg)[0],
                     in_axes=(axes, 0))(p, x)
        return x, None
    h, _ = jax.vmap(lambda p_, x_: M.mixer(kind, p_, x_, ctx, cfg),
                    in_axes=({"attn": axes["attn"], "norm1": None}, 0))(
        {"attn": p["attn"], "norm1": p["norm1"]}, x)
    x = x + h
    _, b, s, d = x.shape
    xn = rmsnorm(x, p["norm2"], cfg.norm_eps).reshape(B, b * s, d)
    pm = p["moe"]
    y, route = moe_mod.moe_routed(pm, xn, cfg, channel_blocks=B,
                                  probe=ctx.get("probe"))
    if cfg.moe.num_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + jax.vmap(lambda sp, xb: moe_mod.shared_expert(
                dict(pm, shared=sp), xb, cfg))(pm["shared"], xn)
    return x + y.reshape(x.shape), route


def branch_forward(params, batch, cfg, num_branches: int, constrain=None,
                   with_routes=False):
    """Semantic-split execution: B disjoint weight-slice branches run the
    whole depth, batched over a leading branch axis in one program; the
    branches' logits are averaged (their normed final states, the head
    being linear).  Approximate by construction (no cross-branch
    features) — the fidelity cost the MAB trades against latency.
    Branch b takes heads [b h/B, (b+1) h/B) and channels [b w/B, (b+1)
    w/B) of every MLP (dense, shared expert, each held expert); the
    router and the latent down-projections are shared."""
    B = num_branches
    ctx = M._make_ctx(batch, cfg, constrain,
                      cache_len=batch["tokens"].shape[1])
    x = M.embed_tokens(params, batch, cfg, ctx["positions"])
    x = jnp.broadcast_to(x, (B,) + x.shape)
    routes = []
    for kind, block in zip(cfg.layer_kinds, _flat_blocks(params, cfg)):
        bp, axes = _branch_params(block, cfg, B)
        x, r = _branch_block(kind, bp, axes, x, ctx, cfg, B)
        routes.append(r)
    with jax.named_scope("lm_head"):
        xn = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        xn = xn.astype(jnp.float32).mean(0).astype(xn.dtype)
        logits = M.output_head(params, xn, cfg)
    return (logits, M.stack_routes(routes)) if with_routes else logits


def plan_cost_model(cfg, plan: PlanSpec, seq: int, batch: int,
                    chips_per_slice: int = 64):
    """Napkin latency model (seconds) used to seed the MAB estimates:
    layer pipeline pays sequential stages + hop latency; semantic branches
    run 1/B of the width in parallel."""
    from repro.launch.mesh import PRODUCTION_KIND, chip_peaks
    peaks = chip_peaks(PRODUCTION_KIND)
    flops = 2.0 * cfg.active_param_count() * seq * batch
    if plan.kind == LAYER_PLAN:
        hop_bytes = batch * seq * cfg.d_model * 2
        per_stage = flops / plan.num_stages / (
            chips_per_slice * peaks["flops_bf16"] * 0.4)
        return plan.num_stages * per_stage + \
            (plan.num_stages - 1) * hop_bytes / peaks["ici_bw"]
    per_branch = (flops / plan.num_branches) / \
        (chips_per_slice * peaks["flops_bf16"] * 0.4)
    return per_branch
