"""Execution plans — the paper's split strategies as TPU serving plans.

* ``layer_pipeline``: the layer-split analog.  The layer stack is cut into
  S sequential stages (on hardware: one mesh sub-slice per stage,
  activations forwarded stage-to-stage over ICI).  Full fidelity, higher
  per-request latency, pipelined throughput.

* ``semantic_branch``: the semantic-split analog.  B disjoint branches,
  each using a 1/B head-group and 1/B ffn-channel slice of the weights,
  run in parallel and their logits are combined.  Reduced fidelity
  (measurably — branches share no features), lower latency.

Both are REAL executions of the same parameters (sliced views), so the
accuracy/latency trade-off the MAB consumes is measured, not assumed.
"""
from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp

from repro.models import model as M

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
                     bounds=None):
    """Layer-split execution: identical math to ``forward`` but structured
    as sequential stages (the per-stage boundary is where activations move
    between mesh slices on hardware).  Must equal forward() exactly for
    ANY stage boundaries; ``bounds`` defaults to equal layer counts, the
    serving engine passes Gillis-DP latency-balanced cuts."""
    ctx = M._make_ctx(batch, cfg, constrain,
                      cache_len=batch["tokens"].shape[1])
    x = M.embed_tokens(params, batch, cfg, ctx["positions"])
    kinds = cfg.layer_kinds
    blocks = _flat_blocks(params, cfg)
    for lo, hi in (bounds or stage_bounds(len(kinds), num_stages)):
        for i in range(lo, hi):
            x, _, _ = M.apply_block(kinds[i], blocks[i], x, ctx, cfg)
    return M.lm_head(params, x, cfg)


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


def _slice_block_params(block, cfg, branch, num_branches):
    """Head-group / channel-group slice of one block's weights."""
    def cut(arr, axis, n=num_branches, b=None):
        b = branch if b is None else b
        size = arr.shape[axis] // n
        return jax.lax.slice_in_dim(arr, b * size, (b + 1) * size, axis=axis)

    out = dict(block)
    if "attn" in block:
        a = dict(block["attn"])
        kvh = cfg.num_kv_heads
        if cfg.num_heads % num_branches == 0 and kvh % num_branches == 0:
            a["wq"] = cut(a["wq"], 1)
            a["wk"] = cut(a["wk"], 1)
            a["wv"] = cut(a["wv"], 1)
            a["wo"] = cut(a["wo"], 0)
            if "bq" in a:
                a["bq"], a["bk"], a["bv"] = (cut(a["bq"], 0), cut(a["bk"], 0),
                                             cut(a["bv"], 0))
        out["attn"] = a
    if "mlp" in block:
        m = dict(block["mlp"])
        m["w_up"] = cut(m["w_up"], 1)
        m["w_down"] = cut(m["w_down"], 0)
        if "w_gate" in m:
            m["w_gate"] = cut(m["w_gate"], 1)
        out["mlp"] = m
    return out


def branch_forward(params, batch, cfg, num_branches: int, constrain=None):
    """Semantic-split execution: B disjoint weight-slice branches run the
    whole depth in parallel; branch logits are averaged.  Approximate by
    construction (no cross-branch features) — the fidelity cost the MAB
    trades against latency."""
    ctx = M._make_ctx(batch, cfg, constrain,
                      cache_len=batch["tokens"].shape[1])
    kinds = cfg.layer_kinds
    blocks = _flat_blocks(params, cfg)

    def one_branch(branch):
        x = M.embed_tokens(params, batch, cfg, ctx["positions"])
        for kind, block in zip(kinds, blocks):
            sliced = _slice_block_params(block, cfg, branch, num_branches)
            x, _, _ = M.apply_block(kind, sliced, x, ctx, cfg)
        return M.lm_head(params, x, cfg)

    logits = [one_branch(b) for b in range(num_branches)]
    return sum(logits) / num_branches


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
