"""Layer-split execution as a REAL SPMD pipeline.

``shard_map`` over a ``stage`` mesh axis: each device group holds only its
own contiguous slice of the layer stack (the stacked scan-body params are
sharded on their leading layer dim), activations move stage-to-stage with
``jax.lax.ppermute`` (ICI neighbor hops on hardware), and microbatches
flow through a GPipe schedule of M + S − 1 ticks.

This is the paper's layer-wise split realized as a distributed program —
fragment ≙ stage, activation forwarding ≙ collective-permute — rather
than the stage-structured-but-local ``pipeline_forward``.  Supports the
dense/uniform-pattern architectures (every layer the same block kind).

Validated against the monolithic ``forward`` on a 4-device CPU mesh in
``tests/test_pipeline_smap.py`` (subprocess, 4 forced host devices).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import model as M


def _uniform_kind(cfg):
    kinds = set(cfg.layer_kinds)
    if len(kinds) != 1:
        raise ValueError(f"shard_map pipeline needs a uniform layer "
                         f"pattern, got {kinds}")
    return next(iter(kinds))


def pipeline_shard_map(params, batch, cfg, mesh: Mesh, num_microbatches: int,
                       stage_axis: str = "stage"):
    """Full-sequence forward through an S-stage, M-microbatch pipeline.

    params: standard model params (body stacked over layers; the layer dim
    must divide the stage axis size).  batch: {"tokens": (b, s)} with b
    divisible by num_microbatches.  Returns logits identical to
    ``forward`` (up to float reassociation).
    """
    kind = _uniform_kind(cfg)
    S = mesh.shape[stage_axis]
    tokens = batch["tokens"]
    b, seq = tokens.shape
    Mb = num_microbatches
    assert b % Mb == 0, (b, Mb)
    prefix, (pattern, periods), suffix = cfg.scan_segments
    assert not prefix and not suffix and len(pattern) == 1
    assert periods % S == 0, (periods, S)

    ctx = M._make_ctx({"tokens": tokens[: b // Mb]}, cfg, None,
                      cache_len=seq)

    # embed on every device (replicated), split into microbatches
    x = M.embed_tokens(params, batch, cfg, M._make_ctx(batch, cfg, None,
                                                       cache_len=seq)["positions"])
    x_mb = x.reshape(Mb, b // Mb, seq, cfg.d_model)

    body = params["body"]            # stacked (periods, ...)
    per_stage = periods // S

    def stage_fn(local_body, x_mb_local):
        # local_body: (per_stage, ...) this stage's layers
        # x_mb_local: (Mb, mb, s, d) — full microbatch set (replicated in)
        sidx = jax.lax.axis_index(stage_axis)
        T = Mb + S - 1
        mb_shape = x_mb_local.shape[1:]

        def run_stage(act):
            out = act
            for i in range(per_stage):
                layer = jax.tree.map(lambda a: a[i], local_body)
                out = M.apply_block(kind, layer[f"b0"] if isinstance(
                    layer, dict) and "b0" in layer else layer, out, ctx, cfg)[0]
            return out

        right_perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            inbox, outputs = carry
            # stage 0 injects microbatch t (zeros once drained)
            mb_t = jax.lax.dynamic_index_in_dim(
                x_mb_local, jnp.clip(t, 0, Mb - 1), 0, keepdims=False)
            inject = jnp.where(t < Mb, mb_t, jnp.zeros(mb_shape, mb_t.dtype))
            act = jnp.where(sidx == 0, inject, inbox)
            out = run_stage(act)
            # last stage writes its finished microbatch (t - S + 1)
            done_idx = jnp.clip(t - (S - 1), 0, Mb - 1)
            write = jnp.logical_and(sidx == S - 1, t >= S - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, done_idx, 0,
                                               keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, out, cur), done_idx, 0)
            # forward activations one stage to the right
            inbox = jax.lax.ppermute(out, stage_axis, right_perm)
            return (inbox, outputs), None

        # zeros of the local shape, not zeros_like: the latter would carry
        # the replicated input's sharding into the stage-varying carry
        inbox0 = jax.lax.pcast(jnp.zeros(mb_shape, x_mb_local.dtype),
                               (stage_axis,), to="varying")
        outputs0 = jax.lax.pcast(
            jnp.zeros(x_mb_local.shape, x_mb_local.dtype), (stage_axis,),
            to="varying")
        (inbox, outputs), _ = jax.lax.scan(tick, (inbox0, outputs0),
                                           jnp.arange(T))
        # every stage returns its buffer; only the last stage's is real
        return outputs[None]

    body_specs = jax.tree.map(lambda _: P(stage_axis), body)
    out = jax.shard_map(stage_fn, mesh=mesh,
                        in_specs=(body_specs, P()),
                        out_specs=P(stage_axis))(body, x_mb)
    # the last stage's buffer, replicated; the explicit output sharding
    # is what an Explicit-axis mesh requires to index a sharded dim
    x_out = out.at[S - 1].get(out_sharding=NamedSharding(mesh, P()))
    x_out = x_out.reshape(b, seq, cfg.d_model)
    return M.lm_head(params, x_out, cfg)
