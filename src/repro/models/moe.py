"""Mixture-of-Experts FFN: shared experts + routed top-k experts.

Tokens are dispatched in GROUPS (GShard-style): capacity and slot
positions are per-group, so dispatch tensors are (G, gs, E, C) with
gs = group_size — the group dim shards over the batch axes and experts
over the model axis (expert parallelism).

Two dispatch implementations:

* ``onehot`` — GShard/Switch-style capacity dispatch via one-hot einsums.
  Faithful baseline; dispatch einsum costs O(gs^2 · k · cf · d) per group.
* ``gather`` — scatter/gather dispatch: same routing, O(gs · k · d) data
  movement and no one-hot matmuls.  The §Perf hillclimb variant.

* ``dropless`` — no capacity: the token-expert pairs routed to the
  experts this chip holds (``MoEConfig.held``) are sorted by expert and
  run through grouped matmuls (``lax.ragged_dot``) over the held experts
  only; the router still scores all experts, and what the absent experts
  would add is left to the chips that hold them (expert parallelism
  without its exchange).

Routers: softmax (Qwen2-MoE), or sigmoid with a per-expert correction bias
that counts for selection only (DeepSeek-V3 / Kimi K2 ``noaux_tc``); the
chosen scores are renormalised and scaled by ``routed_scaling_factor``.

Semantic-split note (paper mapping): the router IS the paper's semantic
input->branch assignment; expert-group partitioning over the `model` mesh
axis realizes the semantic-split placement natively.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import activation_fn, dense_init, mlp_apply, mlp_init


def moe_init(key, cfg, dtype):
    """Router over all experts; expert weights for the held ones only."""
    m = cfg.moe
    d = cfg.d_model
    n = cfg.held_experts[1]
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (d, m.num_experts), jnp.float32),
        "w_gate": dense_init(ks[1], (n, d, m.d_ff_expert), dtype),
        "w_up": dense_init(ks[2], (n, d, m.d_ff_expert), dtype),
        "w_down": dense_init(ks[3], (n, m.d_ff_expert, d), dtype,
                             fan_in=m.d_ff_expert),
    }
    if m.correction_bias:
        p["bias"] = 0.1 * jax.random.normal(ks[6], (m.num_experts,),
                                            jnp.float32)
    if m.num_shared_experts:
        p["shared"] = mlp_init(ks[4], d, m.shared_d_ff, cfg, dtype)
        if m.shared_gate:
            p["shared_gate"] = dense_init(ks[5], (d, 1), jnp.float32)
    return p


def router_topk(p, x2d, m):
    """x2d (..., d) -> (gates (..., k), idx (..., k), probs (..., E)).

    The k experts are chosen by the scores plus the correction bias, if
    any; the gates are the unbiased scores of those k, renormalised to
    sum 1 and scaled by ``routed_scaling_factor``."""
    logits = x2d.astype(jnp.float32) @ p["router"]
    if m.scoring_func == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    select = probs + p["bias"] if m.correction_bias else probs
    _, top_idx = jax.lax.top_k(select, m.top_k)
    top_vals = jnp.take_along_axis(probs, top_idx, axis=-1)
    top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)
    if m.routed_scaling_factor != 1.0:
        top_vals = top_vals * m.routed_scaling_factor
    return top_vals, top_idx, probs


def _group(x, m):
    """(b, s, d) -> (G, gs, d) padded token groups + original count."""
    b, s, d = x.shape
    S = b * s
    gs = min(m.group_size, S)
    pad = (-S) % gs
    x2 = x.reshape(S, d)
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2.reshape(-1, gs, d), S, gs


def _capacity(gs, m):
    return max(int(gs * m.top_k / m.num_experts * m.capacity_factor),
               m.top_k)


def _expert_ffn(p, xin, cfg):
    """xin (G, E, C, d) -> (G, E, C, d), per-expert gated MLP."""
    act = activation_fn(cfg.activation)
    h = act(jnp.einsum("gecd,edf->gecf", xin, p["w_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", xin, p["w_up"])
    return jnp.einsum("gecf,efd->gecd", h, p["w_down"])


def moe_apply_onehot(p, x, cfg, constrain=None):
    m = cfg.moe
    b, s, d = x.shape
    xg, S, gs = _group(x, m)
    if constrain is not None:
        # group-parallel re-shard: the (b·s)->groups reshape mixes the
        # batch- and seq-sharded dims; without a target GSPMD all-gathers
        # the full activation (observed 18x collective blowup multi-pod)
        xg = constrain(xg, "moe_group")
    G = xg.shape[0]
    C = _capacity(gs, m)
    top_vals, top_idx, _ = router_topk(p, xg, m)            # (G, gs, k)
    expert_onehot = jax.nn.one_hot(top_idx, m.num_experts, dtype=jnp.int32)
    # slot within expert: prefix count inside the group over the flattened
    # (token, choice) order — per-k cumsum would collide slots
    flat = expert_onehot.reshape(G, gs * m.top_k, m.num_experts)
    pos = (jnp.cumsum(flat, axis=1) - 1) * flat
    pos = pos.sum(-1).reshape(G, gs, m.top_k)               # (G, gs, k)
    keep = pos < C
    slot_onehot = jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                                 dtype=x.dtype)[..., :C]    # (G, gs, k, C)
    eo = expert_onehot.astype(x.dtype)
    disp = jnp.einsum("gske,gskc->gsec", eo, slot_onehot)   # (G, gs, E, C)
    combine = jnp.einsum("gske,gskc,gsk->gsec", eo, slot_onehot,
                         top_vals.astype(x.dtype))
    xin = jnp.einsum("gsec,gsd->gecd", disp, xg)
    if constrain is not None:
        xin = constrain(xin, "moe_expert")
    xout = _expert_ffn(p, xin, cfg)
    y = jnp.einsum("gsec,gecd->gsd", combine, xout)
    y = y.reshape(-1, d)[:S]
    y = _add_shared(p, x.reshape(S, d), y, cfg)
    return y.reshape(b, s, d)


def moe_apply_gather(p, x, cfg, constrain=None):
    """Scatter/gather dispatch: same routing & capacity semantics as the
    onehot path (matches it exactly when nothing overflows), but token
    movement is O(gs·k·d) gathers instead of O(gs·E·C·d) einsums."""
    m = cfg.moe
    b, s, d = x.shape
    xg, S, gs = _group(x, m)
    if constrain is not None:
        xg = constrain(xg, "moe_group")
    G = xg.shape[0]
    C = _capacity(gs, m)
    top_vals, top_idx, _ = router_topk(p, xg, m)
    flat_e = top_idx.reshape(G, gs * m.top_k)               # (G, N)
    onehot_cnt = jax.nn.one_hot(flat_e, m.num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot_cnt, axis=1) - 1                # (G, N, E)
    slot = jnp.take_along_axis(pos, flat_e[..., None], axis=2)[..., 0]
    keep = slot < C
    dest = jnp.where(keep, flat_e * C + slot, m.num_experts * C)  # (G, N)
    token_ids = jnp.arange(gs).repeat(m.top_k)[None].repeat(G, 0)
    buf = jnp.zeros((G, m.num_experts * C + 1, d), x.dtype)
    gidx = jnp.arange(G)[:, None].repeat(gs * m.top_k, 1)
    src = jnp.take_along_axis(xg, token_ids[..., None], axis=1)
    if constrain is not None:
        # keep the scatter group-local: G over batch axes, d over model —
        # without this GSPMD replicates the (G, E*C, d) buffer (§Perf it.2)
        buf = constrain(buf, "moe_buffer")
        src = constrain(src, "moe_buffer")
    buf = buf.at[gidx, dest].set(src)
    xin = buf[:, :-1].reshape(G, m.num_experts, C, d)
    if constrain is not None:
        xin = constrain(xin, "moe_expert")
    xout = _expert_ffn(p, xin, cfg).reshape(G, m.num_experts * C, d)
    xout = jnp.concatenate(
        [xout, jnp.zeros((G, 1, d), xout.dtype)], axis=1)
    if constrain is not None:
        xout = constrain(xout, "moe_buffer")
    gathered = jnp.take_along_axis(xout, dest[..., None], axis=1)
    gathered = gathered.reshape(G, gs, m.top_k, d)
    w = (top_vals * keep.reshape(G, gs, m.top_k)).astype(x.dtype)
    y = jnp.einsum("gskd,gsk->gsd", gathered, w)
    y = y.reshape(-1, d)[:S]
    y = _add_shared(p, x.reshape(S, d), y, cfg)
    return y.reshape(b, s, d)


def shared_expert(p, x, cfg):
    """The MoE layer's shared expert (gated by ``shared_gate`` where the
    config says)."""
    y = mlp_apply(p["shared"], x, cfg)
    if cfg.moe.shared_gate:
        gate = jax.nn.sigmoid(x.astype(jnp.float32) @ p["shared_gate"])
        y = y * gate.astype(x.dtype)
    return y


def _add_shared(p, x2, y, cfg):
    if cfg.moe.num_shared_experts:
        y = y + shared_expert(p, x2, cfg)
    return y


def window_rows(rows: int, held_share: float) -> int:
    """Rows of one grouped-matmul window: twice the expected held pairs
    (at least 256, a multiple of 256, at most ``rows``)."""
    want = max(256, -(-int(2 * rows * held_share) // 256) * 256)
    return min(rows, want)


def moe_routed(p, x, cfg, channel_blocks: int = 1, probe=None):
    """The held experts' part of a dropless MoE layer.  x (C, N, d) ->
    (y (C, N, d), route), route = {"topk": (C, N, k) expert ids, "pairs":
    token-expert pairs computed}; with ``probe`` (P,) token positions,
    also "probe_x" and "probe_y" (C, P, d): the layer's input and this
    part of its output there.

    C = ``channel_blocks``: group c of x's leading axis runs on channel
    block [c F/C, (c+1) F/C) of each held expert's hidden width (C = 1:
    the whole experts).  The pairs are sorted by (held expert, group);
    windows of ``window_rows`` sorted rows run through ``lax.ragged_dot``
    while any held pair is left (one window unless the routing is far
    from even), so nothing is dropped and the matmuls cost the pairs
    routed here.  Gate and up are computed at full width and the group's
    block kept (XLA's grouped matmul takes no strided weight slice);
    down reads the (expert, block) rows in place."""
    m = cfg.moe
    first, count = cfg.held_experts
    C, N, d = x.shape
    k, F = m.top_k, m.d_ff_expert
    G, R = count * C, C * N * k
    act = activation_fn(cfg.activation)
    with jax.named_scope("moe.route"):
        gates, idx, _ = router_topk(p, x, m)                 # (C, N, k)
        local = idx - first
        held = (local >= 0) & (local < count)
        block = jnp.arange(C, dtype=jnp.int32)[:, None, None]
        key = jnp.where(held, local * C + block, G).reshape(R)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=G + 1)[:G]          # (G,)
        pairs = sizes.sum()
        W = window_rows(R, count / m.num_experts)
        n_win = -(-R // W)
        pad = n_win * W - R
        tok = jnp.pad(order // k, (0, pad))                  # row of (C*N, d)
        gw = jnp.pad(gates.reshape(R)[order], (0, pad))
        cw = jnp.pad(key[order] % C, (0, pad))
        ends = jnp.cumsum(sizes)
        starts = ends - sizes
    xf = x.reshape(C * N, d)
    w_down = p["w_down"].reshape(G, F // C, d)

    def window(i, out):
        lo = i * W
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, lo, W)
        t, g, c = sl(tok), sl(gw), sl(cw)
        gs = jnp.clip(ends, lo, lo + W) - jnp.clip(starts, lo, lo + W)
        gs_up = gs.reshape(count, C).sum(1)
        rows = xf[t]
        h = act(jax.lax.ragged_dot(rows, p["w_gate"], gs_up)) \
            * jax.lax.ragged_dot(rows, p["w_up"], gs_up)     # (W, F)
        if C > 1:
            h = jnp.take_along_axis(h.reshape(W, C, F // C),
                                    c[:, None, None], axis=1)[:, 0]
        y = jax.lax.ragged_dot(h, w_down, gs)                # (W, d)
        live = lo + jnp.arange(W) < pairs
        y = jnp.where(live[:, None], y.astype(jnp.float32) * g[:, None], 0.0)
        return out.at[t].add(y)

    with jax.named_scope("moe.experts"):
        out = jax.lax.fori_loop(
            0, n_win, lambda i, o: jax.lax.cond(i * W < pairs, window,
                                                lambda i_, o_: o_, i, o),
            jnp.zeros((C * N, d), jnp.float32))
        y = out.reshape(C, N, d).astype(x.dtype)
    route = {"topk": idx, "pairs": pairs}
    if probe is not None:
        route.update(probe_x=x[:, probe], probe_y=y[:, probe])
    return y, route


def moe_dropless(p, x, cfg, probe=None):
    """Dropless MoE layer over the held experts: x (1, N, d) -> (y,
    route), the held experts' part (``moe_routed``) plus the shared
    expert."""
    y, route = moe_routed(p, x, cfg, probe=probe)
    if cfg.moe.num_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + shared_expert(p, x, cfg)
    return y, route


def moe_apply(p, x, cfg, constrain=None):
    if cfg.moe.dispatch == "dropless":
        y, _ = moe_dropless(p, x.reshape(1, -1, x.shape[-1]), cfg)
        return y.reshape(x.shape)
    if cfg.moe.dispatch == "gather":
        return moe_apply_gather(p, x, cfg, constrain)
    return moe_apply_onehot(p, x, cfg, constrain)


def aux_load_balance_loss(p, x, cfg):
    """Switch-style auxiliary load-balance loss (mean fraction * mean prob)."""
    m = cfg.moe
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    _, top_idx, probs = router_topk(p, x2, m)
    frac = jax.nn.one_hot(top_idx, m.num_experts).sum(1).mean(0)  # (E,)
    return m.num_experts * jnp.sum(frac * probs.mean(0))
