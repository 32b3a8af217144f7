"""Unified decoder model over all block kinds.

Public API:
    init_params(key, cfg)                         -> params pytree
    forward(params, batch, cfg, ...)              -> (logits, aux)
    loss_fn(params, batch, cfg, ...)              -> (scalar, metrics)
    prefill(params, batch, cfg, ...)              -> (logits, cache)
    init_cache(cfg, batch, ctx_len, sliding)      -> cache pytree
    decode_step(params, tokens, cache, pos, ...)  -> (logits, cache)

The layer stack is organized as (prefix, scanned body of pattern periods,
suffix): the body is a ``lax.scan`` over stacked period parameters (with
optional remat), keeping the HLO O(1) in depth; MoE first-k-dense prefixes
and partial trailing periods are unrolled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (dense_init, dtype_of, mlp_apply, mlp_init,
                                 rmsnorm, sinusoidal_embedding)


def _identity_constrain(x, kind):
    return x


# ------------------------------------------------------------ block init

def init_block(key, kind, cfg):
    dtype = dtype_of(cfg.param_dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    if kind in ("attn", "local_attn"):
        return {"norm1": jnp.zeros((d,), dtype),
                "attn": attn.attn_init(ks[0], cfg, dtype),
                "norm2": jnp.zeros((d,), dtype),
                "mlp": mlp_init(ks[1], d, cfg.d_ff, cfg, dtype)}
    if kind == "xattn":
        return {"norm1": jnp.zeros((d,), dtype),
                "attn": attn.attn_init(ks[0], cfg, dtype),
                "norm_x": jnp.zeros((d,), dtype),
                "xattn": attn.attn_init(ks[1], cfg, dtype, cross=True),
                "norm2": jnp.zeros((d,), dtype),
                "mlp": mlp_init(ks[2], d, cfg.d_ff, cfg, dtype)}
    if kind == "attn_moe":
        return {"norm1": jnp.zeros((d,), dtype),
                "attn": attn.attn_init(ks[0], cfg, dtype),
                "norm2": jnp.zeros((d,), dtype),
                "moe": moe_mod.moe_init(ks[1], cfg, dtype)}
    if kind in ("mla", "mla_moe"):
        ffn = ({"moe": moe_mod.moe_init(ks[1], cfg, dtype)} if kind == "mla_moe"
               else {"mlp": mlp_init(ks[1], d, cfg.d_ff, cfg, dtype)})
        return {"norm1": jnp.zeros((d,), dtype),
                "attn": attn.mla_init(ks[0], cfg, dtype),
                "norm2": jnp.zeros((d,), dtype), **ffn}
    if kind == "mamba":
        return {"norm1": jnp.zeros((d,), dtype),
                "mamba": ssm_mod.mamba_init(ks[0], cfg, dtype)}
    if kind == "rglru":
        return {"norm1": jnp.zeros((d,), dtype),
                "rglru": rglru_mod.rglru_init(ks[0], cfg, dtype),
                "norm2": jnp.zeros((d,), dtype),
                "mlp": mlp_init(ks[1], d, cfg.d_ff, cfg, dtype)}
    raise ValueError(kind)


def _block_window(kind, cfg):
    if kind == "local_attn":
        return cfg.rglru.local_window
    return cfg.sliding_window


# --------------------------------------------------------- block apply

ATTN_KINDS = ("attn", "local_attn", "xattn", "attn_moe", "mla", "mla_moe")
MOE_KINDS = ("attn_moe", "mla_moe")


def mixer(kind, p, x, ctx, cfg):
    """The block's normed attention: (output, (k, v))."""
    xn = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if kind in ("mla", "mla_moe"):
        with jax.named_scope("mla"):
            return attn.mla_attention(p["attn"], xn, ctx, cfg)
    return attn.self_attention(p["attn"], xn, ctx, cfg,
                               window=_block_window(kind, cfg))


def apply_block(kind, p, x, ctx, cfg, collect_cache=False):
    """Returns (x, aux_loss, cache_or_None, route_or_None).

    ``route`` is a dropless MoE layer's record (``moe.moe_routed``), its
    probe taken at ``ctx["probe"]`` if the batch gave one."""
    con = ctx.get("constrain", _identity_constrain)
    aux = jnp.zeros((), jnp.float32)
    cache = route = None
    dropless = kind in MOE_KINDS and cfg.moe.dispatch == "dropless"
    if kind in ATTN_KINDS:
        h, kv = mixer(kind, p, x, ctx, cfg)
        x = con(x + h, "residual")
        if collect_cache:
            w = _block_window(kind, cfg) or ctx["cache_len"]
            w = min(w, ctx["cache_len"])
            k, v = kv
            s = k.shape[1]
            dt = dtype_of(cfg.compute_dtype)
            if s >= w:
                # keep last w entries, rolled so slot j holds pos ≡ j (mod w)
                shift = (s - w) % w
                k2 = jnp.roll(k[:, s - w:], shift, axis=1)
                v2 = jnp.roll(v[:, s - w:], shift, axis=1)
            else:
                pad = ((0, 0), (0, w - s), (0, 0), (0, 0))
                k2, v2 = jnp.pad(k, pad), jnp.pad(v, pad)
            cache = {"k": k2.astype(dt), "v": v2.astype(dt)}
        if kind == "xattn":
            hx = attn.cross_attention(p["xattn"],
                                      rmsnorm(x, p["norm_x"], cfg.norm_eps),
                                      ctx["cond"], cfg)
            x = con(x + hx, "residual")
        xn = rmsnorm(x, p["norm2"], cfg.norm_eps)
        if dropless:
            b, s, d = xn.shape
            h2, route = moe_mod.moe_dropless(p["moe"], xn.reshape(1, b * s, d),
                                             cfg, probe=ctx.get("probe"))
            h2 = h2.reshape(xn.shape)
        elif kind in MOE_KINDS:
            h2 = moe_mod.moe_apply(p["moe"], xn, cfg, con)
            aux = moe_mod.aux_load_balance_loss(p["moe"], xn, cfg)
        else:
            with jax.named_scope("dense_mlp"):
                h2 = mlp_apply(p["mlp"], xn, cfg, con)
        x = con(x + h2, "residual")
        return x, aux, cache, route
    if kind == "mamba":
        if collect_cache:
            y, cache = ssm_mod.mamba_prefill(p["mamba"],
                                             rmsnorm(x, p["norm1"], cfg.norm_eps),
                                             cfg, con)
        else:
            y = ssm_mod.mamba_apply(p["mamba"],
                                    rmsnorm(x, p["norm1"], cfg.norm_eps), cfg, con)
        return con(x + y, "residual"), aux, cache, route
    if kind == "rglru":
        if collect_cache:
            y, cache = rglru_mod.rglru_prefill(
                p["rglru"], rmsnorm(x, p["norm1"], cfg.norm_eps), cfg, con)
        else:
            y = rglru_mod.rglru_apply(p["rglru"],
                                      rmsnorm(x, p["norm1"], cfg.norm_eps), cfg, con)
        x = con(x + y, "residual")
        with jax.named_scope("dense_mlp"):
            h2 = mlp_apply(p["mlp"], rmsnorm(x, p["norm2"], cfg.norm_eps), cfg, con)
        return con(x + h2, "residual"), aux, cache, route
    raise ValueError(kind)


def decode_block(kind, p, x, cache, pos, ctx, cfg):
    con = ctx.get("constrain", _identity_constrain)
    if kind in ATTN_KINDS:
        xn = rmsnorm(x, p["norm1"], cfg.norm_eps)
        if kind in ("mla", "mla_moe"):
            h, cache_a = attn.mla_decode(p["attn"], xn, cache, pos, cfg)
        else:
            h, cache_a = attn.decode_attention(p["attn"], xn, cache, pos,
                                               ctx, cfg)
        x = x + h
        if kind == "xattn":
            hx = attn.cross_attention(p["xattn"],
                                      rmsnorm(x, p["norm_x"], cfg.norm_eps),
                                      ctx["cond"], cfg)
            x = x + hx
        xn = rmsnorm(x, p["norm2"], cfg.norm_eps)
        if kind in MOE_KINDS:
            x = x + moe_mod.moe_apply(p["moe"], xn, cfg, con)
        else:
            x = x + mlp_apply(p["mlp"], xn, cfg, con)
        return x, cache_a
    if kind == "mamba":
        y, cache = ssm_mod.mamba_decode(p["mamba"],
                                        rmsnorm(x, p["norm1"], cfg.norm_eps),
                                        cache, cfg)
        return x + y, cache
    if kind == "rglru":
        y, cache = rglru_mod.rglru_decode(p["rglru"],
                                          rmsnorm(x, p["norm1"], cfg.norm_eps),
                                          cache, cfg)
        x = x + y
        x = x + mlp_apply(p["mlp"], rmsnorm(x, p["norm2"], cfg.norm_eps), cfg, con)
        return x, cache
    raise ValueError(kind)


def init_block_cache(kind, cfg, batch, ctx_len, sliding=None):
    dtype = dtype_of(cfg.compute_dtype)
    if kind in ("attn", "xattn", "attn_moe", "mla", "mla_moe"):
        w = cfg.sliding_window or (sliding or ctx_len)
        return attn.init_attn_cache(cfg, batch, ctx_len, window=w, dtype=dtype)
    if kind == "local_attn":
        return attn.init_attn_cache(cfg, batch, ctx_len,
                                    window=cfg.rglru.local_window, dtype=dtype)
    if kind == "mamba":
        return ssm_mod.init_mamba_cache(cfg, batch, dtype)
    if kind == "rglru":
        return rglru_mod.init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


# ----------------------------------------------------------- embeddings

def init_params(key, cfg):
    dtype = dtype_of(cfg.param_dtype)
    d, v = cfg.d_model, cfg.vocab_size
    kE, kH, kB = jax.random.split(key, 3)
    cb = cfg.num_codebooks
    params = {
        "embed": dense_init(kE, (cb, v, d) if cb else (v, d), dtype, fan_in=d),
        "final_norm": jnp.zeros((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(kH, (cb, d, v) if cb else (d, v), dtype)
    prefix, (pattern, periods), suffix = cfg.scan_segments
    keys = jax.random.split(kB, len(prefix) + periods + len(suffix) + 1)
    params["prefix"] = [init_block(keys[i], k, cfg) for i, k in enumerate(prefix)]

    def init_period(pk):
        pks = jax.random.split(pk, len(pattern))
        return {f"b{j}": init_block(pks[j], kind, cfg)
                for j, kind in enumerate(pattern)}

    if periods:
        params["body"] = jax.vmap(init_period)(
            jax.random.split(keys[len(prefix)], periods))
    params["suffix"] = [init_block(keys[len(prefix) + 1 + i], k, cfg)
                        for i, k in enumerate(suffix)]
    return params


def embed_tokens(params, batch, cfg, positions):
    tokens = batch["tokens"]
    if cfg.num_codebooks:
        # tokens (b, s, cb): sum codebook embeddings
        x = sum(jnp.take(params["embed"][i], tokens[..., i], axis=0)
                for i in range(cfg.num_codebooks))
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.visual_frontend and "visual_embeds" in batch:
        mask = batch["visual_mask"][..., None]
        x = jnp.where(mask, batch["visual_embeds"].astype(x.dtype), x)
    if cfg.pos_emb == "sinusoidal":
        x = x + sinusoidal_embedding(positions, cfg.d_model).astype(x.dtype)
    return x.astype(dtype_of(cfg.compute_dtype))


def lm_head(params, x, cfg):
    """Final norm and output head; float32 logits."""
    with jax.named_scope("lm_head"):
        return output_head(params, rmsnorm(x, params["final_norm"],
                                           cfg.norm_eps), cfg)


def output_head(params, xn, cfg):
    """Float32 logits of final-normed states."""
    w = params["embed"].swapaxes(-1, -2) if cfg.tie_embeddings \
        else params["head"]
    if cfg.num_codebooks:
        return jnp.einsum("bsd,cdv->bscv", xn, w).astype(jnp.float32)
    return (xn @ w).astype(jnp.float32)


def _make_ctx(batch, cfg, constrain, cache_len=0):
    b, s = batch["tokens"].shape[:2]
    positions = batch.get("positions")
    arange = positions is None
    if arange:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    ctx = {"positions": positions, "constrain": constrain or _identity_constrain,
           "cache_len": cache_len, "arange_positions": arange,
           "causal_skip": getattr(cfg, "attn_causal_skip", False)}
    if "probe" in batch:
        ctx["probe"] = batch["probe"]
    if cfg.pos_emb == "mrope":
        p3 = batch.get("positions3")
        if p3 is None:
            p3 = jnp.broadcast_to(positions[:, None, :], (b, 3, s))
        ctx["positions3"] = p3
    if cfg.cross_attention:
        cond = batch.get("cond")
        if cond is None:
            cond = jnp.zeros((b, cfg.cond_len, cfg.d_model),
                             dtype_of(cfg.compute_dtype))
        ctx["cond"] = cond
    return ctx


# ------------------------------------------------------------- forward

def stack_routes(routes):
    """One record of the dropless MoE layers' routes (``moe.moe_routed``):
    each array stacked over the layers ((layers, C, ...)) and the pairs
    computed, summed."""
    routes = [r for r in routes if r is not None]
    if not routes:
        return {"topk": None, "pairs": jnp.zeros((), jnp.int32)}
    out = {name: jnp.concatenate([r[name].reshape((-1,) + r[name].shape[-3:])
                                  for r in routes])
           for name in routes[0] if name != "pairs"}
    out["pairs"] = sum(r["pairs"].sum() for r in routes)
    return out


def forward(params, batch, cfg, constrain=None, collect_cache=False,
            max_ctx=None, with_routes=False):
    """Full-sequence forward.  Returns (logits, aux_loss[, cache][,
    routes]); ``routes`` is ``stack_routes`` of the dropless MoE layers."""
    ctx = _make_ctx(batch, cfg, constrain,
                    cache_len=max_ctx or batch["tokens"].shape[1])
    x = embed_tokens(params, batch, cfg, ctx["positions"])
    x = ctx["constrain"](x, "residual")
    prefix, (pattern, periods), suffix = cfg.scan_segments
    aux = jnp.zeros((), jnp.float32)
    caches = {"prefix": [], "suffix": []}
    routes = []
    for p, kind in zip(params["prefix"], prefix):
        x, a, c, r = apply_block(kind, p, x, ctx, cfg, collect_cache)
        aux, _ = aux + a, caches["prefix"].append(c)
        routes.append(r)

    if periods:
        def period_fn(carry, pp):
            x, aux = carry
            cs, rs = {}, []
            for j, kind in enumerate(pattern):
                x, a, c, r = apply_block(kind, pp[f"b{j}"], x, ctx, cfg,
                                         collect_cache)
                aux = aux + a
                rs.append(r)
                if collect_cache:
                    cs[f"b{j}"] = c
            return (x, aux), (cs, rs)
        fn = jax.checkpoint(period_fn) if cfg.remat else period_fn
        (x, aux), (body_cache, body_routes) = jax.lax.scan(
            fn, (x, aux), params["body"])
        if collect_cache:
            caches["body"] = body_cache
        routes += body_routes

    for p, kind in zip(params["suffix"], suffix):
        x, a, c, r = apply_block(kind, p, x, ctx, cfg, collect_cache)
        aux, _ = aux + a, caches["suffix"].append(c)
        routes.append(r)

    logits = lm_head(params, x, cfg)
    out = (logits, aux)
    if collect_cache:
        out += (caches,)
    if with_routes:
        out += (stack_routes(routes),)
    return out


def loss_fn(params, batch, cfg, constrain=None, aux_weight=0.01):
    logits, aux = forward(params, batch, cfg, constrain)
    labels = batch["labels"]
    # sharding-safe CE: logsumexp reduces over the (vocab-sharded) last dim
    # and the label logit is a contraction — no gather that would force an
    # all-gather of the full logits
    con = constrain or _identity_constrain
    logits = con(logits, "logits")
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = con(jax.nn.one_hot(labels, logits.shape[-1],
                                dtype=logits.dtype), "logits")
    label_logit = jnp.einsum("...v,...v->...", logits, onehot)
    nll = lse - label_logit
    loss = nll.mean()
    total = loss + aux_weight * aux
    return total, {"ce": loss, "aux": aux}


def prefill(params, batch, cfg, constrain=None, max_ctx=None):
    """Full-seq forward returning logits + a decode cache.

    ``max_ctx`` sets the allocated KV-cache length (defaults to seq + 32 so
    decoding can continue past the prompt without ring-wrap).
    """
    if max_ctx is None:
        max_ctx = batch["tokens"].shape[1] + 32
    logits, aux, cache = forward(params, batch, cfg, constrain,
                                 collect_cache=True, max_ctx=max_ctx)
    return logits, cache


# -------------------------------------------------------------- decode

def init_cache(cfg, batch, ctx_len, sliding=None):
    prefix, (pattern, periods), suffix = cfg.scan_segments
    mk = lambda kind: init_block_cache(kind, cfg, batch, ctx_len, sliding)
    cache = {"prefix": [mk(k) for k in prefix],
             "suffix": [mk(k) for k in suffix]}
    if periods:
        period = {f"b{j}": mk(kind) for j, kind in enumerate(pattern)}
        cache["body"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (periods,) + a.shape), period)
    return cache


def decode_step(params, tokens, cache, pos, cfg, batch_extras=None,
                constrain=None):
    """One-token decode.

    tokens (b, 1) or (b, 1, cb); pos scalar int32; cache from init_cache /
    prefill.  Returns (logits, new_cache).
    """
    batch = {"tokens": tokens}
    if batch_extras:
        batch.update(batch_extras)
    ctx = _make_ctx(batch, cfg, constrain)
    b = tokens.shape[0]
    ctx["positions"] = jnp.full((b, 1), pos, jnp.int32)
    x = embed_tokens(params, batch, cfg, ctx["positions"])
    prefix, (pattern, periods), suffix = cfg.scan_segments
    new_cache = {"prefix": [], "suffix": []}
    for p, kind, c in zip(params["prefix"], prefix, cache["prefix"]):
        x, nc = decode_block(kind, p, x, c, pos, ctx, cfg)
        new_cache["prefix"].append(nc)
    if periods:
        def f(x, pc):
            pp, cc = pc
            ncs = {}
            for j, kind in enumerate(pattern):
                x, ncs[f"b{j}"] = decode_block(kind, pp[f"b{j}"], x,
                                               cc[f"b{j}"], pos, ctx, cfg)
            return x, ncs
        x, body_cache = jax.lax.scan(f, x, (params["body"], cache["body"]))
        new_cache["body"] = body_cache
    for p, kind, c in zip(params["suffix"], suffix, cache["suffix"]):
        x, nc = decode_block(kind, p, x, c, pos, ctx, cfg)
        new_cache["suffix"].append(nc)
    logits = lm_head(params, x, cfg)
    return logits, new_cache
