"""GQA and multi-head latent attention (MLA): full, blockwise
(flash-style online-softmax), and decode.  The query-key head dim may
differ from the value head dim (MLA: 192 against 128).

The blockwise path is the pure-JAX twin of ``repro.kernels.flash_attention``
(the Pallas TPU kernel) and doubles as its oracle; the model uses this path
for long sequences so compiled temporaries stay O(block) instead of O(seq^2).
MLA's prefill lowers to ``repro.kernels.causal_flash`` on the TPU
(``mla_core``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.causal_flash import LANES, causal_flash_attention
from repro.models.layers import (apply_mrope, apply_rope, dense_init,
                                 rmsnorm, yarn_inv_freq, yarn_mscale)

NEG_INF = -1e30


def attn_init(key, cfg, dtype, cross=False):
    d, h, k, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, hd), dtype),
        "wk": dense_init(ks[1], (d, k, hd), dtype),
        "wv": dense_init(ks[2], (d, k, hd), dtype),
        "wo": dense_init(ks[3], (h, hd, d), dtype, fan_in=h * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((k, hd), dtype)
        p["bv"] = jnp.zeros((k, hd), dtype)
    return p


def project_qkv(p, x, cfg):
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dke->bske", x, p["wk"])
    v = jnp.einsum("bsd,dke->bske", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def _rope_qk(q, k, ctx, cfg):
    if cfg.pos_emb == "rope":
        q = apply_rope(q, ctx["positions"], cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, ctx["positions"], cfg.rope_theta, cfg.rope_fraction)
    elif cfg.pos_emb == "mrope":
        q = apply_mrope(q, ctx["positions3"], cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, ctx["positions3"], cfg.mrope_sections, cfg.rope_theta)
    return q, k


def _group(q, num_kv):
    b, s, h, hd = q.shape
    return q.reshape(b, s, num_kv, h // num_kv, hd)


def full_attention(q, k, v, pos_q, pos_k, window=0, kv_mask=None, causal=True,
                   scale=None):
    """Reference full-materialization attention.

    q (b,sq,h,hd); k (b,sk,kv,hd); v (b,sk,kv,hv); pos_q (b,sq);
    pos_k (b,sk).  ``scale`` defaults to hd^-0.5.
    """
    kvh = k.shape[2]
    qg = _group(q, kvh)                                     # (b,sq,kv,g,hd)
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32) * scale
    mask = jnp.ones(scores.shape[-2:], bool)[None]
    if causal:
        mask = pos_q[:, :, None] >= pos_k[:, None, :]
    if window:
        mask &= (pos_q[:, :, None] - pos_k[:, None, :]) < window
    if kv_mask is not None:
        mask &= kv_mask[:, None, :]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    b, sq = q.shape[:2]
    return out.reshape(b, sq, -1, v.shape[-1])


def blockwise_attention(q, k, v, pos_q, pos_k, window=0,
                        q_block=512, kv_block=1024, causal_skip=False,
                        scale=None):
    """Flash-style attention: scan q blocks; stream kv blocks (online softmax).

    With ``causal_skip`` the kv scan for q-block i only covers kv blocks
    0..ceil that can be unmasked (static upper-triangular skipping), halving
    the compute term for causal attention.
    """
    b, sq, h, hd = q.shape
    sk, kvh, hv = k.shape[1], k.shape[2], v.shape[-1]
    g = h // kvh
    nq, nk = -(-sq // q_block), -(-sk // kv_block)
    pq = nq * q_block - sq
    pk = nk * kv_block - sk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    pqp = jnp.pad(pos_q, ((0, 0), (0, pq)), constant_values=-1)
    pkp = jnp.pad(pos_k, ((0, 0), (0, pk)), constant_values=2**30)
    qb = qp.reshape(b, nq, q_block, kvh, g, hd).transpose(1, 0, 2, 3, 4, 5)
    kb = kp.reshape(b, nk, kv_block, kvh, hd)
    vb = vp.reshape(b, nk, kv_block, kvh, hv)
    pqb = pqp.reshape(b, nq, q_block).transpose(1, 0, 2)
    scale = hd ** -0.5 if scale is None else scale

    def one_q_block(args, kv_hi=None):
        qi, posq, q_idx = args                              # (b,qb,kv,g,hd)

        def kv_step(carry, inputs):
            m, l, acc = carry
            ki, vi, posk, k_idx = inputs
            s = jnp.einsum("bqkgh,bskh->bkgqs", qi, ki).astype(jnp.float32) * scale
            mask = posq[:, :, None] >= posk[:, None, :]
            if window:
                mask &= (posq[:, :, None] - posk[:, None, :]) < window
            s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(vi.dtype), vi).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_block), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, q_block, hv), jnp.float32)
        hi = nk if kv_hi is None else kv_hi
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb.transpose(1, 0, 2, 3, 4)[:hi],
             vb.transpose(1, 0, 2, 3, 4)[:hi],
             pkp.reshape(b, nk, kv_block).transpose(1, 0, 2)[:hi],
             jnp.arange(nk)[:hi]))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4)                 # (b,qb,kv,g,hd)

    if causal_skip:
        # §Perf: static upper-triangular skipping — q block i only visits
        # kv blocks 0..ceil((i+1)*qb/kb), halving causal-attention FLOPs.
        # Unrolled per-q-block scans keep trip counts static (honest
        # roofline counting; dynamic fori bounds hide work from both XLA
        # and the jaxpr counter).
        outs = []
        for i in range(nq):
            hi = min(-(-((i + 1) * q_block) // kv_block), nk)
            fn = jax.checkpoint(functools.partial(one_q_block, kv_hi=hi))
            outs.append(fn((qb[i], pqb[i], i)))
        out = jnp.stack(outs, 0)
    else:
        # flash-style memory under AD: recompute score blocks in backward
        # instead of saving the O(s^2) inner-scan residuals
        out = jax.lax.map(jax.checkpoint(one_q_block),
                          (qb, pqb, jnp.arange(nq)))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * q_block, h, hv)
    return out[:, :sq].astype(q.dtype)


def self_attention(p, x, ctx, cfg, window=0):
    """Full-sequence self attention (train / prefill)."""
    q, k, v = project_qkv(p, x, cfg)
    q, k = _rope_qk(q, k, ctx, cfg)
    pos = ctx["positions"]
    if x.shape[1] > ctx.get("blockwise_threshold", 2048):
        out = blockwise_attention(q, k, v, pos, pos, window=window,
                                  causal_skip=ctx.get("causal_skip", False))
    else:
        out = full_attention(q, k, v, pos, pos, window=window)
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    return y, (k, v)


def cross_attention(p, x, cond, cfg):
    """x (b,s,d) attends to cond (b,n,d); no causal mask, no rope."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bnd,dke->bnke", cond, p["wk"])
    v = jnp.einsum("bnd,dke->bnke", cond, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    b, s = x.shape[:2]
    n = cond.shape[1]
    pos_q = jnp.full((b, s), n, jnp.int32)
    pos_k = jnp.zeros((b, n), jnp.int32)
    out = full_attention(q, k, v, pos_q, pos_k, causal=False)
    return jnp.einsum("bshe,hed->bsd", out, p["wo"])


# ------------------------------------------------ multi-head latent attention

def mla_init(key, cfg, dtype):
    """MLA weights (DeepSeek-V3 names in comments).  Heads are an axis of
    ``wq_b``, ``wkv_b`` and ``wo``, so a head slice is a view."""
    a, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 5)
    return {
        "wq_a": dense_init(ks[0], (d, a.q_lora_rank), dtype),      # q_a_proj
        "q_norm": jnp.zeros((a.q_lora_rank,), dtype),
        "wq_b": dense_init(ks[1], (a.q_lora_rank, h, a.qk_head_dim), dtype),
        "wkv_a": dense_init(ks[2], (d, a.kv_lora_rank + a.qk_rope_head_dim),
                            dtype),                    # kv_a_proj_with_mqa
        "kv_norm": jnp.zeros((a.kv_lora_rank,), dtype),
        "wkv_b": dense_init(ks[3], (a.kv_lora_rank, h,
                                    a.qk_nope_head_dim + a.v_head_dim), dtype),
        "wo": dense_init(ks[4], (h, a.v_head_dim, d), dtype,
                         fan_in=h * a.v_head_dim),
    }


def mla_rope_and_scale(cfg):
    """(RoPE frequencies of the rope dims or None, softmax scale): YaRN
    divides slow frequencies and multiplies the scale by mscale^2."""
    a, y = cfg.mla, cfg.rope_scaling
    scale = a.qk_head_dim ** -0.5
    if y is None:
        return None, scale
    scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return jnp.asarray(yarn_inv_freq(a.qk_rope_head_dim, cfg.rope_theta, y)), \
        scale


def mla_qkv(p, x, positions, cfg, heads_major=False):
    """q (b,s,h,qk), k (b,s,h,qk), v (b,s,h,v) of one MLA layer; the
    RoPE key is one per position, shared by every head.  With
    ``heads_major`` each comes (b,h,s,.) straight from its projection,
    and in place of v the key-value projection (b,h,s,nope+v) whose last
    ``v_head_dim`` columns the values are."""
    a = cfg.mla
    inv_freq, _ = mla_rope_and_scale(cfg)
    y = cfg.rope_scaling
    rs = 1.0 if y is None else (yarn_mscale(y.factor, y.mscale)
                                / yarn_mscale(y.factor, y.mscale_all_dim))
    hs = "bhse" if heads_major else "bshe"
    cq = rmsnorm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = jnp.einsum(f"bsr,rhe->{hs}", cq, p["wq_b"])
    kv = x @ p["wkv_a"]
    ckv = rmsnorm(kv[..., :a.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_pe = kv[..., None, a.kv_lora_rank:]                   # (b,s,1,rope)
    kvb = jnp.einsum(f"bsr,rhe->{hs}", ckv, p["wkv_b"])
    k_nope, v = kvb[..., :a.qk_nope_head_dim], kvb[..., a.qk_nope_head_dim:]
    q_pe = q[..., a.qk_nope_head_dim:]
    if heads_major:                               # RoPE runs token-major
        q_pe = q_pe.swapaxes(1, 2)
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta, inv_freq=inv_freq)
    k_pe = apply_rope(k_pe, positions, cfg.rope_theta, inv_freq=inv_freq)
    if heads_major:
        q_pe, k_pe = q_pe.swapaxes(1, 2), k_pe.swapaxes(1, 2)
    if rs != 1.0:
        q_pe, k_pe = q_pe * rs, k_pe * rs
    q = jnp.concatenate([q[..., :a.qk_nope_head_dim], q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:-1] + k_pe.shape[-1:])],
        axis=-1)
    return q, k, (kvb if heads_major else v)


def mla_core(cfg, b, h, s, platform, arange_positions=True,
             threshold=2048) -> str:
    """The attention core of a full-sequence MLA call of b x s tokens
    and h heads, lowered for ``platform``: ``"fused"``, the causal flash
    kernel (on the TPU, for positions 0..s-1 on every row, at least one
    128-key block, and values that fill whole 128-lane column blocks of
    the key-value projection); else ``"blockwise"`` above ``threshold``
    tokens or once the float32 scores would pass 512 MiB (64 heads: 4 x
    1024 tokens); else ``"full"``."""
    a = cfg.mla
    if (platform == "tpu" and arange_positions and s >= LANES
            and a.v_head_dim % LANES == 0
            and a.qk_nope_head_dim % a.v_head_dim == 0):
        return "fused"
    if s > threshold or b * h * s * s * 4 > 2**29:
        return "blockwise"
    return "full"


def mla_attention(p, x, ctx, cfg):
    """Full-sequence MLA (train / prefill) through the core ``mla_core``
    names: where it may fuse (``ctx["arange_positions"]``, set by
    ``model._make_ctx`` for a batch that brings no positions), the TPU
    lowers the causal flash kernel on heads-major operands with the
    queries scaled in float32, and every other platform the XLA core.
    Returns (y, (k, v))."""
    _, scale = mla_rope_and_scale(cfg)
    pos = ctx["positions"]
    b, s = x.shape[:2]
    h = p["wq_b"].shape[1]
    threshold = ctx.get("blockwise_threshold", 2048)
    arange = ctx.get("arange_positions", False)

    def xla(p, x):
        q, k, v = mla_qkv(p, x, pos, cfg)
        if mla_core(cfg, b, h, s, None, arange, threshold) == "blockwise":
            out = blockwise_attention(q, k, v, pos, pos, scale=scale,
                                      causal_skip=ctx.get("causal_skip",
                                                          False))
        else:
            out = full_attention(q, k, v, pos, pos, scale=scale)
        return jnp.einsum("bshe,hed->bsd", out, p["wo"]), (k, v)

    def fused(p, x):
        a = cfg.mla
        q, k, kvb = mla_qkv(p, x, pos, cfg, heads_major=True)
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        out = causal_flash_attention(q, k, kvb, hv=a.v_head_dim,
                                     v_col=a.qk_nope_head_dim // a.v_head_dim)
        v = kvb[..., a.qk_nope_head_dim:]
        return jnp.einsum("bhse,hed->bsd", out, p["wo"]), \
            (k.swapaxes(1, 2), v.swapaxes(1, 2))

    if mla_core(cfg, b, h, s, "tpu", arange, threshold) != "fused":
        return xla(p, x)
    return jax.lax.platform_dependent(p, x, tpu=fused, default=xla)


def mla_decode(p, x, cache, pos, cfg):
    """One-token MLA decode over a ring cache of the expanded keys and
    values (not the latent: decoding is not on the plan engine's path)."""
    _, scale = mla_rope_and_scale(cfg)
    b = x.shape[0]
    pos_b = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = mla_qkv(p, x, pos_b, cfg)
    ck, cv, pos_k = _ring_write(cache, k, v, pos, b)
    out = full_attention(q, ck, cv, jnp.ones_like(pos_b), pos_k, scale=scale)
    return jnp.einsum("bshe,hed->bsd", out, p["wo"]), {"k": ck, "v": cv}


# ----------------------------------------------------------- decoding

def init_attn_cache(cfg, batch, ctx_len, window=0, dtype=jnp.bfloat16):
    w = min(ctx_len, window) if window else ctx_len
    kvh, hd, hv = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.resolved_head_dim
    if cfg.mla is not None:
        kvh, hd, hv = cfg.num_heads, cfg.mla.qk_head_dim, cfg.mla.v_head_dim
    return {
        "k": jnp.zeros((batch, w, kvh, hd), dtype),
        "v": jnp.zeros((batch, w, kvh, hv), dtype),
    }


def _ring_write(cache, k, v, pos, b):
    """Write one position's k, v into the ring cache; returns (k, v,
    key positions with empty slots masked by the pos trick)."""
    W = cache["k"].shape[1]
    slot = (pos % W).astype(jnp.int32)
    # mask-based ring write: dynamic_update_slice on a sharded cache dim
    # makes GSPMD all-gather the cache; a select against iota is purely
    # elementwise and keeps the seq-sharded layout (§Perf iteration 0)
    hit = (jnp.arange(W, dtype=jnp.int32) == slot)[None, :, None, None]
    ck = jnp.where(hit, k.astype(cache["k"].dtype), cache["k"])
    cv = jnp.where(hit, v.astype(cache["v"].dtype), cache["v"])
    valid = jnp.arange(W)[None, :] < jnp.minimum(pos + 1, W)
    valid = jnp.broadcast_to(valid, (b, W))
    return ck, cv, jnp.where(valid, 0, 2**30)              # mask via pos trick


def decode_attention(p, x, cache, pos, ctx, cfg, window=0):
    """One-token decode.  x (b,1,d); pos scalar int32 (current position).

    The cache is a ring buffer of size W; attention is permutation-invariant
    over kv slots so ring order needs no unrotation.
    """
    q, k, v = project_qkv(p, x, cfg)
    b = x.shape[0]
    pos_b = jnp.full((b, 1), pos, jnp.int32)
    if cfg.pos_emb == "mrope":
        p3 = jnp.broadcast_to(pos_b[:, None, :], (b, 3, 1))
        q = apply_mrope(q, p3, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, p3, cfg.mrope_sections, cfg.rope_theta)
    elif cfg.pos_emb == "rope":
        q = apply_rope(q, pos_b, cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, pos_b, cfg.rope_theta, cfg.rope_fraction)
    ck, cv, pos_k = _ring_write(cache, k, v, pos, b)
    out = full_attention(q, ck, cv, jnp.ones_like(pos_b), pos_k,
                         causal=True, window=0)
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    return y, {"k": ck, "v": cv}
