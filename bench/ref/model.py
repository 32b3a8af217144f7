"""Plain float32 reference of the Kimi-K2 cut (moonshotai/Kimi-K2-Instruct
config.json, the DeepSeek-V3 architecture) that the plan cell serves.

It reads the weights by the published checkpoint's names and layout
(``bench/ref/checkpoint.py``, made from the seed by the harness), not the
program's parameter tree.  Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one layer and one held
expert at a time, so that the float32 copies of a layer's weights fit
beside the bf16 weights on the chip.  Per layer, as DeepSeek-V3's
modeling code: RMSNorm, multi-head latent attention (queries through
``q_a_proj``, its norm and ``q_b_proj``; keys and values from the
``kv_lora_rank`` latent of ``kv_a_proj_with_mqa``, its norm and
``kv_b_proj``, plus one ``qk_rope_head_dim`` RoPE key shared by all
heads, its pairs de-interleaved and rotated by halves; YaRN frequencies,
cos/sin and softmax scale; causal), RMSNorm, then the dense SwiGLU MLP
(the first layer) or the MoE layer: sigmoid scores over all routed
experts, the top ``num_experts_per_tok`` chosen by score plus the
correction bias, weighted by their unbiased scores renormalised and
times ``routed_scaling_factor``, plus one ungated shared expert.

Departures from the published model, each also made by the program:

- depth, routed experts and vocabulary are the cell's cut: the layers
  the configuration keeps, the ``experts_held`` share of each MoE layer
  (what the absent experts would add is left out, it belongs to the
  chips that hold them), and the first ``vocab_size`` rows of the
  vocabulary;
- no multi-token prediction (``num_nextn_predict_layers`` is 0).

The MoE layers are routed as the program routed (``routes``), so that a
near-tie between bf16 and float32 hidden states does not send the two
down different experts; each routing is checked against the reference's
own scores instead: a token counts as a flip when an expert the program
left out scores above one it chose by more than ``eps``.

``semantic`` B > 1 is the semantic-branch plan: branch b keeps heads
[b h/B, (b+1) h/B) of ``q_b_proj``, ``kv_b_proj`` and ``o_proj`` and
channels [b w/B, (b+1) w/B) of every MLP (dense, shared and each held
expert); the latent down-projections, norms, router and embeddings are
shared; the branches' logits are averaged.

``dtype`` rounds every matmul operand to another float type (the
control: float8 must fail the comparison).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: the weights a semantic branch slices: by rows (out features) and by
#: columns (in features)
_ROWS = ("self_attn.q_b_proj.weight", "self_attn.kv_b_proj.weight",
         "gate_proj.weight", "up_proj.weight")
_COLS = ("self_attn.o_proj.weight", "down_proj.weight")


def _rnd(a, dtype):
    """Round to ``dtype`` and back; saturating where its range is short
    (float8_e4m3fn has no infinity and reads an overflow as NaN)."""
    a = a.astype(F32)
    if dtype == F32:
        return a
    top = float(jnp.finfo(dtype).max)
    return jnp.clip(a, -top, top).astype(dtype).astype(F32)


def _mm(eq, a, b, dtype):
    return jnp.einsum(eq, _rnd(a, dtype), _rnd(b, dtype))


def _linear(x, w, dtype):
    """x (..., in) through a (out, in) weight."""
    return _mm("...i,oi->...o", x, w, dtype)


def _norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(F32)


def _yarn_mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(cfg):
    """(inverse frequencies of the rope dims, cos/sin scale, softmax
    scale) from ``rope_theta`` and ``rope_scaling`` (DeepSeek-V3's
    yarn)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg.get("rope_scaling")
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    if not rs:
        return freq.astype(np.float32), 1.0, scale

    def corr(rot):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))
    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    freq = freq / rs["factor"] * ramp + freq * (1 - ramp)
    f = rs["factor"]
    cs = _yarn_mscale(f, rs["mscale"]) / _yarn_mscale(f, rs["mscale_all_dim"])
    m = _yarn_mscale(f, rs["mscale_all_dim"])
    return freq.astype(np.float32), cs, scale * m * m


def _rope(x, pos, freq, cs):
    """DeepSeek-V3's rotary embedding of x (L, ..., r): the rope dims
    de-interleaved (even dims, then odd), then rotated by halves."""
    r = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    ang = pos[:, None].astype(F32) * freq                  # (L, r/2)
    ang = jnp.concatenate([ang, ang], -1)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    rot = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * (jnp.cos(ang) * cs) + rot * (jnp.sin(ang) * cs)


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype"))
def _attention(w, x, freq, cfg_key, dtype):
    """Layer input plus the MLA of one sequence x (L, d) with the heads
    the weights ``w`` hold."""
    nope, rope, vdim, kvr, eps, cs, scale = cfg_key
    L = x.shape[0]
    xn = _norm(x, w["input_layernorm.weight"], eps)
    cq = _norm(_linear(xn, w["self_attn.q_a_proj.weight"], dtype),
               w["self_attn.q_a_layernorm.weight"], eps)
    q = _linear(cq, w["self_attn.q_b_proj.weight"], dtype)
    q = q.reshape(L, -1, nope + rope)
    kv = _linear(xn, w["self_attn.kv_a_proj_with_mqa.weight"], dtype)
    ckv = _norm(kv[:, :kvr], w["self_attn.kv_a_layernorm.weight"], eps)
    kvb = _linear(ckv, w["self_attn.kv_b_proj.weight"], dtype)
    kvb = kvb.reshape(L, -1, nope + vdim)
    pos = jnp.arange(L)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, freq, cs)],
                        -1)
    k_pe = _rope(kv[:, kvr:], pos, freq, cs)               # (L, rope)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_pe[:, None], kvb.shape[:2] + (rope,))], -1)
    v = kvb[..., nope:]
    blk = min(L, 256)
    qb = q.reshape(L // blk, blk, *q.shape[1:])

    def one(args):
        qi, i = args
        s = _mm("qhe,khe->hqk", qi, k, dtype) * scale
        mask = (i * blk + jnp.arange(blk))[:, None] >= pos[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        return _mm("hqk,khe->qhe", jax.nn.softmax(s, -1), v, dtype)
    out = jax.lax.map(one, (qb, jnp.arange(L // blk))).reshape(L, -1)
    return x + _linear(out, w["self_attn.o_proj.weight"], dtype)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _mlp(gate, up, down, xn, dtype):
    h = jax.nn.silu(_linear(xn, gate, dtype)) * _linear(xn, up, dtype)
    return _linear(h, down, dtype)


def _mlp_of(w, prefix, xn, dtype):
    return _mlp(w[f"{prefix}.gate_proj.weight"], w[f"{prefix}.up_proj.weight"],
                w[f"{prefix}.down_proj.weight"], xn, dtype)


@functools.partial(jax.jit, static_argnames=("eps",))
def _pre_ffn(norm2, x, eps):
    return _norm(x, norm2, eps)


@jax.jit
def _scores(router, bias, xn):
    s = jax.nn.sigmoid(xn @ router.astype(F32).T)
    return s, s + bias.astype(F32)


@functools.partial(jax.jit, static_argnames=("k", "scale"))
def _route(s, biased, idx, eps, k, scale):
    """(gates (N, k) of the program's choice ``idx``, count of tokens
    whose choice is not within eps of the reference's own top-k, the
    widest such gap)."""
    chosen = jnp.zeros(s.shape, bool).at[jnp.arange(s.shape[0])[:, None],
                                         idx].set(True)
    worst_in = jnp.where(chosen, biased, jnp.inf).min(-1)
    best_out = jnp.where(chosen, -jnp.inf, biased).max(-1)
    gap = best_out - worst_in
    g = jnp.take_along_axis(s, idx, -1)
    return g / g.sum(-1, keepdims=True) * scale, jnp.sum(gap > eps), gap.max()


@functools.partial(jax.jit, static_argnames=("dtype",))
def _expert_add(acc, xn, gates, rows, slots, live, gate, up, down, dtype):
    """acc += gate * expert(xn[rows]) at rows (padding rows not live)."""
    w = jnp.where(live, gates[rows, slots], 0.0)
    return acc.at[rows].add(_mlp(gate, up, down, xn[rows], dtype)
                            * w[:, None])


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _logits(final_norm, head, x, eps, dtype):
    return _linear(_norm(x, final_norm, eps), head, dtype)


def _slice(w, b, B):
    """Branch b of B of one layer's weights: heads of the attention,
    channels of the MLPs."""
    if B == 1:
        return w

    def cut(a, axis):
        n = a.shape[axis] // B
        return jax.lax.slice_in_dim(a, b * n, (b + 1) * n, axis=axis)
    return {n: cut(a, 0) if n.endswith(_ROWS) else
            cut(a, 1) if n.endswith(_COLS) else a for n, a in w.items()}


def _held(w, cfg, xn, idx, eps, dtype):
    """(the held experts' part of a MoE layer at tokens xn (N, d) routed
    to ``idx`` (N, k), the route check's flips and widest gap)."""
    first, count = cfg["experts_held"]
    s, biased = _scores(w["mlp.gate.weight"],
                        w["mlp.gate.e_score_correction_bias"], xn)
    gates, n_flip, gap = _route(s, biased, jnp.asarray(idx), eps,
                                cfg["num_experts_per_tok"],
                                cfg["routed_scaling_factor"])
    acc = jnp.zeros_like(xn)
    for e in range(first, first + count):
        tok, slot = np.nonzero(idx == e)
        if not len(tok):
            continue
        pad = max(64, 1 << (len(tok) - 1).bit_length())
        rows, slots = np.zeros((2, pad), np.int32)
        rows[:len(tok)], slots[:len(tok)] = tok, slot
        pre = f"mlp.experts.{e}"
        acc = _expert_add(acc, xn, gates, rows, slots,
                          np.arange(pad) < len(tok),
                          w[f"{pre}.gate_proj.weight"],
                          w[f"{pre}.up_proj.weight"],
                          w[f"{pre}.down_proj.weight"], dtype)
    return acc, int(n_flip), float(gap)


def held_part(ckpt, cfg, i, xn, idx, branch=0, semantic=1, dtype=F32):
    """The held experts' part of MoE layer i's output at normed inputs xn
    (N, d), routed to ``idx`` (N, k): branch ``branch`` of ``semantic``
    (its channel block of each expert), float32."""
    with jax.default_matmul_precision("highest"):
        w = _slice({n: a for n, a in ckpt["layers"][i].items()
                    if n.startswith("mlp.")}, branch, semantic)
        return np.asarray(_held(w, cfg, jnp.asarray(xn, F32), np.asarray(idx),
                                0.0, dtype)[0])


def logits_at(ckpt, cfg, tokens, flat_pos, routes=None, semantic=1,
              eps=0.0, dtype=F32):
    """Reference logits (n, V) at ``flat_pos`` (indices into the b x L
    positions of ``tokens``), and the routing check: {"flips": tokens
    routed more than ``eps`` below the reference's top-k, "gap": the
    widest gap of a program's choice below it (negative: none)}.

    ``ckpt`` is ``checkpoint.load``'s.  ``routes`` (MoE layers, B, b*L,
    k): the program's top-k expert ids per token, branch and layer; the
    reference routes as they say and counts the tokens whose choice is
    more than ``eps`` below its own scores.  Without ``routes`` it routes
    by its own top-k."""
    with jax.default_matmul_precision("highest"):
        return _logits_at(ckpt, cfg, np.asarray(tokens), np.asarray(flat_pos),
                          routes, semantic, eps, dtype)


def _logits_at(ckpt, cfg, tokens, flat_pos, routes, B, eps, dtype):
    b, L = tokens.shape
    k = cfg["num_experts_per_tok"]
    ne = cfg["rms_norm_eps"]
    freq, cs, sm = yarn(cfg)
    key = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
           cfg["v_head_dim"], cfg["kv_lora_rank"], ne, cs, sm)
    top = ckpt["top"]
    emb = jnp.take(top["model.embed_tokens.weight"],
                   jnp.asarray(tokens.reshape(-1)), axis=0).astype(F32)
    logits, flips, gap = 0.0, 0, -np.inf
    for br in range(B):
        x = emb
        moe_i = 0
        for i, w in enumerate(ckpt["layers"]):
            w = _slice(w, br, B)
            x = jnp.concatenate([_attention(w, x[j * L:(j + 1) * L], freq,
                                            key, dtype) for j in range(b)])
            xn = _pre_ffn(w["post_attention_layernorm.weight"], x, ne)
            if i < cfg["first_k_dense_replace"]:
                x = x + jnp.concatenate([_mlp_of(w, "mlp", xn[j * L:(j + 1) * L],
                                                 dtype) for j in range(b)])
                continue
            if routes is not None:
                idx = np.asarray(routes[moe_i, br])
            else:
                _, biased = _scores(w["mlp.gate.weight"],
                                    w["mlp.gate.e_score_correction_bias"], xn)
                idx = np.asarray(jax.lax.top_k(biased, k)[1])
            moe_i += 1
            acc, n_flip, g = _held(w, cfg, xn, idx, eps, dtype)
            flips, gap = flips + n_flip, max(gap, g)
            shared = jnp.concatenate([_mlp_of(w, "mlp.shared_experts",
                                              xn[j * L:(j + 1) * L], dtype)
                                      for j in range(b)])
            x = x + acc + shared
        logits = logits + _logits(top["model.norm.weight"],
                                  top["lm_head.weight"],
                                  x[jnp.asarray(flat_pos)], ne, dtype)
    return np.asarray(logits / B), {"flips": flips, "gap": gap}
