"""Shared neural building blocks: norms, MLPs, position embeddings, init."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    scale = 1.0 / np.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rmsnorm(x, weight, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def activation_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name in ("gelu", "gelu_plain"):
        return jax.nn.gelu
    if name == "relu2":  # nemotron squared-ReLU
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# ----------------------------------------------------------------- MLP

def mlp_init(key, d_model, d_ff, cfg, dtype):
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (d_model, d_ff), dtype),
         "w_down": dense_init(ks[1], (d_ff, d_model), dtype, fan_in=d_ff)}
    if cfg.mlp_gated:
        p["w_gate"] = dense_init(ks[2], (d_model, d_ff), dtype)
    return p


def mlp_apply(p, x, cfg, constrain=None):
    act = activation_fn(cfg.activation)
    up = x @ p["w_up"]
    if cfg.mlp_gated:
        h = act(x @ p["w_gate"]) * up
    else:
        h = act(up)
    if constrain is not None:
        h = constrain(h, "ffn_hidden")
    return h @ p["w_down"]


# ---------------------------------------------------------------- RoPE

def rope_angles(positions, dim, theta, dtype=jnp.float32):
    """positions (...,) -> cos/sin of shape (..., dim//2)."""
    freqs = (theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, y):
    """YaRN's per-pair frequencies (DeepSeek-V3 ``yarn`` rope_scaling):
    pairs faster than ``beta_fast`` rotations over the original context
    keep their frequency, those slower than ``beta_slow`` are divided by
    ``factor``, with a linear ramp between."""
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr_dim(rot):
        return dim * math.log(y.original_max_position_embeddings
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr_dim(y.beta_fast)), 0)
    high = min(math.ceil(corr_dim(y.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp
    return (base / y.factor * (1 - keep) + base * keep).astype(np.float32)


def apply_rope(x, positions, theta=10000.0, fraction=1.0, inv_freq=None):
    """x (b, s, h, hd); positions (b, s). Rotates leading `fraction` of hd
    (adjacent pairs), at ``inv_freq`` if given, else theta's frequencies."""
    hd = x.shape[-1]
    rot = int(hd * fraction)
    rot -= rot % 2
    xr, xp = x[..., :rot], x[..., rot:]
    if inv_freq is None:
        cos, sin = rope_angles(positions, rot, theta)      # (b, s, rot/2)
    else:
        ang = positions[..., None].astype(jnp.float32) * inv_freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = xr[..., ::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    y = jnp.stack([y1, y2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([y, xp], axis=-1) if rot < hd else y


def apply_mrope(x, positions3, sections, theta=10000.0):
    """Qwen2-VL multimodal RoPE.

    x (b, s, h, hd); positions3 (b, 3, s) = (temporal, height, width) ids.
    `sections` gives the number of (cos,sin) slots taken from each of the
    three position streams; sum(sections) == hd // 2.
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)  # (hd/2,)
    ang_all = positions3[..., None].astype(jnp.float32) * freqs       # (b,3,s,hd/2)
    parts, off = [], 0
    for i, sec in enumerate(sections):
        parts.append(ang_all[:, i, :, off:off + sec])
        off += sec
    ang = jnp.concatenate(parts, axis=-1)                              # (b,s,hd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


def sinusoidal_embedding(positions, dim, max_scale=10000.0):
    """positions (b, s) -> (b, s, dim)."""
    half = dim // 2
    freqs = max_scale ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------- causal conv

def causal_conv1d(x, weight, bias):
    """Depthwise causal conv.  x (b, s, d); weight (k, d); bias (d)."""
    k = weight.shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + x.shape[1], :] * weight[i] for i in range(k))
    return out + bias


def causal_conv1d_step(x_t, conv_state, weight, bias):
    """One decode step.  x_t (b, d); conv_state (b, k-1, d) past inputs."""
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (b,k,d)
    out = jnp.einsum("bkd,kd->bd", window, weight) + bias
    return out, window[:, 1:, :]
