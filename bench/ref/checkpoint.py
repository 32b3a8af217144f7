"""The Kimi-K2 cut's weights, made from the seed by the published
checkpoint's tensor names and shapes (moonshotai/Kimi-K2-Instruct, the
DeepSeek-V3 layout: a linear layer's weight is (out, in); layers and
routed experts numbered as published), with nothing of the system under
test.  The reference (``bench/ref/model.py``) reads them as they are;
the plan path loads them into the program's parameter tree
(``bench/paths/plan.load_params``).

Each tensor is a function of the seed and its name alone, made on the
device: a linear layer's weight normal / sqrt(in), the embedding normal
/ sqrt(hidden), RMSNorm weights 1 + 0.05 normal, the router's weight
normal / sqrt(hidden) and its correction bias 0.1 normal in float32 (as
published), the rest in ``dtype``.  Only the cut's tensors are made: its
layers, the held routed experts (``experts_held``) and the vocabulary
slice.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

F32 = jnp.float32


def layer_specs(cfg: dict, i: int) -> dict:
    """{name within ``model.layers.<i>.``: (shape, kind)} of layer i."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    qr, kvr, v = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["v_head_dim"]
    out = {
        "input_layernorm.weight": ((d,), "norm"),
        "self_attn.q_a_proj.weight": ((qr, d), "linear"),
        "self_attn.q_a_layernorm.weight": ((qr,), "norm"),
        "self_attn.q_b_proj.weight": ((H * (nope + rope), qr), "linear"),
        "self_attn.kv_a_proj_with_mqa.weight": ((kvr + rope, d), "linear"),
        "self_attn.kv_a_layernorm.weight": ((kvr,), "norm"),
        "self_attn.kv_b_proj.weight": ((H * (nope + v), kvr), "linear"),
        "self_attn.o_proj.weight": ((d, H * v), "linear"),
        "post_attention_layernorm.weight": ((d,), "norm"),
    }

    def mlp(prefix, width):
        out.update({f"{prefix}.gate_proj.weight": ((width, d), "linear"),
                    f"{prefix}.up_proj.weight": ((width, d), "linear"),
                    f"{prefix}.down_proj.weight": ((d, width), "linear")})
    if i < cfg["first_k_dense_replace"]:
        mlp("mlp", cfg["intermediate_size"])
        return out
    E, F = cfg["router_experts"], cfg["moe_intermediate_size"]
    out.update({"mlp.gate.weight": ((E, d), "router"),
                "mlp.gate.e_score_correction_bias": ((E,), "bias")})
    first, count = cfg["experts_held"]
    for e in range(first, first + count):
        mlp(f"mlp.experts.{e}", F)
    mlp("mlp.shared_experts", cfg["n_shared_experts"] * F)
    return out


def top_specs(cfg: dict) -> dict:
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    return {"model.embed_tokens.weight": ((V, d), "embed"),
            "model.norm.weight": ((d,), "norm"),
            "lm_head.weight": ((V, d), "linear")}


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype"))
def _make(key, shape, kind, dtype):
    n = jax.random.normal(key, shape, F32)
    if kind == "norm":
        return (1.0 + 0.05 * n).astype(dtype)
    if kind == "bias":
        return 0.1 * n
    out = n * shape[-1] ** -0.5
    return out if kind == "router" else out.astype(dtype)


def tensor(seed: int, name: str, shape, kind: str, dtype=jnp.bfloat16):
    """The tensor ``name`` of the checkpoint of ``seed``."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             zlib.crc32(name.encode()))
    return _make(key, tuple(shape), kind, jnp.dtype(dtype).name)


def layer(cfg: dict, seed: int, i: int, dtype=jnp.bfloat16) -> dict:
    """Layer i's tensors, by their names within ``model.layers.<i>.``."""
    return {n: tensor(seed, f"model.layers.{i}.{n}", shape, kind, dtype)
            for n, (shape, kind) in layer_specs(cfg, i).items()}


def load(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """{"top": embedding, final norm and head by full name, "layers": one
    ``layer`` dict per layer of the cut}."""
    return {"top": {n: tensor(seed, n, shape, kind, dtype)
                    for n, (shape, kind) in top_specs(cfg).items()},
            "layers": [layer(cfg, seed, i, dtype)
                       for i in range(cfg["num_hidden_layers"])]}
