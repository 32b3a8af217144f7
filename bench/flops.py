"""Operations and bytes of the plan cell's Kimi-K2 cut, from the
configuration file's numbers and the request's shape (no trace, no
program).  Model FLOPs count what the plan's mathematics needs: a
multiply-add is 2 FLOPs, attention is causal (each query against the
keys at or before it), the held experts take their expected share of
each token's top-k (``num_experts_per_tok`` x held / routed), and the
semantic plan's branches each run the shared down-projections, router
and norms in full and their slice of heads and channels."""
from __future__ import annotations


def _mlp(d, f):
    return 3 * d * f


def forward_flops(cfg: dict, b: int, L: int, branches: int = 1) -> float:
    """Model FLOPs of one forward of a (b, L) batch through the cut; with
    ``branches`` B > 1, the semantic plan of B branches."""
    d, h, B = cfg["hidden_size"], cfg["num_attention_heads"], branches
    rope, nope = cfg["qk_rope_head_dim"], cfg["qk_nope_head_dim"]
    v, r, q = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
    held = cfg["experts_held"][1]
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    F = cfg["moe_intermediate_size"]
    # MLA: the latent down-projections per branch, the heads once in all
    mla = B * (d * q + d * (r + rope)) \
        + q * h * (nope + rope) + r * h * (nope + v) + h * v * d
    dense = _mlp(d, cfg["intermediate_size"])
    moe = B * d * E + cfg["n_shared_experts"] * _mlp(d, F) \
        + k * held / E * _mlp(d, F)
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    weights = n_dense * (mla + dense) + n_moe * (mla + moe) \
        + d * cfg["vocab_size"]
    # causal scores and values: a token at position t meets t + 1 keys
    attn = cfg["num_hidden_layers"] * L * h * (nope + rope + v)
    return b * L * (2.0 * weights + attn)


def request_flops(cfg: dict, b: int, L: int, semantic: int = 1) -> float:
    """One served request: the plan's forward (``semantic`` branches, 1
    for the layer plan) and the engine's monolithic fidelity forward."""
    return forward_flops(cfg, b, L, semantic) + forward_flops(cfg, b, L)


def expert_cost(cfg: dict, pairs: int, layer_reads: int) -> tuple:
    """(FLOPs, bytes) of the held experts' grouped matmuls for ``pairs``
    token-expert pairs over ``layer_reads`` executions of a MoE layer,
    each reading every held expert's bf16 weights once; activations: the
    pair's hidden row in and out (bf16) and its gate, up and product rows
    (bf16)."""
    d, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    flops = pairs * 2 * _mlp(d, F)
    weights = layer_reads * held * _mlp(d, F) * 2
    acts = pairs * (2 * d + 3 * F) * 2
    return float(flops), float(weights + acts)
