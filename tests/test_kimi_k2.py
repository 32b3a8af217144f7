"""Kimi-K2's mechanisms at a tiny size on the CPU (seeded random weights,
float32), each against the plain float32 reference of the benchmark
(``bench/ref/model.py``, on the harness's own checkpoint in the published
layout, ``bench/ref/checkpoint.py``) or an identity it must keep: MLA, full and
blockwise; the sigmoid router with its selection-only bias; dropless
dispatch over the held experts; the expert-parallel share's additivity;
the layer plan's exactness; the semantic plan; the causal flash kernel
MLA's prefill lowers to on the TPU (Pallas interpret mode), and when it
is chosen."""
import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.paths.plan import load_params, program_config  # noqa: E402
from bench.ref import checkpoint  # noqa: E402
from bench.ref import model as ref  # noqa: E402
from repro.kernels.causal_flash import causal_flash_attention  # noqa: E402
from repro.models import attention, forward, init_params  # noqa: E402
from repro.models import moe as M  # noqa: E402
from repro.models.layers import rmsnorm  # noqa: E402
from repro.models.model import _make_ctx  # noqa: E402
from repro.obs import RunLedger, use_ledger  # noqa: E402
from repro.serving.engine import Request, SplitPlaceEngine  # noqa: E402
from repro.serving.plans import branch_forward, pipeline_forward  # noqa: E402

#: the cell's configuration at CPU widths (every mechanism kept)
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
            moe_intermediate_size=32, router_experts=16, n_routed_experts=16,
            experts_held=[0, 16], num_experts_per_tok=4, vocab_size=256,
            num_hidden_layers=3, param_dtype="float32",
            compute_dtype="float32")


def tiny(**kw):
    with open(os.path.join(ROOT, "bench", "configs", "kimi-k2-ep32.json")) as f:
        cfg = dict(json.load(f), **dict(TINY, **kw))
    return cfg, program_config(cfg)


def tokens(cfg, b=2, L=32, seed=0):
    return np.random.RandomState(seed).randint(
        0, cfg["vocab_size"], (b, L)).astype(np.int32)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("blockwise", [False, True])
def test_mla_matches_reference(blockwise):
    """One MLA layer (YaRN RoPE, qk head dim 24 against v head dim 16),
    full and blockwise, against the reference's attention on the same
    checkpoint, loaded into the program's layout."""
    cfg, mcfg = tiny()
    p = load_params(cfg, mcfg, 1)["prefix"][0]
    L = 64
    x = jax.random.normal(jax.random.PRNGKey(2), (1, L, cfg["hidden_size"]))
    ctx = {"positions": jnp.arange(L)[None],
           "blockwise_threshold": 16 if blockwise else 4096}
    xn = rmsnorm(x, p["norm1"], cfg["rms_norm_eps"])
    got, _ = attention.mla_attention(p["attn"], xn, ctx, mcfg)
    freq, cs, scale = ref.yarn(cfg)
    key = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
           cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"], cs,
           scale)
    with jax.default_matmul_precision("highest"):
        want = ref._attention(checkpoint.layer(cfg, 1, 0, jnp.float32), x[0],
                              freq, key, jnp.float32) - x[0]
    assert rel(got[0], want) < 1e-5


def test_router_bias_selects_but_does_not_weigh():
    """The correction bias picks the experts; the gates are the chosen
    experts' unbiased sigmoid scores, renormalised and scaled."""
    _, mcfg = tiny()
    m = mcfg.moe
    E, d = m.num_experts, mcfg.d_model
    x = jnp.eye(d)[:1]
    logits = jnp.linspace(2.0, -2.0, E)
    router = jnp.zeros((d, E)).at[0].set(logits)
    bias = jnp.zeros((E,)).at[E - 1].set(10.0)     # the lowest score wins
    gates, idx, probs = M.router_topk({"router": router, "bias": bias}, x, m)
    assert int(idx[0, 0]) == E - 1 and set(map(int, idx[0, 1:])) == {0, 1, 2}
    s = jax.nn.sigmoid(logits)[np.asarray(idx[0])]
    want = s / s.sum() * m.routed_scaling_factor
    np.testing.assert_allclose(np.asarray(gates[0]), np.asarray(want),
                               rtol=1e-6)
    np.testing.assert_allclose(float(gates.sum()), m.routed_scaling_factor,
                               rtol=1e-6)


def _dense_loop(p, x, mcfg):
    """Per token, per chosen expert that is held: gate x expert(x)."""
    first, count = mcfg.held_experts
    gates, idx, _ = M.router_topk(p, x, mcfg.moe)
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[t, j]) - first
            if 0 <= e < count:
                h = jax.nn.silu(x[t] @ p["w_gate"][e]) * (x[t] @ p["w_up"][e])
                out[t] += float(gates[t, j]) * np.asarray(h @ p["w_down"][e])
    return out + np.asarray(M.shared_expert(p, x, mcfg))


@pytest.mark.parametrize("held", [(0, 16), (4, 4), (12, 4)])
def test_dropless_matches_dense_loop(held):
    """Dropless dispatch over the held experts equals a dense per-expert
    loop; nothing is dropped however unevenly the tokens route."""
    _, mcfg = tiny(experts_held=list(held), n_routed_experts=held[1])
    p = M.moe_init(jax.random.PRNGKey(3), mcfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (96, mcfg.d_model))
    x = x.at[:48].set(x[0])                      # half the tokens alike
    y, route = M.moe_dropless(p, x[None], mcfg)
    assert rel(y[0], _dense_loop(p, x, mcfg)) < 1e-5
    first, count = held
    idx = np.asarray(route["topk"])
    assert int(route["pairs"]) == int(((idx >= first)
                                       & (idx < first + count)).sum())


def test_expert_shares_add_up_to_the_layer():
    """The guide's share test: the routed parts of all four shares of the
    experts, with the shared expert counted once, give the uncut layer."""
    _, full = tiny()
    p = M.moe_init(jax.random.PRNGKey(5), full, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 40, full.d_model))
    want = M.moe_dropless(p, x, full)[0]
    shared = M.shared_expert(p, x, full)
    total = shared
    for first in range(0, 16, 4):
        cfg = dataclasses.replace(full, moe=dataclasses.replace(
            full.moe, held=(first, 4)))
        part = dict(p, **{k: p[k][first:first + 4]
                          for k in ("w_gate", "w_up", "w_down")})
        total = total + (M.moe_dropless(part, x, cfg)[0] - shared)
    assert rel(total, want) < 1e-5


def test_layer_plan_equals_forward_exactly():
    """Unrolled alike, the layer plan is the monolithic forward, bit for
    bit, for any stage cuts."""
    cfg, mcfg = tiny(experts_held=[4, 8], n_routed_experts=8)
    params = init_params(jax.random.PRNGKey(7), mcfg)
    batch = {"tokens": jnp.asarray(tokens(cfg))}
    want = jax.jit(lambda p, b: forward(p, b, mcfg)[0])(params, batch)
    for bounds in ([(0, 1), (1, 3)], [(0, 2), (2, 3)], [(0, 3)]):
        got = jax.jit(lambda p, b: pipeline_forward(
            p, b, mcfg, 2, bounds=bounds))(params, batch)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("branches", [1, 2])
def test_plan_matches_reference(branches):
    """Both plans' logits, their routing, and each MoE layer's held-expert
    part at the probed positions, against the reference's forward on the
    expert share (branches=1: the layer plan)."""
    cfg, mcfg = tiny(experts_held=[4, 8], n_routed_experts=8)
    params = load_params(cfg, mcfg, 8)
    ckpt = checkpoint.load(cfg, 8, jnp.float32)
    tok = tokens(cfg, L=64, seed=1)
    pos = np.arange(0, tok.size, 3)
    batch = {"tokens": jnp.asarray(tok), "probe": jnp.asarray(pos)}
    if branches == 1:
        logits, routes = pipeline_forward(params, batch, mcfg, 2,
                                          with_routes=True)
    else:
        logits, routes = branch_forward(params, batch, mcfg, branches,
                                        with_routes=True)
    want, routing = ref.logits_at(ckpt, cfg, tok, pos,
                                  np.asarray(routes["topk"]),
                                  semantic=branches, eps=1e-4)
    got = np.asarray(logits).reshape(-1, cfg["vocab_size"])[pos]
    assert routing["flips"] == 0
    assert rel(got, want) < 1e-4
    # the reference's own routing agrees with the program's here
    own, _ = ref.logits_at(ckpt, cfg, tok, pos, semantic=branches)
    assert rel(own, want) < 1e-4
    topk = np.asarray(routes["topk"])
    for li in range(len(topk)):
        for br in range(branches):
            part = ref.held_part(ckpt, cfg, 1 + li, routes["probe_x"][li, br],
                                 topk[li, br][pos], br, branches)
            assert np.abs(part).max() > 0
            assert rel(routes["probe_y"][li, br], part) < 1e-4


# ------------------------------------------- MLA's fused prefill attention

#: published MLA head widths (qk 128 + 64 RoPE, v 128) at a CPU width
WIDE_HEADS = dict(qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128)


def _on_the_tpu_path(monkeypatch):
    """Lower ``mla_attention``'s TPU branch on the CPU, the kernel in
    Pallas interpret mode."""
    monkeypatch.setattr(attention, "causal_flash_attention", functools.partial(
        causal_flash_attention, interpret=True))
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))


@pytest.mark.parametrize("L,branches", [(2048, 1), (1100, 1), (2048, 2),
                                        (1100, 2)])
def test_fused_mla_matches_full_attention(monkeypatch, L, branches):
    """The TPU branch of one MLA layer (heads-major projections, queries
    scaled by Kimi's YaRN scale in float32, the causal flash kernel
    reading the values inside the key-value projection) against the XLA
    core's full attention, float32: at a length that is a multiple of the
    1024 block and one that is padded, plain and vmapped over branches of
    half the heads, as the semantic plan runs it."""
    cfg, mcfg = tiny(**WIDE_HEADS)
    assert (mcfg.mla.qk_head_dim, mcfg.mla.v_head_dim) == (192, 128)
    p = load_params(cfg, mcfg, 1)["prefix"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, L, cfg["hidden_size"]))
    ctx = _make_ctx({"tokens": jnp.zeros((2, L), jnp.int32)}, mcfg, None)
    assert attention.mla_core(mcfg, 2, 4 // branches, L, "tpu") == "fused"

    def run(p, x):
        return attention.mla_attention(p, x, ctx, mcfg)[0]
    if branches > 1:
        cut = {"wq_b": 1, "wkv_b": 1, "wo": 0}
        p = {n: jnp.moveaxis(a.reshape(a.shape[:cut[n]] + (branches, -1)
                                       + a.shape[cut[n] + 1:]), cut[n], 0)
             if n in cut else a for n, a in p.items()}
        run = jax.vmap(run, in_axes=({n: 0 if n in cut else None
                                      for n in p}, None))
    want = run(p, x)
    _on_the_tpu_path(monkeypatch)
    got = run(p, x)
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("case", ["cpu", "tpu", "own_positions"])
def test_mla_core_choice(case):
    """Which attention core an MLA call lowers to: the CPU keeps the XLA
    core, bit for bit; the TPU lowers the kernel, with no loop left;
    a batch that brings its own positions keeps the XLA core (blockwise
    at the plan cell's sizes) on the TPU too."""
    cfg, mcfg = tiny(**WIDE_HEADS)
    p = load_params(cfg, mcfg, 1)["prefix"][0]["attn"]
    L = 256
    x = jax.random.normal(jax.random.PRNGKey(2), (1, L, cfg["hidden_size"]))
    batch = {"tokens": jnp.zeros((1, L), jnp.int32)}
    if case == "own_positions":
        batch["positions"] = jnp.arange(L)[None]
    ctx = _make_ctx(batch, mcfg, None)
    run = jax.jit(lambda p, x: attention.mla_attention(p, x, ctx, mcfg)[0])
    if case == "cpu":
        assert "tpu_custom_call" not in run.lower(p, x).as_text()
        xla = _make_ctx(dict(batch, positions=ctx["positions"]), mcfg, None)
        np.testing.assert_array_equal(np.asarray(run(p, x)), np.asarray(
            jax.jit(lambda p, x: attention.mla_attention(p, x, xla,
                                                         mcfg)[0])(p, x)))
        return
    text = run.trace(p, x).lower(lowering_platforms=("tpu",)).as_text()
    fused = case == "tpu"
    assert ("tpu_custom_call" in text) == fused
    assert ctx["arange_positions"] == fused
    for L, h in ((1024, 64), (4096, 64), (4096, 32)):
        assert attention.mla_core(mcfg, 4, h, L, "tpu", fused) == \
            ("fused" if fused else "blockwise")
        assert attention.mla_core(mcfg, 4, h, L, "cpu", fused) == "blockwise"


def test_engine_counts_mla_cores():
    """The engine counts each MLA call of a request, the plan's and the
    fidelity forward's, by the core it lowered to: on the CPU none is
    fused."""
    cfg, mcfg = tiny(**WIDE_HEADS)
    eng = SplitPlaceEngine(load_params(cfg, mcfg, 1), mcfg)
    led = RunLedger("mla-cores")
    with use_ledger(led):
        for _ in range(2):
            eng.serve(Request(tokens=tokens(cfg, L=16), deadline_s=1.0))
    layers = cfg["num_hidden_layers"]
    assert led.counters.get("mla.fused", 0) == 0
    assert led.counters.get("mla.full", 0) == 2 * 2 * layers
