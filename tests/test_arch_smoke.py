"""Per-architecture smoke tests: reduced (2-layer, d<=512, <=4 experts)
variants of every assigned architecture run one forward + one train step on
CPU; output shapes and finiteness asserted.  Also checks analytic parameter
counts against the assignment targets."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.launch.steps import make_train_step
from repro.models import decode_step, forward, init_params, prefill
from repro.optim.optimizers import make_optimizer

PARAM_TARGETS_B = {
    "qwen1.5-110b": (100, 120), "recurrentgemma-9b": (8, 12),
    "musicgen-medium": (1.2, 2.2), "qwen2-moe-a2.7b": (12, 16),
    "tinyllama-1.1b": (1.0, 1.25), "nemotron-4-340b": (325, 355),
    "falcon-mamba-7b": (6.5, 8.0), "qwen2-vl-7b": (7.0, 8.3),
    "kimi-k2-1t-a32b": (950, 1100), "llama3-405b": (390, 420),
}
ACTIVE_TARGETS_B = {"qwen2-moe-a2.7b": (2.0, 3.4), "kimi-k2-1t-a32b": (28, 38)}


def make_batch(cfg, b=2, s=16, seed=0, labels=True):
    rng = np.random.RandomState(seed)
    shape = (b, s) if not cfg.num_codebooks else (b, s, cfg.num_codebooks)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, shape),
                                   jnp.int32)}
    if labels:
        batch["labels"] = batch["tokens"]
    if cfg.visual_frontend:
        batch["visual_embeds"] = jnp.asarray(
            rng.randn(b, s, cfg.d_model) * 0.1, jnp.float32)
        batch["visual_mask"] = jnp.zeros((b, s), bool).at[:, 2:5].set(True)
    if cfg.cross_attention:
        batch["cond"] = jnp.asarray(
            rng.randn(b, cfg.cond_len, cfg.d_model) * 0.1, jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_forward_shapes_and_finite(arch):
    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg)
    logits, aux = forward(params, batch, cfg)
    want = (2, 16, cfg.num_codebooks, cfg.vocab_size) if cfg.num_codebooks \
        else (2, 16, cfg.vocab_size)
    assert logits.shape == want
    assert bool(jnp.isfinite(logits).all()), "NaN/Inf in logits"
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_train_step(arch):
    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    init_opt, _ = make_optimizer(cfg.optimizer)
    opt_state = init_opt(params)
    step = make_train_step(cfg, mesh=None, lr=1e-3)
    batch = make_batch(cfg)
    params2, opt_state2, metrics = jax.jit(step)(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    delta = max(float(jnp.abs(a - b).max())
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(params2)))
    assert delta > 0


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    b, s = 2, 12
    batch = make_batch(cfg, b=b, s=s + 1, labels=False)
    logits_full, _ = forward(params, batch, cfg)
    pre = {k: (v[:, :s] if k != "cond" else v) for k, v in batch.items()}
    _, cache = prefill(params, pre, cfg)
    extras = {}
    if cfg.cross_attention:
        extras["cond"] = batch["cond"]
    if cfg.visual_frontend:
        extras = {"visual_embeds": batch["visual_embeds"][:, s:s + 1],
                  "visual_mask": batch["visual_mask"][:, s:s + 1]}
    ld, _ = decode_step(params, batch["tokens"][:, s:s + 1], cache,
                        jnp.int32(s), cfg, batch_extras=extras or None)
    err = float(jnp.abs(ld[:, 0] - logits_full[:, s]).max())
    assert err < 2e-3, f"decode mismatch {err}"


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_full_config_param_count(arch):
    cfg = get_config(arch)
    lo, hi = PARAM_TARGETS_B[arch]
    n = cfg.param_count() / 1e9
    assert lo <= n <= hi, f"{arch}: {n:.1f}B outside [{lo},{hi}]"
    if arch in ACTIVE_TARGETS_B:
        lo, hi = ACTIVE_TARGETS_B[arch]
        a = cfg.active_param_count() / 1e9
        assert lo <= a <= hi, f"{arch} active: {a:.1f}B outside [{lo},{hi}]"


def test_layer_kinds_cover_patterns():
    cfg = get_config("recurrentgemma-9b")
    kinds = cfg.layer_kinds
    assert len(kinds) == 38
    assert kinds[:3] == ("rglru", "rglru", "local_attn")
    assert kinds.count("local_attn") == 12          # 12 full periods
    kimi = get_config("kimi-k2-1t-a32b")
    assert kimi.layer_kinds[0] == "mla"             # first_k_dense
    assert set(kimi.layer_kinds[1:]) == {"mla_moe"}
