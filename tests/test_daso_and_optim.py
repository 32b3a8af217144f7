"""DASO surrogate + placement optimization; optimizer substrate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import daso, sliced_dot
from repro.optim import optimizers as opt


def _cfg(w=4, c=3):
    return daso.DASOConfig(num_workers=w, max_containers=c,
                           state_features=2, hidden=32, depth=2,
                           place_iters=60, lr_place=0.3)


def test_surrogate_trains_to_low_mse():
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    theta, opt_state = daso.make_trainer(cfg, key)
    n = 128
    xs = jax.random.normal(jax.random.PRNGKey(1), (n, daso.feature_size(cfg)))
    w_true = jax.random.normal(jax.random.PRNGKey(2),
                               (daso.feature_size(cfg),)) * 0.3
    ys = jnp.tanh(xs @ w_true)
    losses = []
    for _ in range(300):
        theta, opt_state, l = daso.train_epoch(cfg, theta, opt_state, xs, ys)
        losses.append(float(l))
    assert losses[-1] < 0.05 * losses[0]


def test_placement_gradient_ascent_improves_score():
    """eq. 12: the optimized placement must score >= the initial one."""
    cfg = _cfg()
    theta, _ = daso.make_trainer(cfg, jax.random.PRNGKey(3))
    state = jnp.zeros((cfg.num_workers, cfg.state_features))
    p0 = jax.random.normal(jax.random.PRNGKey(4),
                           (cfg.max_containers, cfg.num_workers))
    dec = jnp.zeros((cfg.max_containers,), jnp.int32)
    mask = jnp.ones((cfg.max_containers,))
    s0 = daso.surrogate_apply(theta, daso.pack_input(cfg, state, p0, dec, mask))
    p_opt, score, iters = daso.optimize_placement(cfg, theta, state, p0, dec,
                                                  mask)
    assert float(score) >= float(s0) - 1e-6
    assert int(iters) > 0
    a = daso.placement_to_assignment(p_opt, mask)
    assert a.shape == (cfg.max_containers,)
    assert ((np.asarray(a) >= 0) & (np.asarray(a) < cfg.num_workers)).all()


def test_decision_aware_input_differs():
    cfg = _cfg()
    blind = daso.DASOConfig(**{**cfg._asdict(), "decision_aware": False})
    state = jnp.ones((cfg.num_workers, cfg.state_features))
    p = jnp.zeros((cfg.max_containers, cfg.num_workers))
    mask = jnp.ones((cfg.max_containers,))
    d0 = jnp.zeros((cfg.max_containers,), jnp.int32)
    d1 = jnp.ones((cfg.max_containers,), jnp.int32)
    x0 = daso.pack_input(cfg, state, p, d0, mask)
    x1 = daso.pack_input(cfg, state, p, d1, mask)
    assert float(jnp.abs(x0 - x1).max()) > 0           # DASO sees decisions
    y0 = daso.pack_input(blind, state, p, d0, mask)
    y1 = daso.pack_input(blind, state, p, d1, mask)
    assert float(jnp.abs(y0 - y1).max()) == 0          # GOBI does not


def _quadratic_losses(update_fn, init_fn, steps=200, lr=0.05):
    target = jnp.asarray([1.5, -2.0, 0.5])
    params = {"w": jnp.zeros(3)}
    state = init_fn(params)
    for _ in range(steps):
        grads = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(params)
        params, state = update_fn(grads, state, params, lr)
    return float(jnp.abs(params["w"] - target).max())


def test_adamw_converges():
    err = _quadratic_losses(
        lambda g, s, p, lr: opt.adamw_update(g, s, p, lr, weight_decay=0.0),
        opt.adamw_init)
    assert err < 0.05


def test_adafactor_converges():
    err = _quadratic_losses(opt.adafactor_update, opt.adafactor_init,
                            steps=400, lr=0.05)
    assert err < 0.1


def test_adafactor_factored_state_is_small():
    params = {"w": jnp.zeros((64, 128))}
    st = opt.adafactor_init(params)
    assert st.vr["w"].shape == (64,)
    assert st.vc["w"].shape == (128,)


def test_clip_by_global_norm():
    tree = {"a": jnp.ones(4) * 10.0}
    clipped, n = opt.clip_by_global_norm(tree, 1.0)
    np.testing.assert_allclose(float(opt.global_norm(clipped)), 1.0,
                               rtol=1e-5)
    assert float(n) == 20.0


def test_warmup_cosine_schedule():
    assert float(opt.warmup_cosine(0, 1.0, 10, 100)) < 0.2
    assert float(opt.warmup_cosine(10, 1.0, 10, 100)) > 0.9
    assert float(opt.warmup_cosine(100, 1.0, 10, 100)) < 0.2


# ------------------------------------------- sliced float64 dot (TPU ascent)

def _exact_dot(x, w):
    """Correctly rounded ``x @ w`` for 1-D ``x``: each factor split into
    26-bit halves (every partial product exact), summed by ``fsum``."""
    import math

    def halves(a):
        c = a * (2.0**27 + 1)
        hi = c - (c - a)
        return hi, a - hi

    xh, xl = halves(np.asarray(x, np.float64)[:, None])
    wh, wl = halves(np.asarray(w, np.float64))
    terms = np.concatenate([xh * wh, xh * wl, xl * wh, xl * wl])
    return np.array([math.fsum(terms[:, n]) for n in range(w.shape[1])])


def _sliced_operands(K, N, kind, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((K, N)) / np.sqrt(K)
    if kind == "span30":             # magnitudes 2^-30 .. 2^30 in a column
        w = w * 2.0 ** rng.integers(-30, 31, (K, N))
    if kind == "zeros":              # exact zeros and an all-zero column
        w[rng.random((K, N)) < 0.3] = 0.0
        w[:, -1] = 0.0
    x = rng.standard_normal(K)
    x[::7] = 0.0
    return x, w, rng.standard_normal(N)


@pytest.mark.parametrize("kind", ["normal", "span30", "zeros"])
@pytest.mark.parametrize("K,N", [(3528, 128), (128, 128), (128, 1), (37, 5)])
def test_sliced_dot_and_vjp_match_float64(K, N, kind):
    """``sliced_matmul`` and its VJP land within 2 ulp of
    ``sum |x||w|`` of the exact ``x @ w`` and ``w @ g``, which the float64
    dot itself only approximates."""
    x, w, g = _sliced_operands(K, N, kind)
    with jax.enable_x64(True):
        sw = sliced_dot.slice_weight(jnp.asarray(w))
        y, vjp = jax.vjp(lambda v: sliced_dot.sliced_matmul(v, sw),
                         jnp.asarray(x))
        (gx,) = vjp(jnp.asarray(g))
    for got, exact, scale in (
            (y, _exact_dot(x, w), np.abs(x) @ np.abs(w)),
            (gx, _exact_dot(g, w.T), np.abs(w) @ np.abs(g))):
        err = np.abs(np.asarray(got) - exact)
        assert (err <= 2 * np.spacing(scale)).all(), \
            float(np.max(err / np.spacing(scale)))
    if kind == "zeros":
        assert float(y[-1]) == 0.0


@pytest.mark.parametrize("seed", [0, 1])
def test_sliced_ascent_matches_native_at_serve_sizes(seed):
    """The ascent with the TPU's sliced dot (reached through ``_ascend``,
    which both platform branches call) against the plain float64 dot:
    the same argmax on every valid row, ``p`` within 1e-12."""
    from repro.launch.experiments import seeded_surrogate
    theta, cfg = seeded_surrogate(50, seed=seed)
    rng = np.random.default_rng(seed)
    C = cfg.max_containers
    valid = np.arange(C) < 40 + 20 * seed
    with jax.enable_x64(True):
        args = (theta, jnp.asarray(rng.random((50, cfg.state_features))),
                daso.warm_start_logits(cfg, jnp.asarray(rng.integers(0, 50, C)),
                                       jnp.asarray(valid)),
                jnp.asarray(rng.integers(0, 2, C)),
                jnp.asarray(valid.astype(np.float64)))
        ascend = jax.jit(lambda dot, *a: daso._ascend(cfg, *a, dot),
                         static_argnums=0)
        p_nat, it_nat = ascend(daso._plain, *args)
        p_sl, it_sl = ascend(daso._sliced, *args)
    p_nat, p_sl = np.asarray(p_nat), np.asarray(p_sl)
    assert p_sl.dtype == np.float64 and int(it_sl) == int(it_nat) > 0
    assert (p_sl.argmax(-1) == p_nat.argmax(-1))[valid].all()
    assert np.max(np.abs(p_sl - p_nat)) <= 1e-12 * np.max(np.abs(p_nat))


def test_optimize_placement_cpu_keeps_the_plain_dot():
    """On the CPU ``platform_dependent`` lowers the plain branch only:
    no integer dot in the program, and bit-for-bit the ascent of
    ``surrogate_apply``."""
    cfg = _cfg()
    theta, _ = daso.make_trainer(cfg, jax.random.PRNGKey(3))
    with jax.enable_x64(True):
        theta = jax.tree_util.tree_map(lambda a: a.astype(jnp.float64), theta)
        state = jnp.zeros((cfg.num_workers, cfg.state_features))
        p0 = jax.random.normal(jax.random.PRNGKey(4),
                               (cfg.max_containers, cfg.num_workers))
        dec = jnp.zeros((cfg.max_containers,), jnp.int32)
        mask = jnp.ones((cfg.max_containers,))
        args = (cfg, theta, state, p0, dec, mask)
        text = daso.optimize_placement.lower(*args).as_text()
        p, _, _ = daso.optimize_placement(*args)
        p_plain, _ = jax.jit(lambda *a: daso._ascend(cfg, *a, daso._plain))(
            *args[1:])
    assert "i8" not in text
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_plain))


def test_sliced_dot_counter_per_traced_ascent():
    """Each trace of the ascent counts its 8 sliced dots (4 layers,
    forward and backward) once; float32 weights keep ``@`` and count
    none."""
    from repro.obs import RunLedger, use_ledger
    cfg = _cfg()._replace(depth=3)
    theta, _ = daso.make_trainer(cfg, jax.random.PRNGKey(0))
    state = jnp.zeros((cfg.num_workers, cfg.state_features))
    p0 = jnp.zeros((cfg.max_containers, cfg.num_workers))
    dec = jnp.zeros((cfg.max_containers,), jnp.int32)
    mask = jnp.ones((cfg.max_containers,))
    counts = []
    for x64 in (True, False):
        daso.optimize_placement.clear_cache()
        ledger = RunLedger("t")
        with jax.enable_x64(x64), use_ledger(ledger):
            th = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float64 if x64 else jnp.float32), theta)
            daso.optimize_placement.trace(cfg, th, state, p0, dec, mask)
        counts.append(ledger.counters.get("daso.sliced_dot", 0))
    assert counts == [8, 0]
