"""Plain copies of the SplitPlace decision and placement functions
(arXiv 2205.10635 §4): the UCB multi-armed bandit over deadline contexts
with its end-of-interval feedback, and the DASO surrogate ascent.
They run on the CPU under 64-bit JAX, padded to fixed widths so that a
run compiles each of them a few times only.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGH, LOW = 0, 1
#: (ucb_c, phi, gamma, k_rbed), the system's deploy hyperparameters
MAB_HP = (0.5, 0.3, 0.3, 0.1)


class MABState(NamedTuple):
    Q: jnp.ndarray
    N: jnp.ndarray
    R: jnp.ndarray
    eps: jnp.ndarray
    rho: jnp.ndarray
    t: jnp.ndarray


class DASOConfig(NamedTuple):
    num_workers: int
    max_containers: int
    state_features: int
    hidden: int = 128
    depth: int = 3
    lr_train: float = 1e-3
    lr_place: float = 0.1
    place_iters: int = 50
    momentum: float = 0.9
    tol: float = 1e-3
    decision_aware: bool = True


def feature_size(cfg):
    return (cfg.num_workers * cfg.state_features
            + cfg.max_containers * cfg.num_workers + cfg.max_containers * 2)


def init_surrogate(key, cfg):
    """Layers of the surrogate MLP, normal weights scaled by fan-in."""
    dims = [feature_size(cfg)] + [cfg.hidden] * cfg.depth + [1]
    ks = jax.random.split(key, len(dims) - 1)
    return [{"w": jax.random.normal(k, (a, b)) / jnp.sqrt(a),
             "b": jnp.zeros((b,))}
            for k, a, b in zip(ks, dims[:-1], dims[1:])]


def surrogate_apply(theta, x):
    for i, layer in enumerate(theta):
        x = x @ layer["w"] + layer["b"]
        if i < len(theta) - 1:
            x = jnp.tanh(x)
    return x[..., 0]


def pack_input(cfg, state, placement, decisions, mask):
    d1 = jax.nn.one_hot(decisions, 2) * mask[:, None]
    p = jax.nn.softmax(placement, axis=-1) * mask[:, None]
    if not cfg.decision_aware:
        d1 = jnp.zeros_like(d1)
    return jnp.concatenate([state.reshape(-1), p.reshape(-1), d1.reshape(-1)])


@functools.partial(jax.jit, static_argnums=(0, 6))
def daso_assign(cfg, theta, state, warm_workers, row_valid, decisions,
                dtype=jnp.float64):
    """Eq. 12: ascend the surrogate from the warm placement with
    momentum until the step norm falls below ``tol``; row argmax.  The
    placement logits and the worker state are in ``dtype``."""
    p0 = (((warm_workers[:, None] == jnp.arange(cfg.num_workers))
           & row_valid[:, None]) * 2.0).astype(dtype)
    mask = row_valid.astype(dtype)
    state = state.astype(dtype)

    def score(p):
        return surrogate_apply(theta, pack_input(cfg, state, p, decisions,
                                                 mask))

    def cond(c):
        return jnp.logical_and(c[2] < cfg.place_iters, c[3] > cfg.tol)

    def body(c):
        p, vel, i, _ = c
        vel = cfg.momentum * vel + jax.grad(score)(p)
        new_p = p + cfg.lr_place * vel
        return new_p, vel, i + 1, jnp.linalg.norm(new_p - p)

    p, _, _, _ = jax.lax.while_loop(
        cond, body, (p0, jnp.zeros_like(p0), jnp.asarray(0),
                     jnp.asarray(jnp.inf)))
    return jnp.argmax(p, axis=-1)


@functools.partial(jax.jit, static_argnums=(3,))
def mab_decide(state, sla, app, c):
    """Eq. 9, per row: the arm of largest Q + UCB bonus in the row's
    deadline context."""
    def one(s, a):
        ctx = jnp.where(s >= state.R[a], HIGH, LOW).astype(jnp.int32)
        bonus = c * jnp.sqrt(jnp.log(jnp.maximum(
            state.t.astype(jnp.float32), 2.0)) / jnp.maximum(state.N[ctx],
                                                             1.0))
        return jnp.argmax(state.Q[ctx] + bonus).astype(jnp.int32)
    return jax.vmap(one)(sla, app)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def mab_feedback(state, apps, sla, resp, acc, decisions, mask, phi, gamma,
                 k):
    """Algorithm 1's end of interval over masked rows: the EMA of the
    layer-split response per app (eq. 2), per (context, arm) rewards
    (eqs. 3-4), the Q step (eq. 5) and the RBED decay (eqs. 7-8)."""
    def ema(R, inp):
        a, r, w = inp
        new = phi * r + (1.0 - phi) * R[a]
        return R.at[a].set(jnp.where(w, new, R[a])), None

    R, _ = jax.lax.scan(ema, state.R, (apps, resp, mask & (decisions == 0)))
    state = state._replace(R=R)
    ctx = jnp.where(sla >= state.R[apps], HIGH, LOW)
    per_task = 0.5 * ((resp <= sla).astype(jnp.float32) + acc)
    w = (mask[:, None, None]
         & (ctx[:, None] == jnp.arange(2))[:, :, None]
         & (decisions[:, None] == jnp.arange(2))[:, None, :]) * 1.0
    cnt = jnp.sum(w, axis=0)
    O = jnp.sum(w * per_task[:, None, None], axis=0)
    O = jnp.where(cnt > 0, O / jnp.maximum(cnt, 1.0), 0.0).astype(jnp.float32)
    cnt = cnt.astype(jnp.float32)
    Q = jnp.where(cnt > 0, state.Q + gamma * (O - state.Q), state.Q)
    have = cnt > 0
    o_mab = jnp.where(jnp.any(have), jnp.sum(jnp.where(have, O, 0.0))
                      / jnp.maximum(have.sum(), 1), 0.0)
    up = o_mab > state.rho
    return state._replace(
        Q=Q, N=state.N + cnt,
        eps=jnp.where(up, (1.0 - k) * state.eps, state.eps),
        rho=jnp.where(up, (1.0 + k) * state.rho, state.rho),
        t=state.t + 1)


def pad_to(x, width, fill=0):
    x = np.asarray(x)
    out = np.full((width,) + x.shape[1:], fill, x.dtype)
    out[:len(x)] = x
    return out


def width(k, least=8):
    """Padded width for k rows: the next power of two, at least ``least``."""
    return max(least, 1 << max(0, int(k - 1).bit_length()))
