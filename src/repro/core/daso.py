"""DASO — Decision-Aware Surrogate Optimization placement module (§4.2).

An FCN surrogate f([S_t, P_t, D_t]; θ) predicts the QoS objective
O^P = O^MAB − α·AEC − β·ART (eq. 10).  It is trained with MSE (eq. 11,
AdamW) on execution traces, then the placement is found by gradient ascent
of the surrogate output w.r.t. a relaxed placement matrix (eq. 12), with
momentum/annealing as in GOBI, followed by feasibility repair.

The placement matrix is relaxed to logits (C_max × H); the simulator
consumes the row-argmax.  "Decision-aware" = the per-container split
decision one-hot is part of the surrogate input; the vanilla GOBI ablation
(M+G / S+G / L+G baselines) simply zeroes that slice.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import sliced_dot
from repro.optim.optimizers import adamw_init, adamw_update


class DASOConfig(NamedTuple):
    num_workers: int
    max_containers: int
    state_features: int          # per-worker utilization features
    hidden: int = 128
    depth: int = 3
    lr_train: float = 1e-3
    lr_place: float = 0.1
    place_iters: int = 50
    momentum: float = 0.9
    tol: float = 1e-3
    decision_aware: bool = True


def feature_size(cfg: DASOConfig) -> int:
    # worker utilization state + placement logits + split-decision one-hots
    return (cfg.num_workers * cfg.state_features
            + cfg.max_containers * cfg.num_workers
            + cfg.max_containers * 2)


def init_surrogate(key, cfg: DASOConfig):
    dims = [feature_size(cfg)] + [cfg.hidden] * cfg.depth + [1]
    ks = jax.random.split(key, len(dims) - 1)
    return [{"w": jax.random.normal(k, (a, b)) / jnp.sqrt(a),
             "b": jnp.zeros((b,))}
            for k, a, b in zip(ks, dims[:-1], dims[1:])]


def _plain(w, x_dtype):
    return lambda x: x @ w


def _sliced(w, x_dtype):
    """The TPU's ascent dot: for a float64 product, ``w`` cut once into
    integer slices for the MXU (``core/sliced_dot``); others keep ``@``."""
    if jnp.result_type(x_dtype, w.dtype) != jnp.float64:
        return _plain(w, x_dtype)
    sw = sliced_dot.slice_weight(w.astype(jnp.float64))
    return lambda x: sliced_dot.sliced_matmul(x, sw)


def _forward(theta, muls, x):
    for i, (layer, mul) in enumerate(zip(theta, muls)):
        x = mul(x) + layer["b"]
        if i < len(theta) - 1:
            x = jnp.tanh(x)
    return x[..., 0]


def surrogate_apply(theta, x):
    return _forward(theta, [_plain(layer["w"], x.dtype) for layer in theta],
                    x)


def pack_input(cfg: DASOConfig, state, placement, decisions, mask):
    """state (W, F); placement logits (C, W); decisions (C,) in {0,1};
    mask (C,) active containers."""
    d1 = jax.nn.one_hot(decisions, 2) * mask[:, None]
    p = jax.nn.softmax(placement, axis=-1) * mask[:, None]
    if not cfg.decision_aware:
        d1 = jnp.zeros_like(d1)
    return jnp.concatenate([state.reshape(-1), p.reshape(-1), d1.reshape(-1)])


# --------------------------------------------------------------- training

@functools.partial(jax.jit, static_argnums=(0,))
def train_epoch(cfg: DASOConfig, theta, opt_state, xs, ys):
    """One epoch of MSE training (eq. 11) over a batch of packed inputs."""
    def loss(theta):
        pred = surrogate_apply(theta, xs)
        return jnp.mean(jnp.square(pred - ys))

    l, g = jax.value_and_grad(loss)(theta)
    theta, opt_state = adamw_update(g, opt_state, theta, cfg.lr_train,
                                    weight_decay=0.0)
    return theta, opt_state, l


@functools.partial(jax.jit, static_argnums=(0,))
def train_epoch_weighted(cfg: DASOConfig, theta, opt_state, xs, ys, w):
    """Shape-stable variant of ``train_epoch``: ``xs``/``ys`` are padded
    to a fixed window and ``w`` masks the real rows, so the online
    finetuning loop compiles once per config instead of once per replay
    length.  With 0/1 weights the loss equals the unpadded MSE."""
    def loss(theta):
        pred = surrogate_apply(theta, xs)
        return jnp.sum(w * jnp.square(pred - ys)) / jnp.maximum(
            jnp.sum(w), 1.0)

    l, g = jax.value_and_grad(loss)(theta)
    theta, opt_state = adamw_update(g, opt_state, theta, cfg.lr_train,
                                    weight_decay=0.0)
    return theta, opt_state, l


def make_trainer(cfg: DASOConfig, key):
    theta = init_surrogate(key, cfg)
    opt_state = adamw_init(theta)
    return theta, opt_state


# ------------------------------------------------- online finetuning carry
#
# The in-kernel training loop (repro.env.jaxsim, mode="train") threads the
# DASO trainer through the jitted interval carry: a fixed REPLAY_WINDOW-row
# rolling window of (packed placement features, O^P target) pairs plus the
# (theta, AdamW opt_state) pair train_epoch_weighted advances.  Everything
# below is a pure function shared verbatim by the kernel and the host-side
# parity replay (reference.replay_trace_edgesim_trained), which is what
# makes the finetuned-theta trajectory reproducible across backends.

#: fixed replay-window rows — matches the host ``SurrogatePlacer``'s
#: shape-stable 64-row training window
REPLAY_WINDOW = 64

#: place-stage gate: ascend the surrogate only once this many interval
#: records exist (cold start keeps the warm/BestFit placement), and train
#: only once ``TRAIN_MIN`` records exist — the host placer's thresholds
PLACE_MIN, TRAIN_MIN = 32, 8


def window_init(cfg: DASOConfig, dtype=jnp.float64):
    """Empty replay window: (xs, ys, count) as a flat dict pytree."""
    return {"xs": jnp.zeros((REPLAY_WINDOW, feature_size(cfg)), dtype),
            "ys": jnp.zeros((REPLAY_WINDOW,), dtype),
            "count": jnp.zeros((), jnp.int32)}


def window_append(win, x, y):
    """Append one (x, y) record, oldest-first, dropping the oldest row
    once the window is full — the array form of the host placer's
    ``replay[-64:]`` list slice (row order is part of the shared
    contract, so both backends feed ``train_epoch_weighted`` identical
    operands)."""
    full = win["count"] >= REPLAY_WINDOW
    idx = jnp.minimum(win["count"], REPLAY_WINDOW - 1)
    xs = jnp.where(full, jnp.roll(win["xs"], -1, axis=0), win["xs"])
    ys = jnp.where(full, jnp.roll(win["ys"], -1), win["ys"])
    return {"xs": xs.at[idx].set(x.astype(xs.dtype)),
            "ys": ys.at[idx].set(y.astype(ys.dtype)),
            "count": jnp.minimum(win["count"] + 1, REPLAY_WINDOW)}


def op_objective(resp, sla, acc, fin_mask, cpu_util, interval_s: float,
                 alpha: float = 0.5, beta: float = 0.5):
    """The per-interval training target O^P = O^MAB − α·AEC − β·ART
    (eq. 10) over masked fixed-width arrays.

    ``fin_mask`` selects the tasks that finished this interval (their
    reward mean is O^MAB, their response mean feeds ART); an empty
    interval contributes O^MAB = ART = 0 exactly as the host
    ``MABDecider.interval_reward`` / ``SurrogatePlacer.feedback`` pair.
    """
    finf = fin_mask.astype(resp.dtype)
    nfin = jnp.sum(finf)
    d = jnp.maximum(nfin, 1.0)
    o_mab = jnp.sum(finf * ((resp <= sla).astype(resp.dtype) + acc))
    o_mab = jnp.where(nfin > 0, 0.5 * o_mab / d, 0.0)
    aec = jnp.mean(cpu_util)
    art = jnp.where(nfin > 0,
                    jnp.sum(finf * resp) / d / (6.0 * interval_s), 0.0)
    return o_mab - alpha * aec - beta * jnp.minimum(art, 1.0)


def finetune_window(cfg: DASOConfig, theta, opt_state, win,
                    train_steps: int = 4, train_min: int = TRAIN_MIN):
    """Advance (theta, opt_state) by ``train_steps`` weighted epochs over
    the replay window — a no-op until ``train_min`` records exist (the
    cold-start gate of the host placer's ``feedback``; ``TRAIN_MIN``
    matches its default)."""
    w = (jnp.arange(REPLAY_WINDOW) < win["count"]).astype(win["ys"].dtype)

    def train(args):
        theta, opt_state = args
        for _ in range(train_steps):
            theta, opt_state, _ = train_epoch_weighted(
                cfg, theta, opt_state, win["xs"], win["ys"], w)
        return theta, opt_state

    return jax.lax.cond(win["count"] >= train_min, train,
                        lambda args: args, (theta, opt_state))


def window_loss(cfg: DASOConfig, theta, win):
    """The weighted replay-window MSE ``train_epoch_weighted`` descends,
    evaluated without taking a step — the train engine's
    ``daso_last_loss`` telemetry column.  With an empty window every
    weight is zero and the loss is exactly 0.  Shared verbatim by the
    kernel engine and the host parity replay, so the telemetry series
    agree across backends."""
    w = (jnp.arange(REPLAY_WINDOW) < win["count"]).astype(win["ys"].dtype)
    pred = surrogate_apply(theta, win["xs"])
    return jnp.sum(w * jnp.square(pred - win["ys"])) / jnp.maximum(
        jnp.sum(w), 1.0)


# -------------------------------------------------------------- placement

@functools.partial(jax.jit, static_argnums=(0,))
def optimize_placement(cfg: DASOConfig, theta, state, placement0, decisions,
                       mask):
    """Gradient ascent of the surrogate w.r.t. placement logits (eq. 12).

    Iterates with momentum until the L2 step norm falls below tol (or
    place_iters), mirroring GOBI's converged-iteration rule.  Lowered for
    the TPU, the ascent's surrogate products run as exact integer slices
    on the MXU (``_sliced``); elsewhere as the plain float64 dot.
    """
    def ascend(dot):
        return lambda: _ascend(cfg, theta, state, placement0, decisions,
                               mask, dot)

    p, iters = jax.lax.platform_dependent(tpu=ascend(_sliced),
                                          default=ascend(_plain))
    score = surrogate_apply(theta, pack_input(cfg, state, p, decisions, mask))
    return p, score, iters


def _ascend(cfg, theta, state, placement0, decisions, mask, dot):
    """The ascent of ``optimize_placement``, with each layer's product
    ``x @ w`` taken as ``dot(w, x0.dtype)(x)``; ``dot`` prepares its
    weights once, before the loop."""
    x0 = jax.eval_shape(
        lambda: pack_input(cfg, state, placement0, decisions, mask))
    muls = [dot(layer["w"], x0.dtype) for layer in theta]

    def score(p):
        return _forward(theta, muls, pack_input(cfg, state, p, decisions,
                                                 mask))

    def cond(carry):
        p, vel, i, delta = carry
        return jnp.logical_and(i < cfg.place_iters, delta > cfg.tol)

    def body(carry):
        p, vel, i, _ = carry
        g = jax.grad(score)(p)
        vel = cfg.momentum * vel + g
        new_p = p + cfg.lr_place * vel          # ascent: maximize O^P
        delta = jnp.linalg.norm(new_p - p)
        return new_p, vel, i + 1, delta

    # a strongly typed ``inf``, as ``body`` returns: the loop traces once
    p, _, iters, _ = jax.lax.while_loop(
        cond, body, (placement0, jnp.zeros_like(placement0),
                     jnp.asarray(0), jnp.asarray(jnp.inf, placement0.dtype)))
    return p, iters


def placement_to_assignment(placement_logits, mask):
    """Row argmax -> worker index per container (-1 for inactive rows)."""
    idx = jnp.argmax(placement_logits, axis=-1)
    return jnp.where(mask.astype(bool), idx, -1)


def warm_start_logits(cfg: DASOConfig, warm_workers, row_valid):
    """(C,) warm-start worker per container row -> (C, W) logits: 2.0 at
    the warm worker of each valid row, zeros elsewhere.

    This is the shared eq.-12 initialization (iterate from the previous /
    BestFit placement) used by both the host-side parity replay and the
    in-kernel array-form DASO stage, so their ``optimize_placement``
    inputs are identical.  dtype follows the ambient default float (the
    learned-policy paths run it under ``enable_x64``).
    """
    oh = (warm_workers[:, None] == jnp.arange(cfg.num_workers)) \
        & row_valid[:, None]
    return oh * 2.0
