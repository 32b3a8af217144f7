"""SplitPlace's learned inputs, made by the benchmark from the seed and
the traffic file: the MAB state and the DASO surrogate's weights.  Both
the system and the reference get the same host copies."""
from __future__ import annotations

import functools

import numpy as np

from bench.ref import policy

MAB_FIELDS = ("Q", "N", "R", "eps", "rho", "t")


def mab_state(spec) -> tuple:
    """Host arrays in ``MABState`` field order (float32; ``t`` int32)."""
    return tuple(np.asarray(spec[k], np.int32 if k == "t" else np.float32)
                 for k in MAB_FIELDS)


def surrogate(jax, seed: int, num_workers: int, spec):
    """(theta as host arrays, the reference's ``DASOConfig``): random
    weights from ``seed``, made on the default device in one jitted
    call, at the host placer's sizes that ``spec`` gives."""
    cfg = policy.DASOConfig(num_workers=num_workers,
                            max_containers=spec["max_containers"],
                            state_features=spec["state_features"])
    init = jax.jit(functools.partial(policy.init_surrogate, cfg=cfg))
    theta = init(jax.random.PRNGKey(seed))
    return jax.tree_util.tree_map(np.asarray, theta), cfg
