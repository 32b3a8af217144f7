"""Jit'd public wrappers for the Pallas kernels.

On TPU the compiled kernels run natively; on the CPU they run
in interpret mode so every call is still exercised end-to-end.  Callers use
these entry points; models fall back to the jnp twins for SPMD tracing
(Pallas-TPU ops do not lower on the CPU dry-run backend).
"""
from __future__ import annotations

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.moe_route import moe_route as _route
from repro.kernels.rglru_scan import rglru_scan as _rglru
from repro.kernels.selective_scan import selective_scan as _scan


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, causal=True, window=0, q_block=128,
                    kv_block=128):
    return _flash(q, k, v, causal=causal, window=window, q_block=q_block,
                  kv_block=kv_block, interpret=_interpret())


def selective_scan(dA, dBx, C, chunk=128, d_block=128):
    return _scan(dA, dBx, C, chunk=chunk, d_block=d_block,
                 interpret=_interpret())


def rglru_scan(a, bx, chunk=128, w_block=512):
    return _rglru(a, bx, chunk=chunk, w_block=w_block,
                  interpret=_interpret())


def moe_route(logits, top_k, block=256):
    return _route(logits, top_k, block=block, interpret=_interpret())
