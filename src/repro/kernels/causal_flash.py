"""Pallas TPU causal flash attention for prefill, with a value head dim of
its own (MLA: queries and keys of 192, values of 128).

  * The grid walks, for each (batch, head), only the (query block, key
    block) pairs on or below the diagonal: a scalar-prefetched table
    gives each step's pair, so a key block wholly above the diagonal is
    neither fetched nor computed.  Only the diagonal blocks are masked,
    by index (query row i sees keys 0..i).
  * q·kᵀ and p·v take their operands in the inputs' dtype (bf16 on the
    chip) into the MXU with float32 accumulation; the running max,
    denominator and accumulator stay float32 in VMEM scratch, and the
    score block never leaves VMEM.
  * Everything is heads-major, (b, h, s, .): the layout XLA gives the
    projections' outputs and wants for the output projection's input.
    Values are read as the ``v_col``-th ``hv``-wide column block of their
    operand's last axis, so that a fused key-value projection (MLA's
    ``wkv_b`` output, no-RoPE keys then values) is read in place.

The caller scales the queries (the softmax scale is not applied here).
Validated in interpret mode against ``models.attention.full_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
_NT = (((1,), (1,)), ((), ()))          # a (m, e) x (n, e) -> (m, n)
_NN = (((1,), (0,)), ((), ()))          # a (m, n) x (n, e) -> (m, e)


def block_size(s: int) -> int:
    """The query and key block for a sequence of ``s``: 1024 where the
    length allows, else the length rounded up to a multiple of 128.  On
    a v5e, at 4 x 64 heads of 1-4k tokens, 1024 was the fastest of the
    query x key blocks from 256 x 256 to 1024 x 1024 (PERF.md, PR 16)."""
    return min(1024, -(-s // LANES) * LANES)


def block_pairs(n: int):
    """(query block, key block) index arrays of the n x n blocks on or
    below the diagonal, row by row, key blocks ascending."""
    qi, kj = np.tril_indices(n)
    return qi.astype(np.int32), kj.astype(np.int32)


def _kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, blk):
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked):
        s = lax.dot_general(q_ref[...], k_ref[...], _NT,
                            preferred_element_type=jnp.float32)
        if masked:
            row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col <= row, s, NEG_INF)
        m_prev = m_ref[...]                       # (blk, 128), lanes alike
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_next[:, :1])
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_next
        pv = lax.dot_general(p.astype(v_ref.dtype), v_ref[...], _NN,
                             preferred_element_type=jnp.float32)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + pv

    # the diagonal block (the only one that can hold padded keys) is the
    # last of its row
    pl.when(j < i)(lambda: update(False))

    @pl.when(j == i)
    def _finish():
        update(True)
        o_ref[...] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


# jitted: traced once per shape, so that every program calling it embeds
# one kernel (a Mosaic body carries the source locations of its trace)
@functools.partial(jax.jit, static_argnames=("hv", "v_col", "interpret"))
def causal_flash_attention(q, k, v, hv=None, v_col=0, interpret=False):
    """Causal attention of query i over keys 0..i, by index.

    q, k (b, h, s, e); v (b, h, s, W) holding the values in columns
    [v_col * hv, (v_col + 1) * hv) (``hv`` defaults to W; on the chip a
    multiple of 128 where it is not W) -> (b, h, s, hv) in q's dtype.
    The length is padded at the end to a multiple of ``block_size(s)``.
    """
    b, h, s, e = q.shape
    hv = v.shape[-1] if hv is None else hv
    blk = block_size(s)
    sp = -(-s // blk) * blk
    if sp != s:
        pad = ((0, 0), (0, 0), (0, sp - s), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    qi, kj = block_pairs(sp // blk)
    pairs = len(qi)
    out = pl.pallas_call(
        functools.partial(_kernel, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, pairs),
            in_specs=[
                pl.BlockSpec((None, None, blk, e),
                             lambda bb, hh, t, qi, kj: (bb, hh, qi[t], 0)),
                pl.BlockSpec((None, None, blk, e),
                             lambda bb, hh, t, qi, kj: (bb, hh, kj[t], 0)),
                pl.BlockSpec((None, None, blk, hv),
                             lambda bb, hh, t, qi, kj: (bb, hh, kj[t],
                                                        v_col)),
            ],
            out_specs=pl.BlockSpec(
                (None, None, blk, hv),
                lambda bb, hh, t, qi, kj: (bb, hh, qi[t], 0)),
            scratch_shapes=[pltpu.VMEM((blk, LANES), jnp.float32),
                            pltpu.VMEM((blk, LANES), jnp.float32),
                            pltpu.VMEM((blk, hv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, sp, hv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * pairs * blk * blk * (e + hv),
            transcendentals=b * h * pairs * blk * blk,
            bytes_accessed=(q.size + k.size) * q.dtype.itemsize
            + b * h * sp * hv * (v.dtype.itemsize + q.dtype.itemsize)),
        name="causal_flash",
        interpret=interpret,
    )(jnp.asarray(qi), jnp.asarray(kj), q, k, v)
    return out[:, :, :s]
