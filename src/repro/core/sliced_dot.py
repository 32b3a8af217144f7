"""Exact float64 products on integer units: the Ozaki scheme.

The TPU has no float64 unit: XLA keeps a float64 as a pair of float32
and emulates every float64 ``dot_general`` on the vector unit, limb by
limb, re-splitting a loop-invariant operand on every trip of the loop
around it.  Here a float64 ``x @ w`` is computed instead as int8
products, which the MXU runs natively:

* each operand is cut into ``SLICES`` signed 7-bit integer slices under
  a power-of-two scale, ``v = 2^e * sum_j 2^(-7j) A_j``: the operand,
  scaled to ``|v| 2^(63 - e) < 2^62``, is converted to an int64 and its
  bits are cut seven at a time.  A vector gets one scale; a weight gets
  one per output column for ``x @ w`` and one per row for the backward
  ``g @ w.T`` (``slice_weight``, done once per weight);
* one ``int8 x int8 -> int32`` dot forms, for each ``d = i + j`` with
  ``2 <= d <= SLICES + 2``, the sum over ``k`` and over the slice pairs
  ``(i, j)`` of ``B_i[k] * A_j[k, n]`` (the vector's slices laid out
  block-Toeplitz against the weight's stacked slices);
* the ``SLICES + 1`` int32 sums are converted to float64, scaled by
  their powers of two and added smallest first.

Exactness contract: every slice product and every int32 accumulation is
exact.  A dot of contraction length ``K`` sums ``SLICES * K`` products
of magnitude at most ``127**2``, so ``SLICES * K * 127**2 < 2**31``
(``K`` up to 14 792 at nine slices; ``_product`` refuses more).  The
only roundings are the truncation of each operand to 63 bits below its
scale, the slice pairs left out (``i + j > SLICES + 2``) and the final
float64 sum: each
result lies within about a float64 ulp of ``sum_k |x_k| |w_kn|``, as a
float64 dot does (``tests/test_daso_and_optim.py`` holds it to 2).  The
scales come from the float32 rounding of the operand, so they cover
magnitudes from 2^-124 to 2^124, as the TPU's float64 (a float32 pair)
does.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs import get_ledger

#: 7-bit slices per float64 operand (63 bits against float64's 53)
SLICES = 9
_BITS = 7
_INT32_MAX = 2**31 - 1
_ROWS = SLICES + 1              # slice pairs i + j <= SLICES + 2
_ROW_SCALES = 2.0 ** (-_BITS * np.arange(_ROWS + 1, 1, -1))[:, None]


def _pow2(e):
    """Exact float64 ``2.0 ** e`` for integer ``e`` in [-1022, 1023]."""
    return lax.bitcast_convert_type((e.astype(jnp.int64) + 1023) << 52,
                                    jnp.float64)


def _split(v, axis):
    """(slices int8 ``(SLICES, *v.shape)``, scale exponent ``e`` with
    ``axis`` reduced): ``v = 2^e * sum_j 2^(-7j) slices[j-1]`` up to the
    bits below ``2^(e - 63)``, which are truncated."""
    hi = lax.bitcast_convert_type(v.astype(jnp.float32), jnp.int32)
    top = jnp.max(jnp.maximum((hi >> 23) & 0xFF, 1), axis=axis, keepdims=True)
    e = jnp.clip(top - 125, -124, 124)          # |v| < 2^(e - 1)
    t = (v * _pow2(-e) * 2.0**63).astype(jnp.int64)
    shift = (_BITS * jnp.arange(SLICES - 1, -1, -1)).reshape(
        (SLICES,) + (1,) * v.ndim)
    q = ((jnp.abs(t) >> shift) & 127).astype(jnp.int8)
    return jnp.where(t < 0, -q, q), jnp.squeeze(e, axis)


class SlicedWeight(NamedTuple):
    """A float64 ``(K, N)`` weight cut once for ``sliced_matmul``."""
    fwd: jax.Array       # (SLICES*K, N) int8, per-column scales
    col: jax.Array       # (N,) scale exponents
    bwd: jax.Array       # (SLICES*N, K) int8, per-row scales, transposed
    row: jax.Array       # (K,) scale exponents


def slice_weight(w) -> SlicedWeight:
    """Cut ``w`` (float64, ``(K, N)``) into the slices of both directions."""
    K, N = w.shape
    cols, col = _split(w, 0)                      # (S, K, N), (N,)
    rows, row = _split(w, 1)                      # (S, K, N), (K,)
    # the barrier keeps XLA from sinking the cut into a loop that reads it
    return SlicedWeight(*lax.optimization_barrier((
        cols.reshape(SLICES * K, N), col,
        rows.transpose(0, 2, 1).reshape(SLICES * N, K), row)))


def _product(v, rhs, rhs_exp):
    """``v @ W`` for ``v`` float64 ``(..., K)`` and ``W`` given as its
    stacked slices ``rhs`` ``(SLICES*K, N)`` with column exponents."""
    get_ledger().count("daso.sliced_dot")
    K = v.shape[-1]
    if SLICES * K * 127**2 > _INT32_MAX:
        raise ValueError(f"contraction of {K} overflows the int32 sums")
    sl, ve = _split(v.astype(jnp.float64), -1)     # (S, ..., K), (...)
    # row r (d = r + 2) takes slice r - j of v against slice j of W
    pad = jnp.concatenate([jnp.zeros_like(sl[:SLICES - 1]), sl,
                           jnp.zeros_like(sl[:_ROWS - SLICES])])
    lhs = jnp.stack([pad[SLICES - 1 - j:SLICES - 1 - j + _ROWS]
                     for j in range(SLICES)], axis=1)  # (R, S, ..., K)
    lhs = jnp.moveaxis(lhs, (0, 1), (-3, -2))
    lhs = lhs.reshape(lhs.shape[:-3] + (_ROWS, SLICES * K))
    out = jnp.dot(lhs, rhs, preferred_element_type=jnp.int32)  # (..., R, N)
    # row r weighs 2^(-7(r + 2)); reversed, the smallest terms come first
    acc = jnp.sum(out[..., ::-1, :].astype(jnp.float64) * _ROW_SCALES, axis=-2)
    return acc * _pow2(rhs_exp) * _pow2(ve)[..., None]


@jax.custom_vjp
def sliced_matmul(x, sw: SlicedWeight):
    """float64 ``x @ w`` (``x`` ``(..., K)``) from ``sw = slice_weight(w)``;
    its VJP with respect to ``x`` is ``g @ w.T`` from the same cut."""
    return _product(x, sw.fwd, sw.col)


def _fwd(x, sw):
    return _product(x, sw.fwd, sw.col), sw


def _bwd(sw, g):
    return _product(g, sw.bwd, sw.row), None


sliced_matmul.defvjp(_fwd, _bwd)
