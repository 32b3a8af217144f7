"""Production mesh definitions (TPU v5e pods).

Defined as functions, never module-level constants, so importing this
module does not touch jax device state.
"""
from __future__ import annotations

import jax

#: published per-chip peaks keyed by ``device_kind`` (Google Cloud
#: documentation, "TPU v5e"): bf16 FLOP/s, HBM bytes/s, ICI bytes/s per
#: link.  Only the dry-run's described v5e pod and the plan engine's
#: napkin cost model read them; a kind missing here is an error, never
#: a v5e default.
CHIP_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
#: the chip the production mesh describes
PRODUCTION_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; raises for a kind that
    has no published entry."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r} (known: {sorted(CHIP_PEAKS)})"
                         ) from None


def make_production_mesh(*, multi_pod: bool = False):
    """The 16x16 (or 2x16x16) pod mesh of the dry-run.  Its axes are
    Auto: ``launch/sharding`` places activations with
    ``with_sharding_constraint``, which only refers to Auto axes."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_grid_mesh(devices="auto"):
    """1-D device mesh over the simulator's stacked-trace grid axis.

    ``devices="auto"`` (or ``None``) takes every visible device; an
    integer takes the first ``devices`` of them.  The single axis is
    named ``"grid"`` — ``env/jaxsim/driver`` shard_maps the vmapped
    interval program over it, one contiguous slice of grid cells per
    device (cells are embarrassingly parallel, so a 1-D mesh is the
    whole story; there is no model axis to cut)."""
    avail = jax.devices()
    n = len(avail) if devices in ("auto", None) else int(devices)
    if not 1 <= n <= len(avail):
        raise ValueError(f"devices={devices!r}: need 1..{len(avail)} "
                         f"(visible: {len(avail)})")
    import numpy as np
    return jax.sharding.Mesh(np.asarray(avail[:n]), ("grid",))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def num_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
