"""GSPMD sharding rules for all architectures on the production mesh.

Strategy (DESIGN.md §3):
  * batch              -> ('pod', 'data')           (pure DP over pods)
  * residual seq       -> 'model'                   (sequence parallelism)
  * heads / ffn hidden / experts / vocab -> 'model' (tensor / expert parallel)
  * params + optimizer state: FSDP over ('pod','data') on the largest
    non-TP dim, TP over 'model'                     (512-way for >=100B)

Divisibility-aware: a dim is sharded over an axis group only if it divides
evenly (e.g. musicgen's 24 heads and qwen2-vl's 28 heads skip head-TP and
keep MLP-TP + FSDP; the head-TP gap is a documented §Perf item).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import batch_axes


def _axes_size(mesh, axes):
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= mesh.shape[a]
    return n


def _fit(mesh, dim_size, axes):
    """axes if dim divides evenly else None."""
    if axes is None or dim_size <= 0:
        return None
    if dim_size % _axes_size(mesh, axes) == 0:
        return axes
    return None


def _path_str(path):
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return "/".join(out)


def param_pspec(mesh, cfg, path, leaf) -> P:
    """PartitionSpec for one parameter leaf, by name and shape."""
    name = _path_str(path)
    shape = leaf.shape
    fsdp = batch_axes(mesh)
    tp = "model"
    nd = len(shape)
    # stacked scan body adds a leading periods dim
    lead = 1 if name.startswith("body/") and nd >= 1 else 0

    def spec(*dims):
        return P(*([None] * lead + list(dims) + [None] * (nd - lead - len(dims))))

    base = name.split("/")[-1]
    d = shape[lead:] if lead else shape

    if base in ("embed",):
        if cfg.num_codebooks:
            return spec(None, _fit(mesh, d[1], tp), _fit(mesh, d[2], fsdp))
        return spec(_fit(mesh, d[0], tp), _fit(mesh, d[1], fsdp))
    if base in ("head",):
        if cfg.num_codebooks:
            return spec(None, _fit(mesh, d[1], fsdp), _fit(mesh, d[2], tp))
        return spec(_fit(mesh, d[0], fsdp), _fit(mesh, d[1], tp))
    if nd - lead <= 1:  # norms, 1D biases, Lambda, D, dt_bias, conv_b
        return spec(_fit(mesh, d[0], tp) if base in ("Lambda", "D", "conv_b", "b_a", "b_i", "dt_bias") else None)

    if base in ("wq", "wk", "wv", "wq_b", "wkv_b"):     # (d or rank, heads, e)
        heads = d[1]
        if _fit(mesh, heads, tp):
            return spec(_fit(mesh, d[0], fsdp), tp, None)
        return spec(_fit(mesh, d[0], fsdp), None, None)
    if base in ("bq", "bk", "bv"):
        return spec(_fit(mesh, d[0], tp), None)
    if base == "wo":
        heads = d[0]
        if _fit(mesh, heads, tp):
            return spec(tp, None, _fit(mesh, d[2], fsdp))
        return spec(None, None, _fit(mesh, d[2], fsdp))
    if base in ("w_up", "w_gate") and nd - lead == 2:       # dense MLP
        return spec(_fit(mesh, d[0], fsdp), _fit(mesh, d[1], tp))
    if base == "w_down" and nd - lead == 2:
        return spec(_fit(mesh, d[0], tp), _fit(mesh, d[1], fsdp))
    if base in ("wq_a", "wkv_a"):             # MLA latent down-projections
        return spec(_fit(mesh, d[0], fsdp), None)
    if base == "router":
        return spec(_fit(mesh, d[0], fsdp), None)
    if base == "shared_gate":
        return spec(_fit(mesh, d[0], fsdp), None)
    if base in ("w_up", "w_gate", "w_down") and nd - lead == 3:  # MoE experts
        E = d[0]
        if _fit(mesh, E, tp):                                # expert parallel
            return spec(tp, _fit(mesh, d[1], fsdp), None)
        if base == "w_down":                                 # TP inside expert
            return spec(None, _fit(mesh, d[1], tp), _fit(mesh, d[2], fsdp))
        return spec(None, _fit(mesh, d[1], fsdp), _fit(mesh, d[2], tp))
    # mamba
    if base == "in_proj":
        return spec(_fit(mesh, d[0], fsdp), _fit(mesh, d[1], tp))
    if base == "conv_w":
        return spec(None, _fit(mesh, d[1], tp))
    if base == "x_proj":
        return spec(_fit(mesh, d[0], tp), None)
    if base == "dt_proj":
        return spec(None, _fit(mesh, d[1], tp))
    if base == "A_log":
        return spec(_fit(mesh, d[0], tp), None)
    if base == "out_proj":
        return spec(_fit(mesh, d[0], tp), _fit(mesh, d[1], fsdp))
    # rglru
    if base in ("in_x", "in_gate"):
        return spec(_fit(mesh, d[0], fsdp), _fit(mesh, d[1], tp))
    if base in ("w_a", "w_i"):                    # block-diag (gb, bw, bw)
        return spec(_fit(mesh, d[0], tp), None, None)
    if base == "out":
        return spec(_fit(mesh, d[0], tp), _fit(mesh, d[1], fsdp))
    # fallback: FSDP on the largest dim
    big = max(range(nd - lead), key=lambda i: d[i])
    dims = [None] * (nd - lead)
    dims[big] = _fit(mesh, d[big], fsdp)
    return spec(*dims)


def params_shardings(mesh, cfg, params_shape):
    """Pytree of NamedShardings matching a params eval_shape tree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_pspec(mesh, cfg, path, leaf)),
        params_shape)


def opt_state_shardings(mesh, cfg, opt_shape, params_shape):
    """Optimizer-state shardings mirror the parameter shardings (ZeRO-style:
    m/v/vr/vc inherit the param pspec where shapes match, else replicate
    scalars / reduced dims)."""
    pspecs = jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_pspec(mesh, cfg, path, leaf), params_shape)

    flat_p, _ = jax.tree_util.tree_flatten(params_shape)
    flat_spec, _ = jax.tree_util.tree_flatten(pspecs,
                                              is_leaf=lambda x: isinstance(x, P))

    by_shape = {}
    for leaf, sp in zip(flat_p, flat_spec):
        by_shape.setdefault(leaf.shape, sp)

    def match(leaf):
        if leaf.shape in by_shape:
            return NamedSharding(mesh, by_shape[leaf.shape])
        # factored adafactor stats: drop trailing dims from a matching param
        for shape, sp in by_shape.items():
            for cut in (1, 2):
                if leaf.shape == shape[:-cut]:
                    return NamedSharding(mesh, P(*sp[:len(leaf.shape)]))
            if len(leaf.shape) == len(shape) and all(
                    a == b or a == 1 for a, b in zip(leaf.shape, shape)):
                sp2 = [s if a == b else None
                       for s, a, b in zip(sp, leaf.shape, shape)]
                return NamedSharding(mesh, P(*sp2))
        # vc with shape[:-2] + shape[-1:]
        for shape, sp in by_shape.items():
            if len(shape) >= 2 and leaf.shape == shape[:-2] + shape[-1:]:
                return NamedSharding(mesh, P(*(list(sp[:-2]) + [sp[-1]])))
        return NamedSharding(mesh, P())

    return jax.tree.map(match, opt_shape)


# --------------------------------------------------- activation constraints

def make_constrain(mesh, cfg):
    """with_sharding_constraint hook threaded through the model (ctx hook)."""
    ba = batch_axes(mesh)

    def constrain(x, kind):
        if x.ndim < 2:
            return x
        dims = [None] * x.ndim
        if kind == "residual":                        # (b, s, d)
            dims[0] = _fit(mesh, x.shape[0], ba)
            if x.ndim == 3:
                dims[1] = _fit(mesh, x.shape[1], "model")
        elif kind in ("ffn_hidden", "ssm_inner", "rnn_inner"):  # (b, s, f)
            dims[0] = _fit(mesh, x.shape[0], ba)
            dims[-1] = _fit(mesh, x.shape[-1], "model")
        elif kind == "logits":                        # (b, s, [cb,] V)
            dims[0] = _fit(mesh, x.shape[0], ba)
            dims[-1] = _fit(mesh, x.shape[-1], "model")
        elif kind == "moe_group":                     # (G, gs, d)
            dims[0] = _fit(mesh, x.shape[0], ba)
        elif kind == "moe_buffer":                    # (G, E*C+1, d)
            dims[0] = _fit(mesh, x.shape[0], ba)
            dims[-1] = _fit(mesh, x.shape[-1], "model")
        elif kind == "moe_expert":                    # (G, E, C, d)
            off = x.ndim - 4
            if off >= 0:
                dims[off] = _fit(mesh, x.shape[off], ba)
            dims[off + 1] = _fit(mesh, x.shape[off + 1], "model")
            if dims[off + 1] is None:
                dims[-1] = _fit(mesh, x.shape[-1], "model")
        else:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*dims)))

    return constrain


def batch_shardings(mesh, batch_shape_tree):
    """Inputs: shard dim0 over batch axes, dim1 (seq) unsharded (the
    residual-stream constraint re-shards inside the model)."""
    ba = batch_axes(mesh)

    def one(leaf):
        dims = [None] * len(leaf.shape)
        if len(leaf.shape) >= 1:
            dims[0] = _fit(mesh, leaf.shape[0], ba)
        return NamedSharding(mesh, P(*dims))

    return jax.tree.map(one, batch_shape_tree)


def cache_shardings(mesh, cfg, cache_shape_tree):
    """KV caches: batch over ('pod','data'), cache length over 'model'
    (sequence-sharded KV); SSM/RNN states: inner dim over 'model'."""
    ba = batch_axes(mesh)

    def one(path, leaf):
        name = _path_str(path)
        dims = [None] * len(leaf.shape)
        nd = len(leaf.shape)
        if name.endswith("k") or name.endswith("v"):
            # (layers?, b, W, kvh, hd)
            off = nd - 4
            dims[off] = _fit(mesh, leaf.shape[off], ba)
            dims[off + 1] = _fit(mesh, leaf.shape[off + 1], "model")
        elif name.endswith("h"):
            off = 1 if nd in (3, 4) and leaf.shape[0] != leaf.shape[-1] and nd > 2 else 0
            # mamba h (layers?, b, d_in, n); rglru h (layers?, b, w)
            dims[-2 if nd >= 3 else -1] = _fit(mesh, leaf.shape[-2 if nd >= 3 else -1], "model")
            b_dim = nd - (3 if nd >= 3 else 2)
            dims[b_dim] = _fit(mesh, leaf.shape[b_dim], ba)
        elif name.endswith("conv"):
            # (layers?, b, k-1, d)
            dims[-1] = _fit(mesh, leaf.shape[-1], "model")
            dims[len(leaf.shape) - 3] = _fit(mesh, leaf.shape[-3], ba)
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(one, cache_shape_tree)
