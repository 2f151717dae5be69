"""Sharding rules: params → PartitionSpec trees, activation constraints.

Logical layout (single pod): mesh ("data", "model"); multi-pod adds a
leading "pod" axis that joins the data-parallel group.

Conventions:
  * column-parallel (D → X) weights shard their OUTPUT dim over "model";
  * row-parallel (X → D) weights shard their INPUT dim over "model";
  * expert-stacked weights shard the EXPERT dim over "model" (EP);
  * embed shards vocab over "model";
  * a dim is only sharded if the axis size divides it — otherwise that
    dim falls back to replicated (robust across the 10 archs whose head
    counts/vocab don't all divide 16).

Activation constraints are applied through ``constrain`` which is a no-op
outside a mesh context — model code is mesh-agnostic.
"""
from __future__ import annotations

import contextlib
import re
import threading

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ctx = threading.local()


def current_mesh() -> Mesh | None:
    return getattr(_ctx, "mesh", None)


def data_axes() -> tuple[str, ...]:
    mesh = current_mesh()
    if mesh is None:
        return ("data",)
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    prev = getattr(_ctx, "mesh", None)
    _ctx.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _ctx.mesh = prev


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    return n


def fit_spec(shape, spec: P, mesh: Mesh | None = None) -> P:
    """Drop sharding on dims the mesh axis doesn't divide evenly."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return spec
    out = []
    for dim, axes in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axes is None:
            out.append(None)
            continue
        axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
        axes_t = tuple(a for a in axes_t if a in mesh.axis_names)
        if axes_t and dim % _axis_size(mesh, axes_t) == 0:
            out.append(axes_t if len(axes_t) > 1 else axes_t[0])
        else:
            out.append(None)
    return P(*out)


def constrain(x, *axes):
    """with_sharding_constraint by per-dim axis names; no-op w/o mesh.
    Use "batch" as sugar for the (pod,)data axes."""
    mesh = current_mesh()
    if mesh is None or x.ndim != len(axes):
        return x
    named = tuple(data_axes() if a == "batch" else a for a in axes)
    spec = fit_spec(x.shape, P(*named), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ------------------------------------------------------------ param rules
# matched against the '/'-joined param path, first hit wins.
_PARAM_RULES: list[tuple[str, P]] = [
    (r"(^|/)embed$", P("model", None)),
    (r"(^|/)unembed$", P(None, "model")),
    # MoE expert-stacked (E, D, F) / (E, F, D): expert-parallel
    (r"moe/w_(gate|up|down)$", P("model", None, None)),
    (r"moe/router$", P()),
    # column-parallel
    (r"(^|/)(wq|wk|wv|wg|w_gate|w_up|in_proj|w_mix1|w_dec1|fuse)$", P(None, "model")),
    (r"cross/(wq|wk|wv)$", P(None, "model")),
    (r"channel_mix/wk$", P(None, "model")),
    # row-parallel
    (r"(^|/)(wo|w_down|out_proj|w_dec2)$", P("model", None)),
    (r"channel_mix/wv$", P("model", None)),
    # rwkv mix lora second factor (5, r, D): replicate
    (r"w_mix2$", P()),
    # conv (W, C): shard channels
    (r"conv_w$", P(None, "model")),
]


def param_pspec(path: str, leaf, *, scan_dims: int = 0) -> P:
    """PartitionSpec for one param; `scan_dims` leading stacked dims get None."""
    spec = P()
    for pat, s in _PARAM_RULES:
        if re.search(pat, path):
            spec = s
            break
    return P(*((None,) * scan_dims + tuple(spec)))


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


_MOE_TP_RULES = [
    (r"moe/w_(gate|up)$", P(None, None, "model")),   # (E, D, F): F-sharded
    (r"moe/w_down$", P(None, "model", None)),        # (E, F, D)
]


def params_pspecs(params, num_layers_hint: int | None = None,
                  moe_tp: bool = False):
    """PartitionSpec pytree for a param pytree. Stacked layer params are
    recognised by path containing 'layers' / 'mamba' / 'groups' — their
    leading scan dim(s) stay unsharded (ZeRO shards them instead).
    ``moe_tp`` switches expert weights from expert-parallel to the
    F-sharded tensor-parallel layout (models.moe.moe_ffn_tp)."""

    def spec_for(path, leaf):
        ps = _path_str(path)
        scan_dims = 0
        if re.search(r"(^|/)(layers|enc_layers|mamba|groups)(/|$)", ps):
            scan_dims = 2 if re.search(r"(^|/)mamba(/|$)", ps) else 1
        if moe_tp:
            for pat, sp in _MOE_TP_RULES:
                if re.search(pat, ps):
                    return P(*((None,) * scan_dims + tuple(sp)))
        return param_pspec(ps, leaf, scan_dims=scan_dims)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def zero1_pspec(spec: P, shape, mesh: Mesh) -> P:
    """ZeRO-1: additionally shard optimizer state over the data axes on
    the first unsharded dim that divides evenly."""
    daxes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    n = _axis_size(mesh, daxes)
    dims = tuple(spec) + (None,) * (len(shape) - len(spec))
    out = list(dims)
    for i, (d, s) in enumerate(zip(shape, dims)):
        if s is None and d % n == 0 and d >= n:
            out[i] = daxes if len(daxes) > 1 else daxes[0]
            break
    return P(*out)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_named(mesh: Mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
