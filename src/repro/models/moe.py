"""Mixture-of-Experts FFN: top-k routing with sort-based capacity dispatch.

We deliberately avoid the GShard one-hot dispatch einsum — at the assigned
shapes its FLOPs (T·E·C·D) would exceed expert compute by >100×. Instead
tokens are argsorted by expert, gathered into a fixed-capacity
(E, C, D) buffer (MegaBlocks-style with capacity drop), run through
expert-stacked GLU einsums (shardable over the expert axis = EP), and
scattered back weighted by gates. Dropped tokens fall through via the
residual connection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import act_fn, cdtype, dense_init, pdtype


def moe_params(key, cfg: ModelConfig):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    dt = pdtype(cfg)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    scale = (1.0 / D) ** 0.5
    p = {
        "router": dense_init(k1, D, E, dt, scale=0.02),
        "w_gate": (jax.random.normal(k2, (E, D, F)) * scale).astype(dt),
        "w_up": (jax.random.normal(k3, (E, D, F)) * scale).astype(dt),
        "w_down": (jax.random.normal(k4, (E, F, D)) * (1.0 / F) ** 0.5).astype(dt),
    }
    if cfg.moe_dense_residual:
        from repro.models.layers import glu_mlp_params
        p["dense"] = glu_mlp_params(k5, cfg)
    return p


def router_topk(logits, k: int):
    """Softmax-then-topk routing. Returns (gates (T,k), idx (T,k), probs)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx, probs


def load_balance_loss(probs, idx, num_experts: int):
    """Switch-style auxiliary loss: E · Σ_e f_e · p_e."""
    T = probs.shape[0]
    me = probs.mean(axis=0)                                    # (E,)
    counts = jnp.zeros((num_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    fe = counts / jnp.maximum(idx.size, 1)
    return num_experts * jnp.sum(fe * me)


def moe_ffn(params, x, cfg: ModelConfig):
    """x (B,S,D) → (out (B,S,D), aux_loss scalar)."""
    if cfg.moe_strategy == "tp":
        return moe_ffn_tp(params, x, cfg)
    dt = cdtype(cfg)
    B, S, D = x.shape
    T = B * S
    E, k = cfg.num_experts, cfg.experts_per_token
    xf = x.reshape(T, D)

    logits = xf @ params["router"].astype(dt)                  # (T,E)
    gates, idx, probs = router_topk(logits, k)
    aux = load_balance_loss(probs, idx, E)

    if T <= 4096:
        # decode / tiny batches: dropless (any expert can take every token);
        # capacity-induced drops would make decode diverge from prefill
        C = T
    else:
        C = int(max(1, round(T * k / E * cfg.capacity_factor)))
    flat_e = idx.reshape(-1)                                   # (T*k,)
    order = jnp.argsort(flat_e)                                # stable
    se = flat_e[order]
    tok = order // k
    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[se]
    valid = pos < C
    slot = jnp.where(valid, se * C + pos, E * C)               # overflow → trash row

    buf = jnp.zeros((E * C + 1, D), dt).at[slot].set(xf[tok])
    h = buf[: E * C].reshape(E, C, D)
    g = jnp.einsum("ecd,edf->ecf", h, params["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", h, params["w_up"].astype(dt))
    y = jnp.einsum("ecf,efd->ecd", act_fn(cfg.act)(g) * u,
                   params["w_down"].astype(dt))
    y = jnp.concatenate([y.reshape(E * C, D), jnp.zeros((1, D), dt)], axis=0)
    gsort = gates.reshape(-1)[order].astype(dt) * valid.astype(dt)
    out = jnp.zeros((T, D), dt).at[tok].add(y[slot] * gsort[:, None])

    if cfg.moe_dense_residual:
        from repro.models.layers import glu_mlp
        out = out + glu_mlp(params["dense"], xf, cfg)
    return out.reshape(B, S, D), aux


def _moe_local_body(params, xf, cfg: ModelConfig):
    """Dispatch + expert GLU for a LOCAL slab of tokens (no collectives;
    the expert einsums' F-contraction may carry the auto "model" axis)."""
    dt = cdtype(cfg)
    T, D = xf.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    logits = xf @ params["router"].astype(dt)
    gates, idx, probs = router_topk(logits, k)
    aux = load_balance_loss(probs, idx, E)
    if T <= 4096:
        C = T
    else:
        C = int(max(1, round(T * k / E * cfg.capacity_factor)))
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e)
    se = flat_e[order]
    tok = order // k
    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[se]
    valid = pos < C
    slot = jnp.where(valid, se * C + pos, E * C)
    buf = jnp.zeros((E * C + 1, D), dt).at[slot].set(xf[tok])
    h = buf[: E * C].reshape(E, C, D)
    g = jnp.einsum("ecd,edf->ecf", h, params["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", h, params["w_up"].astype(dt))
    y = jnp.einsum("ecf,efd->ecd", act_fn(cfg.act)(g) * u,
                   params["w_down"].astype(dt))
    y = jnp.concatenate([y.reshape(E * C, D), jnp.zeros((1, D), dt)], axis=0)
    gsort = gates.reshape(-1)[order].astype(dt) * valid.astype(dt)
    out = jnp.zeros((T, D), dt).at[tok].add(y[slot] * gsort[:, None])
    if cfg.moe_dense_residual:
        from repro.models.layers import glu_mlp
        out = out + glu_mlp(params["dense"], xf, cfg)
    return out, aux


def moe_ffn_tp(params, x, cfg: ModelConfig):
    """Tensor-parallel experts (§Perf beyond-paper optimisation).

    FULLY-MANUAL shard_map: router/argsort/gather/scatter are LOCAL to
    each data shard (no token crosses a shard), expert weights are
    F-sharded over "model", and — critically — the scatter-combine runs
    on the F-partial outputs BEFORE the reduction, so the only collective
    is ONE psum of the combined (T_local, D) activations per layer.

    Hillclimb round 1 (results/hillclimb A/opt1) showed the auto-axis
    variant let GSPMD reduce the (E·C_l, D) buffer pre-combine
    (~2.7 GB/layer on olmoe); combining first shrinks the payload to
    T_l·D·2B ≈ 0.27 GB/layer — scatter is linear, it commutes with psum.
    """
    from repro.parallel.sharding import current_mesh, data_axes
    mesh = current_mesh()
    B, S, D = x.shape
    if mesh is None:
        out, aux = _moe_local_body(params, x.reshape(B * S, D), cfg)
        return out.reshape(B, S, D), aux

    import jax as _jax
    from jax.sharding import PartitionSpec as P
    daxes = tuple(a for a in data_axes() if a in mesh.axis_names)
    has_model = "model" in mesh.axis_names
    d = daxes if len(daxes) > 1 else daxes[0]
    nshards = 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in daxes:
        nshards *= sizes[a]
    msize = sizes.get("model", 1)
    f_ok = has_model and cfg.d_ff % msize == 0

    def body(xl, p):
        Bl = xl.shape[0]
        out, aux = _moe_local_body(p, xl.reshape(Bl * S, D), cfg)
        if f_ok:
            out = _jax.lax.psum(out, "model")   # ONE AR of (T_l, D)
        aux = _jax.lax.psum(aux, daxes) / nshards
        return out.reshape(Bl, S, D), aux

    def wspec(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if not f_ok:
            return P()
        # expert-stacked (3D) and dense-residual (2D) GLU weights are both
        # F-sharded so every contribution to `out` is an F-partial sum and
        # the single psum reduces them together
        if name in ("w_gate", "w_up"):
            return (P(None, None, "model") if leaf.ndim == 3
                    else P(None, "model"))
        if name == "w_down":
            return (P(None, "model", None) if leaf.ndim == 3
                    else P("model", None))
        return P()

    pspec = jax.tree_util.tree_map_with_path(wspec, params)
    manual = set(daxes) | ({"model"} if f_ok else set())
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(d, None, None), pspec),
        out_specs=(P(d, None, None), P()),
        axis_names=manual, check_vma=False)(x, params)
    return out, aux


def moe_ffn_dense_reference(params, x, cfg: ModelConfig):
    """O(T·E) oracle: run every expert on every token (tests only)."""
    dt = cdtype(cfg)
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    logits = xf @ params["router"].astype(dt)
    gates, idx, _ = router_topk(logits, cfg.experts_per_token)
    g = jnp.einsum("td,edf->tef", xf, params["w_gate"].astype(dt))
    u = jnp.einsum("td,edf->tef", xf, params["w_up"].astype(dt))
    y = jnp.einsum("tef,efd->ted", act_fn(cfg.act)(g) * u,
                   params["w_down"].astype(dt))                # (T,E,D)
    w = jnp.zeros((xf.shape[0], cfg.num_experts), dt)
    w = jax.vmap(lambda wr, i, gv: wr.at[i].add(gv.astype(dt)))(w, idx, gates)
    out = jnp.einsum("ted,te->td", y, w)
    if cfg.moe_dense_residual:
        from repro.models.layers import glu_mlp
        out = out + glu_mlp(params["dense"], xf, cfg)
    return out.reshape(B, S, D)
