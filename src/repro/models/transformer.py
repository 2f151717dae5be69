"""Composable backbones: dense/MoE decoder, encoder-decoder, RWKV6 stack,
hybrid Mamba2+shared-attention stack. All stacks scan over layers with
stacked params (HLO size O(1) in depth) and support remat policies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv
from repro.models.kvcache import write_slot
from repro.models.layers import (apply_norm, cdtype, dense_init, glu_mlp,
                                 glu_mlp_params, norm_params, pdtype)
from repro.parallel.sharding import constrain


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint(fn, policy=policy)


def _stack_init(key, n: int, init_fn):
    """vmap an init over layer keys → params stacked on leading dim."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


# ------------------------------------------------------------ dense/MoE
def block_params(key, cfg: ModelConfig, cross: bool = False):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"ln1": norm_params(cfg), "attn": attn.attn_params(k1, cfg),
         "ln2": norm_params(cfg)}
    if cross:
        p["ln_cross"] = norm_params(cfg)
        p["cross"] = attn.attn_params(k3, cfg)
    if cfg.family == "moe":
        p["moe"] = moe_mod.moe_params(k2, cfg)
    else:
        p["mlp"] = glu_mlp_params(k2, cfg)
    return p


def _ffn(p, x, cfg: ModelConfig):
    """Returns (y, aux)."""
    if cfg.family == "moe":
        return moe_mod.moe_ffn(p["moe"], x, cfg)
    return glu_mlp(p["mlp"], x, cfg), jnp.zeros((), jnp.float32)


def block_fwd(p, x, cfg: ModelConfig, positions, *, causal=True,
              enc_out=None):
    """One decoder block (train/prefill). Returns (x, (k, v, aux))."""
    if cfg.seq_parallel:
        # Megatron-SP: residual stream sequence-sharded over "model"; GSPMD
        # turns the two TP all-reduces into RS+AG pairs (half the wire)
        x = constrain(x, "batch", "model", None)
    else:
        x = constrain(x, "batch", None, None)
    a, (k, v) = attn.attention(p["attn"], apply_norm(p["ln1"], x, cfg), cfg,
                               positions, causal=causal)
    if cfg.bf16_reduce:
        # materialise the row-parallel partial sum in bf16 HERE, before any
        # f32 norm math widens the deferred all-reduce payload
        a = constrain(a, "batch", "model" if cfg.seq_parallel else None, None)
    x = x + a
    if enc_out is not None:
        h = apply_norm(p["ln_cross"], x, cfg)
        q, _, _ = attn.qkv_proj(p["cross"], h, cfg, positions=None)
        ck, cv = cross_kv(p["cross"], enc_out, cfg)
        o = attn.chunked_attention(q, ck, cv, causal=False, chunk=cfg.attn_chunk,
                                   unroll=not cfg.scan_layers)
        B, S = x.shape[:2]
        x = x + o.reshape(B, S, cfg.q_dim) @ p["cross"]["wo"].astype(cdtype(cfg))
    f, aux = _ffn(p, apply_norm(p["ln2"], x, cfg), cfg)
    if cfg.bf16_reduce:
        f = constrain(f, "batch", "model" if cfg.seq_parallel else None, None)
    x = x + f
    return x, (k, v, aux)


def cross_kv(p_cross, enc_out, cfg: ModelConfig):
    dt = cdtype(cfg)
    B, Se, _ = enc_out.shape
    k = (enc_out @ p_cross["wk"].astype(dt)).reshape(B, Se, cfg.num_kv_heads, cfg.head_dim)
    v = (enc_out @ p_cross["wv"].astype(dt)).reshape(B, Se, cfg.num_kv_heads, cfg.head_dim)
    return k, v


def decoder_stack(params_stacked, x, cfg: ModelConfig, positions, *,
                  causal=True, enc_out=None, collect_cache=False):
    """Scan over stacked layer params. Returns (x, cache, aux_sum)."""

    def body(carry, p_l):
        h, aux = carry
        h, (k, v, aux_l) = block_fwd(p_l, h, cfg, positions, causal=causal,
                                     enc_out=enc_out)
        out = (k, v) if collect_cache else None
        return (h, aux + aux_l), out

    body = _remat(body, cfg)
    if cfg.scan_layers:
        (x, aux), kv = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                    params_stacked)
    else:
        aux = jnp.zeros((), jnp.float32)
        kvs = []
        L = jax.tree.leaves(params_stacked)[0].shape[0]
        for i in range(L):
            p_l = jax.tree.map(lambda a: a[i], params_stacked)
            (x, aux), out = body((x, aux), p_l)
            kvs.append(out)
        kv = (jax.tree.map(lambda *xs: jnp.stack(xs), *kvs)
              if collect_cache else None)
    return x, kv, aux


def decode_step_stack(params_stacked, x, cfg: ModelConfig, cache, pos):
    """One-token decode through scanned layers.

    x (B,1,D); cache {"k","v"}: (L,B,S,KH,hd); pos (B,) int32 — index of
    the new token. Returns (x, new_cache)."""
    window = cfg.window if cfg.attention == "swa" else 0
    slot = pos % window if window else pos
    cache_len = jnp.minimum(pos + 1, window) if window else pos + 1

    def body(h, inp):
        p_l, kc, vc = inp
        hh = apply_norm(p_l["ln1"], h, cfg)
        q, k, v = attn.qkv_proj(p_l["attn"], hh, cfg, positions=pos[:, None])
        kc, vc = write_slot((kc, vc), k, v, slot)
        o = attn.decode_attention(q, kc, vc, cache_len, window=window,
                                  partials=cfg.decode_partials)
        B = h.shape[0]
        h = h + o.reshape(B, 1, cfg.q_dim) @ p_l["attn"]["wo"].astype(cdtype(cfg))
        f, _ = _ffn(p_l, apply_norm(p_l["ln2"], h, cfg), cfg)
        return h + f, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params_stacked, cache["k"], cache["v"]))
    return x, {"k": k_new, "v": v_new}


# ------------------------------------------------------------ rwkv6
def rwkv_block_params(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {"ln1": norm_params(cfg), "att": rwkv.time_mix_params(k1, cfg),
            "ln2": norm_params(cfg), "ffn": rwkv.channel_mix_params(k2, cfg)}


def rwkv_stack(params_stacked, x, cfg: ModelConfig, state=None,
               collect_state=False):
    """state: dict of stacked per-layer states or None."""

    def body(carry, inp):
        h = carry
        if state is None:
            p_l = inp
            a, st_a = rwkv.time_mix(p_l["att"], apply_norm(p_l["ln1"], h, cfg), cfg)
        else:
            p_l, st = inp
            a, st_a = rwkv.time_mix(p_l["att"], apply_norm(p_l["ln1"], h, cfg),
                                    cfg, state=(st["att_x"], st["att_s"]))
        h = h + a
        if state is None:
            f, st_f = rwkv.channel_mix(p_l["ffn"], apply_norm(p_l["ln2"], h, cfg), cfg)
        else:
            f, st_f = rwkv.channel_mix(p_l["ffn"], apply_norm(p_l["ln2"], h, cfg),
                                       cfg, state=st["ffn_x"])
        h = h + f
        out = ({"att_x": st_a[0], "att_s": st_a[1], "ffn_x": st_f}
               if collect_state else None)
        return h, out

    body = _remat(body, cfg)
    xs = params_stacked if state is None else (params_stacked, state)
    if cfg.scan_layers:
        x, states = jax.lax.scan(body, x, xs)
        return x, states
    outs = []
    for i in range(cfg.num_layers):
        x, o = body(x, jax.tree.map(lambda a: a[i], xs))
        outs.append(o)
    states = (jax.tree.map(lambda *ys: jnp.stack(ys), *outs)
              if collect_state else None)
    return x, states


def rwkv_decode_step(params_stacked, x, cfg: ModelConfig, state):
    """x (B,D); state stacked per layer."""

    def body(h, inp):
        p_l, st = inp
        a, st_a = rwkv.time_mix_step(p_l["att"], apply_norm(p_l["ln1"], h, cfg),
                                     cfg, (st["att_x"], st["att_s"]))
        h = h + a
        f, st_f = rwkv.channel_mix_step(p_l["ffn"], apply_norm(p_l["ln2"], h, cfg),
                                        cfg, st["ffn_x"])
        h = h + f
        return h, {"att_x": st_a[0], "att_s": st_a[1], "ffn_x": st_f}

    return jax.lax.scan(body, x, (params_stacked, state))


# ------------------------------------------------------------ hybrid (zamba2)
def hybrid_params(key, cfg: ModelConfig):
    """G groups; each = 1 shared attn block application + attn_every mamba
    blocks. Shared block params exist ONCE (zamba2 weight sharing)."""
    assert cfg.num_layers % cfg.attn_every == 0
    G = cfg.num_layers // cfg.attn_every
    k1, k2, k3 = jax.random.split(key, 3)
    shared = block_params(k2, cfg)
    shared["fuse"] = dense_init(k3, 2 * cfg.d_model, cfg.d_model, pdtype(cfg))

    def group_init(kg):
        return _stack_init(kg, cfg.attn_every,
                           lambda k: {"ln": norm_params(cfg),
                                      "mamba": m2.mamba2_params(k, cfg)})

    groups = _stack_init(k1, G, group_init)     # (G, attn_every, ...)
    return {"mamba": groups, "shared": shared}


def _shared_block(shared, x, x0, cfg: ModelConfig, positions):
    dt = cdtype(cfg)
    fused = jnp.concatenate([x, x0], axis=-1) @ shared["fuse"].astype(dt)
    y, (k, v, _) = block_fwd(shared, fused, cfg, positions)
    return x + y, (k, v)


def hybrid_stack(params, x, cfg: ModelConfig, positions, state=None,
                 collect=False):
    """Returns (x, {"attn_k","attn_v","conv","ssm"} stacked by group)."""
    x0 = x

    def group_body(carry, inp):
        h, _ = carry
        if state is None:
            pg = inp
            st_g = None
        else:
            pg, st_g = inp
        h, (k, v) = _shared_block(params["shared"], h, x0, cfg, positions)

        def mamba_body(hh, minp):
            if st_g is None:
                p_m = minp
                y, st = m2.mamba2_block(p_m["mamba"],
                                        apply_norm(p_m["ln"], hh, cfg), cfg)
            else:
                p_m, st_m = minp
                y, st = m2.mamba2_block(p_m["mamba"],
                                        apply_norm(p_m["ln"], hh, cfg), cfg,
                                        state=(st_m["conv"], st_m["ssm"]))
            out = {"conv": st[0], "ssm": st[1]} if collect else None
            return hh + y, out

        xs = pg if st_g is None else (pg, {"conv": st_g["conv"], "ssm": st_g["ssm"]})
        h, mst = jax.lax.scan(mamba_body, h, xs,
                              unroll=1 if cfg.scan_layers else cfg.attn_every)
        out = None
        if collect:
            out = {"attn_k": k, "attn_v": v, "conv": mst["conv"], "ssm": mst["ssm"]}
        return (h, jnp.zeros((), jnp.float32)), out

    group_body = _remat(group_body, cfg)
    xs = params["mamba"] if state is None else (params["mamba"], state)
    if cfg.scan_layers:
        (x, _), sts = jax.lax.scan(group_body,
                                   (x, jnp.zeros((), jnp.float32)), xs)
        return x, sts
    G = cfg.num_layers // cfg.attn_every
    carry = (x, jnp.zeros((), jnp.float32))
    outs = []
    for i in range(G):
        carry, o = group_body(carry, jax.tree.map(lambda a: a[i], xs))
        outs.append(o)
    sts = jax.tree.map(lambda *ys: jnp.stack(ys), *outs) if collect else None
    return carry[0], sts


def hybrid_decode_step(params, x, cfg: ModelConfig, cache, pos):
    """x (B,1,D); cache per group: attn k/v (G,B,S,KH,hd), conv
    (G,K,B,W-1,C), ssm (G,K,B,H,P,N). Returns (x, cache)."""
    x0 = x
    slot = pos
    cache_len = pos + 1

    def group_body(h, inp):
        pg, st_g = inp
        # shared attn block (weights closed over, per-group cache)
        dt = cdtype(cfg)
        shared = params["shared"]
        fused = jnp.concatenate([h, x0], axis=-1) @ shared["fuse"].astype(dt)
        hh = apply_norm(shared["ln1"], fused, cfg)
        q, k, v = attn.qkv_proj(shared["attn"], hh, cfg, positions=pos[:, None])
        kc, vc = write_slot((st_g["attn_k"], st_g["attn_v"]), k, v, slot)
        o = attn.decode_attention(q, kc, vc, cache_len)
        B = h.shape[0]
        y = fused + o.reshape(B, 1, cfg.q_dim) @ shared["attn"]["wo"].astype(dt)
        f, _ = _ffn(shared, apply_norm(shared["ln2"], y, cfg), cfg)
        h = h + (y + f)

        def mamba_body(hh2, minp):
            p_m, st_m = minp
            y2, st = m2.mamba2_step(p_m["mamba"],
                                    apply_norm(p_m["ln"], hh2[:, 0], cfg), cfg,
                                    (st_m["conv"], st_m["ssm"]))
            return hh2 + y2[:, None], {"conv": st[0], "ssm": st[1]}

        h, mst = jax.lax.scan(mamba_body, h,
                              (pg, {"conv": st_g["conv"], "ssm": st_g["ssm"]}))
        return h, {"attn_k": kc, "attn_v": vc, "conv": mst["conv"], "ssm": mst["ssm"]}

    x, sts = jax.lax.scan(group_body, x, (params["mamba"], cache))
    return x, sts
