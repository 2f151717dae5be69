"""Attention: GQA, chunked online-softmax (memory-bounded prefill/train),
exact banded sliding-window attention, and decode attention over caches.

TP formulation: all einsums run over the FLAT query-head axis with K/V
broadcast from KH→H (XLA fuses the repeat into the einsum — no
materialisation) so the head axis shards cleanly over "model" whenever
H divides the axis; scan carries are sharding-constrained to stop GSPMD
replicating the online-softmax state (which would insert per-chunk
all-reduces). The chunked path is the pure-JAX analogue of the Pallas
flash kernel in ``repro.kernels.flash_attention``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, cdtype, dense_init, pdtype, rope_angles
from repro.parallel.sharding import constrain

NEG_INF = -1e30


def attn_params(key, cfg: ModelConfig):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = pdtype(cfg)
    return {
        "wq": dense_init(k1, cfg.d_model, cfg.q_dim, dt),
        "wk": dense_init(k2, cfg.d_model, cfg.kv_dim, dt),
        "wv": dense_init(k3, cfg.d_model, cfg.kv_dim, dt),
        "wo": dense_init(k4, cfg.q_dim, cfg.d_model, dt),
    }


def qkv_proj(params, x, cfg: ModelConfig, positions=None):
    """x (B,S,D) → q (B,S,H,hd), k/v (B,S,KH,hd) with RoPE applied."""
    dt = cdtype(cfg)
    B, S, _ = x.shape
    q = (x @ params["wq"].astype(dt)).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (x @ params["wk"].astype(dt)).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (x @ params["wv"].astype(dt)).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.rope_theta > 0 and positions is not None:
        cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = constrain(q, "batch", None, "model", None)
    return q, k, v


def repeat_kv(k, num_heads: int):
    """(B,S,KH,D) → (B,S,H,D) broadcast across the group dim (fused)."""
    B, S, KH, D = k.shape
    G = num_heads // KH
    if G == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None], (B, S, KH, G, D))
    return k.reshape(B, S, num_heads, D)


def chunked_attention(q, k, v, *, causal: bool = True, chunk: int = 1024,
                      q_offset: int = 0, unroll: bool = False,
                      bf16_probs: bool = False):
    """Online-softmax attention scanning KV chunks. q (B,Sq,H,D),
    k/v (B,Sk,KH,D). Returns (B,Sq,H,D). Live buffers O(B·H·Sq·chunk).
    ``unroll`` expands the chunk loop in HLO (dry-run accounting: XLA cost
    analysis counts loop bodies once) — buffer reuse keeps memory bounded."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    k = repeat_kv(k, H)
    v = repeat_kv(v, H)
    chunk = min(chunk, Sk)
    if Sk % chunk:  # pad keys to a multiple of chunk; masked below
        pad = chunk - Sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    scale = D ** -0.5
    qf = q.astype(jnp.float32) * scale
    kc = k.reshape(B, n_chunks, chunk, H, D)
    vc = v.reshape(B, n_chunks, chunk, H, D)
    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inputs):
        m, l, acc = carry
        kb, vb, c_idx = inputs
        k_pos = c_idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
        s = constrain(s, "batch", "model", None, None)
        mask = k_pos[None, :] < Sk  # padding
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        pv = p.astype(jnp.bfloat16) if bf16_probs else p
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", pv, vb.astype(pv.dtype)).astype(jnp.float32)
        acc_new = constrain(acc_new, "batch", "model", None, None)
        return (m_new, l_new, acc_new), None

    m0 = constrain(jnp.full((B, H, Sq), NEG_INF, jnp.float32),
                   "batch", "model", None)
    l0 = constrain(jnp.zeros((B, H, Sq), jnp.float32), "batch", "model", None)
    a0 = constrain(jnp.zeros((B, H, Sq, D), jnp.float32),
                   "batch", "model", None, None)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (kc.swapaxes(0, 1), vc.swapaxes(0, 1), jnp.arange(n_chunks)),
        unroll=n_chunks if unroll else 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 2, 1, 3)                     # (B,Sq,H,D)
    return out.astype(q.dtype)


def swa_attention(q, k, v, *, window: int):
    """Exact sliding-window attention (token t sees [t-window+1, t]) via
    banded blocks: each w-sized query block attends to itself + the
    previous block. Compute O(S·2w)."""
    B, S, H, D = q.shape
    w = window
    if S <= w:  # degenerate: plain causal attention
        return chunked_attention(q, k, v, causal=True, chunk=min(w, 1024))
    if S % w:  # pad tail; padded keys sit after all real queries → masked
        pad = w - S % w
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        out = swa_attention(q, k, v, window=w)
        return out[:, :S]
    k = repeat_kv(k, H)
    v = repeat_kv(v, H)
    nb = S // w
    scale = D ** -0.5
    qb = q.reshape(B, nb, w, H, D).astype(jnp.float32) * scale
    kb = k.reshape(B, nb, w, H, D)
    vb = v.reshape(B, nb, w, H, D)
    # previous block (block 0's previous is zeros, masked out)
    k_prev = jnp.pad(kb[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    v_prev = jnp.pad(vb[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    kc = jnp.concatenate([k_prev, kb], axis=2)   # (B,nb,2w,H,D)
    vc = jnp.concatenate([v_prev, vb], axis=2)
    s = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, kc.astype(jnp.float32))
    s = constrain(s, "batch", None, "model", None, None)
    # q global pos = n*w + i ; k global pos = (n-1)*w + j  (j in [0,2w))
    i = jnp.arange(w)[:, None]
    j = jnp.arange(2 * w)[None, :]
    delta = (i + w) - j                          # q_pos - k_pos
    mask = (delta >= 0) & (delta < w)
    blk0_mask = mask & (j >= w)                  # block 0 has no previous
    full_mask = jnp.broadcast_to(mask[None], (nb, w, 2 * w))
    full_mask = full_mask.at[0].set(blk0_mask)
    s = jnp.where(full_mask[None, :, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", p, vc.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0,
                     partials: bool = False, grouped: bool = False):
    """Single-token decode. q (B,1,H,D); caches (B,Smax,KH,D); cache_len
    (B,) or scalar — number of valid positions (new token's K/V already
    written at cache_len-1). For SWA the cache is a ring buffer.

    ``partials`` (flash-decoding layout): the logits stay SEQ-sharded over
    "model" (matching the seq-sharded cache) and only the softmax
    reductions + the (B,H,D)-sized output cross shards — instead of
    resharding the whole cache onto the heads layout."""
    B, Smax, KH, D = k_cache.shape
    H = q.shape[2]
    scale = D ** -0.5
    pos = jnp.arange(Smax)
    valid = pos[None, :] < jnp.asarray(cache_len).reshape(-1, 1)
    if grouped:
        # KH-grouped einsums: never materialise the (B,S,H,D) repeat — the
        # cache is read once at its native KH width (memory-term win)
        G = H // KH
        qg = q.reshape(B, 1, KH, G, D).astype(jnp.float32) * scale
        if partials:
            qg = constrain(qg, "batch", None, None, None, None)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache.astype(jnp.float32))
        if partials:
            s = constrain(s, "batch", None, None, None, "model")
        s = jnp.where(valid[:, None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_cache.astype(jnp.float32))
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, 1, H, D)
        if partials:
            out = constrain(out, "batch", None, None, None)
        return out.astype(q.dtype)
    k_cache = repeat_kv(k_cache, H)
    v_cache = repeat_kv(v_cache, H)
    qf = q.astype(jnp.float32) * scale
    if partials:
        qf = constrain(qf, "batch", None, None, None)   # q replicated on model
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_cache.astype(jnp.float32))
    if partials:
        s = constrain(s, "batch", None, None, "model")  # seq-sharded logits
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p, v_cache.astype(jnp.float32))
    out = out.transpose(0, 2, 1, 3)
    if partials:
        out = constrain(out, "batch", None, None, None)
    return out.astype(q.dtype)


def full_attention_reference(q, k, v, *, causal=True, window: int = 0):
    """O(S²) reference used only in tests (small shapes)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    k = repeat_kv(k, H)
    v = repeat_kv(v, H)
    s = jnp.einsum("bqhd,bkhd->bhqk",
                   q.astype(jnp.float32) * D ** -0.5, k.astype(jnp.float32))
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attention(params, x, cfg: ModelConfig, positions, *, causal=True):
    """Full attention block for train/prefill. Returns (out, (k, v))."""
    q, k, v = qkv_proj(params, x, cfg, positions)
    if cfg.use_pallas:
        if jax.default_backend() != "tpu":
            raise ValueError(
                f"{cfg.name}: use_pallas needs a TPU, but the default "
                f"backend is {jax.default_backend()!r}")
        from repro.kernels.ops import flash_attention as _fa
        o = _fa(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                causal=causal,
                window=cfg.window if cfg.attention == "swa" else 0)
        o = o.swapaxes(1, 2)
    elif cfg.attention == "swa" and cfg.window:
        o = swa_attention(q, k, v, window=cfg.window)
    else:
        o = chunked_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk,
                              unroll=not cfg.scan_layers,
                              bf16_probs=cfg.attn_bf16_probs)
    B, S, _, _ = q.shape
    out = o.reshape(B, S, cfg.q_dim) @ params["wo"].astype(cdtype(cfg))
    return out, (k, v)
