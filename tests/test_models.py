"""Model substrate correctness: all 10 assigned archs (reduced configs).

Key invariant: prefill(tokens[:S]) then decode(token[S]) must produce the
same logits as a full forward over tokens[:S+1] at the last position.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.models import build_model
from repro.models.attention import (chunked_attention, decode_attention,
                                    full_attention_reference, swa_attention)
from repro.models.mamba2 import ssd_chunked, ssd_reference
from repro.models.moe import moe_ffn, moe_ffn_dense_reference, moe_params

B, S = 2, 64


def make_batch(cfg, key, batch=B, seq=S, labels=True):
    ks = jax.random.split(key, 3)
    d = {}
    if cfg.frontend == "vision":
        d["embeds"] = jax.random.normal(ks[0], (batch, seq, cfg.d_model), jnp.bfloat16)
    else:
        d["tokens"] = jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab_size)
    if labels:
        d["labels"] = jax.random.randint(ks[1], (batch, seq), 0, cfg.vocab_size)
    if cfg.is_encoder_decoder:
        d["frames"] = jax.random.normal(
            ks[2], (batch, seq // cfg.encoder_seq_ratio, cfg.d_model), jnp.bfloat16)
    return d


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_loss_finite_and_shapes(arch):
    cfg = get_reduced(arch)
    m = build_model(cfg)
    params = m.init_params(jax.random.key(0))
    batch = make_batch(cfg, jax.random.key(1))
    loss, metrics = jax.jit(m.loss_fn)(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch} loss not finite"
    assert 3.0 < float(loss) < 9.0  # ~ln(vocab) at init


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_grads_finite(arch):
    cfg = get_reduced(arch)
    m = build_model(cfg)
    params = m.init_params(jax.random.key(0))
    batch = make_batch(cfg, jax.random.key(1))
    grads = jax.jit(jax.grad(lambda p, b: m.loss_fn(p, b)[0]))(params, batch)
    leaves = jax.tree.leaves(grads)
    assert leaves
    for g in leaves:
        assert np.all(np.isfinite(np.asarray(g, np.float32)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch):
    """prefill S tokens + decode token S == forward S+1 tokens (last logits)."""
    cfg = get_reduced(arch)
    if cfg.attention == "swa":
        cfg = get_reduced(arch, window=32)  # exercise windowing with S=64
    m = build_model(cfg)
    params = m.init_params(jax.random.key(0))
    seq = S
    full = make_batch(cfg, jax.random.key(1), seq=seq + 1, labels=False)
    if cfg.frontend == "vision":
        pytest.skip("vlm decode starts from token ids; covered by smoke test")
    pre = dict(full)
    pre["tokens"] = full["tokens"][:, :seq]

    last_logits, cache = jax.jit(m.prefill_fn)(params, pre)

    tok = full["tokens"][:, seq]
    pos = jnp.full((B,), seq, jnp.int32)
    cache = _grow_cache(m, cfg, cache, seq + 1)
    dec_logits, _ = jax.jit(m.decode_fn)(params, cache, tok, pos)

    # reference: full forward; compute last-position logits via prefill on S+1
    ref_logits, _ = jax.jit(m.prefill_fn)(params, full)
    np.testing.assert_allclose(np.asarray(dec_logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=2e-2, atol=2e-2)


def _grow_cache(m, cfg, cache, max_len):
    from repro.models.kvcache import grow_cache
    return grow_cache(cfg, cache, max_len)


def test_chunked_attention_matches_reference():
    key = jax.random.key(0)
    for (h, kh, seq, chunk) in [(4, 2, 96, 32), (8, 8, 64, 64), (4, 1, 128, 32)]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (2, seq, h, 16))
        k = jax.random.normal(ks[1], (2, seq, kh, 16))
        v = jax.random.normal(ks[2], (2, seq, kh, 16))
        out = chunked_attention(q, k, v, causal=True, chunk=chunk)
        ref = full_attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_swa_attention_matches_reference():
    key = jax.random.key(1)
    for (seq, w) in [(128, 32), (64, 64), (96, 32)]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (2, seq, 4, 16))
        k = jax.random.normal(ks[1], (2, seq, 2, 16))
        v = jax.random.normal(ks[2], (2, seq, 2, 16))
        out = swa_attention(q, k, v, window=w)
        ref = full_attention_reference(q, k, v, causal=True, window=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


DECODE_HEADS = [(4, 4, 16), (8, 2, 16), (32, 8, 120)]   # (H, KH, D)


def _decode_case(key, H, KH, D, B, T):
    """bf16 queries (B, 1, H, D) and keys/values (B, T, KH, D)."""
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, D)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, KH, D)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, KH, D)).astype(jnp.bfloat16)
    return q, k, v


def _decode_reference(q, k, v, lengths, window=0):
    """Each slot's last query row of ``full_attention_reference`` over its
    own ``lengths[b]`` keys, in float32."""
    rows = [full_attention_reference(q[b:b + 1].astype(jnp.float32),
                                     k[b:b + 1, :n], v[b:b + 1, :n],
                                     causal=True, window=window)
            for b, n in enumerate(lengths)]
    return np.asarray(jnp.concatenate(rows), np.float32)


@pytest.mark.parametrize("ragged", [True, False], ids=["per_slot", "scalar"])
@pytest.mark.parametrize("H,KH,D", DECODE_HEADS)
def test_decode_attention_matches_reference(H, KH, D, ragged):
    """Grouped decode over a dense bf16 cache == the reference, each slot
    masked to its own ``cache_len`` (per slot or one scalar); the cache
    rows past it hold other keys, which the mask must hide."""
    lengths = [7, 40, 23] if ragged else [29, 29, 29]
    q, k, v = _decode_case(jax.random.key(H * 100 + KH), H, KH, D,
                           len(lengths), 40)
    cache_len = jnp.asarray(lengths, jnp.int32) if ragged else lengths[0]
    out = decode_attention(q, k, v, cache_len)
    assert out.shape == q.shape and out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               _decode_reference(q, k, v, lengths),
                               rtol=2 ** -8, atol=1e-5)


@pytest.mark.parametrize("H,KH,D", DECODE_HEADS)
def test_decode_attention_swa_ring_buffer(H, KH, D):
    """SWA: the cache is a ring buffer of ``window`` < S positions (token t
    at t % window); decode sees each slot's last ``window`` tokens."""
    window = 16
    lengths = [9, 16, 21, 40]           # not yet wrapped, full, wrapped
    q, k, v = _decode_case(jax.random.key(H + KH), H, KH, D, len(lengths),
                           max(lengths))
    kc = jnp.zeros((len(lengths), window, KH, D), jnp.bfloat16)
    vc = jnp.zeros_like(kc)
    for b, n in enumerate(lengths):
        for t in range(n):
            kc = kc.at[b, t % window].set(k[b, t])
            vc = vc.at[b, t % window].set(v[b, t])
    cache_len = jnp.minimum(jnp.asarray(lengths, jnp.int32), window)
    out = decode_attention(q, kc, vc, cache_len, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               _decode_reference(q, k, v, lengths, window),
                               rtol=2 ** -8, atol=1e-5)


def test_decode_reads_cache_at_kv_head_width():
    """The lowered decode step of a GQA model holds no array with the
    cache's length and all query heads after it: neither the repeat
    (…, S, H, D) nor its broadcast (…, S, KH, G, D)."""
    cfg = dataclasses.replace(get_reduced("tinyllama-1.1b"), num_heads=12,
                              num_kv_heads=3, head_dim=20)
    H, KH = cfg.num_heads, cfg.num_kv_heads
    G = H // KH
    Bz, smax = 5, 56
    m = build_model(cfg)
    params = jax.eval_shape(m.init_params, jax.random.key(0))
    cache = m.cache_specs(Bz, smax)
    tok = jax.ShapeDtypeStruct((Bz,), jnp.int32)
    text = jax.jit(m.decode_fn).lower(params, cache, tok, tok).as_text()
    shapes = [[int(d) for d in dims.split("x")[:-1]]
              for dims in re.findall(r"tensor<((?:\d+x)+)\w+>", text)]
    assert any(smax in dims for dims in shapes)   # the cache is there
    for dims in shapes:
        if smax not in dims:
            continue
        after = dims[dims.index(smax) + 1:]
        assert H not in after, dims
        assert not any(after[i:i + 2] == [KH, G]
                       for i in range(len(after))), dims


def test_ssd_chunked_matches_scan():
    key = jax.random.key(2)
    Bz, seq, H, P, N = 2, 128, 4, 8, 16
    ks = jax.random.split(key, 5)
    xh = jax.random.normal(ks[0], (Bz, seq, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bz, seq, H)))
    a_log = jax.random.normal(ks[2], (H,)) * 0.5
    Bm = jax.random.normal(ks[3], (Bz, seq, N))
    Cm = jax.random.normal(ks[4], (Bz, seq, N))
    y1, s1 = ssd_chunked(xh, dt, a_log, Bm, Cm, chunk=32)
    y2, s2 = ssd_reference(xh, dt, a_log, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=2e-4, atol=2e-4)


def test_moe_matches_dense_reference_when_no_drop():
    cfg = get_reduced("olmoe-1b-7b", capacity_factor=8.0)  # no token drops
    key = jax.random.key(3)
    params = moe_params(key, cfg)
    x = jax.random.normal(jax.random.key(4), (2, 16, cfg.d_model), jnp.float32)
    cfg32 = cfg.__class__(**{**cfg.__dict__, "dtype": "float32"})
    out, aux = moe_ffn(params, x, cfg32)
    ref = moe_ffn_dense_reference(params, x, cfg32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)
    assert float(aux) > 0


def test_full_configs_instantiable():
    """Full configs are dry-run-only, but must at least build specs."""
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        m = build_model(cfg)
        from repro.configs import SHAPES
        specs = m.input_specs(SHAPES["train_4k"])
        assert specs
        n = cfg.param_count()
        assert n > 1e8, f"{arch}: param count {n} implausibly small"
