"""H2O-Danube3 for the serving cells: the benchmark's weights, the plain
float32 reference forward pass, and its fp8 control.

The layer equations follow the published architecture (arXiv:2407.09276,
``h2oai/h2o-danube3-4b-base``, a Llama-style decoder): token embedding,
then per layer ``x += Wo·attn(RoPE(Wq·n1(x)), RoPE(Wk·n1(x)), Wv·n1(x))``
with causal grouped-query attention, and ``x += Wd·(silu(Wg·n2(x)) *
Wu·n2(x))``; a final RMSNorm and an untied output head. RoPE rotates the
two halves of each head (rotate-half), RMSNorm is
``x / sqrt(mean(x²) + eps) * w``.

The weights are made by the benchmark from the seed, in bfloat16, as the
engine serves them, laid out as the serving engine's parameter tree
(``tok``/``layers``/``ln_f``; norm weights stored as ``scale`` with
``w = 1 + scale``). The engine's own parameters are replaced by these, so
the reference can make the very same ones again without reading
anything the program made.

This is the model module of the configurations that name it under
``module`` (``common.MODEL_CONTRACT``): ``program_config`` maps the file
onto the program's preset, the only use of the program here; the
weights and the reference import nothing of it; the operation and byte
counts are ``counters.py``'s dense decoder.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from tpu_bench.counters import (decode_bytes, decode_flops,  # noqa: F401
                                prefill_bytes, prefill_flops)

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def program_config(conf: dict):
    """The program's ``ModelConfig`` for this configuration file: its
    preset for the architecture, with the file's ``program`` settings."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(conf["program"]["arch"]),
                              **conf["program"]["settings"])
    m = conf["model"]
    want = {"d_model": m["hidden_size"], "d_ff": m["intermediate_size"],
            "num_heads": m["num_attention_heads"],
            "num_kv_heads": m["num_key_value_heads"],
            "head_dim": m["head_dim"], "num_layers": m["num_hidden_layers"],
            "vocab_size": m["vocab_size"], "rope_theta": m["rope_theta"],
            "tie_embeddings": m["tie_word_embeddings"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want or cfg.padded_vocab != m["vocab_size"]:
        raise ValueError(f"program config {got} departs from the "
                         f"configuration file {want}")
    return cfg


def _sizes(m: dict):
    D, F = m["hidden_size"], m["intermediate_size"]
    H, KH = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim", D // H)
    return D, F, H, KH, hd, m["num_hidden_layers"], m["vocab_size"]


def seed_key(seed: int, tenant: int):
    """The key of one tenant's weights, from a seed of any size."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(key, tenant)


@functools.lru_cache(maxsize=None)
def _weights_fn(sizes: tuple):
    D, F, H, KH, hd, L, V = sizes

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(
            jnp.bfloat16)

    def make(key):
        ks = jax.random.split(key, 12)
        return {
            "tok": {"embed": normal(ks[0], (V, D), 1.0),
                    "unembed": normal(ks[1], (D, V), D ** -0.5)},
            "layers": {
                "ln1": {"scale": normal(ks[2], (L, D), 0.1)},
                "attn": {"wq": normal(ks[3], (L, D, H * hd), D ** -0.5),
                         "wk": normal(ks[4], (L, D, KH * hd), D ** -0.5),
                         "wv": normal(ks[5], (L, D, KH * hd), D ** -0.5),
                         "wo": normal(ks[6], (L, H * hd, D),
                                      (H * hd) ** -0.5)},
                "ln2": {"scale": normal(ks[7], (L, D), 0.1)},
                "mlp": {"w_gate": normal(ks[8], (L, D, F), D ** -0.5),
                        "w_up": normal(ks[9], (L, D, F), D ** -0.5),
                        "w_down": normal(ks[10], (L, F, D), F ** -0.5)},
            },
            "ln_f": {"scale": normal(ks[11], (D,), 0.1)},
        }
    return jax.jit(make)


def make_weights(model: dict, seed: int, tenant: int):
    """One tenant's weights, made on the device in one jitted call."""
    return _weights_fn(_sizes(model))(seed_key(seed, tenant))


# ------------------------------------------------------------ reference
def q8(x, axis):
    """Round to fp8 (e4m3) with one absmax scale per slice along ``axis``
    (the contracted axis), back in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def rms(x, scale, eps):
    w = 1.0 + scale.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x (S, heads, hd) at positions 0..S-1, rotate-half convention."""
    S, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def matmul(fp8: bool):
    """The reference's matmul in float32, or with both operands first
    rounded to fp8 along the contracted axis (the control)."""
    def mm(a, w):
        a, w = a.astype(jnp.float32), w.astype(jnp.float32)
        if fp8:
            a, w = q8(a, -1), q8(w, -2)
        return a @ w
    return mm


def attend(h, p, heads: int, kv_heads: int, hd: int, theta: float, mm):
    """Causal grouped-query attention of one sequence ``h`` (S, D) with
    RoPE, through the output projection: ``Wo·attn(...)``."""
    S = h.shape[0]
    q = rope(mm(h, p["wq"]).reshape(S, heads, hd), theta)
    k = rope(mm(h, p["wk"]).reshape(S, kv_heads, hd), theta)
    v = mm(h, p["wv"]).reshape(S, kv_heads, hd)
    G = heads // kv_heads
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return mm(o.reshape(S, heads * hd), p["wo"])


@functools.lru_cache(maxsize=None)
def _forward_fn(sizes: tuple, theta: float, eps: float, fp8: bool):
    D, F, H, KH, hd, L, V = sizes
    mm = matmul(fp8)

    def layer(x, p):
        h = rms(x, p["ln1"]["scale"], eps)
        x = x + attend(h, p["attn"], H, KH, hd, theta, mm)
        h = rms(x, p["ln2"]["scale"], eps)
        f = jax.nn.silu(mm(h, p["mlp"]["w_gate"])) * mm(h, p["mlp"]["w_up"])
        return x + mm(f, p["mlp"]["w_down"]), None

    def forward(params, tokens, sel):
        """Logits (len(sel), V) at positions ``sel`` of ``tokens``."""
        x = params["tok"]["embed"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = rms(x[sel], params["ln_f"]["scale"], eps)
        return mm(x, params["tok"]["unembed"])
    return jax.jit(forward)


def reference_logits(model: dict, params, tokens, sel, *, fp8: bool = False):
    """The plain forward pass in float32 (``highest`` matmul precision),
    one sequence, returning the logits at positions ``sel``. With
    ``fp8`` every matmul's operands are first rounded to fp8: the
    control, one precision step below the served bfloat16."""
    fn = _forward_fn(_sizes(model), float(model["rope_theta"]),
                     float(model["rms_norm_eps"]), fp8)
    with jax.default_matmul_precision("highest"):
        return fn(params, tokens, sel)
