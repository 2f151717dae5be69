"""A model module for the program's ``moe`` family, for tests only: the
``olmoe-1b-7b`` preset at a tiny size, served through the same harness
as the benchmark's serving cells. It shows that a second architecture
goes in as a module of its own, named by its configuration's ``module``,
with no edit to the harness. It is not a configuration of the benchmark.

The layers follow the program's ``moe`` family: a Llama-style decoder
whose MLP is a mixture of SwiGLU experts. The router's probabilities are
a softmax over all experts; each token keeps its top ``k`` and
renormalises their gates to sum to 1, as ``repro.models.moe.router_topk``
does (OLMoE's published ``norm_topk_prob`` is false: a configuration of
the published model would have to state that departure). The reference
runs every expert on every token in float32 and weights each by its gate,
zero outside the top ``k``; nothing here imports the program but
``program_config``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_bench import danube
from tpu_bench.counters import BF16
from tpu_bench.danube import attend, matmul, rms, seed_key


def program_config(conf: dict):
    """The program's ``ModelConfig`` for this configuration: the decoder's
    widths checked as for danube, then the experts."""
    cfg = danube.program_config(conf)
    m = conf["model"]
    want = {"family": "moe", "num_experts": m["num_experts"],
            "experts_per_token": m["num_experts_per_tok"],
            "moe_dense_residual": False}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {got} departs from the "
                         f"configuration file {want}")
    return cfg


def _sizes(m: dict):
    return (m["hidden_size"], m["intermediate_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["num_hidden_layers"], m["vocab_size"],
            m["num_experts"], m["num_experts_per_tok"])


@functools.lru_cache(maxsize=None)
def _weights_fn(sizes: tuple):
    D, F, H, KH, hd, L, V, E, _ = sizes

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(
            jnp.bfloat16)

    def make(key):
        ks = jax.random.split(key, 13)
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
                "moe": {"router": normal(ks[8], (L, D, E), D ** -0.5),
                        "w_gate": normal(ks[9], (L, E, D, F), D ** -0.5),
                        "w_up": normal(ks[10], (L, E, D, F), D ** -0.5),
                        "w_down": normal(ks[11], (L, E, F, D), F ** -0.5)},
            },
            "ln_f": {"scale": normal(ks[12], (D,), 0.1)},
        }
    return jax.jit(make)


def make_weights(model: dict, seed: int, tenant: int):
    """One tenant's weights, made on the device in one jitted call."""
    return _weights_fn(_sizes(model))(seed_key(seed, tenant))


@functools.lru_cache(maxsize=None)
def _forward_fn(sizes: tuple, theta: float, eps: float, fp8: bool):
    D, F, H, KH, hd, L, V, E, k = sizes
    mm = matmul(fp8)

    def experts(h, p):
        probs = jax.nn.softmax(mm(h, p["router"]), -1)          # (S, E)
        top, idx = jax.lax.top_k(probs, k)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(h.shape[0])[:, None], idx].set(
            top / top.sum(-1, keepdims=True))
        f = jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"])  # (E, S, F)
        return jnp.einsum("se,esd->sd", gates, mm(f, p["w_down"]))

    def layer(x, p):
        x = x + attend(rms(x, p["ln1"]["scale"], eps), p["attn"], H, KH, hd,
                       theta, mm)
        return x + experts(rms(x, p["ln2"]["scale"], eps), p["moe"]), None

    def forward(params, tokens, sel):
        """Logits (len(sel), V) at positions ``sel`` of ``tokens``."""
        x = params["tok"]["embed"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = rms(x[sel], params["ln_f"]["scale"], eps)
        return mm(x, params["tok"]["unembed"])
    return jax.jit(forward)


def reference_logits(model: dict, params, tokens, sel, *, fp8: bool = False):
    """The plain forward pass in float32 (``highest`` matmul precision),
    every expert run densely, one sequence, returning the logits at
    positions ``sel``; with ``fp8`` the control."""
    fn = _forward_fn(_sizes(model), float(model["rope_theta"]),
                     float(model["rms_norm_eps"]), fp8)
    with jax.default_matmul_precision("highest"):
        return fn(params, tokens, sel)


# ------------------------------------------------------------ counts
def _active(model: dict) -> dict:
    """Per-layer parameters a token runs through: attention, the router
    and its ``k`` experts (not the experts it skips)."""
    D, F, H, KH, hd, L, V, E, k = _sizes(model)
    attn = D * (H * hd + 2 * KH * hd) + H * hd * D
    return {"D": D, "H": H, "KH": KH, "hd": hd, "L": L, "V": V,
            "layer_params": attn + D * E + k * 3 * D * F}


def _weight_bytes(model: dict) -> int:
    """Weights a call must read: every layer's attention and router, the
    ``k`` experts that even a call whose tokens all route alike reads,
    and the output head."""
    s = _active(model)
    return (s["L"] * s["layer_params"] + s["D"] * s["V"]) * BF16


def prefill_flops(model: dict, S: int) -> float:
    s = _active(model)
    causal_pairs = S * (S + 1) / 2
    per_layer = 2 * S * s["layer_params"] + 4 * causal_pairs * s["H"] * s["hd"]
    return s["L"] * per_layer + 2 * s["D"] * s["V"]


def prefill_bytes(model: dict, S: int) -> float:
    s = _active(model)
    kv = s["L"] * 2 * S * s["KH"] * s["hd"] * BF16
    return _weight_bytes(model) + S * s["D"] * BF16 + kv


def decode_flops(model: dict, contexts: list[int]) -> float:
    s = _active(model)
    per_tok = s["L"] * 2 * s["layer_params"] + 2 * s["D"] * s["V"]
    attn = sum(s["L"] * 4 * c * s["H"] * s["hd"] for c in contexts)
    return len(contexts) * per_tok + attn


def decode_bytes(model: dict, contexts: list[int]) -> float:
    s = _active(model)
    kv_tok = s["L"] * 2 * s["KH"] * s["hd"] * BF16
    return _weight_bytes(model) + sum(c * kv_tok for c in contexts) + \
        len(contexts) * kv_tok
