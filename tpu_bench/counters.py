"""Operations and bytes that each measured program needs, from shapes.

These are what the algorithm needs, not what the program happens to do:
causal attention counts the causal half of the score matrix, and decode
reads the K/V of each live slot's real context, not the padded cache.
Weights are counted in bfloat16 (2 bytes), as served.
"""
from __future__ import annotations

BF16 = 2


def dense_sizes(model: dict) -> dict:
    D, F = model["hidden_size"], model["intermediate_size"]
    H, KH = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model["head_dim"]
    attn = D * (H * hd + 2 * KH * hd) + H * hd * D
    return {"D": D, "F": F, "H": H, "KH": KH, "hd": hd,
            "L": model["num_hidden_layers"], "V": model["vocab_size"],
            "layer_params": attn + 3 * D * F}


def weight_bytes(model: dict) -> int:
    """Weights one forward pass reads: every layer and the output head
    (the embedding is gathered, a row per token)."""
    s = dense_sizes(model)
    return (s["L"] * s["layer_params"] + s["D"] * s["V"]) * BF16


def prefill_flops(model: dict, S: int) -> float:
    """One prompt of S tokens, logits at the last position only."""
    s = dense_sizes(model)
    causal_pairs = S * (S + 1) / 2
    per_layer = 2 * S * s["layer_params"] + 4 * causal_pairs * s["H"] * s["hd"]
    return s["L"] * per_layer + 2 * s["D"] * s["V"]


def prefill_bytes(model: dict, S: int) -> float:
    """Weights once, the prompt's embedding rows, and its K/V written."""
    s = dense_sizes(model)
    kv = s["L"] * 2 * S * s["KH"] * s["hd"] * BF16
    return weight_bytes(model) + S * s["D"] * BF16 + kv


def decode_flops(model: dict, contexts: list[int]) -> float:
    """One decode step of the live slots, each with its context length
    (the new token included)."""
    s = dense_sizes(model)
    per_tok = (s["L"] * 2 * s["layer_params"] + 2 * s["D"] * s["V"])
    attn = sum(s["L"] * 4 * c * s["H"] * s["hd"] for c in contexts)
    return len(contexts) * per_tok + attn


def decode_bytes(model: dict, contexts: list[int]) -> float:
    """Weights once, plus the K/V of each live slot's real context read
    and its new token's K/V written."""
    s = dense_sizes(model)
    kv_tok = s["L"] * 2 * s["KH"] * s["hd"] * BF16
    return weight_bytes(model) + sum(c * kv_tok for c in contexts) + \
        len(contexts) * kv_tok


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the bandwidth bound."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])


# ---------------------------------------------------------------- fleet
def fleet_kernel_bytes(call: dict) -> float:
    """Bytes a fleet kernel call must read and write, from its shape.

    ``dense`` (rows × seconds): keys (8 B), scale, sigma, SLO (4 B each)
    and the active mask (1 B a cell) in; latency (4 B) and violation
    (1 B) per cell, latency sum and violation count (4 B each) per row
    out. ``jitter`` (rows × L): keys and sigma in, latency factors out.
    ``poisson`` (rows × seconds): keys and rates in, counts out."""
    R, C = call["rows"], call["cols"]
    if call["kind"] == "dense":
        return R * (8 + 12 + 8) + R * C * (1 + 4 + 1)
    if call["kind"] == "jitter":
        return R * (8 + 4) + R * C * 4
    if call["kind"] == "poisson":
        return R * 8 + R * C * (4 + 4)
    raise ValueError(call["kind"])
