"""RWKV-6 recurrence Pallas TPU kernel.

Per head: state S ∈ (K, V);  o_t = r_t·(S + diag(u)·k_t v_tᵀ);
S ← diag(w_t)·S + k_t v_tᵀ, with data-dependent per-channel decay w_t.

Grid (B, H, T/C): the time-chunk axis is innermost/"arbitrary" so the f32
state scratch persists across chunks; within a chunk the recurrence is a
fori_loop of vector ops + one (K,)·(K,V) matvec per step (the recurrence
is inherently serial in t; the chunk framing amortises HBM→VMEM traffic:
one DMA of (C,K)×4 operands per C steps). VMEM per step with C=64, K=64:
4·(C,K) + (K,K) f32 ≈ 80 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_final_ref, s_ref, *,
            chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)                        # (K,)
    r = r_ref[0, 0].astype(jnp.float32)                     # (C, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)

    def step(t, carry):
        s, out = carry
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)       # (1, K)
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)
        kv = kt.T * vt                                      # (K, V) outer
        # o_t = r·S + (r·u·k) v
        o_mat = jax.lax.dot(rt, s)                          # (1, V)
        o_bonus = jnp.sum(rt * u[None, :] * kt, axis=1, keepdims=True) * vt
        out = jax.lax.dynamic_update_slice_in_dim(
            out, o_mat + o_bonus, t, 0)
        s = wt.T * s + kv
        return s, out

    out0 = jnp.zeros((chunk, v.shape[1]), jnp.float32)
    s_fin, out = jax.lax.fori_loop(0, chunk, step, (s_ref[...], out0))
    s_ref[...] = s_fin
    o_ref[0, 0] = out.astype(o_ref.dtype)

    @pl.when(ic == nc - 1)
    def _final():
        s_final_ref[0, 0] = s_ref[...]


def rwkv6_forward(r, k, v, w, u, *, chunk: int = 64, interpret: bool = False):
    """r/k/v (B, H, T, K); w (B, H, T, K) decay in (0,1); u (H, K).
    Returns (o (B, H, T, K), final_state (B, H, K, K) f32)."""
    B, H, T, K = r.shape
    C = min(chunk, T)
    pad = (-T) % C
    if pad:
        padw = ((0, 0), (0, 0), (0, pad), (0, 0))
        r = jnp.pad(r, padw)
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
        w = jnp.pad(w, padw, constant_values=1.0)  # identity decay on pad
    nc = r.shape[2] // C

    kernel = functools.partial(_kernel, chunk=C)
    o, s_fin = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, C, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, K), lambda b, h, c: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, K, K), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, r.shape[2], K), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, K), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)
    return o[:, :, :T], s_fin
