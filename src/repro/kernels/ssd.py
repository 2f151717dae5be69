"""Mamba-2 SSD chunk-scan Pallas TPU kernel.

The state-space dual form turns the recurrence into MXU-friendly work:
  intra-chunk  y = (L ⊙ (C Bᵀ)) · x̃         — (C,C)·(C,P) matmuls
  state pass   S ← γ·S + (x̃·δ_end)ᵀ B       — (P,C)·(C,N) matmul
  inter-chunk  y += (C ⊙ e^cum) Sᵀ_prev      — (C,N)·(N,P) matmul
All chunk math runs on the MXU; the only serial dependency is the (P,N)
state carried in VMEM scratch across the innermost chunk axis.
VMEM per step (C=128, P=64, N=64): ~0.6 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, y_ref, s_final_ref,
            s_ref, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0, 0].astype(jnp.float32)                    # (C, P)
    dt = dt_ref[0, 0].astype(jnp.float32)                  # (C,)
    a = -jnp.exp(alog_ref[0].astype(jnp.float32))          # scalar (head)
    Bm = b_ref[0, 0].astype(jnp.float32)                   # (C, N)
    Cm = c_ref[0, 0].astype(jnp.float32)                   # (C, N)

    da = dt * a                                            # (C,) ≤ 0
    cum = jnp.cumsum(da)                                   # (C,)
    xw = x * dt[:, None]                                   # x̃ = dt-weighted

    # intra-chunk: M[i,j] = exp(cum_i - cum_j) · (C_i·B_j), causal
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (C, C)
    dmat = cum[:, None] - cum[None, :]
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(i_idx >= j_idx, jnp.exp(dmat), 0.0)
    y = jax.lax.dot(CB * L, xw)                            # (C, P)

    # inter-chunk: y += (C ⊙ e^cum) · S_prevᵀ
    s_prev = s_ref[...]                                    # (P, N)
    y = y + jax.lax.dot(Cm * jnp.exp(cum)[:, None], s_prev.T)

    # state update: S ← γ·S + (x̃·δ_end)ᵀ B
    dec_end = jnp.exp(cum[-1] - cum)                       # (C,)
    s_ref[...] = (s_prev * jnp.exp(cum[-1])
                  + jax.lax.dot((xw * dec_end[:, None]).T, Bm))
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _final():
        s_final_ref[0, 0] = s_ref[...]


def ssd_forward(x, dt, a_log, Bm, Cm, *, chunk: int = 128,
                interpret: bool = False):
    """x (B, H, T, P); dt (B, H, T) f32 post-softplus; a_log (H,);
    Bm/Cm (B, T, N). Returns (y (B,H,T,P), final_state (B,H,P,N) f32)."""
    B, H, T, P = x.shape
    N = Bm.shape[-1]
    C = min(chunk, T)
    assert T % C == 0, f"T={T} must be a multiple of chunk={C}"
    nc = T // C

    kernel = functools.partial(_kernel, chunk=C)
    y, s_fin = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, C, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, C, N), lambda b, h, c: (b, 0, c, 0)),
            pl.BlockSpec((1, 1, C, N), lambda b, h, c: (b, 0, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a_log, Bm[:, None], Cm[:, None])
    return y, s_fin
