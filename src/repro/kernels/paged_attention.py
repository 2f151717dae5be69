"""Paged decode attention — the DYVERSE-technique Pallas TPU kernel.

Multi-tenant serving keeps every tenant's KV cache in a shared page pool;
DYVERSE vertical scaling moves page quotas between tenants WITHOUT moving
data. The decode kernel therefore reads K/V through a page table
indirection. On TPU the page table rides in scalar-prefetch SMEM
(PrefetchScalarGridSpec) and the BlockSpec index_map dereferences it, so
each grid step DMAs exactly one page from HBM into VMEM — no gather
materialisation, no defragmentation when quotas change.

Layouts:
  q        (B, H, D)           — one new token per sequence
  k_pool   (KH, P, page, D)    — the shared pool (per layer)
  v_pool   (KH, P, page, D)
  page_table (B, max_pages) int32
  lengths  (B,) int32          — valid tokens per sequence
Grid (B, KH, max_pages); online softmax accumulates in VMEM scratch over
a sequence's pages; pages past ceil(len/page) are skipped via pl.when.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, page: int, scale: float, G: int):
    b = pl.program_id(0)
    ip = pl.program_id(2)
    npages = pl.num_programs(2)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = len_ref[b]
    used_pages = pl.cdiv(seq_len, page)

    @pl.when(ip < used_pages)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, page)
        tok = ip * page + jax.lax.broadcasted_iota(jnp.int32, (1, k.shape[0]), 1)
        mask = tok < seq_len
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ip == npages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, page_table, lengths, *,
                    interpret: bool = False):
    """q (B,H,D); pools (KH,P,page,D); page_table (B,max_pages) int32;
    lengths (B,) int32 → (B,H,D)."""
    B, H, D = q.shape
    KH, P, page, _ = k_pool.shape
    G = H // KH
    max_pages = page_table.shape[1]
    # (B, KH, G, D) so each grid step handles one sequence × kv-head group
    qg = q.reshape(B, KH, G, D)

    kernel = functools.partial(_kernel, page=page, scale=D ** -0.5, G=G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,     # page_table, lengths
        grid=(B, KH, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, p, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, p, pt, ln: (h, pt[b, p], 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, p, pt, ln: (h, pt[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, p, pt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, lengths, qg, k_pool, v_pool)
    return out.reshape(B, H, D)
