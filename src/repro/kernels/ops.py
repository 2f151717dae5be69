"""Jit'd public wrappers for the Pallas kernels.

The kernels compile for the TPU. ``interpret=True`` runs them through the
Pallas interpreter instead, which is how tests exercise them on the CPU;
it is never chosen for the caller.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.rwkv6 import rwkv6_forward as _rwkv6
from repro.kernels.ssd import ssd_forward as _ssd


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=256,
                    block_k=256, interpret=False):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pool, v_pool, page_table, lengths, *,
                    interpret=False):
    return _paged(q, k_pool, v_pool, page_table, lengths,
                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_forward(r, k, v, w, u, *, chunk=64, interpret=False):
    return _rwkv6(r, k, v, w, u, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_forward(x, dt, a_log, Bm, Cm, *, chunk=128, interpret=False):
    return _ssd(x, dt, a_log, Bm, Cm, chunk=chunk, interpret=interpret)
