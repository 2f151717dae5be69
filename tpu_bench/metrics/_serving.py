"""Readers shared by the serving cells' per-layer metrics. A call's
operations and bytes come from the model module that the cell's
configuration names (``common.model_module``)."""
from __future__ import annotations

from tpu_bench import counters
from tpu_bench.common import model_module

PROGRAMS = {"prefill": "jit_prefill_fn", "decode": "jit_decode_fn"}


def calls(ctx, kind: str) -> list:
    """The window's calls of one kind, from the harness's call log."""
    if "window" not in ctx.out:
        return []
    return [c for c in ctx.out["window"]["calls"] if c[0] == kind]


def device_per_call(ctx, kind: str):
    """(calls in the trace, device seconds) of the jitted prefill or
    decode; None unless the trace saw exactly the calls the log saw."""
    n, t = ctx.red.module_time(PROGRAMS[kind])
    logged = calls(ctx, kind)
    if n == 0 or n != len(logged):
        return None
    return n, t


def least_time(ctx, call) -> float:
    arch, model = model_module(ctx.conf), ctx.conf["model"]
    if call[0] == "prefill":
        f, b = (arch.prefill_flops(model, call[2]),
                arch.prefill_bytes(model, call[2]))
    else:
        f, b = (arch.decode_flops(model, call[2]),
                arch.decode_bytes(model, call[2]))
    return counters.roofline_s(f, b, ctx.peaks)


def roofline_pct(ctx, kind: str):
    got = device_per_call(ctx, kind)
    if got is None:
        return None
    return 100.0 * sum(least_time(ctx, c) for c in calls(ctx, kind)) / got[1]


def call_ms(ctx, kind: str):
    got = device_per_call(ctx, kind)
    return None if got is None else 1e3 * got[1] / got[0]


def model_flops(ctx) -> float:
    arch, model = model_module(ctx.conf), ctx.conf["model"]
    return sum(arch.prefill_flops(model, c[2]) if c[0] == "prefill"
               else arch.decode_flops(model, c[2])
               for c in ctx.out["window"]["calls"])
