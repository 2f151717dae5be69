"""Device time per call of the jitted prefill (``jit_prefill_fn``), from
the trace: the model step's prompt half. Moves ``ttft_p95_ms``."""
from tpu_bench.metrics._serving import call_ms


def read(ctx):
    return call_ms(ctx, "prefill")
