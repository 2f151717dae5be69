"""Device time per call of the jitted decode step (``jit_decode_fn``),
from the trace: one token for every live slot of a tenant. Moves
``tokens_per_s``."""
from tpu_bench.metrics._serving import call_ms


def read(ctx):
    return call_ms(ctx, "decode")
