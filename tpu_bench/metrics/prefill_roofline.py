"""Share of its roofline the jitted prefill reaches: the least time its
operations and bytes need at the chip's peaks (compute-bound at these
prompt lengths), over its device time in the trace."""
from tpu_bench.metrics._serving import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "prefill")
