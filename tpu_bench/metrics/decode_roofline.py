"""Share of its roofline the jitted decode reaches: the bytes it needs
(weights once, K/V of each live slot's real context) over the chip's
bandwidth, against its device time in the trace."""
from tpu_bench.metrics._serving import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "decode")
