"""Whole-step share of the chip's peak: the model FLOPs of every prefill
and decode token of the traced window, over the window times the bf16
peak. Silent in cells that run no model."""
from tpu_bench.metrics._serving import model_flops


def read(ctx):
    if ctx.conf["kind"] != "serving" or ctx.peaks is None:
        return None
    window_s = ctx.out["window"]["window_s"]
    return 100.0 * model_flops(ctx) / (window_s * ctx.peaks["flops_bf16"])
