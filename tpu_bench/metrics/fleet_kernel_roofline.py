"""Share of the bandwidth roofline the fleet kernels reach: the bytes
they must read and write, from their shapes, over 819 GB/s, against
their device time in the trace. Bytes-bound, since the threefry draw is
vector-unit work and no FLOP roofline applies.

The kernels are jitted from ``functools.partial`` objects, so their
programs are named ``jit__unknown``; the Poisson kernel is
``jit__poisson_impl``. No other program of that name runs in a fleet
window. The share is taken per call: the calls' mean least time over
the traced executions' mean device time, which is the whole window's
share where the trace holds every execution."""
from tpu_bench import counters

PROGRAMS = ("jit__unknown", "jit__poisson_impl")


def read(ctx):
    calls = ctx.out.get("kernel_calls")
    if not calls or ctx.peaks is None:
        return None
    n, t = 0, 0.0
    for prefix in PROGRAMS:
        k, s = ctx.red.module_time(prefix)
        n, t = n + k, t + s
    if n == 0 or t <= 0:
        return None
    least = sum(counters.fleet_kernel_bytes(c) for c in calls) \
        / ctx.peaks["hbm_bytes_per_s"] / len(calls)
    return 100.0 * least / (t / n)
