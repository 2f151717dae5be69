"""Time requests spend in the scheduler's queue: over the requests due
in the window and sent to the engine, the p95 of the engine's first
admission stamp less its arrival stamp (``admit_t - arrival_t``, both
on the engine's clock), in ms. A request not admitted by the close
counts at its age then, as ``ttft_p95_ms`` counts one unanswered.
Moves ``ttft_p95_ms``. None for a program that stamps no admission."""
from tpu_bench.common import percentile


def read(ctx):
    w = ctx.out.get("window")
    if w is None:
        return None
    waits = []
    for s in w["served"]:
        if not s.in_window or s.rs is None:
            continue
        if not hasattr(s.rs, "admit_t"):
            return None
        if s.rs.admit_t is None:
            waits.append(w["window_s"] - s.submit_t)
        else:
            waits.append(s.rs.admit_t - s.rs.req.arrival_t)
    return 1e3 * percentile(waits, 95) if waits else None
