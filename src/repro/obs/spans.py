"""Host spans of the serving engine, on the profiler's clock.

``MultiTenantEngine`` wraps its host work in
``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` for the step).
With a profiler session running, each span lands in the trace beside the
device's own events, so the device's idle time can be laid against what
the host was doing; with none running, a span costs about a microsecond.
Metadata rides as keyword arguments, which the trace keeps as event
stats; the event name stays the bare span name.

The vocabulary is pinned by tests against a recorded trace, so that the
readers of the spans and the engine stay in step.
"""
from __future__ import annotations

SPAN_NAMES = frozenset({
    "serve.step",     # one MultiTenantEngine.step; step_num
    "serve.admit",    # the scheduler's admit_waiting for one tenant; tenant
    "serve.prefill",  # prompt upload, prefill, cache insert, argmax sync;
                      # tenant, rid, tokens
    "serve.decode",   # one tenant's decode: inputs up, call, argmax back on
                      # the host; tenant, live (slots decoding)
    "serve.commit",   # the decode's per-slot bookkeeping: appends, finishes,
                      # the monitor's records; tenant
    "serve.round",    # one DYVERSE controller round, every
                      # round_interval_steps steps
})
