"""repro.obs — observability for the DYVERSE repro, on two clocks.

- The flight recorder records control decisions on the *virtual* clock
  of the simulator and the serving federation: what was decided, when,
  and why.
- The serving engine's spans record its host work on the *profiler's*
  clock, the one the device trace uses: what the host was doing while
  the chip ran or idled.

The pieces:

- :class:`FlightRecorder` — a bounded ring of typed structured
  :class:`Event` records (placement / eviction / scale_up /
  scale_down / donation / terminate, node fail/recover/degrade, WAN
  fault windows, serving admit/preempt/retry/timeout/shed/
  cloud_fallback, per-round spans), each stamped with the virtual
  clock, round index, node, tenant slot, and cause. Threaded through
  the controller, both federations, and the engine backends.
- :class:`MetricsRegistry` — counters / histograms, with the
  p50/p95/p99 band math (:func:`percentile_bands`) unified out of
  ``repro.serving.federation``.
- Exporters — JSONL event logs (:func:`write_events_jsonl`) and
  Chrome-trace / Perfetto ``trace.json`` (:func:`write_chrome_trace`):
  rounds as spans, events as instants, one track per node. Load the
  file at https://ui.perfetto.dev or ``chrome://tracing``.
- :data:`SPAN_NAMES` — the ``serve.*`` host spans of
  ``MultiTenantEngine`` (``jax.profiler`` annotations, read from a
  profiler trace; see :mod:`repro.obs.spans`).

Contract: tracing draws no RNG and perturbs no control decision —
every bitwise pin (engine trio, both control planes, serving
determinism) holds with tracing on. The recorder's off path is a
single ``is None`` predicate on the hot loops; a span with no profiler
session running costs about a microsecond.
"""
from repro.obs.events import EVENT_KINDS, Event  # noqa: F401
from repro.obs.export import (chrome_trace_events,  # noqa: F401
                              events_to_dicts, write_chrome_trace,
                              write_events_jsonl)
from repro.obs.metrics import (Counter, Histogram,  # noqa: F401
                               MetricsRegistry, percentile_bands)
from repro.obs.recorder import FlightRecorder  # noqa: F401
from repro.obs.spans import SPAN_NAMES  # noqa: F401

__all__ = [
    "EVENT_KINDS", "Event", "FlightRecorder",
    "SPAN_NAMES",
    "Counter", "Histogram", "MetricsRegistry",
    "percentile_bands",
    "chrome_trace_events", "events_to_dicts",
    "write_chrome_trace", "write_events_jsonl",
]
