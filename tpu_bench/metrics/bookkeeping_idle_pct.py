"""Share of the traced window in which the chip idles while the engine
runs its own Python: device idle time under the program's
``serve.admit``, ``serve.commit`` and ``serve.round`` spans (the
innermost open), over the window. Moves ``tokens_per_s`` (chat) and
``ttft_p95_ms`` (code)."""
from tpu_bench.program_spans import idle_pct


def read(ctx):
    return idle_pct(ctx, ("serve.admit", "serve.commit", "serve.round"))
