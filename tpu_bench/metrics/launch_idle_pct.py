"""Share of the traced window in which the chip idles while the engine
prepares a model call's inputs, dispatches it or waits for its result:
device idle time under the program's ``serve.prefill`` and
``serve.decode`` spans (the innermost open), over the window. Moves
``tokens_per_s`` (chat) and ``ttft_p95_ms`` (code)."""
from tpu_bench.program_spans import idle_pct


def read(ctx):
    return idle_pct(ctx, ("serve.prefill", "serve.decode"))
