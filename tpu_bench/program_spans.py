"""The serving engine's own host spans in a traced run, and the chip's
idle time split by them.

``MultiTenantEngine`` wraps its host work in profiler spans named
``serve.*`` (``serve.step``, ``serve.admit``, ``serve.prefill``,
``serve.decode``, ``serve.commit``, ``serve.round``). They land on the
clock of the device's events, beside the harness's own spans. A program
without them, or a trace of another run, gives nothing to read: the
readers then return None.

    python tpu_bench/program_spans.py [<trace dir>]

prints, for the newest trace (under ``.bench_out/trace`` by default),
the device's idle time by innermost program span, the steps and the
longest single idle gap with the span it falls in.
"""
from __future__ import annotations

import glob
import json
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from tpu_bench import run  # noqa: E402
from tpu_bench.trace import clip  # noqa: E402

PREFIX = "serve."
NONE = "no program span"
_loaded: dict = {}   # trace file -> (window, spans)


def newest_trace(root=None) -> str | None:
    files = glob.glob(os.path.join(str(root or run.OUT_DIR / "trace"), "**",
                                   "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def read_trace(path: str):
    """(window, spans) of one trace file: the harness's ``window`` span
    and the program's spans as (name, start, end), in seconds on the
    trace's clock, as ``trace.reduce_profile`` reads the host's events.
    Loaded once per process."""
    if path not in _loaded:
        from jax.profiler import ProfileData

        window, spans = None, []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "window" or ev.name.startswith(PREFIX):
                        iv = (ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                        if ev.name != "window":
                            spans.append((ev.name, *iv))
                        elif window is None:
                            window = iv
        _loaded[path] = (window, spans)
    return _loaded[path]


def load(ctx) -> list | None:
    """The program's spans in this run's trace; None unless the newest
    trace is this run's (its window is ``ctx.red.window``) and holds
    any."""
    path = newest_trace()
    if path is None:
        return None
    window, spans = read_trace(path)
    if window != ctx.red.window or not spans:
        return None
    return spans


def timeline(spans, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """[lo, hi] cut into pieces (start, end, name), each named after the
    innermost span open over it (``no program span`` where none is).
    The spans come from one thread, so they nest: a span that starts
    inside another ends inside it (a later end is cut to the outer's)."""
    out: list[list] = []

    def put(a, b, name):
        if b <= a:
            return
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1][1] = b
        else:
            out.append([a, b, name])

    inside = sorted(((a, b, n) for n, s, e in spans
                     for a, b in clip([(s, e)], lo, hi)),
                    key=lambda x: (x[0], -x[1]))
    stack: list[tuple[float, str]] = []     # (end, name), innermost last
    t = lo
    for s, e, name in inside:
        while stack and stack[-1][0] <= s:
            end, inner = stack.pop()
            put(t, end, inner)
            t = max(t, end)
        put(t, s, stack[-1][1] if stack else NONE)
        t = max(t, s)
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        end, inner = stack.pop()
        put(t, end, inner)
        t = max(t, end)
    put(t, hi, NONE)
    return [tuple(x) for x in out]


def idle_intervals(red, dev) -> list[tuple[float, float]]:
    """The window's pieces in which no operation ran on one device."""
    lo, hi = red.window
    edges = [lo] + [x for iv in red.busy(dev) for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def idle_by_span(red, spans) -> dict[str, float]:
    """Device idle seconds in the window by the innermost program span
    open over them (``no program span`` where none is), averaged over
    the devices: the rule of ``Reduction.idle_gaps``, on the program's
    spans."""
    pieces = timeline(spans, *red.window)
    acc: dict[str, float] = {}
    for dev in red.devices:
        j = 0
        for a, b in idle_intervals(red, dev):
            while j < len(pieces) and pieces[j][1] <= a:
                j += 1
            i = j
            while i < len(pieces) and pieces[i][0] < b:
                x, y, name = pieces[i]
                acc[name] = acc.get(name, 0.0) + (
                    min(b, y) - max(a, x)) / len(red.devices)
                i += 1
    return acc


def longest_gap(red, spans) -> tuple[float, str] | None:
    """The longest single idle gap of any device in the window, in
    seconds, and the innermost program span open over most of it."""
    best = None
    for dev in red.devices:
        for a, b in idle_intervals(red, dev):
            if best is None or b - a > best[1] - best[0]:
                best = (a, b)
    if best is None:
        return None
    cover: dict[str, float] = {}
    for x, y, name in timeline(spans, *best):
        cover[name] = cover.get(name, 0.0) + y - x
    return best[1] - best[0], max(cover, key=cover.get)


def idle_pct(ctx, names) -> float | None:
    """100 × the device's idle seconds under the named program spans
    over the traced window; None where the run has no program spans."""
    spans = load(ctx)
    if spans is None or not ctx.red.devices or ctx.red.window_s <= 0:
        return None
    split = idle_by_span(ctx.red, spans)
    return 100.0 * sum(split.get(n, 0.0) for n in names) / ctx.red.window_s


def summary(red, spans) -> dict:
    """What ``main`` prints: idle seconds by span, steps, ms of idle
    per step by span, and the longest gap."""
    split = idle_by_span(red, spans)
    steps = sum(1 for n, s, e in spans if n == "serve.step"
                and red.window[0] <= s and e <= red.window[1])
    gap = longest_gap(red, spans)
    return {"window_s": red.window_s, "busy_s": red.busy_s,
            "idle_s": dict(sorted(split.items(), key=lambda x: -x[1])),
            "steps": steps,
            "idle_ms_per_step": {n: 1e3 * t / steps for n, t in split.items()}
            if steps else {},
            "longest_gap_ms": 1e3 * gap[0] if gap else None,
            "longest_gap_span": gap[1] if gap else None}


def main(argv=None) -> int:
    from tpu_bench.trace import reduce_profile

    argv = sys.argv[1:] if argv is None else argv
    path = newest_trace(argv[0] if argv else None)
    if path is None:
        print("program_spans: no trace found", file=sys.stderr)
        return 1
    from jax.profiler import ProfileData

    red = reduce_profile(ProfileData.from_file(path))
    _, spans = read_trace(path)
    print(json.dumps(dict(summary(red, spans), trace=path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
