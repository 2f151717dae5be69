"""Profiler trace of a run's window, and its reduction to device busy
time, per-program device time and idle gaps attributed to host spans.

The harness wraps its calls into each layer in host spans
(``jax.profiler.TraceAnnotation``: ``window``, ``engine.step``,
``ctrl.round``, ``fleet.step``, ``fleet.round``), which land on the same
clock as the device's events. On a TPU the device planes are
``/device:TPU:<n>``: their ``XLA Ops`` line holds every operation run on
the chip, and their ``XLA Modules`` line one event per execution of a
compiled program, named after the jitted function (``jit_decode_fn``).
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path

HARNESS_SPANS = ("window", "engine.step", "ctrl.round", "fleet.step",
                 "fleet.round")
_HLO = re.compile(r"^(%[\w.\-]+) = (\w+\[[\d,]*\])\S* (\w[\w\-]*)\(")


def short_op(name: str) -> str:
    """``%broadcast.282 broadcast f32[8,2176,8,4,120]`` for a full HLO
    text of an operation; its name alone where the text has another form."""
    m = _HLO.match(name)
    if m:
        return f"{m.group(1)} {m.group(3)} {m.group(2)}"
    return name.split(" = ")[0][:120]


def tpu_lines(plane_name: str, line_name: str) -> str | None:
    """Which device line a (plane, line) of a TPU trace is, if any."""
    if plane_name.startswith("/device:TPU:"):
        if line_name == "XLA Ops":
            return "ops"
        if line_name == "XLA Modules":
            return "modules"
    return None


class Tracer:
    """Starts and stops the profiler around a window, with the Python
    function tracer off (it would trace every Python call)."""

    def __init__(self, out_dir: Path):
        self.dir = Path(out_dir)

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def reduce(self, select=tpu_lines) -> "Reduction":
        files = sorted(glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise FileNotFoundError(f"no trace under {self.dir}")
        from jax.profiler import ProfileData

        return reduce_profile(ProfileData.from_file(files[-1]), select)


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


@dataclass
class Reduction:
    """What a trace says, in seconds on the trace's own clock."""
    window: tuple[float, float]
    host: list = field(default_factory=list)       # (name, start, end)
    devices: dict = field(default_factory=dict)    # id -> {"ops", "modules"}

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self, device) -> list[tuple[float, float]]:
        ops = [(s, e) for _, s, e in self.devices[device]["ops"]]
        return clip(union(ops), *self.window)

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(sum(b - a for a, b in self.busy(d))
                   for d in self.devices) / len(self.devices)

    def module_time(self, prefix: str) -> tuple[int, float]:
        """(executions, device seconds) of the programs whose name starts
        with ``prefix``, inside the window, summed over devices."""
        n, total = 0, 0.0
        lo, hi = self.window
        for dev in self.devices.values():
            for name, s, e in dev["modules"]:
                if name.startswith(prefix) and s >= lo and e <= hi:
                    n += 1
                    total += e - s
        return n, total

    def top_ops(self, k: int = 10) -> list:
        """The device operations that took most time in the window."""
        acc: dict[str, float] = {}
        lo, hi = self.window
        for dev in self.devices.values():
            for name, s, e in dev["ops"]:
                if e > lo and s < hi:
                    key = short_op(name)
                    acc[key] = acc.get(key, 0.0) + min(e, hi) - max(s, lo)
        return sorted(([n, t] for n, t in acc.items()),
                      key=lambda x: -x[1])[:k]

    def host_timeline(self) -> list[tuple[float, float, str]]:
        """The window cut into pieces, each named after the innermost
        harness span open during it (``no harness span`` where none is)."""
        import numpy as np

        lo, hi = self.window
        spans = [x for x in self.host
                 if x[0] in HARNESS_SPANS and x[0] != "window"]
        cuts = np.unique(np.clip([lo, hi] + [t for _, s, e in spans
                                             for t in (s, e)], lo, hi))
        s0 = np.array([s for _, s, _ in spans])
        s1 = np.array([e for _, _, e in spans])
        out: list[list] = []
        for i in range(0, len(cuts) - 1, 1024):
            a, b = cuts[i:i + 1025][:-1], cuts[i + 1:i + 1025]
            mid = ((a + b) / 2)[:, None]
            inside = (s0[None] <= mid) & (mid <= s1[None])
            for j, (x, y) in enumerate(zip(a, b)):
                row = inside[j]
                name = (spans[int(np.where(row, s1 - s0, np.inf).argmin())][0]
                        if row.any() else "no harness span")
                if out and out[-1][2] == name and out[-1][1] == x:
                    out[-1][1] = float(y)
                else:
                    out.append([float(x), float(y), name])
        return [tuple(x) for x in out]

    def idle_gaps(self, k: int = 10) -> list:
        """Device idle time in the window, split by what the host was
        doing meanwhile (the innermost harness span), summed by span name
        and averaged over the devices."""
        acc: dict[str, float] = {}
        lo, hi = self.window
        timeline = self.host_timeline()
        for dev in self.devices:
            busy = self.busy(dev)
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
            j = 0
            for a, b in gaps:
                while j < len(timeline) and timeline[j][1] <= a:
                    j += 1
                i = j
                while i < len(timeline) and timeline[i][0] < b:
                    x, y, name = timeline[i]
                    acc[name] = acc.get(name, 0.0) + (
                        min(b, y) - max(a, x)) / len(self.devices)
                    i += 1
        return sorted(([n, t] for n, t in acc.items()),
                      key=lambda x: -x[1])[:k]


def reduce_profile(profile, select=tpu_lines) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Reduction`.
    ``select(plane, line)`` names the device lines (``"ops"``,
    ``"modules"``); the harness's spans come from whichever line of the
    host plane holds them (the thread that ran the window). The window is
    the harness's ``window`` span."""
    host, devices = [], {}
    for plane in profile.planes:
        for line in plane.lines:
            kind = select(plane.name, line.name)
            if kind is not None:
                dev = devices.setdefault(plane.name,
                                         {"ops": [], "modules": []})
                dev[kind].extend((ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9)
                                 for ev in line.events)
            elif plane.name.startswith("/host:"):
                host.extend((ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events
                            if ev.name in HARNESS_SPANS)
    windows = [(s, e) for n, s, e in host if n == "window"]
    if not windows:
        raise ValueError("the trace has no 'window' span")
    return Reduction(window=windows[0], host=host, devices=devices)
