"""The program's own spans in a traced run: on a trace of the tiny
serving cell recorded on the CPU here, and on hand-made reductions and
windows whose answers are known."""
import copy
import importlib
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_CHAT, TINY_SERVING
from tpu_bench import program_spans, run
from tpu_bench.common import span
from tpu_bench.trace import Reduction, Tracer

READERS = ("launch_idle_pct", "bookkeeping_idle_pct", "queue_wait_p95_ms")


def cpu_lines(plane, line):
    """On the CPU, XLA's operations run on the PjRt client's threads."""
    if plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient"):
        return "ops"
    return None


def reader(name):
    return importlib.import_module(f"tpu_bench.metrics.{name}").read


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A traced window of the tiny serving cell, as ``run.measure`` lays
    it out under ``OUT_DIR``: (the readers' ctx, the program's spans)."""
    from tpu_bench.serving import ServingCell

    out_dir = tmp_path_factory.mktemp("bench_out")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "OUT_DIR", out_dir)
        conf, traffic = copy.deepcopy(TINY_SERVING), copy.deepcopy(TINY_CHAT)
        cell = ServingCell(conf, traffic, seed=2**33 + 7)
        cell.warm()
        tracer = Tracer(out_dir / "trace" / "serve-chat")
        w = cell.window(1.0, tracer.start)
        tracer.stop()
        red = tracer.reduce(select=cpu_lines)
        ctx = SimpleNamespace(conf=conf, traffic=traffic, out={"window": w},
                              red=red, peaks=None)
        spans = program_spans.load(ctx)
        yield ctx, spans


def test_recorded_spans_match_the_call_log(recorded):
    ctx, spans = recorded
    names = [n for n, _, _ in spans]
    calls = [c[0] for c in ctx.out["window"]["calls"]]
    assert names.count("serve.prefill") == calls.count("prefill") > 0
    assert names.count("serve.decode") == calls.count("decode") > 0
    assert names.count("serve.step") == ctx.out["window"]["steps"]
    assert "serve.round" in names


def test_every_program_span_lies_inside_an_engine_step(recorded):
    ctx, spans = recorded
    steps = sorted((s, e) for n, s, e in ctx.red.host if n == "engine.step")
    starts = np.array([s for s, _ in steps])
    for name, s, e in spans:
        i = int(np.searchsorted(starts, s, side="right")) - 1
        assert i >= 0 and steps[i][0] <= s and e <= steps[i][1], name


def test_idle_by_span_sums_to_window_less_busy(recorded):
    ctx, spans = recorded
    red = ctx.red
    split = program_spans.idle_by_span(red, spans)
    assert set(split) <= {program_spans.NONE} | {n for n, _, _ in spans}
    assert sum(split.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6, abs=1e-9)
    gap, where = program_spans.longest_gap(red, spans)
    assert 0 < gap <= red.window_s - red.busy_s and where in split
    # each reader finds its spans, and the two idle shares part the idle
    launch, book, wait = (reader(n)(ctx) for n in READERS)
    assert 0 < launch and 0 < book
    assert launch + book <= 100.0 * (1 - red.busy_s / red.window_s) + 1e-9
    assert wait >= 0


def test_main_prints_the_steps_of_the_newest_trace(recorded, capsys):
    ctx, _ = recorded
    assert program_spans.main([str(run.OUT_DIR / "trace")]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["steps"] == ctx.out["window"]["steps"]
    assert out["window_s"] == pytest.approx(ctx.red.window_s)


def test_readers_refuse_another_runs_trace(recorded, tmp_path, monkeypatch):
    """A ctx whose window is not the newest trace's, and a trace of a
    program that writes no spans, give nothing to read."""
    ctx, _ = recorded
    lo, hi = ctx.red.window
    other = SimpleNamespace(**vars(ctx))
    other.red = Reduction(window=(lo, hi + 1.0), host=ctx.red.host,
                          devices=ctx.red.devices)
    assert program_spans.load(other) is None
    monkeypatch.setattr(run, "OUT_DIR", tmp_path)
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracer = Tracer(tmp_path / "trace" / "serve-chat")
    tracer.start()
    with span("window"):
        with span("engine.step"):
            f(x).block_until_ready()
    tracer.stop()
    red = tracer.reduce(select=cpu_lines)
    bare = SimpleNamespace(red=red, out={})
    assert program_spans.load(bare) is None
    assert reader("launch_idle_pct")(bare) is None
    assert reader("bookkeeping_idle_pct")(bare) is None


def _hand_made():
    """One device idle in [0, 2.5], [3.5, 4.5] and [5.5, 10]; a second
    busy throughout, so every idle second counts half."""
    red = Reduction(
        window=(0.0, 10.0), host=[("window", 0.0, 10.0)],
        devices={"/device:TPU:0": {"ops": [("a", 2.5, 3.5), ("b", 4.5, 5.5)],
                                   "modules": []},
                 "/device:TPU:1": {"ops": [("c", 0.0, 10.0)],
                                   "modules": []}})
    spans = [("serve.step", 1.0, 9.0), ("serve.admit", 1.0, 2.0),
             ("serve.prefill", 2.0, 4.0), ("serve.decode", 4.0, 6.0),
             ("serve.commit", 6.0, 7.0), ("serve.round", 7.5, 9.0)]
    return red, spans


def test_idle_by_span_by_hand():
    red, spans = _hand_made()
    # device 0's idle: [0,1] none, [1,2] admit (which shares its start
    # with the step), [2,2.5] and [3.5,4] prefill, [4,4.5] and [5.5,6]
    # decode, [6,7] commit, [7,7.5] the step alone, [7.5,9] round,
    # [9,10] none; device 1 idles never
    assert program_spans.idle_by_span(red, spans) == pytest.approx({
        "no program span": 1.0, "serve.admit": 0.5, "serve.prefill": 0.5,
        "serve.decode": 0.5, "serve.commit": 0.5, "serve.step": 0.25,
        "serve.round": 0.75})
    assert program_spans.longest_gap(red, spans) == (4.5, "serve.round")
    assert program_spans.timeline(spans, 0.0, 10.0)[:3] == [
        (0.0, 1.0, "no program span"), (1.0, 2.0, "serve.admit"),
        (2.0, 4.0, "serve.prefill")]


def test_idle_readers_by_hand(monkeypatch):
    red, spans = _hand_made()
    monkeypatch.setattr(program_spans, "load", lambda ctx: spans)
    ctx = SimpleNamespace(red=red)
    assert reader("launch_idle_pct")(ctx) == pytest.approx(10.0)
    assert reader("bookkeeping_idle_pct")(ctx) == pytest.approx(17.5)


def _served(wait_s, in_window=True, admitted=True, submit_t=0.0):
    rs = SimpleNamespace(admit_t=100.0 + wait_s if admitted else None,
                         req=SimpleNamespace(arrival_t=100.0))
    return SimpleNamespace(in_window=in_window, rs=rs, submit_t=submit_t)


def test_queue_wait_reader_by_hand():
    """19 requests wait 0.1 s; one submitted at 9.0 s is still queued
    when the 10 s window closes (1.0 s); one due before the window and
    one never sent do not count."""
    served = [_served(0.1) for _ in range(19)]
    served += [_served(0.0, admitted=False, submit_t=9.0),
               _served(50.0, in_window=False),
               SimpleNamespace(in_window=True, rs=None, submit_t=0.0)]
    ctx = SimpleNamespace(out={"window": {"served": served,
                                          "window_s": 10.0}})
    want = 1e3 * np.percentile([0.1] * 19 + [1.0], 95)
    assert reader("queue_wait_p95_ms")(ctx) == pytest.approx(want)
    # a program that stamps no admission gives nothing to read
    for s in served[:20]:
        del s.rs.admit_t
    assert reader("queue_wait_p95_ms")(ctx) is None
