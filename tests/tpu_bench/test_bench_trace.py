"""The trace reduction: on a tiny trace recorded on the CPU here, and on
a hand-made reduction whose answers are known."""
import jax
import jax.numpy as jnp
import pytest

from tpu_bench.common import span
from tpu_bench.trace import Reduction, Tracer, clip, union


def cpu_lines(plane, line):
    """On the CPU, XLA's operations run on the PjRt client's threads."""
    if plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient"):
        return "ops"
    return None


def test_reduction_of_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    tracer = Tracer(tmp_path)
    tracer.start()
    with span("window"):
        for _ in range(3):
            with span("engine.step"):
                f(x).block_until_ready()
    tracer.stop()
    red = tracer.reduce(select=cpu_lines)
    assert [n for n, _, _ in red.host].count("engine.step") == 3
    assert 0 < red.busy_s <= red.window_s
    gaps = red.idle_gaps(10)
    assert sum(t for _, t in gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6, abs=1e-9)
    assert {n for n, _ in gaps} <= {"engine.step", "no harness span"}
    assert any("dot" in name for name, _ in red.top_ops(10))


def test_union_and_clip():
    assert union([(3, 4), (0, 1), (0.5, 2), (4, 5)]) == [(0, 2), (3, 5)]
    assert clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]


def test_busy_modules_and_idle_attribution_by_hand():
    red = Reduction(
        window=(0.0, 10.0),
        host=[("window", 0.0, 10.0), ("engine.step", 1.0, 5.0),
              ("ctrl.round", 4.0, 5.0), ("fleet.step", 6.0, 9.0)],
        devices={"/device:TPU:0": {
            "ops": [("a", 1.0, 2.0), ("b", 1.5, 3.0), ("a", 6.0, 7.0),
                    ("c", 11.0, 12.0)],
            "modules": [("jit_decode_fn(1)", 1.0, 3.0),
                        ("jit_decode_fn(1)", 6.0, 7.0),
                        ("jit_prefill_fn(2)", 9.5, 11.0)]}})
    assert red.busy_s == 3.0 and red.window_s == 10.0
    assert red.module_time("jit_decode_fn") == (2, 3.0)
    assert red.module_time("jit_prefill_fn") == (0, 0.0)   # leaves window
    assert red.top_ops(2) == [["a", 2.0], ["b", 1.5]]
    gaps = dict(red.idle_gaps(10))
    # idle [0,1] under no span; [3,6]: [3,4] in engine.step, [4,5] in
    # ctrl.round (the innermost), [5,6] under none; [7,10]: [7,9] in
    # fleet.step, [9,10] under none
    assert gaps == pytest.approx({"no harness span": 3.0, "engine.step": 1.0,
                                  "ctrl.round": 1.0, "fleet.step": 2.0})
