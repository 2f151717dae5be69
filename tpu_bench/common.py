"""Shared pieces of the benchmark: its files, the chip, the arithmetic of
its statistics, and the result line.

Nothing here imports the program under test (``src/repro``): the cell
modules (``serving.py``, ``fleet.py``) and a serving configuration's
model module do, and only for the system itself.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import sys
from contextlib import contextmanager
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(name: str, bench: dict | None = None):
    """(workload entry, configuration file, traffic file) of one cell,
    each found by the name ``BENCHMARK.json`` gives it. The configuration
    keeps the path it was read from under ``file``, for its errors."""
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            entry = next(c for c in bench["configs"]
                         if c["name"] == w["config"])
            conf = dict(load_json(ROOT / entry["file"]), file=entry["file"])
            return (w, conf,
                    load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"))
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


#: what the model module of a serving configuration exports:
#: ``program_config(conf)``, the program's ``ModelConfig`` for the file;
#: ``make_weights(model, seed, tenant)``, one tenant's served weights in
#: the program's parameter tree, made on the device from the seed;
#: ``reference_logits(model, params, tokens, sel, *, fp8=False)``, the
#: plain float32 forward pass and its fp8 control; and the operations and
#: bytes of each measured call: ``prefill_flops(model, S)``,
#: ``prefill_bytes(model, S)``, ``decode_flops(model, contexts)``,
#: ``decode_bytes(model, contexts)``.
MODEL_CONTRACT = ("program_config", "make_weights", "reference_logits",
                  "prefill_flops", "prefill_bytes", "decode_flops",
                  "decode_bytes")


def model_module(conf: dict):
    """The model module that a serving configuration names under
    ``module`` (an import path). A configuration that names none, or a
    module that cannot be imported or lacks part of ``MODEL_CONTRACT``,
    is an error that names the configuration's file: there is no
    default model."""
    where = conf.get("file", "serving configuration (read from no file)")
    name = conf.get("module")
    if not isinstance(name, str) or not name:
        raise ValueError(f"{where}: names no model module under 'module'")
    try:
        mod = importlib.import_module(name)
    except ImportError as e:
        raise ImportError(f"{where}: cannot import its model module "
                          f"{name!r}: {e}") from e
    missing = [f for f in MODEL_CONTRACT
               if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"{where}: model module {name!r} lacks "
                         f"{', '.join(missing)}")
    return mod


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` that this cell
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def metric_reader(name: str):
    """The ``read(ctx)`` of a per-layer metric: ``metrics/<name>.py``, or
    for a name split by cell (``mfu_pct.code``) the file of its stem
    (``metrics/mfu_pct.py``)."""
    for stem in (name, name.split(".")[0]):
        path = BENCH_DIR / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"tpu_bench.metrics.{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {name!r}")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. A device not in the table is an
    error, never a default."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"tpu_bench/peaks.json")
    return table[device_kind]


def percentile(values, q: float) -> float:
    """``np.percentile`` with linear interpolation over the raw sample:
    the arithmetic of ``repro.obs.metrics.percentile_bands``, copied so
    that the yardstick does not move with the program."""
    return float(np.percentile(np.asarray(values, np.float64), q))


class CompileCounter:
    """Counts the programs JAX lowers (each new shape or function), so a
    run can say how many compiles fell inside its measured window."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **kw):
        if name == self.EVENT:
            self.count += 1


def device_info(n_used: int) -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n_used}


def memory_peak_bytes(n_used: int) -> int | None:
    """Peak bytes in use on the fullest of the chips used."""
    import jax

    peaks_ = []
    for d in jax.devices()[:n_used]:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


@contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def emit(result: dict, checks: dict) -> None:
    """Print the compared numbers beside their limits as the last lines
    of standard error, then the result as the last line of standard
    output, with the same numbers under ``checks``, last."""
    for name, c in checks.items():
        print(f"check {name}: value={c['value']!r} limit={c['limit']!r} "
              f"ok={c['ok']}", file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)


def check(value: float, limit: float) -> dict:
    """One compared number: it passes when it does not exceed its limit."""
    ok = bool(np.isfinite(value) and value <= limit)
    return {"value": float(value), "limit": float(limit), "ok": ok}
