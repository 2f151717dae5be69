"""Run one cell of the benchmark on the chip it is started on.

    python tpu_bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic are found by name from
``BENCHMARK.json``. The run refuses to start without a TPU, or with
fewer chips than the cell asks for. It builds the cell (set-up, timed
as ``setup_s``), measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints as its last line one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; then ``checks``,
each compared number beside its limit.
"""
from __future__ import annotations

import argparse
import importlib
import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
#: what runs leave behind (traces), inside the checkout
OUT_DIR = ROOT / ".bench_out"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(chips: int) -> str | None:
    """Why the run cannot measure here, or None when it can."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return (f"no TPU found (JAX platform is {devs[0].platform!r}); "
                f"this benchmark measures only on a TPU")
    if len(devs) < chips:
        return f"the cell needs {chips} chips, found {len(devs)}"
    return None


def per_layer(bench, name, ctx) -> dict:
    from tpu_bench.common import cell_metrics, metric_reader

    out = {}
    for m in cell_metrics(bench, name, "per_layer"):
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def measure(args, bench, require_chip: bool = True,
            files: tuple | None = None) -> tuple[dict, dict]:
    """Build, time and check one cell. Returns (result, checks).
    ``files`` stands in for the cell's (configuration, traffic) files,
    and ``require_chip=False`` skips the look for a chip: both for tests
    that drive a run on the CPU at a tiny size."""
    from tpu_bench.common import (cell_metrics, device_info, find_cell,
                                  peaks)
    from tpu_bench.trace import Tracer

    entry, conf, traffic = find_cell(args.workload, bench)
    if files is not None:
        conf, traffic = files
    if require_chip:
        why = find_chips(entry["chips"])
        if why:
            raise SystemExit(f"run.py: {why}")
    dev = device_info(entry["chips"])
    tracer = None
    if args.trace:
        trace_dir = OUT_DIR / "trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = Tracer(trace_dir)
    cell_mod = importlib.import_module(f"tpu_bench.{conf['kind']}")
    out = cell_mod.run(conf, traffic, args.seed, args.seconds, tracer)
    print("run: " + json.dumps(out["info"]), file=sys.stderr, flush=True)
    dev["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": all(c["ok"] for c in out["checks"].values()),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": {}, "device": dev}
    if args.trace:
        t0 = time.perf_counter()
        red = tracer.reduce()
        ctx = SimpleNamespace(conf=conf, traffic=traffic, out=out, red=red,
                              peaks=peaks(dev["kind"]) if require_chip
                              else None)
        result["metrics"] = per_layer(bench, args.workload, ctx)
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.idle_gaps(10)}
        print(f"run: trace reduced in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)
    else:
        values = dict(out["e2e"], setup_s=out["setup_s"])
        for m in cell_metrics(bench, args.workload, "end_to_end"):
            result["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                            "unit": m["unit"]}
    return result, out["checks"]


def setup_process() -> None:
    """Import paths, and JAX's persistent compilation cache in the
    checkout (``JAX_COMPILATION_CACHE_DIR`` where it is set)."""
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # cache every program, however fast it compiled, so that only the
    # first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    args = parse(argv)
    setup_process()
    from tpu_bench.common import benchmark, emit

    result, checks = measure(args, benchmark())
    emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
