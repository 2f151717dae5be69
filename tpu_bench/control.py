"""Readings for the limits of ``correct``: the program's and the
control's numbers on several seeds, at a cell's own size and load, in
one process. The benchmark's own runs never run this.

    python tpu_bench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 12 [--out control.jsonl]

Serving: for each seed, a short window of the cell, then on the sampled
requests the widest logit gap of the served tokens (the program) and of
the tokens the fp8 control puts first (the control), both against the
float32 reference. Fleet: for each seed, a short window, then on the
checked chunks the program's numbers (its outputs, its controllers'
metrics and rounds) and the bfloat16 control's differences from the
float64 reference.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from tpu_bench.run import find_chips, setup_process  # noqa: E402


def serving_readings(conf, traffic, seed, seconds) -> dict:
    from tpu_bench import serving

    cell = serving.ServingCell(conf, traffic, seed)
    cell.warm()
    w = cell.window(seconds)
    cell.free()
    chk = traffic["check"]
    sample = serving.sample_for_check(w, seed, chk["tokens"],
                                      chk["max_requests"])
    prog = serving.reference_gaps(conf, traffic, seed, sample)
    ctl = serving.reference_gaps(conf, traffic, seed, sample, fp8=True)
    return {"program": {"max_logit_gap": max(prog)},
            "control": {"max_logit_gap": max(ctl)},
            "per_request": {"program": prog, "control": ctl},
            "checked_tokens": sum(len(s.rs.generated) for s in sample)}


def fleet_readings(conf, traffic, seed, seconds) -> dict:
    from tpu_bench import fleet, fleetref

    chk = traffic["check"]
    cell = fleet.FleetCell(conf, traffic, seed)
    for _ in range(traffic["warm_chunks"]):
        cell.chunk()
    cell.capture, cell.n_chunks = [], 0
    cell.sampled = fleet.sample_chunks(seed, chk["sample_every"])
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        cell.chunk()
    chunks = cell.capture
    prog, pick = fleet.check_chunks(cell, chunks, conf, traffic, seed)
    by_i = {c["i"]: c for c in chunks}
    ctl = []
    for i in pick:
        ref = fleetref.reference(by_i[i], cell.table, traffic, seed,
                                 band=chk["viol_band_rel"])
        ctl.append(fleetref.compare(fleetref.reference(
            by_i[i], cell.table, traffic, seed, dtype="bfloat16"), ref))
    worst = lambda ds: {k: max(d[k] for d in ds) for k in ds[0]}  # noqa
    return {"program": worst(prog), "control": worst(ctl),
            "chunks_checked": pick}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    setup_process()
    from tpu_bench.common import find_cell

    entry, conf, traffic = find_cell(args.workload)
    why = find_chips(entry["chips"])
    if why:
        raise SystemExit(f"control.py: {why}")
    read = serving_readings if conf["kind"] == "serving" else fleet_readings
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        row = {"workload": args.workload, "seed": seed,
               **read(conf, traffic, seed, args.seconds),
               "wall_s": time.perf_counter() - t0}
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
