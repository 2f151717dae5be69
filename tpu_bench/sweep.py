"""Knee sweep of a serving mix: offered against completed load at a
series of fixed rates, on one engine, in one process.

    python tpu_bench/sweep.py --workload serve-code --rates 6,8,10,12 \
        --seconds 20 --seed 7

For each rate, one window of the mix's open loop (as the benchmark's
runs drive it), then the engine drains before the next rate. Each line
gives the offered and completed request rates, output tokens per second,
the tails, and the backlog at the window's end. The knee is the highest
rate whose backlog does not grow; the mix's ``rate_rps`` is then set at
its ``rate_of_knee`` times the knee.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from tpu_bench.run import find_chips, setup_process  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    setup_process()
    from tpu_bench.common import find_cell, percentile
    from tpu_bench.serving import ServingCell, e2e_metrics

    entry, conf, traffic = find_cell(args.workload)
    why = find_chips(entry["chips"])
    if why:
        raise SystemExit(f"sweep.py: {why}")
    cell = ServingCell(conf, traffic, args.seed)
    cell.warm()
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(traffic, rate_rps=rate)
        cell.calls.clear()
        w = cell.window(args.seconds)
        sent = [s for s in w["served"] if s.in_window and s.rs is not None]
        done = [s for s in sent if s.rs.finish_t is not None]
        waiting = sum(len(tq.waiting)
                      for tq in cell.eng.sched.tenants.values())
        m = e2e_metrics(w)
        ttft = [(s.stamps[0] - s.req.due_s) * 1e3 for s in sent if s.stamps]
        print(json.dumps({
            "workload": args.workload, "offered_rps": rate,
            "completed_rps": len(done) / w["window_s"],
            "tokens_per_s": m["tokens_per_s"],
            "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
            "ttft_p95_ms": m["ttft_p95_ms"], "itl_p95_ms": m["itl_p95_ms"],
            "sent": len(sent), "finished": len(done),
            "waiting_at_end": waiting, "steps": w["steps"],
            "window_s": w["window_s"]}), flush=True)
        while cell._busy():
            cell.eng.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
