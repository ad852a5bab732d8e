"""The rate sweep that fixes an open-loop cell's rate: one process, one
set-up, a window at each of a few fixed rates.  The highest rate at which the
queue does not grow over the window is the knee; the cell's traffic file gets
0.8 of it, as a number.  Run on the chip, once, when the cell is defined:

    python3 benchmarks/checks/sweep.py --workload <cell> --rates 4,6,8,10 --seconds 15
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as bench                       # noqa: E402
from benchmarks.harness import manifest, tracer           # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload, args.manifest)
    bench.find_devices(cell.chips, args.rehearse)
    bench.compile_cache()
    import importlib
    drv = importlib.import_module("benchmarks.drivers.serve").Driver(
        cell, args.seed, bench.say)
    drv.setup()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        drv.mix = dict(cell.traffic, rate_per_s=rate)
        drv.seed = args.seed + i
        drv.window(args.seconds, tracer.NoTracer())
        e2e = drv.end_to_end()
        print("SWEEP " + json.dumps({
            "rate_per_s": rate, "sent": drv.attempted, "failed": drv.failed,
            "finished_in_window": len(drv.finished_in_window),
            "queue_depth": drv.queue_depth, **e2e}), flush=True)


if __name__ == "__main__":
    main()
