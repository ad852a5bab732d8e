"""Readings for the limits of `correct` in the `joyai_llm_flash`
configuration's training cell, taken on the chip at the cell's own size,
several seeds in one process:

    python3 benchmarks/checks/readings_joyai.py --workload <cell> \\
        --seeds 1,2,3 [--control-seeds 1,2]

For every seed: the trainer's first steps against the reference (the lower
readings; no window is run).  For every control seed, the reference computed
wrongly put in the program's place (the upper readings): in fp8, with half
the batch left out, with the next-n module's loss left out of the sum, with
the router's bias rule skipped, and with the rotary left off the key.
Prints one JSON line per reading.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as bench                       # noqa: E402
from benchmarks.checks.readings import out                # noqa: E402
from benchmarks.drivers import train_joyai                # noqa: E402
from benchmarks.harness import manifest                   # noqa: E402

# (name, precision, fault, leave the second half of the batch out)
CONTROLS = (("control.fp8", "fp8", "", False),
            ("fault.half_batch", "f32", "", True),
            ("fault.mtp_off", "f32", "mtp_off", False),
            ("fault.bias_rule_off", "f32", "bias_rule_off", False),
            ("fault.k_rope_off", "f32", "k_rope_off", False))


def control(drv, prec: str, fault: str, half: bool) -> dict:
    """The reference computed wrongly, as a program's readings."""
    rows = list(range(drv.mix["batch"] // 2)) if half else None
    got = drv.reference_readings(prec, fault, rows)
    got["pairs_over_bound"] = 0
    return got


def train(cell, seeds, control_seeds):
    for seed in seeds:
        drv = train_joyai.Driver(cell, seed, bench.say)
        drv.setup()
        drv.release()
        want = drv.reference_readings()
        out("program", seed, **drv.readings(drv.got, want))
        if seed in control_seeds:
            for name, prec, fault, half in CONTROLS:
                out(name, seed, **drv.readings(control(drv, prec, fault, half),
                                               want))
        del drv


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload, args.manifest)
    bench.find_devices(cell.chips, args.rehearse)
    bench.compile_cache()
    train(cell, [int(s) for s in args.seeds.split(",")],
          [int(s) for s in args.control_seeds.split(",") if s])


if __name__ == "__main__":
    main()
