"""Readings for the limit of `correct` in the hybrid configuration's cell,
taken on the chip at the cell's own size, several seeds in one process:

    python3 benchmarks/checks/readings_hybrid.py --workload <cell> \
        --seeds 1,2,3 [--control-seeds 1,2] [--seconds 8]

What `readings.py` does for the dense serving cells, through
`drivers/serve_hybrid.py` (that file names `drivers.serve` outright).  For
every seed: the program's `served_logit_gap_p99`, `served_inexact_share` and
`served_logit_gap_max` against the reference (the lower reading).  For every control seed: the reference computed in fp8 put in
the program's place (the upper reading: the gap of ITS picks on the float32
reference's logits).  Prints one JSON line per reading.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as bench                       # noqa: E402
from benchmarks.checks.readings import out                # noqa: E402
from benchmarks.drivers import serve_hybrid               # noqa: E402
from benchmarks.harness import compare, manifest, tracer  # noqa: E402


def serve(cell, seeds, control_seeds, control_prec, seconds):
    import jax
    import numpy as np
    for seed in seeds:
        drv = serve_hybrid.Driver(cell, seed, bench.say)
        drv.setup()
        drv.window(seconds, tracer.NoTracer())
        drv.release()
        sample = drv.sample()
        logits, served = drv.reference_logits(sample)
        logits = np.asarray(logits)
        gaps = compare.served_logit_gap(logits, served)
        out("program", seed, served_logit_gap_max=float(gaps.max()),
            gap_p99=float(np.percentile(gaps, 99)),
            inexact_share=float((gaps > 0).mean()),
            served_tokens=int(served.size),
            failed=drv.failed, finished=len(drv.finished_in_window))
        if seed in control_seeds:
            low, _ = drv.reference_logits(sample, control_prec)
            picks = np.asarray(low).argmax(-1)
            cg = compare.served_logit_gap(logits, picks)
            out(f"control.{control_prec}", seed,
                served_logit_gap_max=float(cg.max()),
                gap_p99=float(np.percentile(cg, 99)),
                inexact_share=float((cg > 0).mean()))
        for leaf in jax.tree_util.tree_leaves(drv.params):
            if not leaf.is_deleted():
                leaf.delete()
        del drv


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control-prec", default="fp8")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload, args.manifest)
    bench.find_devices(cell.chips, args.rehearse)
    bench.compile_cache()
    serve(cell, [int(s) for s in args.seeds.split(",")],
          [int(s) for s in args.control_seeds.split(",") if s],
          args.control_prec, args.seconds)


if __name__ == "__main__":
    main()
