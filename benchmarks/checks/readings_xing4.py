"""Readings for the limits of `correct` in the `xing4_0` configuration's
cell, taken on the chip at the cell's own size, several seeds in one process:

    python3 benchmarks/checks/readings_xing4.py --workload <cell> \
        --seeds 1,2,3 [--control-seeds 1,2] [--seconds 8]

What `readings_hybrid.py` does for the hybrid cell, through
`drivers/serve_xing4.py`.  For every seed: the program's
`served_logit_gap_p99`, `served_inexact_share` and `served_logit_gap_max`
against the reference (the lower reading).  For every control seed, the
reference computed wrongly put in the program's place (the upper readings:
the gap of ITS picks on the float32 reference's logits): in fp8, with the
Sinkhorn skipped, and with the rotary left off the cached key.  Prints one
JSON line per reading.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as bench                       # noqa: E402
from benchmarks.checks.readings import out                # noqa: E402
from benchmarks.drivers import serve_xing4                # noqa: E402
from benchmarks.harness import compare, manifest, tracer  # noqa: E402

CONTROLS = (("control.fp8", "fp8", ""),
            ("fault.sinkhorn_skipped", "f32", "sinkhorn_skipped"),
            ("fault.k_rope_off", "f32", "k_rope_off"))


def numbers(gaps) -> dict:
    import numpy as np
    return dict(served_logit_gap_max=float(gaps.max()),
                gap_p99=float(np.percentile(gaps, 99)),
                gap_p95=float(np.percentile(gaps, 95)),
                gap_p90=float(np.percentile(gaps, 90)),
                gap_mean=float(gaps.mean()),
                inexact_share=float((gaps > 0).mean()))


def serve(cell, seeds, control_seeds, seconds):
    import jax
    import numpy as np
    for seed in seeds:
        drv = serve_xing4.Driver(cell, seed, bench.say)
        drv.setup()
        drv.window(seconds, tracer.NoTracer())
        drv.release()
        sample = drv.sample()
        logits, served = drv.reference_logits(sample)
        logits = np.asarray(logits)
        out("program", seed, served_tokens=int(served.size),
            failed=drv.failed, finished=len(drv.finished_in_window),
            distinct_served=int(np.unique(served).size),
            **numbers(compare.served_logit_gap(logits, served)))
        if seed in control_seeds:
            for name, prec, fault in CONTROLS:
                low, _ = drv.reference_logits(sample, prec, fault)
                picks = np.asarray(low).argmax(-1)
                out(name, seed,
                    **numbers(compare.served_logit_gap(logits, picks)))
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
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload, args.manifest)
    bench.find_devices(cell.chips, args.rehearse)
    bench.compile_cache()
    serve(cell, [int(s) for s in args.seeds.split(",")],
          [int(s) for s in args.control_seeds.split(",") if s], args.seconds)


if __name__ == "__main__":
    main()
