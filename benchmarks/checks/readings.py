"""Readings for the limits of `correct`, taken on the chip at a cell's own
size, several seeds in one process (set-up is most of a run):

    python3 benchmarks/checks/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2] [--seconds 8]

For every seed: the program's numbers against the reference (the lower
reading of each limit).  For every control seed: the control's numbers (the
reference put in the program's place, one precision lower: fp8 for a bfloat16
configuration), and for a training cell the fault "half of the batch left
out, the mean taken over the rest".  For a serving cell also the program's
own lower-precision path (int8 weights) where `--engine-control` is given.
Prints one JSON line per reading; the benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as bench                       # noqa: E402
from benchmarks.harness import compare, manifest, tracer  # noqa: E402


def out(kind: str, seed: int, **numbers) -> None:
    print("READING " + json.dumps({"kind": kind, "seed": seed, **numbers}),
          flush=True)


def train(cell, seeds, control_seeds, control_prec):
    import importlib
    mod = importlib.import_module("benchmarks.drivers.train")
    B = cell.traffic["batch"]
    for seed in seeds:
        t0 = time.perf_counter()
        drv = mod.Driver(cell, seed, bench.say)
        drv.setup()
        drv.release()
        want = drv.reference_readings()
        out("program", seed, **drv.readings(drv.got, want),
            worst_grad_leaf=drv.worst_grad_leaf,
            worst_change_leaf=drv.worst_change_leaf,
            losses=[drv.got[f"loss{i}"] for i in (1, 2, 3)],
            ref_losses=[want[f"loss{i}"] for i in (1, 2, 3)],
            seconds=round(time.perf_counter() - t0, 1))
        if seed in control_seeds:
            ctrl = drv.reference_readings(prec=control_prec)
            out(f"control.{control_prec}", seed, **drv.readings(ctrl, want))
            half = drv.reference_readings(rows=slice(0, B // 2))
            out("fault.half_batch", seed, **drv.readings(half, want))


def serve(cell, seeds, control_seeds, control_prec, seconds, engine_control):
    import importlib

    import jax
    import numpy as np
    mod = importlib.import_module("benchmarks.drivers.serve")
    for seed in seeds:
        variants = [None]
        if engine_control and seed in control_seeds:
            variants.append(engine_control)
        for variant in variants:
            drv = mod.Driver(cell, seed, bench.say)
            if variant == "int8w":
                drv.engine_kwargs["weight_dtype"] = "int8"
            drv.setup()
            drv.window(seconds, tracer.NoTracer())
            drv.release()
            sample = drv.sample()
            logits, served = drv.reference_logits(sample)
            logits = np.asarray(logits)
            gaps = compare.served_logit_gap(logits, served)
            out("program" if variant is None else f"engine.{variant}", seed,
                served_logit_gap_max=float(gaps.max()),
                gap_p99=float(np.percentile(gaps, 99)),
                served_tokens=int(served.size), failed=drv.failed,
                finished=len(drv.finished_in_window))
            if variant is None and seed in control_seeds:
                low, _ = drv.reference_logits(sample, control_prec)
                picks = np.asarray(low).argmax(-1)
                cg = compare.served_logit_gap(logits, picks)
                out(f"control.{control_prec}", seed,
                    served_logit_gap_max=float(cg.max()),
                    gap_p99=float(np.percentile(cg, 99)))
            for leaf in jax.tree_util.tree_leaves((drv.params,
                                                   drv.eng.params)):
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
    ap.add_argument("--engine-control", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload, args.manifest)
    bench.find_devices(cell.chips, args.rehearse)
    bench.compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = [int(s) for s in args.control_seeds.split(",") if s]
    if cell.traffic["driver"] == "train":
        train(cell, seeds, ctrl, args.control_prec)
    else:
        serve(cell, seeds, ctrl, args.control_prec, args.seconds,
              args.engine_control)


if __name__ == "__main__":
    main()
