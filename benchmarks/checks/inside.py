"""What the program says of itself over one window of a cell, on the chip:
the counters, ring keys and stamps that have no reader in `run.py` yet (the
swap boundaries' bytes, `turnaround_ms`, `emit_times`), and from a traced
slice every program span's count and time, and which names reach the text of
the device's "XLA Ops" events.

    python3 benchmarks/checks/inside.py --workload <cell> --seed 3 [--trace 1]

Prints `INSIDE {json}` lines; the benchmark's own runs never call this."""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import shutil
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as bench                       # noqa: E402
from benchmarks.harness import manifest, tracer, xplane   # noqa: E402

SCOPES = ("fwd", "bwd", "opt", "kv_write", "attn", "mlp", "head")


def out(kind: str, **numbers) -> None:
    print("INSIDE " + json.dumps({"kind": kind, **numbers}), flush=True)


def pcts(values, scale=1.0) -> dict:
    v = np.asarray(values, float) * scale
    return {"n": int(v.size), "mean": float(v.mean()),
            **{f"p{q}": float(np.percentile(v, q)) for q in (50, 95)},
            "max": float(v.max())} if v.size else {"n": 0}


def serve_counters(drv) -> None:
    st = drv.eng.stats()
    keys = ("engine_steps", "decode_tokens", "swap_ms", "turnaround_ms",
            "swap_d2h_fetches", "swap_d2h_bytes", "swap_d2h_useful_bytes",
            "swap_h2d_bytes", "swap_h2d_useful_bytes")
    facts = {k: st.get(k) for k in keys}
    facts["kv_tier"] = {k: st["kv_tier"][k] for k in
                        ("spills", "restores", "pages_host")}
    ring = [r for r in drv.eng.step_trace() if r["dispatches"]]
    fetch = [r["d2h_ms"] for r in ring if r.get("d2h_ms")]
    out("counters", **facts,
        d2h_useful_over_moved=(facts["swap_d2h_useful_bytes"] /
                               facts["swap_d2h_bytes"])
        if facts["swap_d2h_bytes"] else None,
        ring_steps=len(ring), step_ms=pcts([r["dur_s"] for r in ring], 1e3),
        sync_ms=pcts([r["sync_ms"] for r in ring]),
        turnaround_ms_ring=pcts([r.get("turnaround_ms", 0.0) for r in ring]),
        d2h_ms_of_steps_that_fetched=pcts(fetch))
    gaps, first = [], []
    for rec in drv.finished_in_window:
        stamps = getattr(drv.by_rid[rec["rid"]].metrics, "emit_times", None)
        if stamps:
            t = [s[0] for s in stamps]
            gaps += list(np.diff(t) / [s[1] for s in stamps[1:]])
            first.append(len(stamps))
    out("itl_ms", requests=len(first), **pcts(gaps, 1e3))


def spans_and_names(tr, steps: int) -> None:
    from paddle_tpu.inference.engine import ENGINE_SPANS
    from paddle_tpu.parallel import hybrid
    names = tuple(ENGINE_SPANS) + tuple(getattr(hybrid, "TRAINER_SPANS", ()))
    trace = xplane.load(xplane.find_xplane(tr.out_dir), keep_text="")
    table = {}
    for n in names:
        secs, k = xplane.span_self_seconds(trace, n, [])
        if k:
            table[n] = {"n": k, "ms_each": 1e3 * secs / k}
    out("spans", steps_in_slice=steps, spans=table,
        spans_a_step=sum(v["n"] for v in table.values()) / max(steps, 1))
    texts = trace["texts"]
    hits = {s: sum(f"/{s}/" in t or f"/{s}\"" in t for t in texts.values())
            for s in SCOPES}
    kernels = sorted({k.split(" ")[0] for k in texts
                      if xplane.KERNEL_TARGET in k})
    sample = next((t for t in texts.values() if "op_name" in t), None)
    out("names", ops_with_text=len(texts), ops_naming_a_scope=hits,
        kernel_instruction_names=kernels, any_op_name_metadata=sample
        and sample[:600], **raw_peek(xplane.find_xplane(tr.out_dir)))


def raw_peek(path: str) -> dict:
    """What `xplane.load` drops: the step markers on the host plane with
    their stats, the device plane's other lines (is there a "Steps" line,
    and what are its events called) and the stats of one fusion event (does
    a scope name ride there, if not in the text)."""
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    seen, marks = {}, {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("engine_step", "train_step"):
                        rec = marks.setdefault(e.name, {"events": 0})
                        rec["events"] += 1
                        rec["last_stats"] = {str(k): str(v)[:40]
                                             for k, v in e.stats}
        if seen or not xplane.DEVICE_PLANE.match(plane.name):
            continue                    # the first device plane only
        for line in plane.lines:
            events = list(line.events)
            seen[line.name] = {"events": len(events), "names": sorted(
                {e.name[:60] for e in events[:400]})[:6]}
            if line.name == xplane.OPS_LINE:
                ev = next((e for e in events if "fusion" in e.name), None)
                if ev is not None:
                    seen["a_fusion_events_stats"] = {
                        str(k): str(v)[:200] for k, v in ev.stats}
    return {"device_lines": seen, "host_step_markers": marks}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload, args.manifest)
    bench.find_devices(cell.chips, args.rehearse)
    bench.compile_cache()
    kind = cell.traffic["driver"]
    drv = importlib.import_module(f"benchmarks.drivers.{kind}").Driver(
        cell, args.seed, bench.say)
    tdir = ROOT / "benchmarks_out" / cell.name / "inside_trace"
    shutil.rmtree(tdir, ignore_errors=True)
    tr = tracer.Tracer(str(tdir), bench.span_switch()) if args.trace \
        else tracer.NoTracer()
    drv.setup()
    drv.window(args.seconds, tr)
    out("window", workload=cell.name, seed=args.seed, trace=args.trace,
        **drv.end_to_end())
    if kind == "serve":
        serve_counters(drv)
    if args.trace:
        spans_and_names(tr, drv.facts.get("slice_steps", 0))
        shutil.rmtree(tdir, ignore_errors=True)


if __name__ == "__main__":
    main()
