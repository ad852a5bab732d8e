"""The small fixed set of reducer kinds behind the per-layer metrics.  A
metric is a data file `layer_metrics/<metric>.json` = {"reducer": kind,
"args": {...}}; a reducer takes the traced run's context and returns a
number, or None when it finds nothing to read (the harness then leaves the
metric out of the line; it never prints 0 for a share)."""
from __future__ import annotations

import importlib
import statistics

import numpy as np

from . import xplane


def _work(name: str):
    module, fn = name.rsplit(".", 1)
    return getattr(importlib.import_module(f"benchmarks.work.{module}"), fn)


def _arg(value, facts):
    return facts[value] if isinstance(value, str) else value


def device_idle(args, ctx):
    b = ctx["busy"]
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])


def op_time_share(args, ctx):
    secs, n = xplane.matching(ctx["ops"], args["pattern"])
    return 100.0 * secs / ctx["busy"]["busy_s"] if n else None


def kernel_roofline(args, ctx):
    """Least time the chip could take for the calls seen, over their time."""
    peaks, facts = ctx["peaks"], ctx["facts"]
    least, took = 0.0, 0.0
    for k in args["kernels"]:
        secs, n = xplane.matching(ctx["ops"], k["pattern"])
        if not n:
            continue
        w = _work(k["work"])(**{p: _arg(v, facts)
                                for p, v in k["args"].items()})
        calls = n / k.get("events_per_call", 1)
        least += calls * max(w["flops"] / peaks["flops_per_s_bf16"],
                             w["bytes"] / peaks["hbm_bytes_per_s"])
        took += secs
    return 100.0 * least / took if took else None


def program_device_ms(args, ctx):
    durs = xplane.whole_events(ctx["trace"], xplane.MODULES_LINE,
                               args["pattern"])
    return 1e3 * sum(durs) / len(durs) if durs else None


def host_span_self_ms(args, ctx):
    secs, n = xplane.span_self_seconds(ctx["trace"], args["span"],
                                       args.get("children", []))
    return 1e3 * secs / n if n else None


def request_stamp(args, ctx):
    vals = [r[args["field"]] for r in ctx["facts"].get("requests", [])
            if r.get(args["field"]) is not None]
    if not vals:
        return None
    stat = args["stat"]
    if stat == "mean":
        v = statistics.fmean(vals)
    elif stat.startswith("p"):
        v = float(np.percentile(vals, float(stat[1:])))
    else:
        raise ValueError(f"unknown stat {stat!r}")
    return v * args.get("scale", 1.0)


def step_mfu(args, ctx):
    """Model operations of the slice's work over the slice's seconds, as a
    share of the chips' peak."""
    facts = ctx["facts"]
    if not facts.get("slice_tokens"):
        return None
    flops = _work(args["work"])(ctx["model"], facts)
    peak = ctx["peaks"]["flops_per_s_bf16"] * ctx["chips"]
    return 100.0 * flops / facts["slice_seconds"] / peak


KINDS = {f.__name__: f for f in (
    device_idle, op_time_share, kernel_roofline, program_device_ms,
    host_span_self_ms, request_stamp, step_mfu)}


def reduce(spec: dict, ctx: dict):
    kind = spec["reducer"]
    if kind not in KINDS:
        raise SystemExit(f"unknown reducer kind {kind!r}")
    return KINDS[kind](spec.get("args", {}), ctx)
