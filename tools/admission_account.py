"""The account of a serving cell's admission steps, from ONE traced run of the
benchmark's own command on the chip:

    python3 tools/admission_account.py --workload <cell> --seed <n> \
        [--seconds 51] [--out chiprun_out/admission_account]

Runs `benchmarks/run.py --trace 1` in this process (so the run's result line
with its per-layer metrics is printed as ever), keeps the loaded trace that
`run.py` deletes from disk, and reads the engine's counters where the traced
slice starts and stops and after the window.  Then prints `ACCOUNT {json}`
lines and writes `<out>/<cell>.json`:

- `identities`: every `engine.step` of the slice holds at most one of
  `engine.step.ahead` / `engine.step.serial`; every `engine.turnaround` lies
  in a `.serial`; `engine.prefill.sync` against the `jit_prefill_impl` events
  of the device's "XLA Modules" line (counts, and each event ENDS inside its
  span: the shared clock shown, not assumed); `engine.swap.gather` against
  `jit_swap_out_impl` (each event starts after its span opens);
  `engine.swap.fetch` spans against the engine thread's takes and
  `swap_d2h_fetches` over the slice;
- `serial_step_ms` / `ahead_step_ms`: the mean step by part — every instant
  of the step booked to the innermost engine span open on the engine thread
  — with the programs that ran inside and the device's idle time inside;
  `serial_steps` holds one such row a serial step;
- `idle`: the slice's idle seconds and the share of them inside serial steps,
  inside ahead steps and outside any step;
- `counters`: `fused_ahead_late`, `fused_launched_ahead`, `fused_serial_steps`
  by reason and their neighbours, over the slice and over the whole window;
  `span_counts`: every engine span's number in the slice.

On the CPU (`--rehearse`, the checks' tiny manifest) there is no device plane:
the host-side identities and parts are printed, the device's are left out.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import importlib
import json
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import run as bench                          # noqa: E402
from benchmarks.harness import manifest, tracer, xplane      # noqa: E402

AHEAD, SERIAL = "engine.step.ahead", "engine.step.serial"
FETCH = "engine.swap.fetch"
COUNTERS = ("decode_iterations", "admitted_requests",
            "fused_launched_ahead", "fused_ahead_late",
            "fused_ahead_discarded_lanes", "swap_d2h_fetches",
            "swap_d2h_bytes", "swap_d2h_useful_bytes",
            "swap_d2h_landed_free", "swap_d2h_backpressure_waits",
            "prefix_evictions", "kv_tier_spills", "turnaround_ms")
PROGRAM = re.compile(r"^(jit_\w+)\(")


def say(kind: str, **numbers) -> None:
    print("ACCOUNT " + json.dumps({"kind": kind, **numbers}), flush=True)


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------

def inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def by_innermost(step, spans) -> dict:
    """{span name: ns} of one step: each instant goes to the shortest of
    `spans` (name, start, end; all on the engine thread, nested) open then."""
    mine = [s for s in spans if inside(s, step)] + [step]
    edges = sorted({t for _, a, b in mine for t in (a, b)})
    out = collections.Counter()
    for a, b in zip(edges, edges[1:]):
        open_ = [(e - s, n) for n, s, e in mine if s <= a and b <= e]
        out[min(open_)[1]] += b - a
    return out


class DeviceLine:
    """One line of the first device plane, in time order, cut by interval
    without a walk over the whole line (a slice holds ~10^5 operations)."""

    def __init__(self, trace, line: str):
        planes = xplane.device_planes(trace)
        self.events = sorted(xplane._line(planes[0], line),
                             key=lambda e: e[1]) if planes else []
        self.starts = [e[1] for e in self.events]

    def __bool__(self) -> bool:
        return bool(self.events)

    def clip(self, a, b):
        """(name, start, end) of the events that overlap [a, b], cut to it;
        events of one line run one after another, so the one before the
        first that starts inside is the only other that can reach in."""
        lo = max(bisect.bisect_left(self.starts, a) - 1, 0)
        hi = bisect.bisect_left(self.starts, b)
        return xplane._clip(self.events[lo:hi], a, b)

    def busy_ns(self, a, b) -> int:
        return sum(hi - lo for lo, hi in xplane._union(
            (lo, hi) for _, lo, hi in self.clip(a, b)))


# ---------------------------------------------------------------------------
# the account
# ---------------------------------------------------------------------------

def step_rows(steps, spans, modules, ops) -> list:
    rows = []
    for st in steps:
        _, a, b = st
        parts = by_innermost(st, spans)
        progs = collections.defaultdict(lambda: [0, 0.0])
        for name, lo, hi in modules.clip(a, b):
            m = PROGRAM.match(name)
            rec = progs[m.group(1) if m else name]
            rec[0] += 1
            rec[1] += (hi - lo) / 1e6
        rows.append({"ms": (b - a) / 1e6,
                     "parts_ms": {n: v / 1e6 for n, v in parts.items()},
                     "programs": {n: {"n": k, "ms": ms}
                                  for n, (k, ms) in progs.items()},
                     "device_idle_ms": (b - a - ops.busy_ns(a, b)) / 1e6
                     if ops else None})
    return rows


def mean_rows(rows) -> dict:
    if not rows:
        return {"n": 0}
    n = len(rows)
    parts = collections.Counter()
    progs = collections.defaultdict(lambda: [0, 0.0])
    for r in rows:
        parts.update(r["parts_ms"])
        for name, p in r["programs"].items():
            progs[name][0] += p["n"]
            progs[name][1] += p["ms"]
    idle = [r["device_idle_ms"] for r in rows
            if r["device_idle_ms"] is not None]
    return {"n": n, "ms": sum(r["ms"] for r in rows) / n,
            "parts_ms": {k: v / n for k, v in sorted(
                parts.items(), key=lambda kv: -kv[1])},
            "programs_a_step": {k: {"n": c / n, "ms": ms / n}
                                for k, (c, ms) in progs.items()},
            "device_idle_ms": sum(idle) / n if idle else None}


def pair_in_order(spans, events, at: int) -> list:
    """Spans and device events of one kind, each side in time order, paired
    the i-th with the i-th once the slice's edges are cut off: an event
    whose instant `at` (1 its start, 2 its end) falls before the first span
    opens belongs to a span before the slice, a span that opens after the
    last event's instant to an event after it."""
    spans, events = list(spans), list(events)
    while events and spans and events[0][at] < spans[0][1]:
        events.pop(0)
    while spans and events and spans[-1][1] > events[-1][at]:
        spans.pop()
    return list(zip(spans, events))


def identities(trace, spans, modules, t0, t1, slice_counters) -> dict:
    def named(name):
        return [s for s in spans if s[0] == name]
    steps = named("engine.step")
    ahead, serial = named(AHEAD), named(SERIAL)
    holds = [(sum(inside(x, s) for x in ahead),
              sum(inside(x, s) for x in serial)) for s in steps]
    out = {"steps": len(steps), "ahead": len(ahead), "serial": len(serial),
           "steps_with_neither": sum(h == (0, 0) for h in holds),
           "steps_with_both_or_two": sum(a + b > 1 for a, b in holds),
           "turnarounds": len(named("engine.turnaround")),
           "turnarounds_outside_a_serial": sum(
               not any(inside(t, s) for s in serial)
               for t in named("engine.turnaround")),
           "admits_outside_a_serial": sum(
               not any(inside(t, s) for s in serial)
               for t in named("engine.admit")),
           "prefill_syncs_inside_an_ahead": sum(
               any(inside(p, s) for s in ahead)
               for p in named("engine.prefill.sync"))}
    if modules:
        def events(pattern):
            rx = re.compile(pattern)
            return [(n, s, s + d) for n, s, d in modules.events
                    if rx.search(n) and s >= t0 and s + d <= t1]
        pre, syncs = events(r"^jit_prefill_impl\("), \
            named("engine.prefill.sync")
        pairs = pair_in_order(syncs, pre, at=2)
        out["prefill"] = {
            "spans": len(syncs), "device_events": len(pre),
            "paired": len(pairs),
            "event_ends_inside_its_span": sum(
                sp[1] <= ev[2] <= sp[2] for sp, ev in pairs),
            "span_end_after_event_end_ms_median": _median(
                [(sp[2] - ev[2]) / 1e6 for sp, ev in pairs])}
        gat, gspans = events(r"^jit_swap_out_impl\("), \
            named("engine.swap.gather")
        pairs = pair_in_order(gspans, gat, at=1)
        out["gather"] = {
            "spans": len(gspans), "device_events": len(gat),
            "paired": len(pairs),
            "event_starts_after_its_span_opens": sum(
                ev[1] >= sp[1] for sp, ev in pairs),
            "event_start_after_span_start_ms_median": _median(
                [(ev[1] - sp[1]) / 1e6 for sp, ev in pairs])}
    takes = named("engine.swap.d2h")
    copies = named("engine.swap.d2h.copy")
    fetches = [s for s in xplane.host_spans(trace, [FETCH])
               if s[1] >= t0 and s[2] <= t1]
    out["fetch"] = {
        "worker_fetch_spans": len(fetches),
        "engine_thread_takes": len(takes),
        "swap_d2h_fetches_over_the_slice":
            slice_counters.get("swap_d2h_fetches"),
        # a hand-over is microseconds: a long `.copy` is a piece the worker
        # had not reached, copied on the engine thread (no fetch span)
        "engine_thread_copies_over_half_a_ms": sum(
            c[2] - c[1] > 500_000 for c in copies),
        "fetch_ms": _pcts([(s[2] - s[1]) / 1e6 for s in fetches])}
    return out


def _median(v):
    v = sorted(v)
    return v[len(v) // 2] if v else None


def _pcts(v) -> dict:
    v = sorted(v)
    if not v:
        return {"n": 0}
    return {"n": len(v), "min": v[0], "p50": v[len(v) // 2],
            "mean": sum(v) / len(v), "max": v[-1]}


def account(trace, names, slice_counters) -> dict:
    t0, t1 = xplane.slice_window(trace)
    fetch_free = [n for n in names if n != FETCH]   # the worker's thread
    spans = [s for s in xplane.host_spans(trace, fetch_free)
             if s[1] >= t0 and s[2] <= t1]
    ops = DeviceLine(trace, xplane.OPS_LINE)
    modules = DeviceLine(trace, xplane.MODULES_LINE)
    serial = step_rows([s for s in spans if s[0] == SERIAL], spans,
                       modules, ops)
    ahead = step_rows([s for s in spans if s[0] == AHEAD], spans,
                      modules, ops)
    counts = collections.Counter(s[0] for s in spans)
    counts[FETCH] = sum(s[1] >= t0 and s[2] <= t1
                        for s in xplane.host_spans(trace, [FETCH]))
    out = {"slice_s": (t1 - t0) / 1e9, "span_counts": dict(counts),
           "identities": identities(trace, spans, modules, t0, t1,
                                    slice_counters),
           "serial_step_ms": mean_rows(serial),
           "ahead_step_ms": mean_rows(ahead), "serial_steps": serial}
    if ops:
        idle = (t1 - t0 - ops.busy_ns(t0, t1)) / 1e6
        in_serial = sum(r["device_idle_ms"] for r in serial)
        in_ahead = sum(r["device_idle_ms"] for r in ahead)
        out["idle"] = {
            "slice_idle_ms": idle, "in_serial_steps_ms": in_serial,
            "in_ahead_steps_ms": in_ahead,
            "outside_both_ms": idle - in_serial - in_ahead,
            "share_in_serial_steps": in_serial / idle if idle else None}
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def read_counters(eng) -> dict:
    snap = eng.metrics.snapshot()["counters"]
    out = {k: snap.get(k) for k in COUNTERS}
    out["fused_serial_steps"] = {
        k[k.index('"') + 1:-2]: v for k, v in snap.items()
        if k.startswith("fused_serial_steps{")}
    return out


def delta(a: dict, b: dict) -> dict:
    out = {}
    for k, v in b.items():
        if isinstance(v, dict):
            out[k] = delta(a.get(k) or {}, v)
        elif v is not None:
            out[k] = v - (a.get(k) or 0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" /
                                         "admission_account"))
    args = ap.parse_args(argv)

    cell = manifest.load_cell(args.workload, args.manifest)
    drivers = importlib.import_module(
        f"benchmarks.drivers.{cell.traffic['driver']}")
    kept = {}

    # the three seams: the driver in hand when the window opens, the
    # counters where the slice starts and stops, the trace before it goes
    window, start, stop, load = drivers.Driver.window, tracer.Tracer.start, \
        tracer.Tracer.stop, tracer.Tracer.load

    def keeping_window(self, seconds, tr):
        kept["driver"] = self
        window(self, seconds, tr)
        kept["window"] = read_counters(self.eng)

    def keeping_start(self):
        start(self)
        kept["c0"] = read_counters(kept["driver"].eng)

    def keeping_stop(self):
        kept["c1"] = read_counters(kept["driver"].eng)
        stop(self)

    def keeping_load(self):
        kept["trace"] = load(self)
        return kept["trace"]

    drivers.Driver.window = keeping_window
    tracer.Tracer.start, tracer.Tracer.stop = keeping_start, keeping_stop
    tracer.Tracer.load = keeping_load
    rc = bench.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1",
                     "--manifest", args.manifest] +
                    (["--rehearse"] if args.rehearse else []))

    from paddle_tpu.inference.engine import ENGINE_SPANS
    in_slice = delta(kept["c0"], kept["c1"])
    acc = account(kept["trace"], ENGINE_SPANS, in_slice)
    acc["counters"] = {"slice": in_slice, "window": kept["window"]}
    acc["workload"], acc["seed"] = args.workload, args.seed
    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"{args.workload}.json", "w") as f:
        json.dump(acc, f, indent=1)
    for kind in ("span_counts", "identities", "serial_step_ms",
                 "ahead_step_ms", "idle", "counters"):
        if kind in acc:
            say(kind, **acc[kind])
    return rc


if __name__ == "__main__":
    sys.exit(main())
