"""The benchmark's command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell that BENCHMARK.json names, makes weights and traffic from
`--seed`, warms up every program the cell uses (set-up), measures for
`--seconds`, reads the device's memory peak, frees the program's state, runs
the plain reference for `correct`, and prints one JSON object as its last
line.  With `--trace 1` the profiler is on for the last seconds of the window
and the line carries the cell's per-layer metrics instead of the end-to-end
ones.  Fails (exit 2, no result line) when JAX reports no TPU or fewer chips
than the cell asks for; `--rehearse` lifts that for a dry run on the CPU,
whose line carries no metric at all.

This file holds no model width, length law or rate: see `configs/`,
`traffic/`, `cells/` and `layer_metrics/`.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import pathlib       # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.harness import manifest, reducers, tracer as tracing, xplane  # noqa: E402


def say(tag: str, **facts) -> None:
    print(f"[{tag}] " + json.dumps(facts, sort_keys=True, default=str),
          flush=True)


def find_devices(chips: int, rehearse: bool):
    import jax
    devs = jax.devices()
    if rehearse:
        return devs
    if devs[0].platform != "tpu":
        sys.exit(f"benchmarks/run.py: JAX found no accelerator (platform "
                 f"{devs[0].platform!r}); the benchmark measures only on a TPU")
    if len(devs) < chips:
        sys.exit(f"benchmarks/run.py: the cell needs {chips} chip(s), JAX "
                 f"reports {len(devs)}")
    return devs


def compile_cache() -> str:
    """The program's own switch (`<checkout>/.jax_cache`, or where
    JAX_COMPILATION_CACHE_DIR says), with every program cached however
    quickly it compiled, so that a second run compiles nothing."""
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


_CACHE_EVENTS = {"hits": 0, "misses": 0}


def count_cache_events() -> None:
    import jax.monitoring

    def listener(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _CACHE_EVENTS["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _CACHE_EVENTS["misses"] += 1
    jax.monitoring.register_event_listener(listener)


def span_switch():
    """The program's host-span recorder (its spans are real only while one
    records); the device trace is the harness's own."""
    from paddle_tpu.profiler.profiler import Profiler
    return Profiler(timer_only=True)


def memory_peak(devs) -> int:
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs))


def layer_metrics(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        value = reducers.reduce(manifest.layer_metric(m["name"]), ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def traced(cell, driver, trace, dev, device, line, out_dir) -> dict:
    """The traced run's part of the line: busy and window seconds, the
    breakdown, the per-layer metrics; and the summary file."""
    ctx = {"trace": trace, "busy": xplane.busy(trace),
           "ops": xplane.op_seconds(trace), "facts": driver.facts,
           "model": cell.config["model"], "chips": cell.chips,
           "peaks": manifest.peaks(dev.device_kind)}
    device["busy_s"] = ctx["busy"]["busy_s"]
    device["window_s"] = ctx["busy"]["window_s"]
    gaps = xplane.idle_gaps(trace, driver.facts["host_spans"])
    line["breakdown"] = {"device_ops": xplane.top(ctx["ops"]),
                         "idle_gaps": xplane.top(gaps)}
    summary = {"busy": ctx["busy"], "ops": xplane.top(ctx["ops"], 60),
               "programs": xplane.top(xplane.op_seconds(
                   trace, xplane.MODULES_LINE), 20),
               "idle_gaps": gaps, "texts": trace.get("texts", {}),
               "facts": {k: v for k, v in driver.facts.items()
                         if k != "requests"}}
    with open(out_dir / "trace_summary.json", "w") as f:
        json.dump(summary, f, indent=1)
    return layer_metrics(cell, ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"),
                    help="another manifest (the checks' tiny one)")
    ap.add_argument("--rehearse", action="store_true",
                    help="dry run on whatever JAX finds; prints no metric")
    args = ap.parse_args(argv)

    cell = manifest.load_cell(args.workload, args.manifest)
    devs = find_devices(cell.chips, args.rehearse)[:cell.chips]
    dev = devs[0]
    on_chip = dev.platform == "tpu"
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devs), compile_cache_dir=compile_cache(),
        workload=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace)

    driver_mod = importlib.import_module(
        f"benchmarks.drivers.{cell.traffic['driver']}")
    driver = driver_mod.Driver(cell, args.seed, say)
    out_dir = ROOT / "benchmarks_out" / cell.name
    trace_dir = out_dir / "trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True, exist_ok=True)
        tracer = tracing.Tracer(str(trace_dir), span_switch())
    else:
        tracer = tracing.NoTracer()

    count_cache_events()
    driver.setup()
    setup_s = time.perf_counter() - T_PROCESS
    in_setup = dict(_CACHE_EVENTS)
    driver.window(args.seconds, tracer)
    peak = memory_peak(devs)
    say("memory", memory_peak_bytes=peak, setup_s=setup_s,
        compile_cache_in_setup=in_setup,
        compile_cache_in_window={k: _CACHE_EVENTS[k] - in_setup[k]
                                 for k in in_setup})
    end_to_end = driver.end_to_end()
    end_to_end["setup_s"] = setup_s
    driver.release()
    if args.trace:
        trace = tracer.load()
        shutil.rmtree(trace_dir, ignore_errors=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    line = {"correct": None, "attempted": driver.attempted,
            "failed": driver.failed, "metrics": {}, "device": device}
    if args.trace:
        if xplane.device_planes(trace):
            line["metrics"] = traced(cell, driver, trace, dev, device, line,
                                     out_dir)
        elif on_chip:
            raise SystemExit("the trace holds no device plane")
        else:
            say("trace", rehearsal="no device plane in a CPU trace",
                host_spans=len(xplane.host_spans(
                    trace, driver.facts["host_spans"])))
    elif on_chip:
        line["metrics"] = {
            m["name"]: {"value": end_to_end[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in end_to_end}

    checks = driver.check()
    correct = all(c.ok for c in checks) and driver.failed == 0 and bool(checks)
    line["correct"] = correct
    line["checks"] = {c.name: c.as_dict() for c in checks}

    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(f"correct: {correct} (failed requests/steps: {driver.failed} of "
          f"{driver.attempted})", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
