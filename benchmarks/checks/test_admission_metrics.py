"""The admission step's per-layer metrics (ISSUE 36): each has its manifest
entry and its data file over one of the two reducer kinds that were there; a
traced run of the tiny serve cell on the CPU (no device plane, so the host
spans and the reducers over them are looked at) gives every span metric a
number; a program that lacks the span (the parent commit) leaves the metric
out; and the two program metrics read the engine's own program names off a
device's "XLA Modules" line."""
import importlib
import json
import pathlib

import pytest

from benchmarks import run as bench
from benchmarks.harness import manifest, reducers, tracer, xplane

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = ROOT / "benchmarks/checks/tiny/BENCHMARK.json"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
PER_LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}
SERVE_CELLS = {w["name"] for w in MANIFEST["workloads"]
               if w["name"].startswith("serve.")}

# metric -> the span it reads, per what
SPAN_METRICS = {
    "engine_serial_step_ms": "engine.step.serial",
    "engine_ahead_host_ms_per_step": "engine.step.ahead",
    "prefill_sync_ms_per_admission": "engine.prefill.sync",
    "admit_reserve_ms_per_attempt": "engine.admit.reserve",
    "swap_gather_host_ms_per_gather": "engine.swap.gather",
    "swap_fetch_ms_per_piece": "engine.swap.fetch",
}
# metric -> the program it reads, as the engine names it
PROGRAM_METRICS = {
    "prefill_program_device_ms": "jit_prefill_impl",
    "swap_gather_program_device_ms": "jit_swap_out_impl",
}


@pytest.mark.parametrize("name", [*SPAN_METRICS, *PROGRAM_METRICS])
def test_metric_has_its_entry_and_its_file(name):
    from paddle_tpu.inference.engine import ENGINE_SPANS
    entry, spec = PER_LAYER[name], manifest.layer_metric(name)
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    assert entry["moves"] == "tpot_p50_ms"
    assert set(entry["workloads"]) <= SERVE_CELLS and entry["workloads"]
    assert spec["reads"] and " per " in spec["reads"]
    if name in SPAN_METRICS:
        assert spec["reducer"] == "host_span_self_ms"
        assert entry["source"] == "program_span"
        assert spec["args"]["span"] == SPAN_METRICS[name]
        # children are spans the program records, and never the worker's
        assert set(spec["args"].get("children", [])) <= \
            set(ENGINE_SPANS) - {"engine.swap.fetch"}
    else:
        assert spec["reducer"] == "program_device_ms"
        assert entry["source"] == "device_trace"
        assert PROGRAM_METRICS[name] in spec["args"]["pattern"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The trace of the tiny serve cell run as `run.py --trace 1` runs it:
    the program's span recorder switched on with the profiler."""
    cell = manifest.load_cell("serve.gpt-tiny.open", TINY)
    mod = importlib.import_module(
        f"benchmarks.drivers.{cell.traffic['driver']}")
    drv = mod.Driver(cell, 2**31 + 36, lambda *a, **k: None)
    tr = tracer.Tracer(str(tmp_path_factory.mktemp("trace")),
                       bench.span_switch())
    drv.setup()
    drv.window(2.0, tr)
    drv.release()
    assert tr.t_stop is not None
    return tr.load()


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_metric_reads_a_number_from_a_traced_run(traced, name):
    spec = manifest.layer_metric(name)
    assert xplane.host_spans(traced, [SPAN_METRICS[name]]), \
        f"no {SPAN_METRICS[name]} span"
    value = reducers.reduce(spec, {"trace": traced})
    assert value is not None and value >= 0.0


def test_a_serial_step_is_longer_than_what_it_holds(traced):
    """The spans nest as the metrics' `children` assume: a serial step
    holds the prefill's wait and the reservation, so its length is no less
    than either mean."""
    def read(name):
        return reducers.reduce(manifest.layer_metric(name),
                               {"trace": traced})
    assert read("engine_serial_step_ms") >= \
        read("prefill_sync_ms_per_admission")
    assert read("engine_serial_step_ms") >= \
        read("admit_reserve_ms_per_attempt")


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_program_without_the_span_leaves_the_metric_out(name):
    """What the parent commit gives: no such span, no number, no error."""
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "t", "events": [[xplane.SLICE_SPAN, 0, 1000],
                                 ["engine.step", 100, 500],
                                 ["engine.admit", 150, 100],
                                 ["engine.sample.sync", 300, 50]]}]}]}
    assert reducers.reduce(manifest.layer_metric(name),
                           {"trace": trace}) is None


@pytest.mark.parametrize("name", PROGRAM_METRICS)
def test_program_metric_reads_the_engines_program_name(name):
    """Mean device time of the events whose name is the engine's own
    (`jit_<fn>(<fingerprint>)`), wholly inside the slice; none, no number."""
    mine = PROGRAM_METRICS[name]
    events = [["jit_fused_impl(111)", 1_000, 4_000_000],
              [f"{mine}(222)", 5_000_000, 2_000_000],
              [f"{mine}(222)", 8_000_000, 4_000_000],
              [f"{mine}(222)", 19_000_000, 4_000_000]]   # past the slice
    def trace(events):
        return {"planes": [
            {"name": "/host:CPU", "lines": [{"name": "t", "events": [
                [xplane.SLICE_SPAN, 0, 20_000_000]]}]},
            {"name": "/device:TPU:0", "lines": [
                {"name": xplane.MODULES_LINE, "events": events}]}]}
    spec = manifest.layer_metric(name)
    assert reducers.reduce(spec, {"trace": trace(events)}) == \
        pytest.approx(3.0)
    assert reducers.reduce(spec, {"trace": trace(events[:1])}) is None
