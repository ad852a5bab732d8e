"""Every per-layer metric the manifest names has its data file with a known
reducer kind; and the metrics that read the program's own spans find them: a
traced run of the tiny cells on the CPU (no device plane, so only the host
spans and the reducers over them are looked at) holds the span names on the
host plane and gives each span metric's reducer a number."""
import importlib
import json
import pathlib

import pytest

from benchmarks import run as bench
from benchmarks.harness import manifest, reducers, tracer, xplane

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = ROOT / "benchmarks/checks/tiny/BENCHMARK.json"
PER_LAYER = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]

# span metrics by the tiny cell whose traced run has to feed them
SPAN_METRICS = {
    "serve.gpt-tiny.open": [
        "engine_host_ms_per_step", "engine_turnaround_host_ms_per_step",
        "swap_d2h_ready_ms_per_fetch", "swap_d2h_copy_ms_per_fetch",
        "fused_h2d_ms_per_step"],
    "train.gpt-tiny.steps": ["trainer_dispatch_ms_per_step"],
}


@pytest.mark.parametrize("name", [m["name"] for m in PER_LAYER])
def test_per_layer_entry_has_its_file_and_a_known_reducer(name):
    spec = manifest.layer_metric(name)
    assert spec["reducer"] in reducers.KINDS
    assert isinstance(spec.get("args", {}), dict) and spec["reads"]


def test_every_span_metric_is_checked_against_a_traced_run():
    spans = {m["name"] for m in PER_LAYER
             if manifest.layer_metric(m["name"])["reducer"]
             == "host_span_self_ms"}
    assert spans == {n for names in SPAN_METRICS.values() for n in names}


@pytest.fixture(scope="module", params=sorted(SPAN_METRICS))
def traced(request, tmp_path_factory):
    """(cell name, trace) of one tiny cell run as `run.py --trace 1` runs it:
    the program's span recorder switched on with the profiler."""
    cell = manifest.load_cell(request.param, TINY)
    mod = importlib.import_module(
        f"benchmarks.drivers.{cell.traffic['driver']}")
    drv = mod.Driver(cell, 2**31 + 5, lambda *a, **k: None)
    tr = tracer.Tracer(str(tmp_path_factory.mktemp("trace")),
                       bench.span_switch())
    drv.setup()
    drv.window(1.5, tr)
    drv.release()
    assert tr.t_stop is not None
    return request.param, tr.load()


def test_span_metrics_read_a_number_from_a_traced_run(traced):
    cell, trace = traced
    for name in SPAN_METRICS[cell]:
        spec = manifest.layer_metric(name)
        spans = [spec["args"]["span"]] + spec["args"].get("children", [])
        assert xplane.host_spans(trace, spans[:1]), f"no {spans[0]} span"
        value = reducers.reduce(spec, {"trace": trace})
        assert value is not None and value >= 0.0, name


def test_program_without_the_span_leaves_the_metric_out():
    """What the parent commit gives: no such span, no number, no error."""
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "t", "events": [[xplane.SLICE_SPAN, 0, 1000],
                                 ["engine.step", 100, 500]]}]}]}
    for names in SPAN_METRICS.values():
        for name in names:
            if name != "engine_host_ms_per_step":
                assert reducers.reduce(manifest.layer_metric(name),
                                       {"trace": trace}) is None
