"""BENCHMARK.json and the files it names.  A cell is found by its name; its
configuration, traffic mix, limits and per-layer metrics are files of their
own, found by the names the manifest gives:

    configs/<config>.json   traffic/<traffic>.json   cells/<workload>.json
    layer_metrics/<metric>.json     drivers/<traffic's "driver">.py
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"


def _read(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<traffic>.json
    limits: dict            # cells/<workload>.json "limits"
    end_to_end: list        # manifest entries of the metrics this cell reports
    per_layer: list


def _reports(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, manifest_path: pathlib.Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    """The cell `name` of a manifest.  The repo's own manifest keeps its data
    under benchmarks/; any other (the tiny one of the checks) keeps configs/,
    traffic/ and cells/ beside itself."""
    manifest_path = pathlib.Path(manifest_path).resolve()
    man = _read(manifest_path)
    base = manifest_path.parent
    data = BENCH if base == ROOT else base
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in man["workloads"])
        raise SystemExit(f"no workload {name!r} in {manifest_path} ({known})")
    cfg = next(c for c in man["configs"] if c["name"] == entry["config"])
    e2e = [m for m in man["end_to_end"] if _reports(m, name)]
    have = {m["name"] for m in e2e}
    layer = [m for m in man["per_layer"]
             if _reports(m, name) and m["moves"] in have]
    return Cell(name=name, chips=entry["chips"],
                config=_read(base / cfg["file"]),
                traffic=_read(data / "traffic" / f"{entry['traffic']}.json"),
                limits=_read(data / "cells" / f"{name}.json")["limits"],
                end_to_end=e2e, per_layer=layer)


def layer_metric(name: str) -> dict:
    return _read(BENCH / "layer_metrics" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    table = _read(BENCH / "harness" / "peaks.json")["device_kinds"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"benchmarks/harness/peaks.json")
    return table[device_kind]
