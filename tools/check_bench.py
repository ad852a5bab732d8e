#!/usr/bin/env python
"""CI guard + trajectory keeper for the serving bench.

The HBM/collective/program budgets are declared once and re-measured every
run (`tools/tpu_cost.py`, `tools/check_program_count.py`); serving PERF had
no such discipline — each PR's `bench_serve.py` JSON line scrolled away and
nothing noticed a regression until a human did.  This tool closes that gap:

- **Trajectory** (`BENCH_SERVE.jsonl`): every bench run appends ONE
  schema-versioned row — the mode axes that make rows comparable across PRs
  (mp, spec, dtypes, oversubscribe, tracing) plus the key perf
  metrics (tokens/s, goodput, dispatches/step, host-sync ms,
  parity flags, tracing overhead, roofline predicted/measured/model_error).
  `bench_serve.py` writes the row by default (`--no-history` opts out)
  through `append_bench_row()` here, so the row shape and its validator
  live in one file.
- **Floors** (`--ci`): runs a fresh CPU-smoke bench (subprocess, exactly
  what a human would run — `--replicas 2 --disagg P:D` so the dp-fleet and
  disaggregated prefill/decode passes run too)
  and enforces `SERVE_PERF_FLOORS` — declared ONCE in
  `paddle_tpu/analysis/registry.py` next to the resource budgets: every
  parity flag true (fleet_parity included), dispatches/step within the
  decode-side program budget, the
  deterministic tracing account under 2%, model_error a sane positive
  ratio, and on fleet rows the affinity-vs-round-robin prefix-hit odds
  ratio >= 1 with replicas sharing the leader's compiled programs.  The
  passing row is appended, so a green CI run IS a trajectory point.

Exits non-zero with a diff on violation.  Usage:
    JAX_PLATFORMS=cpu python tools/check_bench.py --ci      # bench + floors
    python tools/check_bench.py                             # history schema
    python tools/check_bench.py --from-json out.json        # external row
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_HISTORY = os.path.join(_REPO, "BENCH_SERVE.jsonl")

ROW_SCHEMA_VERSION = 5

# the axes that make rows comparable across PRs: two rows agree on "mode"
# or their perf numbers are not the same experiment.  v1 rows (pre KV
# tiering) validate against the v1 sets — old history stays parseable.
# The "fused" axis, its speedup and its parity flag are carried by rows
# written while the engine had a second, three-program step; newer rows leave
# them null.
MODE_AXES_V1 = ("mp", "fused", "spec_len", "prefill_chunk", "weight_dtype",
                "kv_dtype", "oversubscribe", "preempt_mode", "admission",
                "request_tracing")
# v2 (KV tiering PR): the tier switch and the multi-turn session axes
MODE_AXES_V2 = MODE_AXES_V1 + ("kv_tier", "multi_turn",
                               "session_return_frac")
# v3 (serving front door PR): the dp fleet axes — replica count + routing
# policy (router is null on single-engine rows)
MODE_AXES_V3 = MODE_AXES_V2 + ("replicas", "router")
# v4 (disaggregated serving PR): the prefill/decode role split ("P:D" on
# disagg rows, null otherwise) and the engine-restart restore sub-pass
MODE_AXES = MODE_AXES_V3 + ("disagg", "restart")
# the perf surface a trajectory reader plots; absent-in-this-mode metrics
# (e.g. goodput_ratio without --oversubscribe) ride as null
PERF_KEYS_V1 = ("decode_tokens_per_sec_per_chip", "generated_tokens_per_sec",
                "goodput_tokens_per_sec", "goodput_ratio",
                "dispatches_per_step", "host_sync_ms_per_step",
                "predicted_step_ms", "measured_step_ms", "model_error",
                "roofline_drift", "steady_state_recompiles",
                "fused_speedup", "spec_speedup", "accepted_per_step",
                "tracing_overhead", "tracing_overhead_measured",
                "preemptions_per_step", "prefix_hit_rate",
                "ttft_p50_ms", "ttft_p99_ms", "tpot_p99_ms",
                "requests", "elapsed_s", "device_spec")
# v2: tier spill/restore traffic + the returning-session view the tier's
# win is measured on (prefilled_tokens rides along so the drop is
# recomputable from any two rows)
PERF_KEYS_V2 = PERF_KEYS_V1 + (
    "prefilled_tokens", "resume_hits", "resume_restored_tokens",
    "partial_page_hits", "returning_prefilled_tokens",
    "returning_prefilled_drop", "returning_ttft_p50_ms")
# v3: the fleet surface — requested-router throughput/balance plus the
# affinity-vs-round-robin A/B on the identical session stream
PERF_KEYS_V3 = PERF_KEYS_V2 + (
    "fleet_generated_tokens_per_sec", "replica_balance", "fleet_shed",
    "affinity_prefix_hit_rate", "round_robin_prefix_hit_rate",
    "affinity_prefix_hit_ratio", "affinity_returning_ttft_p50_ms",
    "round_robin_returning_ttft_p50_ms", "fleet_shared_executables")
# v4: the disaggregation surface — store-handoff latency, the prefill-
# interference delta on decode TPOT, and the restart restore sub-pass
PERF_KEYS_V4 = PERF_KEYS_V3 + (
    "handoff_p50_ms", "handoff_p99_ms", "handoff_count",
    "interference_tpot_delta_ms", "restart_restored_tokens",
    "restart_ttft_ms")
# v5 (vocab-sharded head PR): the at-rest param-placement surface — per-
# device replicated vs sharded bytes next to the fp wte size, so the
# "replicated embedding ceiling" stays visibly retired across PRs
PERF_KEYS = PERF_KEYS_V4 + (
    "replicated_bytes_per_device", "sharded_bytes_per_device", "wte_bytes")
PARITY_KEYS = ("fuse_parity", "spec_parity", "oversubscribe_parity",
               "tracing_parity", "kv_tier_parity", "fleet_parity",
               "disagg_parity")
REQUIRED_ROW_KEYS = frozenset({"schema_version", "t", "mode", "perf",
                               "parity"})
_AXES_BY_VERSION = {1: (MODE_AXES_V1, PERF_KEYS_V1),
                    2: (MODE_AXES_V2, PERF_KEYS_V2),
                    3: (MODE_AXES_V3, PERF_KEYS_V3),
                    4: (MODE_AXES, PERF_KEYS_V4),
                    5: (MODE_AXES, PERF_KEYS)}


def bench_row(stats, t=None):
    """Project one `bench_serve` result dict onto the trajectory row."""
    return {
        "schema_version": ROW_SCHEMA_VERSION,
        "t": time.time() if t is None else float(t),
        "mode": {k: stats.get(k) for k in MODE_AXES},
        "perf": {k: stats.get(k) for k in PERF_KEYS},
        # only the parity flags this run's comparison passes produced
        "parity": {k: stats[k] for k in PARITY_KEYS if k in stats},
    }


def validate_row(row):
    """Schema check for one trajectory row; returns error strings."""
    errors = []
    if not isinstance(row, dict):
        return [f"row is not an object: {type(row).__name__}"]
    missing = REQUIRED_ROW_KEYS - set(row)
    if missing:
        errors.append(f"row missing keys: {sorted(missing)}")
        return errors
    if row["schema_version"] not in _AXES_BY_VERSION:
        errors.append(f"schema_version {row['schema_version']!r} not in "
                      f"{sorted(_AXES_BY_VERSION)} (migrate the row or bump "
                      f"the reader)")
        return errors
    mode_axes, perf_keys = _AXES_BY_VERSION[row["schema_version"]]
    if not isinstance(row["t"], (int, float)) or row["t"] <= 0:
        errors.append(f"bad timestamp t={row['t']!r}")
    for section, keys in (("mode", mode_axes), ("perf", perf_keys)):
        if not isinstance(row[section], dict):
            errors.append(f"row[{section!r}] is not an object")
            continue
        miss = set(keys) - set(row[section])
        if miss:
            errors.append(f"row[{section!r}] missing axes: {sorted(miss)}")
    if not isinstance(row["parity"], dict):
        errors.append("row['parity'] is not an object")
    tok = (row.get("perf") or {}).get("decode_tokens_per_sec_per_chip")
    if not isinstance(tok, (int, float)):
        errors.append(f"perf.decode_tokens_per_sec_per_chip is not a "
                      f"number: {tok!r}")
    return errors


def check_floors(row, floors=None):
    """Enforce `SERVE_PERF_FLOORS` on one row; returns error strings.  Mode-
    conditional bars apply only where the row's mode reaches them (the
    dispatch cap: every row but a history row of the three-program step,
    `mode.fused` false); the parity and tracing bars apply wherever the run
    produced the number."""
    if floors is None:
        from paddle_tpu.analysis.registry import SERVE_PERF_FLOORS
        floors = SERVE_PERF_FLOORS
    errors = []
    perf = row.get("perf") or {}
    mode = row.get("mode") or {}
    for k in floors["parity_flags"]:
        v = row.get("parity", {}).get(k)
        if v is not None and v is not True:
            errors.append(f"parity flag {k} is {v!r} — byte-exact parity is "
                          f"the one bar noise cannot excuse")
    tok = perf.get("decode_tokens_per_sec_per_chip")
    if not isinstance(tok, (int, float)) or \
            tok < floors["tokens_per_sec_min"]:
        errors.append(f"decode_tokens_per_sec_per_chip {tok!r} below "
                      f"{floors['tokens_per_sec_min']}")
    if mode.get("fused") is not False:
        d = perf.get("dispatches_per_step")
        cap = floors["dispatches_per_step_max"]
        if not isinstance(d, (int, float)) or d > cap + 1e-9:
            errors.append(f"dispatches_per_step {d!r} exceeds the declared "
                          f"{cap} (the one-dispatch claim broke)")
    # bench_row fills absent keys with None, so fall back on None — not
    # just on a missing key — or a raw run_serve_bench row (which carries
    # only the measured account) would skip the tracing bar entirely
    overhead = perf.get("tracing_overhead")
    if overhead is None:
        overhead = perf.get("tracing_overhead_measured")
    if overhead is not None and overhead >= floors["tracing_overhead_max"]:
        errors.append(f"tracing overhead {overhead} at or above the "
                      f"{floors['tracing_overhead_max']} bar")
    me = perf.get("model_error")
    if me is None or not (0.0 < me <= floors["model_error_max"]):
        errors.append(f"model_error {me!r} outside "
                      f"(0, {floors['model_error_max']}] — the roofline "
                      f"prediction is missing or broken")
    # KV-tier capacity floor: deterministic (token counts, not wall clock)
    # wherever a multi-turn row ran the tier comparison pass
    drop = perf.get("returning_prefilled_drop")
    drop_min = floors.get("returning_prefilled_drop_min")
    if drop is not None and drop_min is not None and \
            mode.get("kv_tier") and (mode.get("multi_turn") or 1) > 1 and \
            drop < drop_min:
        errors.append(f"returning_prefilled_drop {drop} below the declared "
                      f"{drop_min} — returning sessions are re-prefilling "
                      f"KV the tier should have restored")
    # affinity-routing floor: deterministic (token-count hit rates, not
    # wall clock) on any row whose mode ran the fleet passes
    ratio = perf.get("affinity_prefix_hit_ratio")
    ratio_min = floors.get("affinity_prefix_hit_ratio_min")
    if (mode.get("replicas") or 1) > 1 and ratio_min is not None:
        if not isinstance(ratio, (int, float)) or ratio < ratio_min:
            errors.append(f"affinity_prefix_hit_ratio {ratio!r} below the "
                          f"declared {ratio_min} — affinity routing is "
                          f"hitting the prefix cache no better than the "
                          f"cache-blind round-robin baseline")
        if perf.get("fleet_shared_executables") is not True:
            errors.append("fleet_shared_executables is not True — dp "
                          "replicas stopped adopting the leader's compiled "
                          "programs (replication must add zero executables)")
    # vocab-sharded head floor: at mp>=2 the per-device replicated param
    # bytes must sit STRICTLY below the fp wte size — the exact ceiling the
    # sharded layout retired.  Deterministic (byte counts off the cached
    # cost account, not wall clock); only v5+ rows carry the fields.
    if floors.get("replicated_below_wte") and (mode.get("mp") or 1) >= 2:
        rep = perf.get("replicated_bytes_per_device")
        wte = perf.get("wte_bytes")
        if isinstance(rep, (int, float)) and isinstance(wte, (int, float)) \
                and rep >= wte:
            errors.append(f"replicated_bytes_per_device {rep} not strictly "
                          f"below wte_bytes {wte} at mp>=2 — the embedding/"
                          f"head replication ceiling is back")
    # disaggregation floor: every handoff must complete within the declared
    # ceiling (a store handoff slower than a re-prefill defeats the split)
    if mode.get("disagg"):
        hp99 = perf.get("handoff_p99_ms")
        cap = floors.get("handoff_p99_ms_max")
        if cap is not None and (not isinstance(hp99, (int, float)) or
                                hp99 > cap):
            errors.append(f"handoff_p99_ms {hp99!r} missing or above the "
                          f"declared {cap} ceiling — prefill->decode store "
                          f"handoff is slower than the re-prefill it "
                          f"replaces")
    return errors


def append_bench_row(stats, path=DEFAULT_HISTORY, t=None):
    """`bench_serve.py`'s post-run hook: build, validate and append the
    trajectory row; returns it.  Raises ValueError on a malformed result —
    a bench that cannot produce a valid row must fail loudly, not seed the
    trajectory with garbage."""
    row = bench_row(stats, t=t)
    errors = validate_row(row)
    if errors:
        raise ValueError(f"bench result does not project onto a valid "
                         f"trajectory row: {errors}")
    with open(path, "a") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")
    return row


def read_history(path=DEFAULT_HISTORY):
    """((line_no, row) pairs, error strings) for every line of the
    trajectory file; a missing file is an empty (valid) trajectory."""
    rows, errors = [], []
    if not os.path.exists(path):
        return rows, errors
    with open(path) as f:
        for i, line in enumerate(f, 1):
            if not line.strip():
                continue
            try:
                row = json.loads(line)
            except ValueError as e:
                errors.append(f"{path}:{i}: not JSON: {e}")
                continue
            errors.extend(f"{path}:{i}: {e}" for e in validate_row(row))
            rows.append((i, row))
    return rows, errors


def run_ci_bench():
    """Run the CPU-smoke bench exactly as a human would (subprocess,
    `--no-history` so THIS tool owns the append) and return its result
    dict."""
    import subprocess
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench_serve.py"),
         "--no-history", "--replicas", "2", "--disagg", "P:D"],
        capture_output=True, text=True, cwd=_REPO, env=env, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"bench_serve.py failed (rc={proc.returncode}):\n"
                           f"{proc.stderr[-4000:]}")
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(f"no JSON line in bench_serve.py output:\n"
                       f"{proc.stdout[-2000:]}")


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ci", action="store_true",
                    help="run a fresh CPU-smoke bench, enforce "
                         "SERVE_PERF_FLOORS, append the passing row")
    ap.add_argument("--from-json", type=str, default=None,
                    help="validate + floor-check an existing bench_serve "
                         "result JSON (the printed line) instead of running")
    ap.add_argument("--history", type=str, default=DEFAULT_HISTORY,
                    help="trajectory file (default BENCH_SERVE.jsonl at the "
                         "repo root)")
    ap.add_argument("--no-append", action="store_true",
                    help="check only; do not append the row")
    args = ap.parse_args(argv)

    errors = []
    row = None
    stats = None
    if args.ci:
        stats = run_ci_bench()
    elif args.from_json:
        with open(args.from_json) as f:
            stats = json.load(f)
    if stats is not None:
        row = bench_row(stats)
        errors.extend(validate_row(row))
        errors.extend(check_floors(row))
    # the drop-in schema pass over the whole trajectory (also the default
    # no-args mode) runs BEFORE any append: a red run must not mutate the
    # trajectory (reruns would stack duplicate rows on a broken history) —
    # a green CI run IS a trajectory point, a red one leaves no trace
    rows, hist_errors = read_history(args.history)
    errors.extend(hist_errors)
    if row is not None and not errors and not args.no_append:
        with open(args.history, "a") as f:
            f.write(json.dumps(row, sort_keys=True) + "\n")
        rows.append((len(rows) + 1, row))

    report = {"metric": "serve_bench_trajectory", "ok": not errors,
              "history": args.history, "history_rows": len(rows),
              "appended": bool(row is not None and not errors
                               and not args.no_append),
              "errors": errors}
    if row is not None:
        report["row_perf"] = {
            k: row["perf"].get(k)
            for k in ("decode_tokens_per_sec_per_chip", "dispatches_per_step",
                      "tracing_overhead", "model_error")}
        report["row_parity"] = row["parity"]
    for e in errors:
        print(f"FAIL: {e}", file=sys.stderr)
    print(json.dumps(report))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
