#!/usr/bin/env python
"""tpu_cost: static HBM/collective/roofline accounting over the serving
executables, with CI-enforced resource budgets.

The serving jaxprs are traced (no dispatch) and compiled (no execution) and
four accounts are read off them (`paddle_tpu/analysis/cost_model.py`):

- **at-rest HBM** per device: param bytes split sharded-vs-replicated via
  the mp serving layout, plus the KVH-sharded page-pool bytes.  JXP006
  flags any replicated buffer above the declared ceiling — the
  embedding/head replication that blocks 70B-class configs.
- **peak transient HBM**: donation-aware per-eqn liveness over each
  program's jaxpr (the donated pool aliases out and allocates nothing).
  JXP008 flags a program over its declared peak budget.  XLA's own
  `memory_analysis()` temp bytes print alongside for calibration.
- **collectives**: psum/all-gather/reduce-scatter/collective-permute
  traffic read from the OPTIMIZED HLO (GSPMD inserts them after tracing),
  payload bytes x while-loop trip counts (the layer scan).  JXP007 flags
  undeclared or over-budget collective bytes/step; mp1 programs must be
  collective-free.
- **roofline**: analytic flops + compulsory HBM traffic over nameplate
  device specs -> a predicted step time per executable.

Budgets are declared ONCE in `paddle_tpu/analysis/registry.py::
SERVE_RESOURCE_BUDGET`, next to the program-count budget — one declaration,
one yardstick for the quantized-KV and 70B-head roadmap arcs.

Usage:
  JAX_PLATFORMS=cpu python tools/tpu_cost.py          # human report, mp1/2/4
  JAX_PLATFORMS=cpu python tools/tpu_cost.py --ci     # enforce budgets (CI)
  python tools/tpu_cost.py --json                     # machine-readable
  python tools/tpu_cost.py --no-mp                    # single-device hosts
  python tools/tpu_cost.py --replicated-ceiling N     # override (testing)
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the mp pass needs virtual chips; must land before jax initializes
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")


def _fmt_bytes(n: int) -> str:
    if n >= 1 << 20:
        return f"{n / (1 << 20):.2f} MiB"
    if n >= 1 << 10:
        return f"{n / (1 << 10):.1f} KiB"
    return f"{n} B"


def _print_report(reports) -> None:
    for mp, rep in sorted(reports.items()):
        ar = rep["at_rest"]
        print(f"== mp={mp} — at-rest HBM per device "
              f"({_fmt_bytes(ar['per_device_bytes'])})")
        print(f"   params sharded   "
              f"{_fmt_bytes(ar['param_bytes_sharded_per_device'])}"
              f"  (global {_fmt_bytes(ar['param_bytes_sharded'])})")
        print(f"   params replicated {_fmt_bytes(ar['param_bytes_replicated'])}"
              f"  (top: " + ", ".join(
                  f"{b['name']}={_fmt_bytes(b['bytes'])}"
                  for b in ar["top_replicated"][:2]) + ")")
        print(f"   page pool        "
              f"{_fmt_bytes(ar['pool_bytes_per_device'])}"
              f"  (global {_fmt_bytes(ar['pool_bytes'])})")
        qr = rep.get("at_rest_quantized")
        if qr is not None:
            print(f"   int8 engine      pool "
                  f"{_fmt_bytes(qr['pool_bytes'])} "
                  f"({rep['quantized_pool_ratio']}x smaller), replicated "
                  f"params {_fmt_bytes(qr['param_bytes_replicated'])} (fp "
                  f"{_fmt_bytes(ar['param_bytes_replicated'])}), host-pool "
                  f"bound {_fmt_bytes(rep['host_pool_bytes_int8'])}")
        print(f"   {'program':28s} {'flops':>10s} {'peak HBM':>10s} "
              f"{'xla temp':>10s} {'coll B/step':>11s} {'pred ms':>8s}")
        for p in rep["programs"]:
            xla = p.get("xla_temp_bytes")
            print(f"   {p['name']:28s} {p['flops']:>10d} "
                  f"{_fmt_bytes(p['peak_bytes']):>10s} "
                  f"{(_fmt_bytes(xla) if xla is not None else '-'):>10s} "
                  f"{p.get('collective_bytes_per_step', 0):>11d} "
                  f"{p['predicted_ms']:>8.4f}")


def main() -> int:
    ap = argparse.ArgumentParser(
        prog="tpu_cost", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--ci", action="store_true",
                    help="CI mode (recipe symmetry with tpu_lint --ci); any "
                         "JXP006/JXP007/JXP008 finding exits nonzero with or "
                         "without this flag")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON object with the full account")
    ap.add_argument("--no-mp", action="store_true",
                    help="skip the mp=2/mp=4 passes (single-device hosts)")
    ap.add_argument("--replicated-ceiling", type=int, default=None,
                    help="override the declared replicated-bytes ceiling "
                         "(budget-injection hook for tests)")
    ap.add_argument("--peak-budget", type=int, default=None,
                    help="override EVERY executable's peak-HBM budget with "
                         "one value (budget-injection hook for tests)")
    args = ap.parse_args()

    from paddle_tpu.analysis import registry
    from paddle_tpu.analysis.cost_model import device_spec, run_cost_checks

    budget = dict(registry.SERVE_RESOURCE_BUDGET)
    if args.replicated_ceiling is not None:
        budget["replicated_bytes_ceiling"] = args.replicated_ceiling
    if args.peak_budget is not None:
        budget["peak_hbm_bytes"] = {
            k: args.peak_budget for k in budget.get("peak_hbm_bytes", {})}
    reports, findings = run_cost_checks(include_mp=not args.no_mp,
                                        budget=budget)
    spec = device_spec()

    if args.json:
        print(json.dumps({
            "tool": "tpu_cost", "ok": not findings,
            "device_spec": spec.name,
            "reports": {f"mp{m}": rep for m, rep in reports.items()},
            "findings": [f.to_json() for f in findings],
        }))
    else:
        _print_report(reports)
        for f in findings:
            print(f.format())
        print(f"tpu_cost: {len(findings)} finding(s) against "
              f"SERVE_RESOURCE_BUDGET", file=sys.stderr)
    # same convention as tpu_lint: findings fail the run in EVERY mode — a
    # human-report invocation must not mask a budget regression with exit 0
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
