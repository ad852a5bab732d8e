#!/usr/bin/env python
"""tpu_lint: static analysis proving the engine's dispatch/sync discipline.

Two levels under one entry point (reference counterpart: the `tools/` CI
layer of custom static checks — op-registry audits, API guards):

- **AST** (`paddle_tpu/analysis/visitor.py`): TPL001 host-sync in
  step()-reachable code, TPL002 jit/shard_map site not in
  `analysis/registry.py`, TPL003 missing donation on hot buffers, TPL004
  Python branch on a traced value, TPL005 untimed blocking device fetch,
  TPL006 broad except around device code, TPL007 page-state mutation with a
  double-buffered dispatch in flight (harvest first), LINT000 suppression
  without a reason.  Suppress per line with
  `# tpu-lint: disable=TPL001 -- reason`.
- **jaxpr** (`analysis/jaxpr_checks.py`): traces the serving executables
  (the fused one-dispatch step and the cold-path programs, mp1+mp2) and
  audits the programs — JXP001 embedded transfers, JXP002 donation
  mismatches, JXP003 f64 upcasts, JXP004 missing mp sharding constraints,
  JXP005 oversized host-visible output (the fused step must return O(B*K)
  ints, never [B, V] logits).

Exit status is non-zero on any unsuppressed finding.

Usage:
  python tools/tpu_lint.py [paths...]         # default: paddle_tpu/
  python tools/tpu_lint.py --ci               # repo-wide, both levels (CI)
  python tools/tpu_lint.py --level ast f.py   # fast, no jax import
  python tools/tpu_lint.py --json ...         # machine-readable findings
  python tools/tpu_lint.py --list-rules
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the mp jaxpr pass needs virtual chips; must land before jax initializes
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

CI_PATHS = ["paddle_tpu", "tools"]


def main() -> int:
    ap = argparse.ArgumentParser(
        prog="tpu_lint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/directories to lint (default: paddle_tpu/)")
    ap.add_argument("--level", choices=("ast", "jaxpr", "all"), default="all",
                    help="ast = source rules only (no jax import); jaxpr = "
                         "traced-program audits only; all = both (default)")
    ap.add_argument("--ci", action="store_true",
                    help=f"CI mode: lint {CI_PATHS} at --level all")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON object with every finding "
                         "(suppressed included)")
    ap.add_argument("--no-mp", action="store_true",
                    help="skip the mp=2 jaxpr pass (single-device hosts)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    args = ap.parse_args()

    if args.list_rules:
        from paddle_tpu.analysis import rule_table
        for code, title, rationale in rule_table():
            print(f"{code}  {title:34s} {rationale}")
        return 0

    paths = args.paths or (CI_PATHS if args.ci else ["paddle_tpu"])
    level = "all" if args.ci else args.level
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [p if os.path.exists(p) else os.path.join(repo, p)
             for p in paths]
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        # a typo'd path must not report "clean" — that is how a CI job lints
        # nothing forever
        print(f"tpu_lint: no such path(s): {missing}", file=sys.stderr)
        return 2
    from paddle_tpu.analysis import iter_python_files
    if not iter_python_files(paths):
        # same guard for the subtler shape of the mistake: the paths exist
        # but contain nothing lintable
        print(f"tpu_lint: no python files under {paths}", file=sys.stderr)
        return 2

    findings = []
    if level in ("ast", "all"):
        from paddle_tpu.analysis import run_ast_checks
        findings.extend(run_ast_checks(paths))
    if level in ("jaxpr", "all"):
        # the jaxpr targets are the serving executables — only meaningful
        # when the lint scope covers the serving engine
        in_scope = any(
            os.path.isdir(p) and (
                os.path.exists(os.path.join(p, "inference", "engine.py")) or
                os.path.exists(os.path.join(p, "engine.py")))
            or p.endswith("engine.py")
            for p in paths)
        if in_scope:
            from paddle_tpu.analysis import run_jaxpr_checks
            findings.extend(run_jaxpr_checks(include_mp=not args.no_mp))

    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    live = [f for f in findings if not f.suppressed]
    suppressed = [f for f in findings if f.suppressed]

    if args.json:
        print(json.dumps({
            "tool": "tpu_lint", "level": level, "paths": paths,
            "ok": not live,
            "findings": [f.to_json() for f in findings],
            "live": len(live), "suppressed": len(suppressed),
        }))
    else:
        for f in live:
            print(f.format())
        print(f"tpu_lint: {len(live)} finding(s), "
              f"{len(suppressed)} suppressed", file=sys.stderr)
    return 1 if live else 0


if __name__ == "__main__":
    sys.exit(main())
