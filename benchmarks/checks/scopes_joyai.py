"""Where a step's device time goes, by `named_scope`, in the
`joyai_llm_flash` training cell — on the chip, one short traced window:

    python3 benchmarks/checks/scopes_joyai.py --workload <cell> --seed 3

The scope names reach neither an "XLA Ops" event's name nor its stats on
this runtime (two chip runs of PR 34 found none), only the `op_name` metadata
of the compiled step's HLO text; an event is named by its instruction, so
this compiles the trainer's step once more (a cache hit), maps instruction ->
`op_name`, and sums device seconds by the most specific scope of `SCOPES`
found there, apart for the forward pass, the backward pass (ops
named `transpose(...)` or under `bwd`) and the optimizer.  Prints `SCOPES
{json}`: milliseconds a step, largest first; the benchmark's own runs never
call this."""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import re
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as bench                       # noqa: E402
from benchmarks.drivers import train_joyai                # noqa: E402
from benchmarks.harness import manifest, tracer, xplane   # noqa: E402

# most specific first
SCOPES = ("mla.attn", "mla.q", "mla.kv_write", "mla.kv", "mla.out",
          "gmm_bwd_rows", "gmm_bwd_weights", "router_bias", "router",
          "shared_expert", "experts", "ffn", "loss", "mtp", "opt", "head")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(text: str) -> str:
    m = _OP_NAME.search(text)
    if not m:
        return "(no op_name)"
    name = m.group(1)
    phase = "opt" if "/opt/" in name else \
        "bwd" if ("transpose(" in name or "/bwd/" in name) else "fwd"
    inside = "mtp/" if re.search(r"[/(]mtp[/)]", name) else ""
    for s in SCOPES:
        if re.search(r"[/(]" + re.escape(s) + r"[/)]", name):
            return f"{phase}:{inside}{s}" if s != "mtp" else f"{phase}:mtp"
    return f"{phase}:(other)"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=14.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload, args.manifest)
    bench.find_devices(cell.chips, args.rehearse)
    bench.compile_cache()
    drv = train_joyai.Driver(cell, args.seed, bench.say)
    tdir = ROOT / "benchmarks_out" / cell.name / "scopes_trace"
    shutil.rmtree(tdir, ignore_errors=True)
    tr = tracer.Tracer(str(tdir), bench.span_switch())
    drv.setup()
    drv.window(args.seconds, tr)
    import jax.profiler
    tr_ = drv.trainer
    tok, lab = tr_.shard_batch(*drv.batches.take())
    # no public name for the jitted step: `_step_fn` (checks only)
    hlo = tr_._step_fn.lower(tr_.params, tr_.opt_state, tok, lab
                             ).compile().as_text()
    op_names = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        n = _OP_NAME.search(line)
        if m and n:
            op_names[m.group(1)] = n.group(0)
    data = jax.profiler.ProfileData.from_file(xplane.find_xplane(str(tdir)))
    by_scope, by_kind = collections.Counter(), collections.Counter()
    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for e in line.events:
                short = xplane.short_name(e.name)
                if xplane.opcode(short) in xplane.CONTAINERS:
                    continue
                instr = short.split(" ")[0].lstrip("%")
                by_scope[scope_of(op_names.get(instr, ""))] += e.duration_ns
                by_kind[re.sub(r"^%[\w.\-]+ ", "", short)
                        .split(" out=")[0]] += e.duration_ns
    # steps (and parts of steps) the trace saw, by the window's step time
    steps = sum(by_kind.values()) / 1e9 / (drv.elapsed / drv.steps)
    ms = lambda table: {k: round(v / 1e6 / steps, 2)
                        for k, v in table.most_common(40)}
    print("SCOPES " + json.dumps({"steps_traced": round(steps, 2),
                                  "ms_a_step_by_scope": ms(by_scope),
                                  "ms_a_step_by_op_kind": ms(by_kind)}),
          flush=True)
    shutil.rmtree(tdir, ignore_errors=True)


if __name__ == "__main__":
    main()
