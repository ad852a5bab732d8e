"""The DSA layer's device time by piece in the GLM-5 cell, from ONE traced
run of the benchmark's own command on the chip:

    python3 tools/dsa_account.py --workload serve.glm5-d5e16.longctx-closed24 \
        --seed <n> [--seconds 51] [--out chiprun_out/dsa_account]

Runs `benchmarks/run.py --trace 1` in this process (its result line is
printed as ever), then reads the same trace file once more with each device
operation's whole HLO text, which the benchmark's op table drops
(`xplane.short_name` keeps an XLA fusion's number only).  By that text it
tells apart what no per-layer metric can: the decode step's top-k (on the
chip the threshold kernel over the slots as one tile's rows, out (s32[1,R,1],
s32[1,R,1]) with R a few dozen at most, and `topk_compact`; where the sort
route still runs, `lax.top_k` over a slot's scores: a sort of (f32[B,1,S],
s32[B,1,S])), the expert layers' routing sorts, and XLA's gather of the
chosen latent rows (a kCustom fusion out bf16[B * index_topk, lane]); beside
them the DSA kernels by name, and of a long pass's expanded form the
per-head keys' product (a fusion out bf16[B, H, nope + rope, S]) and the
keep mask (int8 out) apart from `masked_expanded`, which forms the values
itself.
On the CPU (`--rehearse`) there is no device plane and every piece reads 0.
Prints `DSA {json}` and writes `<out>/<cell>.json`: seconds and events in the
slice by piece, ms a fused step, the fused steps counted, the prefill's
attention a query block (`masked_latent`, or the expanded form's three
pieces, over the prefill's `topk_threshold` calls: one a block and layer;
and over the prefill's index walk, which grows with a block's keys as the
attention does, so that slices of other prompts compare), the share of
prefilled tokens the expanded form took, and the gather +
`sparse_latent` unit's roofline (rows selected x 576 x 2 B read once from HBM
against the absorbed form's operations, over the two pieces' time).

The prefill programs of the slice split by `named_scope` (`router`,
`experts`, `shared_expert`, `ffn`, `mla.q`, `mla.kv_write`, `mla.kv`,
`mla.attn`, `mla.out`, `dsa.index_q`, `dsa.index_k_write`, `dsa.score`,
`dsa.topk`; a mixer's scope alone, its norm and residual, as
`<kind>.layer`; no `op_name` as "(no scope)"), as
`benchmarks/checks/scopes_joyai.py` splits the training step: the scope
reaches only the `op_name` metadata of the compiled program, so the
prefill programs of the buckets the slice ran are compiled once more after
the run (the arguments as the engine gave them; a cache hit), their
instructions mapped to `op_name`, and each device operation of a
`jit_prefill_impl` module booked by its instruction (loops, whose events
span their bodies' operations, left out).  Seconds in the slice
and ms a 2,048-token query block (`prefill_scope_s`,
`prefill_scope_ms_per_block`).  Beside them the prefill programs' expert
counters (`moe_pairs_here`, `moe_pairs_compacted`,
`moe_compact_extra_chunks` over the run's prefills, where the program
returns them) and `moe_pairs_compacted / moe_pairs_here` there.
The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import run as bench                       # noqa: E402
from benchmarks.drivers import serve_glm5                 # noqa: E402
from benchmarks.harness import manifest, tracer, xplane   # noqa: E402
from benchmarks.work import dsa                            # noqa: E402
from paddle_tpu.inference.engine import LLMEngine         # noqa: E402
from paddle_tpu.models import hybrid                      # noqa: E402

# whole HLO text -> piece; the first that matches
PIECES = (
    ("sparse_latent", r"^%sparse_latent\b"),
    ("index_scores.decode", r"^%paged_index_scores\S* = f32\[\d+,\d+,1,\d+\]"),
    ("index_scores.prefill", r"^%paged_index_scores\b"),
    ("topk_threshold.decode",
     r"^%topk_threshold\S* = \(s32\[1,\d{1,2},1\]"),
    ("topk_compact.decode", r"^%topk_compact\b"),
    ("topk_threshold", r"^%topk_threshold\b"),
    ("masked_latent", r"^%masked_latent\b"),
    ("masked_expanded", r"^%masked_expanded\b"),
    ("keep_mask", r"= s8\[\d+,\d+,\d+,\d+\]\{[^}]*\} fusion\("),
    ("topk_sort.decode",
     r"= \(f32\[\d+,1,\d+\]\{[^}]*\}, s32\[\d+,1,\d+\]\{[^}]*\}\) sort\("),
    ("sort.routing", r"\) sort\("),
)


# the prefill's attention by piece, booked a query block
PREFILL_ATTENTION = ("masked_latent", "masked_expanded", "expanded_keys",
                     "keep_mask")


# the prefill's scopes (`named_scope` in models/hybrid.py, moe/dropless.py):
# the innermost on an operation's `op_name` path names it
PREFILL_SCOPES = ("mla.attn", "mla.q", "mla.kv_write", "mla.kv", "mla.out",
                  "dsa.index_q", "dsa.index_k_write", "dsa.score",
                  "dsa.topk", "dsa", "router", "shared_expert", "experts",
                  "ffn", "mla", "head")
# a mixer's own scope (`hybrid.KINDS`), inside which the others lie
KIND_SCOPES = ("mla", "experts")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ")


def scope_of(op_name: str | None) -> str:
    """The innermost prefill scope on an `op_name` path; a mixer's scope
    with nothing inside it is "<kind>.layer" (the expert layer's scope and
    the routed experts' share the name `experts`)."""
    if not op_name:
        return "(no scope)"
    known = [p for p in op_name.split("/") if p in PREFILL_SCOPES]
    if not known:
        return "(other)"
    last = known[-1]
    return f"{last}.layer" if last in KIND_SCOPES and \
        known.count(last) == 1 else last


def result_type(text: str) -> str:
    """An HLO instruction's result type without layouts."""
    rest = text.split(" = ", 1)[-1]
    m = re.search(r"(?<=[\}\]\)] )[a-z][\w\-]*\(", rest)
    return re.sub(r"\{[^{}]*\}", "", rest[:m.start()] if m else rest).strip()


def op_names(hlo: str) -> dict:
    """{instruction: (result type, op_name)} of a compiled program's HLO
    text."""
    out = {}
    for line in hlo.splitlines():
        m, n = _INSTR.match(line), _OP_NAME.search(line)
        if m:
            out[m.group(1)] = (result_type(line), n.group(1) if n else None)
    return out


def buckets_seen(texts, hidden: int, buckets) -> set:
    """The buckets whose [..., S, hidden] rows a prefill program's
    operations hold (its own, and its blocks' where those are buckets)."""
    return {int(n) for t in texts
            for n in re.findall(rf"\[(?:1,)?(\d+),{hidden}\]", t)} & \
        set(buckets)


def piece_of(text: str, gather_out: str, keys_out: str) -> str | None:
    for name, rx in PIECES:
        if re.search(rx, text):
            return name
    if re.search(keys_out, text.split(" = ", 1)[-1]) and " fusion(" in text:
        return "expanded_keys"
    if text.split(" = ", 1)[-1].startswith(gather_out) and \
            " fusion(" in text and "kind=kCustom" in text:
        return "row_gather"
    return None


def account(path: str, trace: dict, gather_out: str, keys_out: str) -> dict:
    import jax.profiler
    t0, t1 = xplane.slice_window(trace)
    secs, events = {}, {}
    ops, modules = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == xplane.MODULES_LINE:
                modules = sorted((int(e.start_ns), e.name)
                                 for e in line.events)
            if line.name != xplane.OPS_LINE:
                continue
            for e in line.events:
                a = max(int(e.start_ns), t0)
                b = min(int(e.start_ns) + int(e.duration_ns), t1)
                piece = piece_of(e.name, gather_out, keys_out) \
                    if b > a else None
                if piece:
                    secs[piece] = secs.get(piece, 0.0) + (b - a) / 1e9
                    events[piece] = events.get(piece, 0) + 1
                # a loop's own event spans its body's operations, which
                # have events of their own
                if b > a and xplane.opcode(xplane.short_name(e.name)) \
                        not in xplane.CONTAINERS:
                    ops.append((int(e.start_ns), e.name, (b - a) / 1e9))
        break                               # one chip
    # the slice's prefill operations by module (the program event that
    # began last before each): {module: {instruction: (text, seconds)}}
    prefill = collections.defaultdict(dict)
    starts = [m[0] for m in modules]
    for start, text, sec in ops:
        i = bisect.bisect_right(starts, start) - 1
        m = _INSTR.match(text)
        if i >= 0 and m and modules[i][1].startswith("jit_prefill_impl"):
            old = prefill[modules[i][1]].get(m.group(1), (text, 0.0))
            prefill[modules[i][1]][m.group(1)] = (text, old[1] + sec)
    steps = len(xplane.whole_events(trace, xplane.MODULES_LINE,
                                    r"^jit_fused_impl"))
    busy = xplane.busy(trace)["busy_s"] if xplane.device_planes(trace) \
        else 0.0
    blocks = events.get("topk_threshold", 0)
    attention = sum(secs.get(k, 0.0) for k in PREFILL_ATTENTION)
    walk = secs.get("index_scores.prefill", 0.0)
    return {"slice_s": (t1 - t0) / 1e9, "busy_s": busy, "fused_steps": steps,
            "seconds": secs, "events": events,
            "ms_per_fused_step": {k: 1e3 * v / max(1, steps)
                                  for k, v in secs.items()},
            "prefill_blocks": blocks,
            "prefill_attention_ms_per_block": {
                k: 1e3 * secs[k] / blocks for k in PREFILL_ATTENTION
                if k in secs and blocks},
            # the index walk of the same blocks scales with their keys as
            # causal attention does: attention over it compares slices
            # that hold other prompts
            "prefill_attention_per_index_walk":
                attention / walk if walk else None,
            "_prefill_ops": prefill}


def prefill_scopes(acc: dict, compiled: dict, hidden: int,
                   latent_layers: int) -> None:
    """Book the slice's prefill operations (`account`'s `_prefill_ops`) by
    scope, through each bucket's compiled text (`compiled`: {bucket: HLO
    text})."""
    names = {b: op_names(t) for b, t in compiled.items()}
    by_scope, unmapped = collections.Counter(), 0.0
    for module, instrs in acc.pop("_prefill_ops").items():
        # the compiled bucket whose instructions of those names have the
        # events' result types
        def hits(b):
            return sum(names[b].get(i, ("",))[0] == result_type(t)
                       for i, (t, _) in instrs.items())
        b = max(names, key=hits, default=None)
        for instr, (text, sec) in instrs.items():
            got = names[b].get(instr) if b is not None else None
            if got is None or got[0] != result_type(text):
                unmapped += sec
            else:
                by_scope[scope_of(got[1])] += sec
    # query blocks of 2,048: the prefill's threshold calls, one a block
    # and latent layer
    blocks = acc["events"].get("topk_threshold", 0) / max(1, latent_layers)
    acc["prefill_scope_s"] = dict(by_scope.most_common())
    acc["prefill_scope_unmapped_s"] = unmapped
    acc["prefill_scope_ms_per_block"] = {
        k: 1e3 * v / blocks for k, v in by_scope.most_common()} \
        if blocks else {}


class _Recording:
    """The engine's prefill program, recording the abstract arguments of
    the first call at each bucket (to compile the same program again)."""

    def __init__(self, fn):
        self.fn, self.args = fn, {}

    def __call__(self, *args):
        import jax
        bucket = args[1].shape[-1]
        if bucket not in self.args:
            self.args[bucket] = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=getattr(x, "sharding", None)), args)
        return self.fn(*args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="on the CPU: the checks' tiny manifest, no device")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" /
                                         "dsa_account"))
    args = ap.parse_args(argv)

    kept = {}
    window, load = serve_glm5.Driver.window, tracer.Tracer.load

    def keeping_window(self, seconds, tr):
        kept["driver"] = self
        self.eng._prefill_fn = kept["prefill"] = \
            _Recording(self.eng._prefill_fn)
        window(self, seconds, tr)

    # the prefill programs' counters apart from the fused steps'
    note, prefill_aux = LLMEngine._note_aux, collections.Counter()

    def noting(self, aux, fused=False):
        if not fused:
            prefill_aux.update(dict(zip(hybrid.aux_fields(self.config),
                                        (int(v) for v in aux))))
        return note(self, aux, fused)

    def keeping_load(self):
        # read here: run.py deletes the trace once it has loaded it
        path = xplane.find_xplane(self.out_dir)
        trace = load(self)
        drv = kept["driver"]
        lane = serve_glm5.program_config(drv.model).latent_lane
        m = drv.model
        rows = drv.engine_kwargs["num_slots"] * m["index_topk"]
        keys = rf"^bf16\[\d+,{m['num_attention_heads']}," \
            rf"{m['qk_nope_head_dim'] + m['qk_rope_head_dim']},\d+\]"
        kept["acc"] = account(path, trace, f"bf16[{rows},{lane}]", keys)
        return trace

    serve_glm5.Driver.window = keeping_window
    tracer.Tracer.load = keeping_load
    LLMEngine._note_aux = noting
    rc = bench.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1",
                     "--manifest", args.manifest] +
                    (["--rehearse"] if args.rehearse else []))

    acc, drv = kept["acc"], kept["driver"]
    m, facts = drv.model, drv.facts
    rec = kept["prefill"]
    ran = set().union(*(buckets_seen([t for t, _ in instrs.values()],
                                     m["hidden_size"], rec.args)
                        for instrs in acc["_prefill_ops"].values()))
    compiled = {b: rec.fn.lower(*rec.args[b]).compile().as_text()
                for b in sorted(ran)}
    prefill_scopes(acc, compiled, m["hidden_size"],
                   m["mixer_pattern"].count("L"))
    acc["prefill_buckets_in_slice"] = sorted(compiled)
    # the prefill programs' expert counters over the run
    for name in ("moe_pairs_here", "moe_pairs_compacted",
                 "moe_compact_extra_chunks"):
        acc[f"prefill_{name}"] = prefill_aux.get(name)
    if prefill_aux.get("moe_pairs_here"):
        acc["prefill_moe_compacted_share"] = \
            prefill_aux.get("moe_pairs_compacted", 0) / \
            prefill_aux["moe_pairs_here"]
    acc["workload"], acc["seed"] = args.workload, args.seed
    # query tokens whose selection needed the search, all programs and the
    # fused steps' (the `[glm5]` line predates the counter)
    st = drv.eng.stats()
    acc["dsa_rows_searched"] = st.get("dsa_rows_searched")
    acc["dsa_step_rows_searched"] = st.get("dsa_step_rows_searched")
    # how often a long pass took the expanded form
    acc["dsa_expanded_tokens"] = st.get("dsa_expanded_tokens")
    acc["prefilled_tokens"] = st.get("prefilled_tokens")
    # the long pass's compact expert layer, all programs
    acc["moe_pairs_compacted"] = st.get("moe_pairs_compacted")
    acc["moe_compact_extra_chunks"] = st.get("moe_compact_extra_chunks")
    if st.get("moe_pairs_here"):
        acc["moe_compacted_share_all_programs"] = \
            st.get("moe_pairs_compacted", 0) / st["moe_pairs_here"]
    if st.get("prefilled_tokens"):
        acc["dsa_expanded_share"] = \
            st.get("dsa_expanded_tokens", 0) / st["prefilled_tokens"]
    per_call = facts.get("slice_dsa_step_rows_selected_per_call")
    calls = acc["events"].get("sparse_latent", 0)
    unit_s = acc["seconds"].get("sparse_latent", 0.0) + \
        acc["seconds"].get("row_gather", 0.0)
    if per_call and calls and unit_s:
        w = dsa.sparse_latent(per_call, facts["slice_decode_queries_per_call"],
                              m["num_attention_heads"], m["kv_lora_rank"],
                              m["qk_rope_head_dim"])
        peaks = manifest.peaks("TPU v5 lite")
        hbm = per_call * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * 2
        least = calls * max(w["flops"] / peaks["flops_per_s_bf16"],
                            hbm / peaks["hbm_bytes_per_s"])
        acc["sparse_unit"] = {"calls": calls, "hbm_bytes_per_call": hbm,
                              "flops_per_call": w["flops"],
                              "roofline_pct": 100.0 * least / unit_s}
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}.json").write_text(json.dumps(acc, indent=1))
    print("DSA " + json.dumps(acc), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
