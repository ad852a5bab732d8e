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
against the absorbed form's operations, over the two pieces' time).  The
benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
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
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
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
        break                               # one chip
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
                attention / walk if walk else None}


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
        window(self, seconds, tr)

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
    rc = bench.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1",
                     "--manifest", args.manifest] +
                    (["--rehearse"] if args.rehearse else []))

    acc, drv = kept["acc"], kept["driver"]
    m, facts = drv.model, drv.facts
    acc["workload"], acc["seed"] = args.workload, args.seed
    # query tokens whose selection needed the search, all programs and the
    # fused steps' (the `[glm5]` line predates the counter)
    st = drv.eng.stats()
    acc["dsa_rows_searched"] = st.get("dsa_rows_searched")
    acc["dsa_step_rows_searched"] = st.get("dsa_step_rows_searched")
    # how often a long pass took the expanded form
    acc["dsa_expanded_tokens"] = st.get("dsa_expanded_tokens")
    acc["prefilled_tokens"] = st.get("prefilled_tokens")
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
