"""Readings for a long DSA pass's attention, taken on the chip: ONE latent
layer of the GLM-5 cell's prefill (`models/hybrid._dsa_write_and_attend`
with the pass's own rows, at the cell's widths) in its two forms, at
several prompt lengths.

    python3 tools/dsa_prefill_sweep.py [--lengths 4096,16384,32768] \
        [--calls 2] [--forms absorbed,expanded] [--seed 0]

Forms: `absorbed` is the masked latent walk (`masked_latent`, the route of
a pass shorter than `hybrid.DSA_EXPANDED_MIN_TOKENS`), `expanded` the
per-head keys built once and `masked_expanded` (the route from it on).  For
each length and form: the layer program's device time a call from a
profiler trace, the time of each DSA kernel in it, and its largest
operations.  Then every output against an oracle: the layer's output by the
two forms against each other, and for the last 2,048-query block (the
longest key range) each kernel on 8 heads against its XLA oracle
(`masked_expanded_attention_xla`; `paged_latent_attention_xla` through W^V),
as the largest difference over the largest value.  Prints one JSON line a
length and form, and one a length for the checks.
"""
# tpu-lint: disable-file=TPL002 -- a measuring script: its jits are one layer of the prefill alone
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import tracer, xplane      # noqa: E402

KERNELS = ("masked_latent", "masked_expanded", "paged_index_scores",
           "topk_threshold")
HEADS_CHECKED = 8


def _config():
    from benchmarks.drivers import serve_glm5
    conf = json.loads((ROOT / "benchmarks/configs/glm5-d5e16.json"
                       ).read_text())
    model = dict(serve_glm5.model_of(conf), mixer_pattern="L")
    return serve_glm5.program_config(model), conf["engine"]["page_size"]


@contextlib.contextmanager
def _form(hybrid, form):
    """The route a pass of any length takes, for the length of the block."""
    old = hybrid.DSA_EXPANDED_MIN_TOKENS
    hybrid.DSA_EXPANDED_MIN_TOKENS = 1 << 30 if form == "absorbed" else 1
    try:
        yield
    finally:
        hybrid.DSA_EXPANDED_MIN_TOKENS = old


def _layer(hybrid, cfg, n, page):
    """fn(lp, h, cache) -> (y, cache): the prefill's latent layer 0 over a
    prompt of n tokens at positions 0 .. n - 1, pages 1 .. n / page."""
    import jax.numpy as jnp
    pages = jnp.arange(1, n // page + 1, dtype=jnp.int32)[None]
    pos = jnp.arange(n)[None]
    pidx = jnp.take(pages, pos // page, axis=1)
    off = pos % page
    zero, length = jnp.zeros((1,), jnp.int32), jnp.full((1,), n, jnp.int32)

    def dsa_layer_impl(lp, h, cache):
        return hybrid._dsa_write_and_attend(lp, h, pos, cache, 0, pidx, off,
                                            pages, zero, length, cfg,
                                            own=True)
    return dsa_layer_impl, pages


def _last_block_checks(hybrid, SA, PA, cfg, lp, h, cache, pages, n,
                       interpret):
    """Each kernel against its oracle on the last query block, 8 heads."""
    import jax.numpy as jnp
    c = cfg
    blk = min(n, hybrid.DSA_QUERY_BLOCK)
    start = n - blk
    hb, pb = h[:, start:], jnp.arange(start, n)[None]
    sin, cos = hybrid._rope_tables(pb, c)
    q_nope, q_rope, cq = hybrid._latent_q(lp, hb, sin, cos, c)
    qi, w = hybrid.index_query(lp, hb, cq, sin, cos, c)
    qo, vl = jnp.asarray([start], jnp.int32), jnp.asarray([blk], jnp.int32)
    scores = SA.paged_index_scores(qi, w, cache["ix"][0], pages, qo, vl)
    sel = (scores,) + SA.topk_threshold(scores, c.index_topk, qo)
    hs = slice(0, HEADS_CHECKED)
    psin, pcos = hybrid._rope_tables(jnp.arange(n)[None], c)
    rows = hybrid._latent_row(lp, h, psin, pcos, c)
    lp8 = dict(lp, kv_b_k_w=lp["kv_b_k_w"][hs], kv_b_v_w=lp["kv_b_v_w"][hs])
    keys = hybrid._expanded_keys(
        lp8, rows, dataclasses.replace(c, num_heads=HEADS_CHECKED))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)[:, :, hs]
    wv = lp8["kv_b_v_w"]
    want = SA.masked_expanded_attention_xla(q, keys, rows, wv, qo, sel,
                                            c.mla_softmax_scale)
    got = SA.masked_expanded_attention_pallas(q, keys, rows, wv, qo, sel,
                                              c.mla_softmax_scale,
                                              interpret=interpret)
    # the absorbed kernel at its own shape (all heads: its query tile is
    # 512 rows), its oracle on the 8 heads checked
    qa = hybrid._absorbed_q(lp, q_nope, q_rope, c)
    rest = (cache["c"][0], pages, qo, vl, c.kv_lora_rank, c.mla_softmax_scale)
    lat_x = PA.paged_latent_attention_xla(qa[:, :, hs], *rest, select=sel)
    lat_p = PA.paged_latent_attention_pallas(qa, *rest, select=sel,
                                             interpret=interpret)[:, :, hs]
    through = lambda o: jnp.einsum("bthc,hcv->bthv", o, wv)
    scale = float(jnp.abs(want.astype(jnp.float32)).max())

    def gap(a, b):
        return float(jnp.abs(a.astype(jnp.float32) -
                             b.astype(jnp.float32)).max()) / scale
    return {"expanded_vs_oracle": gap(got, want),
            "absorbed_vs_oracle": gap(through(lat_p), through(lat_x)),
            "oracles_apart": gap(through(lat_x), want)}


def main() -> None:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate.kernels import paged_attention as PA
    from paddle_tpu.incubate.kernels import sparse_attention as SA
    from paddle_tpu.models import hybrid
    ap = argparse.ArgumentParser()
    ap.add_argument("--lengths", default="4096,16384,32768")
    ap.add_argument("--calls", type=int, default=2)
    ap.add_argument("--forms", default="absorbed,expanded")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse off the chip (the XLA routes, the kernels "
                         "of the checks in interpret mode; the times it "
                         "prints mean nothing)")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        sys.exit("dsa_prefill_sweep.py reads times: it runs on a TPU only")
    cfg, page = _config()
    params = hybrid.init_params(cfg, jax.random.key(args.seed))
    lp = params["layers"][0]
    for n in (int(x) for x in args.lengths.split(",")):
        h = jax.random.normal(jax.random.key(args.seed + n),
                              (1, n, cfg.hidden_size)).astype(cfg.dtype)
        fresh = hybrid.init_paged_cache(cfg, n // page + 1, page, 1)
        outs = {}
        for form in args.forms.split(","):
            with _form(hybrid, form):
                fn, pages = _layer(hybrid, cfg, n, page)   # traced anew
                run = jax.jit(fn)
                y, cache = run(lp, h, fresh)
                jax.block_until_ready(y)
                out_dir = tempfile.mkdtemp(prefix="dsa_prefill_sweep_")
                t = tracer.Tracer(out_dir)
                t.start()
                for _ in range(args.calls):
                    jax.block_until_ready(run(lp, h, fresh)[0])
                t.stop()
            outs[form] = y
            if dev.platform != "tpu":
                print(json.dumps({"length": n, "form": form,
                                  "device": dev.device_kind}), flush=True)
                shutil.rmtree(out_dir, ignore_errors=True)
                continue
            trace = t.load()
            shutil.rmtree(out_dir, ignore_errors=True)
            durs = xplane.whole_events(trace, xplane.MODULES_LINE,
                                       r"^jit_dsa_layer_impl\(")
            ops = xplane.op_seconds(trace)
            calls = max(1, len(durs))
            kernels = {}
            for k in KERNELS:
                secs, events = xplane.matching(ops, rf"^%{k}\b")
                if events:
                    kernels[k] = {"ms_per_call": 1e3 * secs / calls,
                                  "events_per_call": events / calls}
            print(json.dumps({
                "length": n, "form": form, "device": dev.device_kind,
                "calls": len(durs),
                "ms_per_call": 1e3 * sum(durs) / calls if durs else None,
                "kernels": kernels,
                "top_ops_ms_per_call": [[k, 1e3 * v / calls] for k, v in
                                        xplane.top(ops, 8)]}), flush=True)
        checks = {"length": n}
        if {"absorbed", "expanded"} <= set(outs):
            a, b = (outs[f].astype(jnp.float32) for f in
                    ("absorbed", "expanded"))
            checks["layer_expanded_vs_absorbed"] = \
                float(jnp.abs(a - b).max() / jnp.abs(a).max())
        checks.update(_last_block_checks(
            hybrid, SA, PA, cfg, lp, h, cache, pages, n,
            interpret=dev.platform != "tpu"))
        print(json.dumps(checks), flush=True)


if __name__ == "__main__":
    main()
