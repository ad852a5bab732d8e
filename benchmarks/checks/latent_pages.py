"""Readings for the page size of the `xing4_0` configuration's latent lane,
taken on the chip: the latent paged kernel alone, at the cell's decode shape
(128 slots, one query token each, 32 heads on one 640-wide row) over a pool
of 393,216 tokens, for each page size and block length asked for:

    python3 benchmarks/checks/latent_pages.py [--pages 32,64,128] \
        [--block-keys 256,512,1024] [--layers 13]

Live lengths are drawn as the cell's traffic leaves them (a slot somewhere
between its prompt's end and its last token).  For every pair: the kernel
against its gather oracle on a few slots (widest difference), the mean time
of a call, and that time against `work/mla.absorbed_attention`'s roofline.
Prints one JSON line a reading.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import manifest          # noqa: E402
from benchmarks.work import mla                  # noqa: E402

B, H, W, LATENT, TOKENS, MAX_LEN = 128, 32, 640, 512, 393216, 6144


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.incubate.kernels import paged_attention as PA
    ap = argparse.ArgumentParser()
    ap.add_argument("--pages", default="32,64,128")
    ap.add_argument("--block-keys", default="256,512,1024")
    ap.add_argument("--layers", type=int, default=13)
    ap.add_argument("--calls", type=int, default=40)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("latent_pages.py reads times: it runs on a TPU only")
    peaks = manifest.peaks(dev.device_kind)
    rng = np.random.default_rng(0)
    prompts = np.clip(np.exp(rng.normal(np.log(512), 0.8, B)), 64, 2048)
    outs = np.clip(np.exp(rng.normal(np.log(1536), 0.5, B)), 384, 4096)
    lengths = (prompts + rng.uniform(0, 1, B) * outs).astype(np.int32)
    live = int(lengths.sum())
    q = jnp.asarray(rng.normal(size=(B, 1, H, W)), jnp.bfloat16
                    ).at[..., 576:].set(0)
    for page in (int(x) for x in args.pages.split(",")):
        P = TOKENS // page + 1
        n = MAX_LEN // page
        pool = jax.jit(lambda k: (jax.random.normal(
            k, (args.layers * P, page, W), jnp.bfloat16)
        ).at[..., 576:].set(0))(jax.random.key(page))
        table = np.zeros((B, n), np.int32)
        nxt = 1
        for b in range(B):
            need = -(-int(lengths[b]) // page)
            table[b, :need] = np.arange(nxt, nxt + need)
            nxt += need
        assert nxt <= P, "the drawn lengths do not fit the pool"
        tbl, qo, vl = (jnp.asarray(table), jnp.asarray(lengths - 1),
                       jnp.ones((B,), jnp.int32))
        want = jax.jit(functools.partial(
            PA.paged_latent_attention_xla, latent=LATENT, scale=0.1))(
            q[:8], pool, tbl[:8], qo[:8], vl[:8])
        for keys in (int(x) for x in args.block_keys.split(",")):
            PA._LATENT_MAX_BLOCK_KEYS = keys
            fn = jax.jit(functools.partial(
                PA.paged_latent_attention_pallas, latent=LATENT, scale=0.1))

            def layers(q, pool, tbl, qo, vl):
                # one call a layer, each on its own rows of the pool
                return [fn(q, pool, tbl + l * P, qo, vl)
                        for l in range(args.layers)]
            run = jax.jit(layers)
            got = run(q, pool, tbl, qo, vl)
            jax.block_until_ready(got)
            gap = float(jnp.abs(got[0][:8].astype(jnp.float32) -
                                want.astype(jnp.float32)).max())
            t0 = time.perf_counter()
            for _ in range(args.calls):
                got = run(q, pool, tbl, qo, vl)
            jax.block_until_ready(got)
            per_call = (time.perf_counter() - t0) / args.calls / args.layers
            w = mla.absorbed_attention(live, B, H, LATENT, 64)
            least = max(w["flops"] / peaks["flops_per_s_bf16"],
                        w["bytes"] / peaks["hbm_bytes_per_s"])
            print(json.dumps({
                "page": page, "block_keys": keys, "live_tokens": live,
                "us_per_call": 1e6 * per_call,
                "roofline_share": 100 * least / per_call,
                "oracle_gap_max": gap,
                "want_abs_max": float(jnp.abs(want).max())}), flush=True)
        del pool


if __name__ == "__main__":
    main()
