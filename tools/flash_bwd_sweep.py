"""Readings for the block of the flash backward, taken on the chip: the
backward alone at a training cell's shape, causal, for every (block_q,
block_k) asked for.

    python3 tools/flash_bwd_sweep.py [--shapes joyai,gpt3] \
        [--blocks 512,1024,2048] [--calls 4]

Shapes: joyai = [4, 8192, 32, 192|128], gpt3 = [4, 2048, 16, 128].  First
the dk/dv + dq split (the route of a length past `_fused_bwd_fits`, and the
only backward before PR 35) at the module's own block, then the one fused
kernel by block pair.  For each: dq, dk, dv against `jax.vjp(attention_xla)`
on the first batch row's first four heads (widest difference over the widest
value; the whole shape's scores do not fit the chip), and the kernels' device
time a call from a profiler trace (the host's clock would count the layout
copies XLA puts around a bare call).  Prints one JSON line a reading.
"""
# tpu-lint: disable-file=TPL002 -- a measuring script: its jits are one kernel call each, no program of the library
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import tracer, xplane      # noqa: E402

SHAPES = {"joyai": (4, 8192, 32, 192, 128), "gpt3": (4, 2048, 16, 128, 128)}
KERNEL = r"tpu_custom_call .* in=6$"


def _traced_ms(fn, calls: int):
    """Device ms a call of the six-operand kernels inside `fn`, and their
    number a call."""
    import jax
    out_dir = tempfile.mkdtemp(prefix="flash_bwd_sweep_")
    t = tracer.Tracer(out_dir)
    t.start()
    for _ in range(calls):
        jax.block_until_ready(fn())
    t.stop()
    secs, n = xplane.matching(xplane.op_seconds(t.load()), KERNEL)
    shutil.rmtree(out_dir, ignore_errors=True)
    return 1e3 * secs / calls, n / calls


def _gap(got, want) -> float:
    import numpy as np
    worst = 0.0
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        worst = max(worst, float(np.abs(a - b).max() / np.abs(b).max()))
    return worst


def main() -> None:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate.kernels import flash_attention as FA
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="joyai,gpt3")
    ap.add_argument("--blocks", default="512,1024,2048")
    ap.add_argument("--calls", type=int, default=4)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("flash_bwd_sweep.py reads times: it runs on a TPU only")
    blocks = [int(x) for x in args.blocks.split(",")]
    resident_max, own_blocks = FA._BWD_DQ_RESIDENT_MAX, FA._bwd_blocks
    for name in args.shapes.split(","):
        B, S, H, D, Dv = SHAPES[name]
        scale = D ** -0.5
        ks = jax.random.split(jax.random.key(S), 4)
        q, k = (jax.random.normal(ks[i], (B, S, H, D), jnp.bfloat16)
                for i in (0, 1))
        v, g = (jax.random.normal(ks[i], (B, S, H, Dv), jnp.bfloat16)
                for i in (2, 3))
        out, lse = jax.jit(lambda q, k, v: FA._flash_fwd_impl(
            q, k, v, True, scale))(q, k, v)
        cut = lambda x: x[:1, :, :4]
        want = jax.jit(lambda q, k, v, g: jax.vjp(
            lambda *a: FA.attention_xla(*a, causal=True, scale=scale),
            q, k, v)[1](g))(*map(cut, (q, k, v, g)))

        def reading(kernels, pair):
            # the route and the block are read while `fn` is traced
            FA._BWD_DQ_RESIDENT_MAX = resident_max if kernels == "fused" else 0
            FA._bwd_blocks = lambda S, Sk: pair
            fn = jax.jit(lambda *a: FA._flash_bwd_impl(*a, True, scale))
            got = jax.block_until_ready(fn(q, k, v, out, lse, g))
            ms, n = _traced_ms(lambda: fn(q, k, v, out, lse, g), args.calls)
            print(json.dumps({
                "shape": name, "device": dev.device_kind, "kernels": kernels,
                "block_q": pair[0], "block_k": pair[1], "ms_per_call": ms,
                "kernels_per_call": n,
                "gap_to_xla": _gap(map(cut, got), want)}), flush=True)

        reading("split", own_blocks(S, S))
        for bq in blocks:
            for bk in blocks:
                if bq <= S and bk <= S:
                    reading("fused", (bq, bk))


if __name__ == "__main__":
    main()
