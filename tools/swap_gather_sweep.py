"""Readings for the spill / swap-out gather, taken on the chip: the engine's
gather program alone (`swap_out_impl`: a slot's width of page ids, one
`swap_out_pages` a piece of `_swap_w`) at a serving cell's pool shape, for
the library's form and for the forms it was chosen among.

    python3 tools/swap_gather_sweep.py [--shapes xing4,mistral,gpt3] \
        [--forms library,index,loop] [--calls 8]

Shapes: xing4 = the latent lane {"c": [13, 6145, 64, 640]}, 96 ids in pieces
of 8; mistral = {"k","v"} [16, 2049, 16, 8, 128], 128 ids in pieces of 16;
gpt3 = {"k","v"} [24, 1025, 16, 16, 128], 64 ids in pieces of 4 (the piece
and the width by the engine's own rule, from the page's bytes).  Forms:
`library` is `models.gpt.swap_out_pages` as it stands (one `dynamic_slice` a
page, concatenated a piece); `index` is `a[:, page_ids]`, the only form
before PR 38; `loop` a `fori_loop` of one `dynamic_slice` +
`dynamic_update_slice` a page into the piece's buffer.  For each: the
program's device time a call from a profiler trace (its event on the
device's "XLA Modules" line, as `swap_gather_program_device_ms` reads it),
its largest operations, and every gathered piece against `numpy` indexing of
the same pool fetched to the host, bit for bit.  Prints one JSON line a
reading.
"""
# tpu-lint: disable-file=TPL002 -- a measuring script: its jits are the gather program alone, no program of the library
from __future__ import annotations

import argparse
import json
import math
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import tracer, xplane      # noqa: E402

# the lanes of a pool by shape (bfloat16), and the pages a slot may hold
SHAPES = {
    "xing4": ({"c": (13, 6145, 64, 640)}, 96),
    "mistral": ({"k": (16, 2049, 16, 8, 128), "v": (16, 2049, 16, 8, 128)},
                128),
    "gpt3": ({"k": (24, 1025, 16, 16, 128), "v": (24, 1025, 16, 16, 128)},
             64),
}
PIECE_BYTES = 16 << 20          # `inference.engine._D2H_PIECE_BYTES`


def _index(cache, page_ids):
    return {n: a[:, page_ids] for n, a in cache.items()}


def _loop(cache, page_ids):
    import jax.numpy as jnp
    from jax import lax
    W = page_ids.shape[0]

    def take(a):
        def page(j, piece):
            return lax.dynamic_update_slice_in_dim(
                piece, lax.dynamic_slice_in_dim(a, page_ids[j], 1, axis=1),
                j, axis=1)
        return lax.fori_loop(
            0, W, page, jnp.zeros((a.shape[0], W) + a.shape[2:], a.dtype))

    return {n: take(a) for n, a in cache.items()}


def _piece_and_width(lanes, per_slot):
    """`LLMEngine._swap_w` and `_d2h_slot_w` for a pool of these lanes."""
    page_bytes = sum(2 * math.prod(s) // s[1] for s in lanes.values())
    w = max(1, min(per_slot, PIECE_BYTES // page_bytes))
    W = 1 << (w.bit_length() - 1)
    return W, -(-per_slot // W) * W, page_bytes


def _pool(lanes):
    """A pool whose every bfloat16 is a function of where it lies, made on
    the device a lane at a time (no temporaries the size of a lane)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def lane(shape, salt):
        word = jnp.full(shape, salt, jnp.uint32)
        for axis, mul in zip(range(len(shape)), (7919, 104729, 1299709,
                                                 15485863, 31)):
            word = word * 33 + lax.broadcasted_iota(jnp.uint32, shape,
                                                    axis) * mul
        # exponent bits all set would be inf/nan: keep them finite, the
        # comparison is on the bits anyway
        return lax.bitcast_convert_type(
            (word & 0x7F7F).astype(jnp.uint16), jnp.bfloat16)

    return {n: jax.jit(lane, static_argnums=(0, 1))(s, i + 1)
            for i, (n, s) in enumerate(lanes.items())}


def main() -> None:
    import jax
    import numpy as np

    from paddle_tpu.models import gpt as gpt_mod
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="xing4,mistral,gpt3")
    ap.add_argument("--forms", default="library,index,loop")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse the script's control flow off the chip "
                         "(tiny shapes; the times it prints mean nothing)")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        sys.exit("swap_gather_sweep.py reads times: it runs on a TPU only")
    forms = {"library": gpt_mod.swap_out_pages, "index": _index,
             "loop": _loop}
    for name in args.shapes.split(","):
        lanes, per_slot = SHAPES[name]
        if args.allow_cpu:      # the same ranks, a few hundred KB
            lanes = {n: (2, 19) + tuple(max(2, d // 16) for d in s[2:])
                     for n, s in lanes.items()}
            per_slot = 12
        W, width, page_bytes = _piece_and_width(lanes, per_slot)
        pool = _pool(lanes)
        host = {n: np.asarray(a).view(np.uint16) for n, a in pool.items()}
        pages = next(iter(lanes.values()))[1]
        rng = np.random.default_rng(pages)
        batches = []
        for _ in range(args.calls):     # a spill's ids: wanted, then null
            ids = np.zeros((width,), np.int32)
            n = int(rng.integers(1, per_slot + 1))
            ids[:n] = rng.integers(1, pages, size=n)
            ids[n - 1] = pages - 1      # the pool's last page among them
            batches.append(ids)
        dev_ids = [jax.device_put(b) for b in batches]
        for form in args.forms.split(","):
            take = forms[form]

            def swap_out_impl(pool, ids):
                return [take(pool, ids[i:i + W])
                        for i in range(0, ids.shape[0], W)]

            # tpu-lint: disable=TPL003 -- the pool is read and stays live, as in the engine's program
            fn = jax.jit(swap_out_impl)
            got = jax.block_until_ready(fn(pool, dev_ids[0]))
            exact = all(
                np.array_equal(np.asarray(piece[n]).view(np.uint16),
                               host[n][:, batches[0][i * W:(i + 1) * W]])
                for i, piece in enumerate(got) for n in lanes)
            del got
            out_dir = tempfile.mkdtemp(prefix="swap_gather_sweep_")
            t = tracer.Tracer(out_dir)
            t.start()
            for ids in dev_ids:
                jax.block_until_ready(fn(pool, ids))
            t.stop()
            trace = t.load()
            shutil.rmtree(out_dir, ignore_errors=True)
            durs = xplane.whole_events(trace, xplane.MODULES_LINE,
                                       r"^jit_swap_out_impl\(")
            print(json.dumps({
                "shape": name, "device": dev.device_kind, "form": form,
                "ids": width, "piece": W, "page_bytes": page_bytes,
                "calls": len(durs),
                "ms_per_call": 1e3 * sum(durs) / len(durs) if durs else None,
                "ms_longest": 1e3 * max(durs) if durs else None,
                "bit_exact": exact,
                "top_ops_s": xplane.top(xplane.op_seconds(trace), 4)}),
                flush=True)
        del pool, host


if __name__ == "__main__":
    main()
