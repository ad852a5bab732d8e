"""Readings for the decode step's top-k of a DSA layer, taken on the chip:
the two routes alone at the GLM-5 cell's shape, one call a layer and step.

    python3 tools/dsa_topk_sweep.py [--slots 12] [--table 34816] [--k 2048] \
        [--calls 16] [--forms sort,counted]

Scores are what `paged_index_scores` hands the decode branch: float32
[slots, n_blocks, 1, 512], a slot live up to its own position (drawn from
4,096 to the table's end, as the cell's slots sit; one slot padding, valid
0) and -1e30 past it; half the calls round the scores to halves, so that
ties and -0 / 0 bind.  Forms: `sort` is `topk_rows` (`lax.top_k` over each
slot's whole table), `counted` is `topk_rows_pallas` (the threshold kernel
over the slots as one tile's rows, then `topk_compact`).  For each: the
program's device time a call from a profiler trace, its largest operations,
and every call's chosen set against the sort's and against `numpy`'s own
ordering (`exact`), with the counted route's order ascending.  Prints one
JSON line a form.
"""
# tpu-lint: disable-file=TPL002 -- a measuring script: its jits are the top-k alone, no program of the library
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


def _scores(rng, slots, table, k, ties):
    import numpy as np
    x = rng.standard_normal((slots, 1, table)).astype(np.float32)
    if ties:
        x = np.round(x * 2) / 2
        x[0, 0, :64] = -0.0
    end = rng.integers(4096, table + 1, slots)
    end[:2] = (table, k)
    valid = np.ones(slots, np.int32)
    valid[-1] = 0
    for b in range(slots):
        x[b, 0, end[b] if valid[b] else 0:] = -1e30
    return x, (end - 1).astype(np.int32), valid


def _want(x, k):
    """The chosen positions of each row by the rule itself: the k largest
    scores among the candidates, ties to the lower position."""
    import numpy as np
    out = []
    for row in x[:, 0]:
        row = np.where(row == 0, 0.0, row)
        cand = np.nonzero(row > -1e29)[0]
        order = np.lexsort((cand, -row[cand]))
        out.append(np.sort(cand[order[:k]]))
    return out


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.incubate.kernels import sparse_attention as SA
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=12)
    ap.add_argument("--table", type=int, default=34816)
    ap.add_argument("--k", type=int, default=2048)
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--forms", default="sort,counted")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse off the chip (a small table, kernels in "
                         "interpret mode; the times it prints mean nothing)")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        sys.exit("dsa_topk_sweep.py reads times: it runs on a TPU only")
    if args.allow_cpu:
        args.table, args.k, args.calls = 5000, 300, 2
    rng = np.random.default_rng(args.table)
    cases = [_scores(rng, args.slots, args.table, args.k, i % 2 == 1)
             for i in range(args.calls)]
    wants = [_want(x, args.k) for x, _, _ in cases]
    dev_cases = [(SA.blocked(jnp.asarray(x), 64), jnp.asarray(qo),
                  jnp.asarray(vl)) for x, qo, vl in cases]
    forms = {
        "sort": lambda s, qo, vl: SA.topk_rows(s, args.k),
        "counted": lambda s, qo, vl: SA.topk_rows_pallas(
            s, args.k, qo, vl, interpret=args.allow_cpu)}
    for form in args.forms.split(","):
        take = forms[form]

        def topk_impl(s, qo, vl):
            return take(s, qo, vl)

        fn = jax.jit(topk_impl)
        exact = True
        for (s, qo, vl), want in zip(dev_cases, wants):
            idx, n = map(np.asarray, fn(s, qo, vl))
            for b, w in enumerate(want):
                got = idx[b, 0, :n[b, 0]]
                exact &= np.array_equal(np.sort(got), w) and \
                    not idx[b, 0, n[b, 0]:].any()
                if form == "counted":
                    exact &= np.array_equal(got, w)
        out_dir = tempfile.mkdtemp(prefix="dsa_topk_sweep_")
        t = tracer.Tracer(out_dir)
        t.start()
        for case in dev_cases:
            jax.block_until_ready(fn(*case))
        t.stop()
        trace = t.load()
        shutil.rmtree(out_dir, ignore_errors=True)
        durs = xplane.whole_events(trace, xplane.MODULES_LINE,
                                   r"^jit_topk_impl\(")
        print(json.dumps({
            "form": form, "device": dev.device_kind, "slots": args.slots,
            "table": args.table, "k": args.k, "calls": len(durs),
            "ms_per_call": 1e3 * sum(durs) / len(durs) if durs else None,
            "ms_longest": 1e3 * max(durs) if durs else None,
            "exact": bool(exact),
            "top_ops_s": xplane.top(xplane.op_seconds(trace), 5)}),
            flush=True)


if __name__ == "__main__":
    main()
