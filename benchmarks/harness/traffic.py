"""The one general traffic generator.  A traffic mix is a data file
(`traffic/<mix>.json`) of parameters; this module turns it, `--seed` and the
window's length into requests or batches.  No mix needs code of its own.

Every seed gets the same work.  The *sizes* (prompt and output lengths) are
not drawn from `--seed`: they are the quantiles of the mix's length laws,
dealt into blocks that each span the whole law.  Their order is shuffled
block by block, by the mix's own `pool.order_seed` where it has one (one fixed
schedule for every `--seed`: the tails of an open loop hang on which long
prompts meet which bursts, and a schedule that moved with the seed made
`ttft_p95_ms` spread by a quarter of its median between seeds, PERF.md PR 24),
else by `--seed`.  The arrival
times of an open loop are one fixed sample path (from the mix's own
`arrival_seed`), the same for every `--seed`, with exactly rate x seconds
arrivals in the window.  The token ids (and the weights) always come from `--seed`;
so two seeds differ in what is computed, not in how much work they were
dealt or when.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due_s: float            # open loop: when it is due; closed loop: 0.0
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def _quantiles(law: dict, n: int) -> np.ndarray:
    """n whole-number sizes at the law's (i + 1/2) / n quantiles."""
    u = (np.arange(n) + 0.5) / n
    kind = law["law"]
    if kind == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(x)) for x in u])
        x = law["median"] * np.exp(law["sigma"] * z)
    elif kind == "uniform":
        x = law["min"] + u * (law["max"] - law["min"])
    elif kind == "fixed":
        x = np.full(n, law["value"], float)
    else:
        raise ValueError(f"unknown length law {kind!r}")
    lo, hi = law.get("min", 1), law.get("max", math.inf)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def size_pool(mix: dict, seed: int) -> np.ndarray:
    """[pool, 2] (prompt length, output length), ordered for this seed."""
    block, blocks = mix["pool"]["block"], mix["pool"]["blocks"]
    n = block * blocks
    # block k holds quantiles k, k + blocks, k + 2 blocks, ...: the whole law
    prompts = _quantiles(mix["prompt_len"], n).reshape(block, blocks).T
    outputs = _quantiles(mix["output_len"], n).reshape(block, blocks).T
    # pair prompts with outputs by one fixed shuffle per block (no seed)
    fixed = np.random.default_rng(mix["pool"]["pairing_seed"])
    outputs = np.stack([row[fixed.permutation(block)] for row in outputs])
    order_seed = mix["pool"].get("order_seed")
    rng = np.random.default_rng([int(seed), 1] if order_seed is None
                                else [int(order_seed), 1])
    order = rng.permutation(blocks)
    pool = []
    for k in order:
        inner = rng.permutation(block)
        pool.append(np.stack([prompts[k][inner], outputs[k][inner]], axis=1))
    return np.concatenate(pool)


def arrival_times(mix: dict, seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of an open loop: one fixed sample path.  A
    Poisson process given its count: round(rate x seconds) arrivals, each
    uniform over the window, so the rate that the mix states is the rate that
    the window gets."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    rng = np.random.default_rng(mix["arrival_seed"])
    n = int(round(float(mix["rate_per_s"]) * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


def _tokens(rng, n: int, vocab: int, mix: dict) -> np.ndarray:
    if mix["token_ids"] != "uniform":
        raise ValueError(f"unknown token law {mix['token_ids']!r}")
    return rng.integers(0, vocab, n, dtype=np.int32)


class RequestSource:
    """Requests in the order this seed sends them.  `take()` hands out the
    next; the pool wraps round (with fresh token ids) if a run outlasts it."""

    def __init__(self, mix: dict, seed: int, vocab: int, seconds: float):
        self.mix, self.vocab = mix, vocab
        self.pool = size_pool(mix, seed)
        self.rng = np.random.default_rng([int(seed), 2])
        self.due = arrival_times(mix, seconds) if mix["loop"] == "open" \
            else None
        self.sent = 0

    def __len__(self):
        return len(self.due) if self.due is not None else len(self.pool)

    def take(self) -> Request:
        i = self.sent
        n_prompt, n_out = self.pool[i % len(self.pool)]
        due = float(self.due[i]) if self.due is not None else 0.0
        self.sent += 1
        return Request(i, due, _tokens(self.rng, int(n_prompt), self.vocab,
                                       self.mix), int(n_out))

    def exhausted(self) -> bool:
        return self.due is not None and self.sent >= len(self.due)


class BatchSource:
    """Training batches: a new [batch, seq + 1] block of ids for every step,
    from `--seed`; tokens are [:, :-1] and labels [:, 1:]."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, vocab
        self.rng = np.random.default_rng([int(seed), 3])

    def take(self):
        ids = _tokens(self.rng, self.mix["batch"] * (self.mix["seq"] + 1),
                      self.vocab, self.mix).reshape(self.mix["batch"], -1)
        return np.ascontiguousarray(ids[:, :-1]), \
            np.ascontiguousarray(ids[:, 1:])
