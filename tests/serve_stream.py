"""A deterministic request stream through one `LLMEngine`, for the tests that
assert on counts and parity (not collected: tests import it as they import
`op_test`).

`run_stream` enqueues every request up front, runs the engine dry under
`jax.transfer_guard("disallow")`, checks the page pool's partition at drain
and returns `eng.stats()` plus the derived counts below.  The engine's clock
counts steps, so every latency it reports is in engine steps, not seconds:
two runs of one stream read the same numbers.

- `requests`: finished outputs, every turn counted;
- `outputs_digest`: every stream's tokens, keyed by (session, turn), so
  passes that schedule differently still compare conversations;
- `output_tokens`: those streams in the same order;
- `final_output_tokens`: tokens in final outputs (replayed work earns none);
- `dispatches_per_step`: decode-side dispatches over busy steps in the ring;
- `returning_prefilled_tokens`: prompt tokens that returning turns
  (turn >= 2) prefilled rather than took from a cache or the tier;
- `returning_steps_to_first_token`: engine steps from each returning turn's
  enqueue to its first token, summed.
"""
from __future__ import annotations

import hashlib

import jax
import numpy as np

from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models import gpt as gpt_mod
from paddle_tpu.quantization.serving import kv_page_bytes


def _prompts(rng, vocab, n, max_prompt, page_size, shared_prefix_frac):
    shared = None
    if shared_prefix_frac > 0.0:
        shared_len = min(max_prompt - 1,
                         max(page_size + page_size // 2, max_prompt // 2))
        shared = rng.randint(0, vocab, (shared_len,)).astype(np.int32)
    prompts = []
    for ln in rng.randint(1, max_prompt + 1, size=n):
        if shared is not None and rng.rand() < shared_prefix_frac:
            # 1 in 4 shared-prefix requests IS the bare prefix: completing it
            # registers its final partial page, so later extensions take the
            # copy-on-write partial-page path too
            tail = 0 if rng.rand() < 0.25 else \
                rng.randint(1, max_prompt - shared.size + 1)
            prompts.append(np.concatenate(
                [shared, rng.randint(0, vocab, (tail,)).astype(np.int32)]))
        else:
            prompts.append(rng.randint(0, vocab, (ln,)).astype(np.int32))
    return prompts


def _warm(eng, vocab, max_prompt, page_size, seed):
    """Compile what the stream can reach, then zero the counters: a prompt
    per reachable bucket (or one of two chunks and a remainder), an
    identical pair for the prefix share and its copy-on-write page, the
    step program and the swap pair."""
    rng = np.random.RandomState(seed + 1)
    if eng.prefill_chunk is None:
        lens = sorted({min(b, max_prompt) for b in eng.buckets})
    else:
        lens = [min(max_prompt, 2 * eng.prefill_chunk + 1)]
    for n in lens:
        eng.add_request(rng.randint(0, vocab, (n,)).astype(np.int32),
                        max_new_tokens=1)
    eng.run()
    if eng.prefix_cache:
        lp = min(max_prompt - 2, page_size + page_size // 2 + 1)
        pair = rng.randint(0, vocab, (lp + 2,)).astype(np.int32)
        for p in (pair[:lp], pair):
            eng.add_request(p, max_new_tokens=1)
            eng.run()
    eng.warm_decode()
    eng.warm_swap()
    eng.reset_counters()


def run_stream(config=None, params=None, *, num_requests, num_slots=4,
               page_size=8, max_model_len=None, max_new_tokens=8, seed=0,
               shared_prefix_frac=0.0, multi_turn=1, oversubscribe=0.0,
               **engine_kw):
    """Serve `num_requests` prompts (tiny GPT unless `config` is given).

    shared_prefix_frac: that share of prompts opens with one common prefix.
    multi_turn=N: each prompt opens a session that comes back N-1 times, a
    turn's prompt being the conversation so far plus a fresh user chunk,
    enqueued the step its previous turn finishes; with the KV tier on, the
    host pool holds every session's last context.
    oversubscribe=F: one slot per request, optimistic admission and a pool
    of 1/F of the stream's token footprint, so preemption carries the load
    (an int8 pool gets the fp pool's bytes in more pages).
    Other keywords go to `LLMEngine`."""
    config = config or gpt_mod.gpt_tiny(128)
    if params is None:
        params = gpt_mod.init_params(config, jax.random.key(seed))
    max_model_len = max_model_len or config.max_seq_len
    max_prompt = max_model_len - max_new_tokens
    vocab = config.vocab_size
    rng = np.random.RandomState(seed)
    prompts = _prompts(rng, vocab, num_requests, max_prompt, page_size,
                       shared_prefix_frac)
    user_chunk = max(2, page_size // 2)
    chunks = {}
    if multi_turn > 1:
        growth = (multi_turn - 1) * (max_new_tokens + user_chunk)
        assert growth < max_prompt, (growth, max_prompt)
        prompts = [p[:max(1, max_prompt - growth)] for p in prompts]
        chunks = {(s, t): rng.randint(0, vocab, (user_chunk,)).astype(np.int32)
                  for s in range(num_requests)
                  for t in range(2, multi_turn + 1)}
        if engine_kw.get("kv_tier", True) and not oversubscribe:
            engine_kw["swap_pool_pages"] = sum(
                -(-(p.size + growth + max_new_tokens) // page_size)
                for p in prompts)
    if oversubscribe:
        footprint = sum(p.size + max_new_tokens for p in prompts)
        biggest = max(-(-(p.size + max_new_tokens) // page_size)
                      for p in prompts)
        # +1 page of growth headroom, +1 the null page
        num_pages = max(-(-footprint // int(oversubscribe * page_size)),
                        biggest + 1) + 1
        if engine_kw.get("kv_dtype") == "int8":
            ratio = kv_page_bytes(config, page_size) / \
                kv_page_bytes(config, page_size, "int8")
            num_pages = int((num_pages - 1) * ratio) + 1
        engine_kw.update(admission="optimistic", num_pages=num_pages)
        num_slots = max(num_slots, num_requests)

    steps = [0]
    eng = LLMEngine(params, config, num_slots=num_slots, page_size=page_size,
                    max_model_len=max_model_len,
                    clock=lambda: float(steps[0]), **engine_kw)
    _warm(eng, vocab, max_prompt, page_size, seed)

    turn_of, outs = {}, {}
    with jax.transfer_guard("disallow"):
        for s, p in enumerate(prompts):
            turn_of[eng.add_request(p, max_new_tokens=max_new_tokens)] = (s, 1)
        while eng.has_work:
            steps[0] += 1
            for o in eng.step():
                s, t = turn_of[o.request_id]
                outs[s, t] = o
                if t < multi_turn:
                    nxt = np.concatenate([o.prompt, np.asarray(
                        o.token_ids, np.int32), chunks[s, t + 1]])
                    turn_of[eng.add_request(
                        nxt, max_new_tokens=max_new_tokens)] = (s, t + 1)
    assert len(outs) == num_requests * multi_turn, len(outs)
    eng.cache.check_invariants()
    assert eng.cache.swapped_page_count == 0, "host swap pool leaked pages"

    keys = sorted(outs)
    digest = hashlib.sha256()
    for k in keys:
        toks = outs[k].token_ids
        digest.update(np.asarray([*k, len(toks), *toks], np.int64).tobytes())
    busy = [r["dispatches"] for r in eng.step_trace() if r["dispatches"]]
    returning = [outs[k] for k in keys if k[1] > 1]
    return dict(
        eng.stats(),
        requests=len(outs),
        outputs_digest=digest.hexdigest(),
        output_tokens=[list(outs[k].token_ids) for k in keys],
        final_output_tokens=sum(len(o.token_ids) for o in outs.values()),
        dispatches_per_step=sum(busy) / max(len(busy), 1),
        returning_prefilled_tokens=sum(
            int(o.prompt.size) - int(o.cached_tokens) for o in returning),
        returning_steps_to_first_token=sum(o.ttft_s for o in returning))
