"""Prefix-cached, chunk-scheduled serving: copy-on-write page sharing
(ref vLLM, Kwon et al. SOSP 2023) + Sarathi-style chunked prefill (Agrawal et
al. OSDI 2024) in the continuous-batching engine.

Covers the PR-2 acceptance bars: refcount/COW/LRU edge cases in
`PagedKVCache`, chunked-prefill vs one-shot logit parity, the q_offset lane
of the paged prefill attention kernel vs its XLA oracle, engine-level token
parity of prefix-cached / chunk-prefilled generation against `generate`,
`LLMEngine.abort`, and the CPU-smoke bench bound (hit rate > 0, prefilled
tokens drop vs the no-cache baseline, <= 2 prefill executables chunked).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import gpt as G
from paddle_tpu.inference.cache import PagedKVCache
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.incubate.kernels.paged_attention import (
    paged_prefill_attention_pallas, paged_prefill_attention_xla)


PRESETS = [G.gpt_tiny, G.llama_tiny]
IDS = ["gpt", "llama"]


# ---------------------------------------------------------------------------
# PagedKVCache: refcounts, prefix index, COW, LRU eviction (pure host)
# ---------------------------------------------------------------------------

def test_cache_shared_page_freed_only_at_refcount_zero():
    mgr = PagedKVCache(num_pages=16, page_size=4, num_slots=4,
                       max_pages_per_slot=8)
    tok = np.arange(10, dtype=np.int32)         # 2 full pages + 2-token tail
    row0, m0, cow0 = mgr.allocate_prefixed(0, 12, tok)
    assert m0 == 0 and cow0 is None             # cold cache
    mgr.register_prefix(0, tok, 10)
    row1, m1, cow1 = mgr.allocate_prefixed(1, 12, tok)
    # page-aligned match capped below len(tokens): 2 full pages; the 2-token
    # partial cannot match (only j <= lp - base - 1 = 1 is probed, and the
    # rolling-hash partial index only matches tails >= _MIN_PARTIAL = 2 —
    # a 1-token hit would cost a COW copy to save one prefill token)
    assert m1 == 8 and cow1 is None
    np.testing.assert_array_equal(row1[:2], row0[:2])   # physically shared
    assert row1[2] != row0[2]
    assert mgr._ref[row0[0]] == 2
    free_before = mgr.num_free_pages
    mgr.release(0)
    # shared pages survive slot 0's retirement; only its private page parks
    assert mgr._ref[row1[0]] == 1
    assert mgr.num_free_pages == free_before    # page 2 registered -> LRU
    assert mgr.num_evictable_pages == 1
    mgr.release(1)
    assert mgr.pages_in_use() == 0
    # slot 0's registered chain (2 full + 1 partial) is evictable; slot 1's
    # private reservation-tail page was never registered -> straight to free
    assert mgr.num_evictable_pages == 3


def test_cache_partial_page_copy_on_write_match():
    mgr = PagedKVCache(num_pages=16, page_size=4, num_slots=4,
                       max_pages_per_slot=8)
    tok = np.arange(10, dtype=np.int32)
    row0, _, _ = mgr.allocate_prefixed(0, 12, tok)
    mgr.register_prefix(0, tok, 10)
    ext = np.concatenate([tok, np.asarray([99, 98, 97], np.int32)])  # 13 toks
    row1, m1, cow1 = mgr.allocate_prefixed(1, 16, ext)
    # 2 full pages shared + the 2-token partial page matched via COW
    assert m1 == 10
    assert cow1 is not None
    src, dst = cow1
    assert src == row0[2] and dst == row1[2]    # copy into slot 1's own page
    assert mgr._ref[src] == 1                   # COW does NOT ref the source
    assert mgr._ref[dst] == 1
    # divergent partial content does not match
    div = np.concatenate([tok[:8], np.asarray([7, 7, 7], np.int32)])
    row2, m2, cow2 = mgr.allocate_prefixed(2, 12, div)
    assert m2 == 8 and cow2 is None


def test_cache_lru_eviction_under_pressure():
    mgr = PagedKVCache(num_pages=8, page_size=4, num_slots=2,
                       max_pages_per_slot=8)          # 7 real pages
    a = np.arange(8, dtype=np.int32)
    b = np.arange(100, 108, dtype=np.int32)
    for slot, tok in ((0, a), (1, b)):
        mgr.allocate_prefixed(slot, 12, tok)          # 3 pages each
        mgr.register_prefix(slot, tok, 8)
        mgr.release(slot)
    # each slot frees its unregistered reservation-tail page; the 2 full
    # prompt pages per chain park in the LRU
    assert mgr.num_free_pages == 3 and mgr.num_evictable_pages == 4
    # 6 fresh pages only fit by evicting cached prefixes, oldest (a) first
    c = np.arange(200, 224, dtype=np.int32)
    row, m, _ = mgr.allocate_prefixed(0, 24, c)
    assert m == 0 and mgr.prefix_evictions == 3
    # chain a was evicted: no match for it anymore
    mgr.release(0)
    _, m2, _ = mgr.allocate_prefixed(0, 12, a)
    assert m2 == 0
    mgr.release(0)


def test_cache_match_revives_evictable_page():
    mgr = PagedKVCache(num_pages=8, page_size=4, num_slots=2,
                       max_pages_per_slot=8)
    tok = np.arange(8, dtype=np.int32)
    mgr.allocate_prefixed(0, 8, tok)
    mgr.register_prefix(0, tok, 8)
    mgr.release(0)
    assert mgr.num_evictable_pages == 2
    ext = np.concatenate([tok, np.asarray([5], np.int32)])
    row, m, cow = mgr.allocate_prefixed(1, 12, ext)
    assert m == 8 and cow is None
    assert mgr.num_evictable_pages == 0          # revived out of the LRU
    assert mgr._ref[row[0]] == 1


# ---------------------------------------------------------------------------
# chunked prefill numerics: q_offset kernel lane + logit parity
# ---------------------------------------------------------------------------

# (kvh, H, T, page-table width, [(written length incl. the chunk, valid)] a
# slot, _MAX_Q_ROWS or None).  page 8, hd 64, float32: at a 128-entry table
# the walk's block is 32 pages = 256 keys (`_pages_per_block`), so 255 / 256 /
# 257 / 512 straddle a block's edge and 1024 fills every entry; (0, 0) is an
# inactive slot (null row, nothing valid).
_WALK_CASES = {
    "gqa-one_tile": (2, 4, 8, 4, [(18, 8), (22, 5)], None),
    "mqa-one_tile": (1, 4, 8, 4, [(18, 8), (22, 5)], None),
    # T=8 splits into three 3-row query tiles: T padded to 9, and slot 1's
    # last tile holds no real row
    "gqa-tiled": (2, 4, 8, 4, [(18, 8), (22, 5)], 12),
    "mqa-tiled": (1, 4, 8, 4, [(18, 8), (22, 5)], 12),
    "ragged-null_slot-T1": (2, 4, 1, 16, [(1, 1), (0, 0), (8, 1), (9, 1),
                                          (128, 1), (77, 1)], None),
    "ragged-null_slot-T5": (2, 4, 5, 16, [(5, 5), (0, 0), (8, 3), (9, 1),
                                          (128, 5), (77, 2)], None),
    "block_edges-128_entries-T1": (2, 4, 1, 128, [
        (255, 1), (256, 1), (257, 1), (512, 1), (1024, 1), (0, 0)], None),
    "block_edges-128_entries-T5": (2, 4, 5, 128, [
        (255, 5), (256, 2), (257, 5), (513, 4), (1024, 5), (0, 0)], None),
    "kvh2_g16-T1": (2, 32, 1, 128, [(300, 1), (16, 1), (0, 0)], None),
    "kvh2_g16-T5": (2, 32, 5, 128, [(300, 5), (16, 2), (0, 0)], None),
    "kvh8_g4-T1": (8, 32, 1, 128, [(300, 1), (16, 1), (0, 0)], None),
    "kvh8_g4-T5": (8, 32, 5, 128, [(300, 4), (16, 5), (0, 0)], None),
    "mha-T1": (4, 4, 1, 16, [(100, 1), (128, 1), (0, 0)], None),
    "mha-T5": (4, 4, 5, 16, [(100, 5), (128, 1), (0, 0)], None),
}


def _walk_inputs(kvh, H, T, width, slots, seed=0, hd=64, page=8):
    """q, a pool in which every slot owns distinct pages (page 0 is the null
    page), the table, q_offset, valid - and the mask of the pool's live
    positions (those some slot's walk has to weigh)."""
    rng = np.random.RandomState(seed)
    B = len(slots)
    need = [-(-n // page) for n, _ in slots]
    P = 1 + sum(need)
    q = jnp.asarray(rng.randn(B, T, H, hd), jnp.float32)
    k = rng.randn(P, page, kvh, hd).astype(np.float32)
    v = rng.randn(P, page, kvh, hd).astype(np.float32)
    tbl = np.zeros((B, width), np.int32)
    live = np.zeros((P, page), bool)
    ids = rng.permutation(np.arange(1, P))
    at = 0
    for b, (n, _) in enumerate(slots):
        tbl[b, :need[b]] = ids[at:at + need[b]]
        live[tbl[b, :need[b]]] = True
        if n % page:
            live[tbl[b, need[b] - 1], n % page:] = False
        at += need[b]
    valid = np.asarray([vl for _, vl in slots], np.int32)
    qoff = np.asarray([n - vl for n, vl in slots], np.int32)
    return q, k, v, jnp.asarray(tbl), jnp.asarray(qoff), valid, live


@pytest.mark.parametrize("heads", ["all_heads", "per_head"])
@pytest.mark.parametrize("case", list(_WALK_CASES))
def test_paged_prefill_attention_pallas_matches_xla_oracle(case, heads,
                                                           monkeypatch):
    """The Pallas chunked-prefill kernel (interpret mode on CPU: its manual
    copies, semaphores and length-bounded loop run there) agrees with the
    gather oracle: the causal-at-q_offset mask, GQA/MQA/MHA grouping, padded
    chunk rows (compared only where valid), ragged lengths in one batch,
    lengths on and around a block's edge, a slot that fills its whole table,
    and an inactive slot, which must come back as zeros.  `heads` runs both
    forms of the score tile (`_heads_per_tile`): all kv heads' keys in one
    tile, as a short query tile takes them, and one head a tile."""
    from paddle_tpu.incubate.kernels import paged_attention
    kvh, H, T, width, slots, q_rows = _WALK_CASES[case]
    if q_rows is not None:
        monkeypatch.setattr(paged_attention, "_MAX_Q_ROWS", q_rows)
    if heads == "per_head":
        monkeypatch.setattr(paged_attention, "_ALL_HEADS_MAX_ROWS", 0)
    q, k, v, tbl, qoff, valid, _ = _walk_inputs(kvh, H, T, width, slots)
    k, v = jnp.asarray(k), jnp.asarray(v)
    ref = paged_prefill_attention_xla(q, k, v, tbl, qoff, jnp.asarray(valid))
    got = paged_prefill_attention_pallas(q, k, v, tbl, qoff,
                                         jnp.asarray(valid), interpret=True)
    for b, n in enumerate(valid):
        np.testing.assert_allclose(np.asarray(got)[b, :n],
                                   np.asarray(ref)[b, :n], atol=2e-5)
        if n == 0:
            assert not np.asarray(got)[b].any()


@pytest.mark.parametrize("heads", ["all_heads", "per_head"])
def test_paged_prefill_walk_reads_nothing_it_should_not_weigh(heads,
                                                              monkeypatch):
    """Every pool position no live query position maps to is NaN - dead
    pages, the null page, the unwritten tail of a slot's last page - and the
    TPU interpreter hands out NaN for memory never written and raises on a
    read out of bounds: the result is finite and equals the oracle's on the
    clean pool, padding rows included."""
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.incubate.kernels import paged_attention
    if heads == "per_head":
        monkeypatch.setattr(paged_attention, "_ALL_HEADS_MAX_ROWS", 0)
    slots = [(255, 5), (256, 2), (300, 5), (3, 3), (1024, 1), (0, 0)]
    q, k, v, tbl, qoff, valid, live = _walk_inputs(2, 8, 5, 128, slots,
                                                   seed=1)
    clean = [jnp.asarray(np.where(live[..., None, None], a, 0.0))
             for a in (k, v)]
    dirty = [jnp.asarray(np.where(live[..., None, None], a, np.nan))
             for a in (k, v)]
    ref = paged_prefill_attention_xla(q, *clean, tbl, qoff,
                                      jnp.asarray(valid))
    got = np.asarray(paged_prefill_attention_pallas(
        q, *dirty, tbl, qoff, jnp.asarray(valid),
        interpret=pltpu.InterpretParams()))
    assert np.isfinite(got).all()
    for b, n in enumerate(valid):
        np.testing.assert_allclose(got[b, :n], np.asarray(ref)[b, :n],
                                   atol=2e-5)


@pytest.mark.parametrize("preset", PRESETS, ids=IDS)
def test_chunked_prefill_matches_one_shot_logits(preset):
    """prefill_chunk_paged chunks (q_offset 0, 6, 12) reproduce the one-shot
    dense-forward logits through the page-table indirection, and decode
    (the same pass at valid 1, as a decode slot rides the fused step)
    continues correctly from the chunk-written pages."""
    cfg = preset(64)
    params = G.init_params(cfg, jax.random.key(0))
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 15)), jnp.int32)
    dense = G.forward(params, toks, cfg)
    page, Tp, C = 4, 13, 6
    pool = G.init_paged_cache(cfg, num_pages=10, page_size=page)
    table = np.zeros((1, 6), np.int32)
    table[0, :5] = [3, 1, 4, 2, 5]              # deliberately non-contiguous
    tbl = jnp.asarray(table)
    filled = 0
    while filled < Tp:
        n = min(C, Tp - filled)
        ids = np.zeros((1, C), np.int32)
        ids[0, :n] = np.asarray(toks[0, filled:filled + n])
        logits, pool = G.prefill_chunk_paged(
            params, jnp.asarray(ids), cfg, pool, tbl,
            jnp.asarray([filled], jnp.int32), jnp.asarray([n], jnp.int32))
        filled += n
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(dense[:, Tp - 1]),
                               atol=2e-4, rtol=2e-4)
    for pos in range(Tp, 15):
        logits, pool = G.prefill_chunk_paged(
            params, toks[:, pos:pos + 1], cfg, pool, tbl,
            jnp.asarray([pos], jnp.int32), jnp.asarray([1], jnp.int32))
        if pos < 14:
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(dense[:, pos]),
                                       atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# engine-level parity: prefix cache + chunked prefill vs generate()
# ---------------------------------------------------------------------------

def test_engine_prefix_cached_matches_uncached_generation():
    """Greedy token parity with `generate` while the scheduler shares pages:
    B extends A (full-page share + partial-page COW off a live donor), C
    repeats A (full-page share only).  Every cached request reports its
    cached_tokens and the pool fully recycles."""
    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(0))
    rng = np.random.RandomState(3)
    base = rng.randint(0, cfg.vocab_size, (21,)).astype(np.int32)
    ext = np.concatenate([base,
                          rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)])
    eng = LLMEngine(params, cfg, num_slots=3, page_size=8, max_model_len=64)
    rids = [eng.add_request(p, max_new_tokens=5) for p in (base, ext,
                                                           base.copy())]
    outs = eng.run()
    for rid, p in zip(rids, (base, ext, base)):
        ref = G.generate(params, jnp.asarray(p)[None], cfg, max_new_tokens=5)
        np.testing.assert_array_equal(outs[rid].tokens, np.asarray(ref[0]))
    # base: 21 = 2 full pages + 5-token partial; ext COWs the partial
    assert outs[rids[0]].cached_tokens == 0
    assert outs[rids[1]].cached_tokens == 21
    # C's partial tail hits the rolling-hash index at j = lp - 16 - 1 = 4
    # (a prefix of the 5-token partial node; the PR-2 exact-content index
    # stopped at the 2 full pages = 16 here)
    assert outs[rids[2]].cached_tokens == 20
    st = eng.stats()
    assert st["cow_page_copies"] == 2   # B's partial COW + C's rolling-hash hit
    assert st["prefix_hit_requests"] == 2
    assert st["pages_in_use"] == 0
    assert all(outs[r].ttft_s is not None and outs[r].ttft_s > 0 for r in rids)


def test_engine_chunked_prefill_matches_generate():
    """Chunked mode (8-token chunks, prefix cache off to isolate chunking):
    mixed-length prompts — including one long enough to interleave its chunks
    with other slots' decode steps — are token-identical to `generate`, with
    at most 2 prefill executables (acceptance bar; this engine needs 1)."""
    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(0))
    eng = LLMEngine(params, cfg, num_slots=3, page_size=8, max_model_len=64,
                    prefill_chunk=8, prefix_cache=False)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (30, 5, 17, 3, 9)]
    rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    outs = eng.run()
    for rid, p in zip(rids, prompts):
        ref = G.generate(params, jnp.asarray(p)[None], cfg, max_new_tokens=6)
        np.testing.assert_array_equal(outs[rid].tokens, np.asarray(ref[0]))
    st = eng.stats()
    assert st["decode_executables"] == 1
    assert st["prefill_executables"] <= 2
    assert st["prefill_chunks"] == sum(-(-p.size // 8) for p in prompts)
    assert st["pages_in_use"] == 0


@pytest.mark.slow
def test_engine_chunked_plus_prefix_parity():
    """Both tentpole features together: chunked prefill over a prefix-cached
    tail (q_offset starts mid-page after a COW) stays token-identical."""
    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(0))
    rng = np.random.RandomState(5)
    base = rng.randint(0, cfg.vocab_size, (21,)).astype(np.int32)
    ext = np.concatenate([base, rng.randint(0, cfg.vocab_size,
                                            (20,)).astype(np.int32)])
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    prefill_chunk=8)
    ra = eng.add_request(base, max_new_tokens=4)
    eng.run()                       # donor completes, registers its pages
    rb = eng.add_request(ext, max_new_tokens=4)
    outs = eng.run()
    for rid, p in ((ra, base), (rb, ext)):
        ref = G.generate(params, jnp.asarray(p)[None], cfg, max_new_tokens=4)
        np.testing.assert_array_equal(outs[rid].tokens, np.asarray(ref[0]))
    assert outs[rb].cached_tokens == 21         # 16 shared + 5 COW
    st = eng.stats()
    assert st["cow_page_copies"] == 1
    assert st["prefill_executables"] <= 2


def test_engine_abort_frees_pages_immediately():
    """abort() cancels queued, mid-prefill and decoding requests, derefs
    their pages at once, and the slot serves the next request correctly."""
    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(0))
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (30, 17, 5)]
    eng = LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=64,
                    num_pages=12, prefill_chunk=8, prefix_cache=False)
    r1 = eng.add_request(prompts[0], max_new_tokens=8)
    r2 = eng.add_request(prompts[1], max_new_tokens=8)
    eng.step()                                  # r1 mid-prefill, r2 queued
    assert eng.cache.pages_in_use() > 0
    assert eng.abort(r1) and eng.abort(r2)
    assert not eng.abort(999)                   # unknown id
    assert eng.cache.pages_in_use() == 0 and not eng.has_work
    assert eng._outputs[r1].finish_reason == "abort"
    assert eng._outputs[r2].finish_reason == "abort"
    # aborting a DECODING request frees mid-generation
    r3 = eng.add_request(prompts[0], max_new_tokens=8)
    while not eng._running:
        eng.step()
    eng.step()
    assert eng.abort(r3)
    assert eng.cache.pages_in_use() == 0
    assert len(eng._outputs[r3].token_ids) >= 1  # partial progress reported
    # the freed slot still serves correctly
    r4 = eng.add_request(prompts[2], max_new_tokens=4)
    out = eng.run()[r4]
    ref = G.generate(params, jnp.asarray(prompts[2])[None], cfg,
                     max_new_tokens=4)
    np.testing.assert_array_equal(out.tokens, np.asarray(ref[0]))
    assert not eng.abort(r4)                    # already finished


# ---------------------------------------------------------------------------
# CI wiring: deterministic CPU smoke with a shared prefix
# ---------------------------------------------------------------------------

def test_bench_serve_shared_prefix_cpu_smoke():
    """Acceptance bar: with shared_prefix_frac=0.5 on the CPU-smoke config,
    hit rate > 0 and prefilled tokens DROP vs the no-cache baseline on the
    same workload, within <= 2 prefill executables (chunked) and <= 4
    compiled programs total."""
    from serve_stream import run_stream
    kw = dict(num_requests=10, num_slots=2, page_size=8, max_model_len=64,
              max_new_tokens=4, prefill_chunk=16, shared_prefix_frac=0.5,
              seed=11)
    stats = run_stream(**kw, prefix_cache=True)
    base = run_stream(**kw, prefix_cache=False)
    assert stats["requests"] == 10
    assert stats["prefix_hit_rate"] > 0
    assert stats["prefix_cached_tokens"] > 0
    # identical workload (same seed): the cache strictly reduces prefill work
    assert stats["prefilled_tokens"] < base["prefilled_tokens"]
    assert base["prefix_hit_rate"] == 0
    assert stats["prefill_executables"] <= 2
    assert (stats["decode_executables"] + stats["prefill_executables"] +
            stats["copy_executables"]) <= 4
    ttft = stats["latency"]["ttft_s"]       # in engine steps (step clock)
    assert ttft["p99"] >= ttft["p50"] > 0
