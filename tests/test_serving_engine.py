"""Continuous-batching serving engine: paged KV cache, slot-indexed decode,
bucketed prefill, scheduler (ref vLLM PagedAttention SOSP 2023 + Orca OSDI
2022; reference repo counterpart: fluid/inference predictor + PaddleNLP
generation)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import gpt as G
from paddle_tpu.inference.cache import PagedKVCache
from paddle_tpu.inference.engine import LLMEngine


PRESETS = [G.gpt_tiny, G.llama_tiny]
IDS = ["gpt", "llama"]


@pytest.mark.parametrize("preset", PRESETS, ids=IDS)
def test_prefill_decode_logits_match_dense_forward(preset):
    """Per-position logits from prefill + chained decode_step equal the dense
    forward pass (the KV-cache path computes the same function)."""
    cfg = preset(64)
    params = G.init_params(cfg, jax.random.key(0))
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 12)), jnp.int32)
    Tp = 5
    dense = G.forward(params, toks, cfg)            # [B, 12, V]

    kv = G.init_cache(cfg, 2, 12)
    logits, kv = G.prefill(params, toks[:, :Tp], cfg, kv)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(dense[:, Tp - 1]),
                               atol=2e-4, rtol=2e-4)
    for pos in range(Tp, 12):
        logits, kv = G.decode_step(params, toks[:, pos], kv, pos, cfg)
        if pos < 11:
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(dense[:, pos]),
                                       atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("preset", PRESETS, ids=IDS)
def test_paged_decode_logits_match_dense_forward(preset):
    """prefill_paged + chained one-token prefill_chunk_paged calls (valid 1
    at q_offset = tokens cached: what a decode slot is in the fused step)
    reproduce dense-forward logits through the page-table indirection
    (bucket-padded prompt, slots in arbitrary page order)."""
    cfg = preset(64)
    params = G.init_params(cfg, jax.random.key(1))
    rng = np.random.RandomState(1)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 12)), jnp.int32)
    dense = G.forward(params, toks, cfg)
    page, Tp, bucket = 4, 5, 8

    pool = G.init_paged_cache(cfg, num_pages=6, page_size=page)
    table = np.zeros((1, 4), np.int32)
    table[0, :4] = [3, 1, 4, 2]                     # deliberately non-contiguous
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :Tp] = np.asarray(toks[0, :Tp])
    logits, pool = G.prefill_paged(params, jnp.asarray(ids), cfg, pool,
                                   jnp.asarray(table[:, :bucket // page]),
                                   jnp.asarray([Tp], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(dense[:, Tp - 1]),
                               atol=2e-4, rtol=2e-4)
    tbl = jnp.asarray(table)
    for pos in range(Tp, 12):
        logits, pool = G.prefill_chunk_paged(
            params, toks[:, pos:pos + 1], cfg, pool, tbl,
            jnp.asarray([pos], jnp.int32), jnp.asarray([1], jnp.int32))
        if pos < 11:
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(dense[:, pos]),
                                       atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("preset", PRESETS, ids=IDS)
def test_engine_matches_generate(preset):
    """End-to-end greedy parity: the continuous-batching engine emits exactly
    the tokens of the one-shot `generate` for mixed-length prompts."""
    cfg = preset(64)
    params = G.init_params(cfg, jax.random.key(0))
    eng = LLMEngine(params, cfg, num_slots=3, page_size=8, max_model_len=64)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 17, 3, 30)]
    rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    outs = eng.run()
    for rid, p in zip(rids, prompts):
        ref = G.generate(params, jnp.asarray(p)[None], cfg, max_new_tokens=6)
        np.testing.assert_array_equal(outs[rid].tokens, np.asarray(ref[0]))
        assert outs[rid].finish_reason == "length"


def test_engine_eos_stop_matches_generate_freeze():
    """A request that emits EOS retires with finish_reason='stop' and its
    tokens equal generate()'s output up to the first EOS (generate then
    freezes the tail at EOS; the engine frees the slot instead)."""
    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(0))
    prompt = np.zeros((3,), np.int32)
    ref = np.asarray(G.generate(params, jnp.asarray(prompt)[None], cfg,
                                max_new_tokens=8)[0])
    eos = int(ref[5])                   # whatever greedy emits at step 5
    frozen = np.asarray(G.generate(params, jnp.asarray(prompt)[None], cfg,
                                   max_new_tokens=8, eos_token_id=eos)[0])
    assert (frozen[6:] == eos).all()    # generate freezes after first EOS

    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    eos_token_id=eos)
    rid = eng.add_request(prompt, max_new_tokens=8)
    out = eng.run()[rid]
    assert out.finish_reason == "stop"
    assert out.token_ids[-1] == eos
    np.testing.assert_array_equal(out.tokens, frozen[:len(out.tokens)])


def test_engine_executable_bound_32_mixed_requests():
    """Acceptance bar: >= 32 mixed-length requests complete with exactly ONE
    decode executable and <= #buckets + 1 prefill executables, on a page pool
    smaller than the dense num_slots * max_model_len footprint."""
    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(0))
    eng = LLMEngine(params, cfg, num_slots=4, page_size=8, max_model_len=64)
    rng = np.random.RandomState(7)
    n = 32
    rids = []
    for i in range(n):
        lp = int(rng.randint(1, 41))
        prompt = rng.randint(0, cfg.vocab_size, (lp,)).astype(np.int32)
        rids.append(eng.add_request(prompt, max_new_tokens=int(rng.randint(1, 8))))
    outs = eng.run()
    assert sorted(outs) == sorted(rids)                 # every request finished
    st = eng.stats()
    assert st["decode_executables"] == 1
    assert st["prefill_executables"] <= len(eng.buckets) + 1
    # paged memory claim: pool capacity < dense B x max_len footprint
    assert st["kv_token_capacity"] < st["dense_token_footprint"]
    assert st["pages_in_use"] == 0                      # all pages recycled


def test_engine_queues_when_out_of_pages():
    """Admission is reservation-based: with a pool too small for all requests
    at once, later requests wait for pages and still complete."""
    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(0))
    # 5 real pages of 8 tokens: one 24-token footprint (3 pages) at a time +
    # change, while 4 slots compete
    eng = LLMEngine(params, cfg, num_slots=4, page_size=8, num_pages=6,
                    max_model_len=64)
    prompts = [np.full((16,), i, np.int32) for i in range(6)]
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    outs = eng.run()
    assert sorted(outs) == sorted(rids)
    for rid, p in zip(rids, prompts):
        ref = G.generate(params, jnp.asarray(p)[None], cfg, max_new_tokens=8)
        np.testing.assert_array_equal(outs[rid].tokens, np.asarray(ref[0]))


def test_engine_rejects_impossible_footprint():
    """A request that can never fit the pool is rejected AT INTAKE
    (finish_reason="rejected") instead of raising mid-run or wedging the
    queue head forever — the fail-fast side of the overload work (see
    tests/test_overload.py for the not-wedged proof)."""
    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(0))
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, num_pages=3,
                    max_model_len=64)      # 2 real pages = 16 tokens capacity
    rid = eng.add_request(np.zeros((20,), np.int32), max_new_tokens=8)
    assert not eng.has_work                # never queued
    assert eng.run()[rid].finish_reason == "rejected"
    assert eng.stats()["rejected_requests"] == 1


def test_engine_non_pow2_max_model_len_served_to_capacity():
    """Buckets cover max_model_len even when it is not a power of 2: a prompt
    longer than the largest power-of-2 bucket still admits and finishes."""
    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(0))
    eng = LLMEngine(params, cfg, num_slots=2, page_size=16, max_model_len=48)
    assert eng.buckets[-1] == 48
    prompt = np.arange(40, dtype=np.int32) % cfg.vocab_size
    rid = eng.add_request(prompt, max_new_tokens=8)
    out = eng.run()[rid]
    ref = G.generate(params, jnp.asarray(prompt)[None], cfg, max_new_tokens=8)
    np.testing.assert_array_equal(out.tokens, np.asarray(ref[0]))


def test_paged_cache_manager_accounting():
    mgr = PagedKVCache(num_pages=8, page_size=4, num_slots=3,
                       max_pages_per_slot=4)
    assert mgr.num_free_pages == 7                  # page 0 reserved (null)
    assert mgr.token_capacity() == 28
    row = mgr.allocate(0, total_tokens=9)           # ceil(9/4) = 3 pages
    assert (row[:3] > 0).all() and (row[3:] == 0).all()
    assert mgr.pages_in_use() == 3 and mgr.num_free_pages == 4
    with pytest.raises(RuntimeError, match="already has pages"):
        mgr.allocate(0, 4)
    assert not mgr.can_allocate(17)                 # 5 pages > slot max of 4
    assert not mgr.can_allocate(5 * 4)              # and > free pages
    mgr.allocate(1, 16)
    assert mgr.num_free_pages == 0
    with pytest.raises(RuntimeError, match="out of KV pages"):
        mgr.allocate(2, 1)
    mgr.release(0)
    assert mgr.num_free_pages == 3 and (mgr.page_table[0] == 0).all()
    assert mgr.lengths[0] == 0


def test_generate_cache_lru_bounded(monkeypatch):
    """Satellite: the generate executable cache is LRU-bounded (it used to
    grow without limit under varied prompt shapes) and exposes a compile
    counter.  The cap is shrunk so overflowing it costs 7 compiles, not 20."""
    cap = 4
    monkeypatch.setattr(G, "GENERATE_CACHE_MAX", cap)
    cfg = G.gpt_tiny(128)
    params = G.init_params(cfg, jax.random.key(0))
    start = G.generate_cache_stats()["compiles"]
    for tp in range(1, cap + 4):                    # more shapes than the cap
        G.generate(params, jnp.zeros((1, tp), jnp.int32), cfg,
                   max_new_tokens=2)
    st = G.generate_cache_stats()
    assert st["size"] <= cap
    assert st["compiles"] >= start + cap + 3
    # a cached (recently used) shape does not recompile
    before = G.generate_cache_stats()["compiles"]
    G.generate(params, jnp.zeros((1, cap + 3), jnp.int32),
               cfg, max_new_tokens=2)
    assert G.generate_cache_stats()["compiles"] == before


def test_eval_loss_jitted_once():
    """Satellite: HybridParallelTrainer.eval_loss compiles once and reuses
    the executable (it used to retrace eagerly on every call)."""
    from paddle_tpu.parallel import HybridParallelTrainer, MeshConfig
    cfg = G.gpt_tiny(64)
    rng = np.random.RandomState(0)
    tok = rng.randint(0, cfg.vocab_size, (4, 64)).astype(np.int32)
    lab = np.roll(tok, -1, 1).astype(np.int32)
    tr = HybridParallelTrainer(cfg, MeshConfig(), seed=3,
                               devices=jax.devices()[:1])
    a = float(tr.eval_loss(tok, lab))
    b = float(tr.eval_loss(tok, lab))
    assert a == b
    assert tr._eval_fn._cache_size() == 1
    ref = float(G.loss_fn(tr.params, jnp.asarray(tok), jnp.asarray(lab), cfg))
    np.testing.assert_allclose(a, ref, rtol=1e-5)


def test_steady_state_decode_loop_transfer_guard_clean():
    """Satellite (runtime twin of tpu_lint TPL001/TPL005): once every
    executable is warm, the engine's decode loop performs NO implicit
    host<->device transfers — every h2d is an explicit numpy-backed
    `_h2d` placement and every d2h an explicit np.asarray inside a
    sample-sync span.  `jax.transfer_guard("disallow")` turns any
    regression (a bare Python scalar into a dispatch, an implicit mp
    reshard) into an immediate error.  Exercises chunked prefill,
    prefix-hit + COW admission, speculative verify and vanilla decode
    inside the guard."""
    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(0))
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    num_pages=32, prefill_chunk=16, spec_len=3)
    # pool big enough that the donor's cached pages survive (no LRU eviction
    # between donor retirement and the extension's admission)
    rng = np.random.RandomState(0)
    for n in (5, 20):                   # warm chunk/decode/verify paths
        eng.add_request(rng.randint(0, cfg.vocab_size, (n,))
                        .astype(np.int32), max_new_tokens=4)
    eng.run()
    eng.warm_decode()
    base = rng.randint(0, cfg.vocab_size, (13,)).astype(np.int32)
    eng.add_request(base, max_new_tokens=1)
    eng.run()                           # donor registers its prompt pages
    rids = [eng.add_request(rng.randint(0, cfg.vocab_size, (n,))
                            .astype(np.int32), max_new_tokens=5)
            for n in (7, 19, 33)]
    # extension of the donor: prefix hit + COW page copy inside the guard
    rids.append(eng.add_request(np.concatenate([base, base[:4]]),
                                max_new_tokens=3))
    with jax.transfer_guard("disallow"):
        outs = eng.run()
    assert sorted(rids) == sorted(o for o in outs
                                  if o >= rids[0])    # all guarded reqs done
    assert eng.stats()["prefix_cached_tokens"] > 0    # the COW lane ran


def test_bench_serve_cpu_smoke():
    """Satellite (CI wiring): a CPU-smoke request stream completes N
    requests within the compiled-program bound."""
    from serve_stream import run_stream
    stats = run_stream(num_requests=8, num_slots=2, page_size=8,
                       max_model_len=32, max_new_tokens=3)
    assert stats["requests"] == 8
    assert stats["decode_executables"] == 1
    assert stats["prefill_executables"] <= len(stats["buckets"]) + 1
    assert stats["decode_tokens"] > 0
