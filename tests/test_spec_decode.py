"""Speculative decoding (Leviathan et al. 2023) in the serving engine:
n-gram self-drafting + single-program multi-token verify over the paged KV
cache.

Covers the PR-3 acceptance bars: n-gram proposer unit behaviour, the verify
lane of the q_offset paged-attention kernel vs its XLA oracle at q_len > 1,
the shared trunk's logit parity against the dense forward at every verified
position, exact
greedy token parity spec-on vs spec-off at engine level (prefix cache on AND
off, chunked and bucketed prefill), rollback/abort refcount invariants, the
per-request greedy fast path, accepted_per_step > 1 on a repetitive stream,
and the compiled-program bound (decode-side exactly 1).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import gpt as G
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.inference.spec import NgramProposer
from paddle_tpu.incubate.kernels.paged_attention import (
    paged_prefill_attention, paged_prefill_attention_pallas)


@pytest.fixture(scope="module")
def tiny():
    cfg = G.gpt_tiny(64)
    return cfg, G.init_params(cfg, jax.random.key(0))


# ---------------------------------------------------------------------------
# n-gram proposer (pure host)
# ---------------------------------------------------------------------------

def test_ngram_proposer_matches_most_recent_occurrence():
    p = NgramProposer(max_ngram=3, min_ngram=1)
    #         0  1  2  3  4  5  6  7  8
    ctx = [9, 1, 2, 3, 7, 1, 2, 3, 5, 1, 2, 3]
    # trailing 3-gram (1,2,3) occurred at 1 and 5; most recent is 5 ->
    # continuation [5, 1, 2, 3] follows it
    np.testing.assert_array_equal(p.propose(np.asarray(ctx), 4), [5, 1, 2, 3])
    # max_tokens truncates
    np.testing.assert_array_equal(p.propose(np.asarray(ctx), 2), [5, 1])


def test_ngram_proposer_prefers_longer_ngrams():
    p = NgramProposer(max_ngram=3, min_ngram=1)
    # trailing 2-gram (2,3) matches at 1..2 (-> 8) and the 1-gram 3 matches
    # at 6 (-> 9); the longer match wins
    ctx = [1, 2, 3, 8, 0, 0, 3, 9, 2, 3]
    np.testing.assert_array_equal(p.propose(np.asarray(ctx), 1), [8])
    # min_ngram=3 refuses the short matches entirely
    assert NgramProposer(max_ngram=3, min_ngram=3).propose(
        np.asarray(ctx), 4) is None


def test_ngram_proposer_self_loop_and_edges():
    p = NgramProposer()
    # a looping generation drafts its own loop: every recent hit is truncated
    # by the tail, so the EARLIEST occurrence supplies the longest run
    # (the trailing 3-gram wins at n=3; its earliest occurrence j=0 leaves a
    # 3-token continuation, vs the single token after the most recent hit)
    np.testing.assert_array_equal(p.propose(np.asarray([7] * 6), 4),
                                  [7, 7, 7])
    np.testing.assert_array_equal(p.propose(np.asarray([7, 7, 7]), 4), [7])
    assert p.propose(np.asarray([1, 2, 3, 4]), 4) is None   # no repeat
    assert p.propose(np.asarray([5]), 4) is None            # too short
    assert p.propose(np.asarray([5, 5]), 0) is None         # no budget
    # bounded lookback: a match older than the window is not scanned (the
    # proposer runs on the host every decode iteration — O(window), not
    # O(context)), while an in-window match still hits
    far = np.concatenate([[3, 1, 4], np.arange(10, 30), [3, 1, 4]])
    assert NgramProposer(max_lookback=6).propose(far, 4) is None
    np.testing.assert_array_equal(
        NgramProposer(max_lookback=far.size).propose(far, 2), [10, 11])
    with pytest.raises(ValueError):
        NgramProposer(max_ngram=2, min_ngram=3)
    with pytest.raises(ValueError):
        NgramProposer(max_lookback=1)


# ---------------------------------------------------------------------------
# verify kernel + verify step numerics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kvh", [2, 1], ids=["gqa", "mqa"])
def test_verify_kernel_matches_xla_oracle_qlen_gt1(kvh):
    """The verify lane (q_len > 1 decode: q_offset = lengths, per-slot valid
    counts including the valid=1 no-draft degenerate) agrees with the gather
    oracle, Pallas kernel in interpret mode on CPU."""
    rng = np.random.RandomState(0)
    B, T, H, hd, page, P, mp = 3, 5, 4, 64, 8, 9, 4
    q = jnp.asarray(rng.randn(B, T, H, hd), jnp.float32)
    k = jnp.asarray(rng.randn(P, page, kvh, hd), jnp.float32)
    v = jnp.asarray(rng.randn(P, page, kvh, hd), jnp.float32)
    tbl = np.zeros((B, mp), np.int32)
    tbl[0, :3] = [1, 2, 3]
    tbl[1, :2] = [4, 5]
    tbl[2, :4] = [6, 7, 8, 3]
    lengths = jnp.asarray([9, 4, 17], jnp.int32)     # q_offset = lengths
    valid = jnp.asarray([5, 1, 3], jnp.int32)        # incl. the no-draft edge
    ref = paged_prefill_attention(q, k, v, jnp.asarray(tbl), lengths, valid)
    got = paged_prefill_attention_pallas(q, k, v, jnp.asarray(tbl), lengths,
                                         valid, interpret=True)
    for b, n in enumerate(np.asarray(valid)):
        np.testing.assert_allclose(np.asarray(got)[b, :n],
                                   np.asarray(ref)[b, :n], atol=2e-5)


@pytest.mark.parametrize("preset", [G.gpt_tiny, G.llama_tiny],
                         ids=["gpt", "llama"])
def test_verify_step_matches_dense_forward(preset):
    """The shared trunk (`_paged_chunk_hidden`, the fused step's body) plus
    the head scores T positions in one pass with the logits of
    the dense forward (== chained single-token decode, per the existing
    decode-parity tests) — the property greedy acceptance relies on — and a
    valid-masked call (the rollback shape) leaves the accepted prefix intact:
    a later verify over the once-rejected positions still matches."""
    cfg = preset(64)
    params = G.init_params(cfg, jax.random.key(1))

    def verify(tokens, pool, tbl, q_offset, valid):
        x, pool = G._paged_chunk_hidden(params, tokens, cfg, pool, tbl,
                                        q_offset, valid)
        return G.head_logits(G.epilogue(params, x, cfg), params, cfg), pool

    rng = np.random.RandomState(1)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 13)), jnp.int32)
    dense = np.asarray(G.forward(params, toks, cfg))        # [1, 13, V]
    page, Tp, T = 4, 8, 4
    table = np.zeros((1, 6), np.int32)
    table[0, :4] = [3, 1, 4, 2]
    tbl = jnp.asarray(table)
    ids = np.zeros((1, 8), np.int32)
    ids[0, :Tp] = np.asarray(toks[0, :Tp])
    pool = G.init_paged_cache(cfg, num_pages=10, page_size=page)
    _, pool = G.prefill_chunk_paged(
        params, jnp.asarray(ids), cfg, pool, tbl,
        jnp.asarray([0], jnp.int32), jnp.asarray([Tp], jnp.int32))
    # verify with valid=2: tokens Tp, Tp+1 land, Tp+2.. masked (rollback)
    vlog, pool = verify(toks[:, Tp:Tp + T], pool, tbl,
                        jnp.asarray([Tp], jnp.int32),
                        jnp.asarray([2], jnp.int32))
    for t in range(2):
        np.testing.assert_allclose(np.asarray(vlog[:, t]), dense[:, Tp + t],
                                   atol=2e-4, rtol=2e-4)
    # re-verify from position Tp+2 over the once-rejected region (3 real
    # tokens + 1 padded row): the accepted prefix survived the masked call
    vt = np.zeros((1, T), np.int32)
    vt[0, :3] = np.asarray(toks[0, Tp + 2:Tp + 5])
    vlog2, pool = verify(jnp.asarray(vt), pool, tbl,
                         jnp.asarray([Tp + 2], jnp.int32),
                         jnp.asarray([3], jnp.int32))
    for t in range(3):
        np.testing.assert_allclose(np.asarray(vlog2[:, t]),
                                   dense[:, Tp + 2 + t],
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# engine-level parity + acceptance + executable bound
# ---------------------------------------------------------------------------

def test_engine_spec_parity_and_program_bound(tiny):
    """Acceptance bar: spec-on emits exactly the spec-off greedy tokens —
    prefix cache on AND off — from the ONE decode-side program (drafts ride
    the fused step; there is no verify executable)."""
    cfg, params = tiny
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 17, 3)]
    base = prompts[2]
    prompts.append(np.concatenate(          # shared prefix: COW lane too
        [base, rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)]))
    outs = {}
    engines = {}
    # one spec-off reference; spec-on with the prefix cache on AND off
    for key, kw in (("off", dict(spec_len=0)),
                    ("spec", dict(spec_len=4)),
                    ("spec-nopfx", dict(spec_len=4, prefix_cache=False))):
        eng = LLMEngine(params, cfg, num_slots=3, page_size=8,
                        max_model_len=64, **kw)
        rids = [eng.add_request(p, max_new_tokens=10) for p in prompts]
        res = eng.run()
        outs[key] = [res[r].tokens for r in rids]
        engines[key] = eng
    for key in ("spec", "spec-nopfx"):
        for a, b in zip(outs["off"], outs[key]):
            np.testing.assert_array_equal(a, b)
        st = engines[key].stats()
        assert st["decode_executables"] + st["verify_executables"] == 1
        assert st["verify_steps"] > 0 and st["spec_emitted_tokens"] > 0
        assert st["pages_in_use"] == 0
        engines[key].cache.check_invariants()
        # spec strictly reduced decode iterations on this stream
        assert st["decode_iterations"] < \
            engines["off"].stats()["decode_iterations"]


def test_engine_spec_chunked_prefill_parity(tiny):
    """Spec decoding composes with Sarathi chunked prefill: mid-prefill slots
    stay masked out of the verify dispatch and tokens match generate()."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, num_slots=3, page_size=8, max_model_len=64,
                    prefill_chunk=8, spec_len=3)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (30, 5, 17)]
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    outs = eng.run()
    for rid, p in zip(rids, prompts):
        ref = G.generate(params, jnp.asarray(p)[None], cfg, max_new_tokens=8)
        np.testing.assert_array_equal(outs[rid].tokens, np.asarray(ref[0]))
    st = eng.stats()
    assert st["decode_executables"] + st["verify_executables"] <= 2
    assert st["prefill_executables"] <= 2
    assert st["pages_in_use"] == 0


def test_engine_spec_eos_inside_accepted_prefix(tiny):
    """A drafted token equal to EOS truncates the emitted run at the EOS —
    token-for-token what vanilla decode does — and retires the slot."""
    cfg, params = tiny
    prompt = np.zeros((3,), np.int32)
    ref = np.asarray(G.generate(params, jnp.asarray(prompt)[None], cfg,
                                max_new_tokens=10)[0])
    eos = int(ref[6])                   # whatever greedy emits mid-stream
    van = LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=64,
                    eos_token_id=eos)
    rv = van.add_request(prompt, max_new_tokens=10)
    spec = LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=64,
                     eos_token_id=eos, spec_len=4)
    rs = spec.add_request(prompt, max_new_tokens=10)
    a, b = van.run()[rv], spec.run()[rs]
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert b.finish_reason == a.finish_reason
    assert spec.cache.pages_in_use() == 0


def test_accepted_per_step_exceeds_one_on_repetitive_stream(tiny):
    """Self-drafting pays off on repetitive continuations: a stream of
    looping/repetitive prompts accepts > 1 token per drafted verify."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, num_slots=3, page_size=8, max_model_len=64,
                    spec_len=4)
    rng = np.random.RandomState(0)
    pat = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
    prompts = [np.tile(pat, 3)] + \
        [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
         for n in (7, 12, 5)]
    rids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
    outs = eng.run()
    # parity holds regardless: spot-check the tiled prompt against generate
    ref = G.generate(params, jnp.asarray(prompts[0])[None], cfg,
                     max_new_tokens=12)
    np.testing.assert_array_equal(outs[rids[0]].tokens, np.asarray(ref[0]))
    st = eng.stats()
    assert st["spec_accepted_tokens"] > 0
    assert st["accepted_per_step"] > 1.0
    # spec emitted more tokens than it ran decode iterations for
    assert st["decode_tokens"] > st["decode_iterations"]


# ---------------------------------------------------------------------------
# rollback / abort refcount invariants (satellite bugfix)
# ---------------------------------------------------------------------------

def test_spec_rollback_keeps_refcount_invariants(tiny):
    """Every engine step during a spec-heavy run (shared prefixes, draft
    rejections, retirements) preserves the free/LRU/in-use page partition and
    exact refcounts."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    num_pages=12, spec_len=4)
    rng = np.random.RandomState(3)
    base = rng.randint(0, cfg.vocab_size, (21,)).astype(np.int32)
    ext = np.concatenate([base,
                          rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)])
    for p in (base, ext, base.copy()):
        eng.add_request(p, max_new_tokens=8)
    while eng.has_work:
        eng.step()
        eng.cache.check_invariants()
    st = eng.stats()
    assert st["pages_in_use"] == 0 and st["verify_steps"] > 0
    # drafts were offered and rejections rolled back (not everything accepts)
    assert st["spec_drafted_tokens"] >= st["spec_accepted_tokens"] > 0


def test_abort_mid_verify_and_mid_chunk_prefill(tiny):
    """abort() of a slot that has speculatively-written (rolled-back) KV, of
    a mid-chunk-prefill slot holding shared prefix pages, and of a queued
    request behind another MUST deref pages cleanly.  The queued case used to
    raise: deque.remove's equality scan hit Request.__eq__, whose numpy
    prompt comparison has no scalar truth value."""
    cfg, params = tiny
    rng = np.random.RandomState(2)
    # --- mid-verify: slot has stale rejected-candidate KV above lengths ---
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    spec_len=4)
    prompt = np.tile(rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32), 4)
    r1 = eng.add_request(prompt, max_new_tokens=12)
    while eng.stats()["verify_steps"] < 2:
        eng.step()
    assert eng.abort(r1)
    eng.cache.check_invariants()
    assert eng.cache.pages_in_use() == 0 and not eng.has_work
    assert eng._outputs[r1].finish_reason == "abort"
    # the freed slot serves the next request with exact parity
    nxt = rng.randint(0, cfg.vocab_size, (9,)).astype(np.int32)
    r2 = eng.add_request(nxt, max_new_tokens=6)
    ref = G.generate(params, jnp.asarray(nxt)[None], cfg, max_new_tokens=6)
    np.testing.assert_array_equal(eng.run()[r2].tokens, np.asarray(ref[0]))
    eng.cache.check_invariants()

    # --- mid-chunk-prefill with SHARED prefix pages: deref exactly once ---
    eng2 = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                     prefill_chunk=8, spec_len=4)
    base = rng.randint(0, cfg.vocab_size, (24,)).astype(np.int32)
    rd = eng2.add_request(base, max_new_tokens=4)
    eng2.run()                          # donor registers its prompt pages
    ext = np.concatenate([base, rng.randint(0, cfg.vocab_size,
                                            (20,)).astype(np.int32)])
    rx = eng2.add_request(ext, max_new_tokens=4)
    eng2.step()                         # admitted w/ shared pages, 1 chunk in
    assert rd in eng2._outputs and rx not in eng2._outputs  # rx mid-prefill
    slot = next(iter(eng2._prefilling))
    shared_page = int(eng2.cache.page_table[slot][0])
    assert eng2.cache._ref[shared_page] == 1    # donor retired, ext holds it
    assert eng2.abort(rx)
    eng2.cache.check_invariants()
    assert eng2.cache.pages_in_use() == 0
    assert eng2.cache._ref[shared_page] == 0    # deref'd exactly once

    # --- queued abort behind another queued request (regression) ---
    eng3 = LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=64,
                     num_pages=9)
    q0 = eng3.add_request(rng.randint(0, cfg.vocab_size, (5,))
                          .astype(np.int32), max_new_tokens=4)
    qa = eng3.add_request(rng.randint(0, cfg.vocab_size, (6,))
                          .astype(np.int32), max_new_tokens=4)
    qb = eng3.add_request(rng.randint(0, cfg.vocab_size, (7,))
                          .astype(np.int32), max_new_tokens=4)
    assert eng3.abort(qb) and eng3.abort(qa)    # qb sits BEHIND qa
    assert eng3.abort(q0) and not eng3.has_work
    eng3.cache.check_invariants()


# ---------------------------------------------------------------------------
# per-request greedy fast path (satellite)
# ---------------------------------------------------------------------------

def test_greedy_fast_path_in_sampling_engine(tiny):
    """add_request(temperature=0.0) on a sampling engine takes argmax —
    exact parity with greedy generate(), PRNG-independent — and spec-decode
    drafts apply to the greedy request only."""
    cfg, params = tiny
    rng = np.random.RandomState(5)
    p = rng.randint(0, cfg.vocab_size, (7,)).astype(np.int32)
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    temperature=0.8, seed=9, spec_len=4)
    rg = eng.add_request(p, max_new_tokens=10, temperature=0.0)
    rs = eng.add_request(p, max_new_tokens=10)          # sampled lane
    outs = eng.run()
    ref = G.generate(params, jnp.asarray(p)[None], cfg, max_new_tokens=10)
    np.testing.assert_array_equal(outs[rg].tokens, np.asarray(ref[0]))
    st = eng.stats()
    assert st["verify_steps"] > 0                       # greedy slot drafted
    assert st["decode_executables"] == 1                # sampled slot decoded
    with pytest.raises(ValueError, match="per-request temperature"):
        eng.add_request(p, temperature=0.3)             # != engine temp
    with pytest.raises(ValueError, match="must be >= 0"):
        eng.add_request(p, temperature=-0.7)            # typo'd sign

    # a fully greedy engine never consumes its PRNG key
    g = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64)
    with pytest.raises(ValueError, match="cannot serve sampled"):
        g.add_request(p, temperature=0.7)
    k0 = np.asarray(jax.random.key_data(g._key)).copy()
    g.add_request(p, max_new_tokens=5)
    g.run()
    np.testing.assert_array_equal(np.asarray(jax.random.key_data(g._key)), k0)


# ---------------------------------------------------------------------------
# CI wiring: bench smoke + program-count guard (acceptance bar)
# ---------------------------------------------------------------------------

def test_bench_serve_spec_cpu_smoke():
    """Acceptance bar: --spec-len 4 on a repetitive/shared-prefix CPU-smoke
    stream shows accepted_per_step > 1.2 and EXACT greedy token parity with
    --no-spec (byte-identical output digests), within <= 2 decode-side
    compiled programs."""
    from bench_serve import run_serve_bench
    kw = dict(num_requests=12, num_slots=2, page_size=8, max_model_len=64,
              max_new_tokens=6, prefill_chunk=16, shared_prefix_frac=0.5,
              seed=11)
    spec = run_serve_bench(**kw, spec_len=4)
    base = run_serve_bench(**kw, spec_len=0)
    assert spec["outputs_digest"] == base["outputs_digest"]     # exact parity
    assert spec["accepted_per_step"] > 1.2
    assert spec["decode_executables"] + spec["verify_executables"] <= 2
    assert base["verify_steps"] == 0 and base["accepted_per_step"] == 0.0
    # spec needs fewer decode iterations for the same emitted tokens
    assert spec["decode_iters"] < base["decode_iters"]


def test_check_program_count_tool():
    """Satellite (CI wiring): the program-count guard measures within budget
    and fails loudly when the budget is exceeded."""
    import tools.check_program_count as cpc
    got, stats = cpc.measure()
    assert got["decode_side_executables"] <= cpc.BUDGET["decode_side_executables"]
    assert got["total_executables"] <= cpc.BUDGET["total_executables"]
    assert stats["accepted_per_step"] > 1.0
    # per-mesh-config budget: the mp=2 tensor-parallel pass replays the same
    # stream within the mp budget and emits byte-identical greedy tokens
    got_mp, stats_mp = cpc.measure(mp=2)
    assert got_mp["decode_side_executables"] <= \
        cpc.BUDGET_MP["decode_side_executables"]
    assert got_mp["total_executables"] <= cpc.BUDGET_MP["total_executables"]
    assert stats_mp["outputs_digest"] == stats["outputs_digest"]


# ---------------------------------------------------------------------------
# adaptive spec back-off (per-slot)
# ---------------------------------------------------------------------------

class _AlwaysWrongProposer:
    """Drafts a constant token stream the tiny random model never emits, so
    acceptance is exactly 0 on every verify event."""
    max_lookback = 4

    def __init__(self, token):
        self.token = token
        self.calls = 0

    def propose(self, context, max_tokens):
        self.calls += 1
        return np.full((max_tokens,), self.token, np.int32)


def test_adaptive_spec_backoff_stops_dead_drafting(tiny):
    """A slot whose drafts are never accepted stops being proposed for after
    `spec_backoff_window` zero-accept verify events: the proposer is no
    longer scanned for it, drafted-token counters freeze, the back-off shows
    in stats(), and the emitted tokens are STILL exactly the vanilla greedy
    stream (acceptance is lossless either way)."""
    cfg, params = tiny
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    W, NEW = 3, 24

    base = LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=64,
                     spec_len=0)
    base.add_request(prompt, max_new_tokens=NEW)
    ref = next(iter(base.run().values())).token_ids

    # pick a draft token the greedy stream never contains -> 0% acceptance
    bad = next(t for t in range(cfg.vocab_size) if t not in ref)
    prop = _AlwaysWrongProposer(bad)
    eng = LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=64,
                    spec_len=3, draft_proposer=prop, spec_backoff_window=W)
    eng.add_request(prompt, max_new_tokens=NEW)
    out = next(iter(eng.run().values())).token_ids
    st = eng.stats()
    assert out == ref                         # parity regardless of back-off
    assert st["spec_backoffs"] == 1           # the slot backed off once
    # exactly W drafted events of spec_len tokens, then drafting stopped
    assert prop.calls == W
    assert st["spec_drafted_tokens"] == W * 3
    assert st["spec_accepted_tokens"] == 0
    eng.cache.check_invariants()

    # window=0 disables the back-off: the proposer is scanned every iteration
    prop2 = _AlwaysWrongProposer(bad)
    eng2 = LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=64,
                     spec_len=3, draft_proposer=prop2, spec_backoff_window=0)
    eng2.add_request(prompt, max_new_tokens=NEW)
    out2 = next(iter(eng2.run().values())).token_ids
    assert out2 == ref
    assert eng2.stats()["spec_backoffs"] == 0
    assert prop2.calls > W


def test_adaptive_spec_backoff_resets_on_acceptance(tiny):
    """Accepted drafts reset the zero-accept streak: an NgramProposer on a
    repetitive greedy stream keeps drafting (no back-off) while emitting the
    exact vanilla tokens."""
    cfg, params = tiny
    prompt = np.asarray([9, 9, 9, 9, 9, 9], np.int32)   # tight loop
    eng = LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=64,
                    spec_len=3, spec_backoff_window=2)
    eng.add_request(prompt, max_new_tokens=16)
    eng.run()
    st = eng.stats()
    if st["spec_accepted_tokens"] > 0:        # stream-dependent, usually true
        assert st["spec_backoffs"] == 0
