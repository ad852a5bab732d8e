"""Oversubscribed serving: optimistic admission, preemption with KV
swap/recompute, deadlines, fail-fast rejection, and the fault-injection
harness that drives every preempt interleaving deterministically (ref vLLM
preempt-then-swap-or-recompute, Kwon et al. SOSP 2023 §4.3, over Sarathi
chunked prefill).

The two hard bars, asserted throughout: (1) byte-exact greedy parity
preempted-vs-undisturbed — preemption may cost throughput, never tokens;
(2) zero leaked pages across preempt/swap/abort/timeout interleavings —
`PagedKVCache.check_invariants` (free/LRU/in-use + the fourth `swapped`
partition) clean at every step boundary and empty at drain."""
import numpy as np
import pytest

import jax

from paddle_tpu.inference.cache import PagedKVCache
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.inference.faults import FaultInjected, FaultPlan
from paddle_tpu.models import gpt as G


@pytest.fixture(scope="module")
def cfg():
    return G.gpt_tiny(64)


@pytest.fixture(scope="module")
def params(cfg):
    return G.init_params(cfg, jax.random.key(0))


def _prompts(cfg, n=6, lo=4, hi=9, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        (int(rng.randint(lo, hi)),)).astype(np.int32)
            for _ in range(n)]


@pytest.fixture(scope="module")
def reference(cfg, params):
    """Undisturbed run: big pool, reservation admission — the token oracle
    every preempted run must match byte-for-byte."""
    prompts = _prompts(cfg)
    eng = LLMEngine(params, cfg, num_slots=6, page_size=8, max_model_len=64,
                    prefill_chunk=8)
    rids = [eng.add_request(p, max_new_tokens=24) for p in prompts]
    outs = eng.run()
    return prompts, [list(outs[r].token_ids) for r in rids]


def _drain_checked(eng):
    """step() to completion, asserting page invariants at EVERY boundary."""
    while eng.has_work:
        eng.step()
        eng.cache.check_invariants()
    st = eng.stats()
    assert st["pages_in_use"] == 0 and st["swapped"] == 0
    return dict(eng._outputs), st


def _assert_parity(outs, rids, ref_tokens):
    for rid, ref in zip(rids, ref_tokens):
        assert outs[rid].finish_reason in ("stop", "length")
        assert list(outs[rid].token_ids) == ref, \
            f"request {rid} diverged under preemption"


# ---------------------------------------------------------------------------
# optimistic admission + token-granular growth
# ---------------------------------------------------------------------------

def test_optimistic_admission_beats_reservation_concurrency(cfg, params,
                                                            reference):
    """Reservation fits two 4-page worst-case footprints into an 8-page
    pool; optimistic admits on 1-page prompts and runs several slots off
    live tokens instead."""
    prompts, ref_tokens = reference

    def peak_running(admission):
        eng = LLMEngine(params, cfg, num_slots=6, page_size=8, num_pages=9,
                        max_model_len=64, prefill_chunk=8,
                        admission=admission)
        rids = [eng.add_request(p, max_new_tokens=24) for p in prompts]
        peak = 0
        while eng.has_work:
            eng.step()
            peak = max(peak, eng.stats()["running"])
            eng.cache.check_invariants()
        outs = dict(eng._outputs)
        _assert_parity(outs, rids, ref_tokens)
        return peak

    assert peak_running("reservation") <= 2
    assert peak_running("optimistic") >= 4


def test_optimistic_admits_watermark_sized_footprint_when_idle(cfg, params):
    """Regression: a prompt whose footprint sits within the admission
    watermark of the WHOLE pool passes intake (it fits), so an idle engine
    must admit it rather than wedge the queue head behind a watermark that
    protects nothing."""
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, num_pages=5,
                    max_model_len=64, prefill_chunk=8,
                    admission="optimistic")
    # 30 + 2 = 32 tokens = all 4 real pages: feasible, zero slack
    rid = eng.add_request(np.arange(30, dtype=np.int32), max_new_tokens=2)
    outs = eng.run()
    assert outs[rid].finish_reason in ("stop", "length")
    eng.cache.check_invariants()


def test_optimistic_growth_tracks_live_tokens(cfg, params):
    """A lone decoding slot grows page by page — admission reserved only the
    prompt footprint, and the page count follows lengths upward."""
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    prefill_chunk=8, admission="optimistic")
    rid = eng.add_request(np.arange(6, dtype=np.int32), max_new_tokens=40)
    held = []
    while eng.has_work:
        eng.step()
        held.append(eng.cache.pages_held(0))
        eng.cache.check_invariants()
    assert held[0] == 1                     # prompt footprint only
    assert max(held) >= 5                   # grew with the 40-token decode
    assert eng._outputs[rid].finish_reason == "length"


# ---------------------------------------------------------------------------
# preemption: recompute and swap, byte parity + zero leaks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("preempt", ["recompute", "swap"])
def test_preemption_parity_and_no_leaks(cfg, params, reference, preempt):
    prompts, ref_tokens = reference
    # kv_tier off: this test proves the PREEMPTION machinery's program
    # accounting in isolation (the default-on tier shares the two swap
    # executables and would mask a recompute path that wrongly compiled
    # them; the tier's own program accounting lives in tests/test_kv_tier)
    eng = LLMEngine(params, cfg, num_slots=6, page_size=8, num_pages=9,
                    max_model_len=64, prefill_chunk=8,
                    admission="optimistic", preempt=preempt, kv_tier=False)
    rids = [eng.add_request(p, max_new_tokens=24) for p in prompts]
    outs, st = _drain_checked(eng)
    assert st["preemptions"] > 0
    if preempt == "swap":
        assert st["preempt_swaps"] > 0 and st["swapped_pages"] > 0
        assert st["swap_executables"] == 2
        assert st["swap_ms"] >= 0.0
    else:
        assert st["preempt_recomputes"] == st["preemptions"]
        assert st["recomputed_tokens"] > 0
        assert st["swap_executables"] == 0
    _assert_parity(outs, rids, ref_tokens)
    for rid in rids:
        m = outs[rid].metrics
        assert m is not None and m.preemptions >= 0


def test_swap_pool_exhaustion_degrades_to_recompute(cfg, params, reference):
    """swap_pool_pages=0 leaves no host room: every preemption must fall
    back to recompute — same tokens, no swap executables ever built."""
    prompts, ref_tokens = reference
    eng = LLMEngine(params, cfg, num_slots=6, page_size=8, num_pages=9,
                    max_model_len=64, prefill_chunk=8,
                    admission="optimistic", preempt="swap",
                    swap_pool_pages=0)
    rids = [eng.add_request(p, max_new_tokens=24) for p in prompts]
    outs, st = _drain_checked(eng)
    assert st["preemptions"] > 0
    assert st["preempt_swaps"] == 0 and st["swapped_pages"] == 0
    assert st["preempt_recomputes"] == st["preemptions"]
    _assert_parity(outs, rids, ref_tokens)


def test_victim_selection_prefers_low_priority(cfg, params):
    """Under forced pressure the priority-0 request is evicted before the
    priority-1 request every time."""
    plan = FaultPlan(pressure_steps=(4,))
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    prefill_chunk=8, admission="optimistic",
                    fault_plan=plan)
    lo = eng.add_request(np.arange(4, dtype=np.int32), max_new_tokens=20,
                         priority=0)
    hi = eng.add_request(np.arange(4, 6, dtype=np.int32), max_new_tokens=20,
                         priority=1)
    preempted = set()
    while eng.has_work:
        eng.step()
        preempted |= set(eng._preempted)
        eng.cache.check_invariants()
    assert lo in preempted and hi not in preempted
    for rid in (lo, hi):
        assert eng._outputs[rid].finish_reason == "length"


def test_preemption_in_bucketed_mode(cfg, params, reference):
    """Growth + preemption also cover the bucketed one-shot prefill (a
    recompute resume replays its longer prompt through the bucket ladder)."""
    prompts, ref_tokens = reference
    eng = LLMEngine(params, cfg, num_slots=6, page_size=8, num_pages=9,
                    max_model_len=64, admission="optimistic",
                    prefill_chunk=None)
    rids = [eng.add_request(p, max_new_tokens=24) for p in prompts]
    outs, st = _drain_checked(eng)
    assert st["preemptions"] > 0
    _assert_parity(outs, rids, ref_tokens)


# ---------------------------------------------------------------------------
# fault injection: forced pressure mid-verify / mid-chunk-prefill, failing
# swap copies — every path must keep parity and leak nothing
# ---------------------------------------------------------------------------

def test_forced_pressure_mid_verify_keeps_spec_parity(cfg, params):
    """Preemption in a step where victims carry speculative drafts: the
    in-flight draft is discarded with the victim, and the replay still
    reproduces the vanilla-greedy stream."""
    rng = np.random.RandomState(3)
    # repetitive prompts so the n-gram proposer actually drafts
    base = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
    prompts = [np.tile(base, 3)[:10 + i] for i in range(4)]

    ref_eng = LLMEngine(params, cfg, num_slots=4, page_size=8,
                        max_model_len=64, prefill_chunk=8)
    ref = [list(o.token_ids) for o in
           (lambda e, r: [e.run()[x] for x in r])(
               ref_eng, [ref_eng.add_request(p, max_new_tokens=20)
                         for p in prompts])]

    plan = FaultPlan(pressure_steps=(3, 5, 7))
    eng = LLMEngine(params, cfg, num_slots=4, page_size=8, max_model_len=64,
                    prefill_chunk=8, spec_len=3, admission="optimistic",
                    fault_plan=plan)
    rids = [eng.add_request(p, max_new_tokens=20) for p in prompts]
    outs, st = _drain_checked(eng)
    assert st["preemptions"] >= 1
    assert st["spec_events"] > 0, "verify lane never exercised"
    _assert_parity(outs, rids, ref)


def test_forced_pressure_mid_chunk_prefill(cfg, params):
    """Preemption while another slot is mid-chunk-prefill: the prefilling
    slot is untouched (its prompt pages are reserved), victims come from
    the decode set, and everyone finishes with exact tokens."""
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 6, 40, 7)]     # the 40-token prompt chunks 5x
    ref_eng = LLMEngine(params, cfg, num_slots=4, page_size=8,
                        max_model_len=64, prefill_chunk=8)
    rr = [ref_eng.add_request(p, max_new_tokens=16) for p in prompts]
    ref_outs = ref_eng.run()
    ref = [list(ref_outs[r].token_ids) for r in rr]

    plan = FaultPlan(pressure_steps=(2, 3, 4, 5, 6))
    eng = LLMEngine(params, cfg, num_slots=4, page_size=8, max_model_len=64,
                    prefill_chunk=8, admission="optimistic", fault_plan=plan)
    rids = [eng.add_request(p, max_new_tokens=16) for p in prompts]
    saw_prefilling_during_preempt = False
    while eng.has_work:
        pre = eng.stats()["preemptions"]
        eng.step()
        st = eng.stats()
        if st["preemptions"] > pre and st["prefilling"] > 0:
            saw_prefilling_during_preempt = True
        eng.cache.check_invariants()
    outs, st = dict(eng._outputs), eng.stats()
    assert st["preemptions"] >= 1
    assert saw_prefilling_during_preempt, \
        "no preemption landed while a chunk prefill was in progress"
    _assert_parity(outs, rids, ref)


@pytest.mark.parametrize("kw", [dict(fail_d2h=2), dict(fail_h2d=2)])
def test_swap_copy_failures_degrade_cleanly(cfg, params, reference, kw):
    """Injected d2h/h2d copy failures turn swaps into recomputes: the host
    obligation is cleared, pages balance, tokens unchanged."""
    prompts, ref_tokens = reference
    eng = LLMEngine(params, cfg, num_slots=6, page_size=8, num_pages=9,
                    max_model_len=64, prefill_chunk=8,
                    admission="optimistic", preempt="swap",
                    fault_plan=FaultPlan(**kw))
    rids = [eng.add_request(p, max_new_tokens=24) for p in prompts]
    outs, st = _drain_checked(eng)
    assert st["preemptions"] > 0
    assert st["preempt_recomputes"] > 0, "no swap ever degraded"
    if "fail_d2h" in kw:
        # a failed d2h never delivered KV to the host pool: it must count
        # as recompute ONLY, so the split sums exactly to preemptions
        assert st["preempt_swaps"] + st["preempt_recomputes"] == \
            st["preemptions"]
    else:
        # an h2d failure degrades a swap that HAD delivered (counted in
        # both swap and recompute) — the split may legitimately exceed
        assert st["preempt_swaps"] + st["preempt_recomputes"] >= \
            st["preemptions"]
    _assert_parity(outs, rids, ref_tokens)


def test_swap_then_abort_releases_host_pool(cfg, params, reference):
    prompts, _ = reference
    eng = LLMEngine(params, cfg, num_slots=6, page_size=8, num_pages=9,
                    max_model_len=64, prefill_chunk=8,
                    admission="optimistic", preempt="swap")
    rids = [eng.add_request(p, max_new_tokens=24) for p in prompts]
    aborted = None
    while eng.has_work:
        eng.step()
        eng.cache.check_invariants()
        if aborted is None:
            swapped = [r for r, rec in eng._preempted.items()
                       if rec["kind"] == "swap"]
            if swapped:
                assert eng.abort(swapped[0])
                aborted = swapped[0]
                eng.cache.check_invariants()
    assert aborted is not None, "no request was ever swapped out"
    out = eng._outputs[aborted]
    assert out.finish_reason == "abort"
    assert len(out.token_ids) > 0           # banked generation survives abort
    assert eng.cache.swapped_page_count == 0
    assert eng.stats()["pages_in_use"] == 0


def test_abort_during_recompute_replay_keeps_banked_tokens(cfg, params):
    """abort() of a preempted request mid-replay (back in the prefilling
    stage with `prior` tokens banked) publishes those tokens and the
    original TTFT — same contract as aborting it queued or running."""
    plan = FaultPlan(pressure_steps=(5,))
    # prefix_cache=False: with the cache on, the victim's own pages are
    # re-matched from the LRU and the replay completes inside one step —
    # a full multi-chunk replay is needed to catch the request mid-prefill
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    prefill_chunk=4, admission="optimistic",
                    prefix_cache=False, fault_plan=plan)
    rids = [eng.add_request(np.arange(8 + i, dtype=np.int32),
                            max_new_tokens=24) for i in range(2)]
    aborted = None
    while eng.has_work:
        eng.step()
        eng.cache.check_invariants()
        if aborted is None:
            resumed = [st for st in eng._prefilling.values() if st.prior]
            if resumed:
                st = resumed[0]
                banked = list(st.prior)
                assert eng.abort(st.request.request_id)
                aborted = st.request.request_id
                out = eng._outputs[aborted]
                assert out.finish_reason == "abort"
                assert list(out.token_ids) == banked
                assert out.ttft_s is not None
                eng.cache.check_invariants()
    assert aborted is not None, "no preempted request was caught mid-replay"
    assert eng.stats()["pages_in_use"] == 0


# ---------------------------------------------------------------------------
# deadlines + clock skew
# ---------------------------------------------------------------------------

def test_deadline_timeout_queued_and_running(cfg, params):
    t = [0.0]
    eng = LLMEngine(params, cfg, num_slots=1, page_size=8, num_pages=9,
                    max_model_len=64, prefill_chunk=8, clock=lambda: t[0])
    slow = eng.add_request(np.arange(4, dtype=np.int32), max_new_tokens=40,
                           deadline_s=5.0)
    queued = eng.add_request(np.arange(6, dtype=np.int32), max_new_tokens=4,
                             deadline_s=3.0)   # expires before its slot frees
    while eng.has_work:
        eng.step()
        t[0] += 1.0
        eng.cache.check_invariants()
    outs = eng._outputs
    assert outs[slow].finish_reason == "timeout"
    assert outs[queued].finish_reason == "timeout"
    assert outs[queued].metrics.t_first_token is None
    assert len(outs[slow].token_ids) > 0    # partial generation published
    st = eng.stats()
    assert st["timeouts"] == 2
    # timeouts are excluded from the e2e latency SLO like aborts
    assert st["latency"]["e2e_s"]["count"] == 0
    assert st["pages_in_use"] == 0


def test_deadline_during_swap(cfg, params):
    """A request whose deadline expires while its KV sits in the host swap
    pool: the obligation is dropped, reason is timeout, nothing leaks."""
    t = [0.0]
    plan = FaultPlan(pressure_steps=(4,))
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    prefill_chunk=8, admission="optimistic", preempt="swap",
                    fault_plan=plan, clock=lambda: t[0])
    rids = [eng.add_request(np.arange(4 + i, dtype=np.int32),
                            max_new_tokens=24, deadline_s=100.0)
            for i in range(2)]
    timed_out = None
    while eng.has_work:
        eng.step()
        t[0] += 1.0
        eng.cache.check_invariants()
        if timed_out is None and eng.stats()["swapped"] > 0:
            t[0] += 1000.0              # expire EVERYTHING, swapped included
            timed_out = True
    assert timed_out, "no request was swapped before the deadline jump"
    assert any(eng._outputs[r].finish_reason == "timeout" for r in rids)
    assert eng.cache.swapped_page_count == 0
    assert eng.stats()["pages_in_use"] == 0
    eng.cache.check_invariants()


def test_clock_skew_expires_early_but_cleanly(cfg, params):
    t = [0.0]
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    prefill_chunk=8, fault_plan=FaultPlan(skew_s=1e6),
                    clock=lambda: t[0])
    rid = eng.add_request(np.arange(4, dtype=np.int32), max_new_tokens=20,
                          deadline_s=50.0)
    ok = eng.add_request(np.arange(5, dtype=np.int32), max_new_tokens=4)
    while eng.has_work:
        eng.step()
        t[0] += 0.01
        eng.cache.check_invariants()
    # the skewed clock expired the deadlined request at its first step; the
    # deadline-free request is untouched by skew
    assert eng._outputs[rid].finish_reason == "timeout"
    assert eng._outputs[ok].finish_reason in ("stop", "length")
    assert eng.stats()["pages_in_use"] == 0


# ---------------------------------------------------------------------------
# fail-fast rejection
# ---------------------------------------------------------------------------

def test_impossible_footprint_rejected_without_wedging(cfg, params):
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, num_pages=3,
                    max_model_len=64)       # 2 real pages = 16 tokens
    big = eng.add_request(np.zeros((20,), np.int32), max_new_tokens=8)
    out = eng._outputs[big]
    assert out.finish_reason == "rejected" and out.token_ids == []
    assert eng.stats()["rejected_requests"] == 1
    assert eng.stats()["queued"] == 0       # never entered the queue
    # the queue head is NOT wedged: a feasible request behind it completes
    ok = eng.add_request(np.zeros((6,), np.int32), max_new_tokens=4)
    outs = eng.run()
    assert outs[ok].finish_reason in ("stop", "length")
    eng.cache.check_invariants()


def test_rejection_applies_under_optimistic_too(cfg, params):
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, num_pages=3,
                    max_model_len=64, admission="optimistic")
    rid = eng.add_request(np.zeros((4,), np.int32), max_new_tokens=20)
    # prompt alone fits, but the worst-case footprint (24 tokens = 3 pages)
    # can never fit 2 real pages — optimistic growth would wedge at the end
    assert eng._outputs[rid].finish_reason == "rejected"


# ---------------------------------------------------------------------------
# cache-level unit coverage of the new machinery
# ---------------------------------------------------------------------------

def test_cache_grow_and_swap_partition_unit():
    mgr = PagedKVCache(num_pages=8, page_size=4, num_slots=2,
                       max_pages_per_slot=4)
    mgr.allocate(0, 4)                      # 1 page
    assert mgr.pages_held(0) == 1
    mgr.grow(0, 5)                          # crosses into page 2
    assert mgr.pages_held(0) == 2
    mgr.grow(0, 5)                          # idempotent
    assert mgr.pages_held(0) == 2
    mgr.check_invariants()
    with pytest.raises(ValueError, match="slot capacity"):
        mgr.grow(0, 17)
    mgr.note_swap_out(7, 2)
    assert mgr.swapped_page_count == 2 and mgr.swapped_requests == 1
    with pytest.raises(RuntimeError, match="already swapped"):
        mgr.note_swap_out(7, 1)
    mgr.check_invariants()
    assert mgr.note_swap_in(7) == 2
    assert mgr.swapped_page_count == 0
    mgr.release(0)
    mgr.check_invariants()
    # growth exhausts the pool -> RuntimeError (the preemption trigger):
    # slot 1 holds 4 of the 7 real pages, slot 0 one — growing slot 0 to
    # its 4-page capacity needs 3 fresh pages but only 2 remain
    mgr.allocate(1, 16)
    mgr.allocate(0, 4)
    with pytest.raises(RuntimeError, match="out of KV pages"):
        mgr.grow(0, 16)


def test_fault_plan_unit():
    plan = FaultPlan(pressure_steps=(2,), fail_d2h=1, skew_s=3.0)
    assert not plan.pool_pressure(1)
    assert plan.pool_pressure(2)
    assert not plan.pool_pressure(2)        # fires once per listed step
    with pytest.raises(FaultInjected):
        plan.d2h()
    plan.d2h()                              # budget spent: no-op
    plan.h2d()                              # never armed: no-op
    assert plan.skew() == 3.0


# ---------------------------------------------------------------------------
# the oversubscription bench smoke (the PR's acceptance bar)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("preempt", ["recompute", "swap"])
def test_bench_oversubscribe_completes_with_parity(preempt):
    from bench_serve import run_serve_bench
    kw = dict(num_requests=16, num_slots=4, page_size=8, max_model_len=64,
              max_new_tokens=12, prefill_chunk=8, seed=7, preempt=preempt)
    pressured = run_serve_bench(oversubscribe=2.0, **kw)
    base = run_serve_bench(oversubscribe=1.0, **kw)
    # every request completed (run_serve_bench asserts the count and the
    # drain invariants internally), pressure actually materialized, and the
    # stream is byte-identical to the unpressured run
    assert pressured["preemptions"] > 0
    assert pressured["outputs_digest"] == base["outputs_digest"]
    assert pressured["goodput_tokens_per_sec"] > 0
    if preempt == "swap":
        assert pressured["preempt_swaps"] > 0
        assert pressured["swap_executables"] == 2


# ---------------------------------------------------------------------------
# ISSUE 31: a victim's pages leave in pieces whose copies run beside the steps
# ---------------------------------------------------------------------------

def _swap_engine(cfg, params, monkeypatch, **kw):
    """preempt="swap" with 2-page pieces, as a real model's page bytes make
    them (the tiny model's whole slot would fit one piece)."""
    from conftest import narrow_d2h_pieces
    narrow_d2h_pieces(monkeypatch, cfg)
    eng = LLMEngine(params, cfg, num_slots=6, page_size=8, num_pages=9,
                    max_model_len=64, prefill_chunk=8,
                    admission="optimistic", preempt="swap", **kw)
    assert eng._swap_w == 2
    return eng


@pytest.mark.parametrize("when", ["landed", "in_flight"])
def test_swap_in_of_a_victim_whose_copy_is_in_flight(cfg, params, reference,
                                                     monkeypatch, held_worker,
                                                     when):
    """A victim re-admitted while its pages' copies are still in flight is
    the one place a swap waits for bytes; re-admitted after they landed it
    waits for nothing.  Either way the KV comes back bit-exact: the tokens
    are the undisturbed run's."""
    prompts, ref_tokens = reference
    eng = held_worker.watch(_swap_engine(cfg, params, monkeypatch))
    if when == "landed":
        from conftest import InlineWorker
        held_worker.gate.set()
        eng._d2h_worker = InlineWorker()    # every copy lands at once
    rids = [eng.add_request(p, max_new_tokens=24) for p in prompts]
    while eng.has_work:
        eng.step()
        eng.cache.check_invariants()
        assert eng._d2h_inflight <= eng._d2h_bound
    eng.drain()
    st = eng.stats()
    assert st["preempt_swaps"] > 0 and st["swapped"] == 0
    assert st["pages_in_use"] == 0 and st["swap_d2h_inflight_pages"] == 0
    assert (held_worker.waited_for > 0) == (when == "in_flight")
    assert st["swap_d2h_landed_free"] == \
        st["swap_d2h_fetches"] - held_worker.waited_for
    # a victim of n pages crossed as ceil(n / 2) pieces, pads and all
    page = eng._kv_page_bytes
    assert st["swap_d2h_bytes"] == st["swap_d2h_fetches"] * 2 * page
    assert st["swap_d2h_bytes"] - st["swap_d2h_useful_bytes"] <= \
        st["preempt_swaps"] * page + st["kv_tier"]["spills"] * page
    assert st["swap_executables"] == 2
    _assert_parity(dict(eng._outputs), rids, ref_tokens)


def test_swap_d2h_fault_with_the_copy_in_flight_degrades_to_recompute(
        cfg, params, reference, monkeypatch, held_worker):
    """The d2h fault fires while every piece of the victim is still in
    flight: the record degrades to recompute, its pieces are let go, the
    host-pool obligation clears and the tokens do not change."""
    prompts, ref_tokens = reference
    eng = held_worker.watch(_swap_engine(
        cfg, params, monkeypatch, kv_tier=False,
        fault_plan=FaultPlan(fail_d2h=1000)))
    rids = [eng.add_request(p, max_new_tokens=24) for p in prompts]
    outs, st = _drain_checked(eng)
    eng.drain()
    held_worker.gate.set()
    assert st["preemptions"] > 0 and st["preempt_swaps"] == 0
    assert st["preempt_recomputes"] == st["preemptions"]
    assert st["swap_d2h_fetches"] == 0 and held_worker.waited_for == 0
    assert eng._d2h_inflight == 0 and not eng._pending_d2h
    assert eng.cache.swapped_page_count == 0
    _assert_parity(outs, rids, ref_tokens)


def test_abort_of_a_victim_in_flight_lets_its_pieces_go(cfg, params,
                                                        reference,
                                                        monkeypatch,
                                                        held_worker):
    """swap-then-abort with the copies held: the dropped record gives up
    its pieces at the next step boundary and nothing stays in flight."""
    prompts, _ = reference
    eng = held_worker.watch(_swap_engine(cfg, params, monkeypatch,
                                         kv_tier=False))
    for p in prompts:
        eng.add_request(p, max_new_tokens=24)
    aborted = None
    while eng.has_work:
        eng.step()
        eng.cache.check_invariants()
        swapped = [r for r, rec in eng._preempted.items()
                   if rec["kind"] == "swap" and not rec["fetched"]]
        if aborted is None and swapped:
            aborted = swapped[0]
            rec = eng._preempted[aborted]
            assert rec["pieces"] and eng._d2h_inflight > 0
            assert eng.abort(aborted)
            eng.step()
            assert "pieces" not in rec and rec not in eng._pending_d2h
    assert aborted is not None, "no request was ever swapped out"
    eng.drain()
    assert eng._outputs[aborted].finish_reason == "abort"
    assert eng._d2h_inflight == 0 and eng.cache.swapped_page_count == 0
    assert eng.stats()["pages_in_use"] == 0
