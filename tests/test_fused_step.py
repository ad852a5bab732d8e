"""One-dispatch engine step: fused decode+prefill+verify program
(`models.gpt.serve_step_paged`), on-device sampling + acceptance, and
double-buffered scheduling (ref `AnalysisPredictor::ZeroCopyRun` single-graph
step; Sarathi-Serve piggybacking, Agrawal et al. OSDI 2024).

Covers: byte-identical greedy tokens against the dense-cache
`gpt.generate` (spec on/off x bucketed/chunked, prefix cache + COW on) and
mp1 against mp2, the sampled path as a function of the seed alone, the
busy-step ONE-dispatch
assertion straight from `step_trace()`, double-buffer ordering (the token for
step n observed during step n+1), a warmed steady-state loop clean under
`jax.transfer_guard("disallow")`, page invariants after aborting a fused
in-flight batch, and the bench-level dispatches_per_step wiring.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import gpt as G
from paddle_tpu.inference.engine import LLMEngine


@pytest.fixture(scope="module")
def tiny():
    cfg = G.gpt_tiny(64)
    return cfg, G.init_params(cfg, jax.random.key(0))


def _mixed_prompts(cfg, seed=0, n_extra=4):
    """Mixed stream: a repetitive prompt (drafts accept), random lengths, and
    a shared-prefix extension pair (full-page share + COW partial page)."""
    rng = np.random.RandomState(seed)
    pat = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
    prompts = [np.tile(pat, 3)]
    prompts += [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
                for n in (5, 9, 17, 30)[:n_extra]]
    base = prompts[-1]
    prompts.append(np.concatenate(
        [base, rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)]))
    return prompts


# ---------------------------------------------------------------------------
# fused program unit: predictions + on-device accept scan
# ---------------------------------------------------------------------------

def test_serve_step_program_matches_verify_and_host_accept(tiny):
    """serve_step_paged's token buffer is the argmax of the head over the
    shared trunk's hidden states at every position, and its on-device accept
    counts equal the host-side greedy longest-prefix scan — the contract the
    harvest path relies on."""
    cfg, params = tiny
    rng = np.random.RandomState(3)
    B, T, page = 2, 4, 8
    pool = G.init_paged_cache(cfg, num_pages=10, page_size=page)
    table = np.zeros((B, 8), np.int32)
    table[0, :2] = [1, 2]
    table[1, :2] = [3, 4]
    tbl = jnp.asarray(table)
    prompts = rng.randint(0, cfg.vocab_size, (B, 6)).astype(np.int32)
    ids = np.zeros((B, 8), np.int32)
    ids[:, :6] = prompts
    _, pool = G.prefill_chunk_paged(
        params, jnp.asarray(ids), cfg, pool, tbl,
        jnp.zeros((B,), jnp.int32), jnp.full((B,), 6, jnp.int32))
    # slot 0: decode (valid=1); slot 1: a 3-token draft (valid=4)
    tokens = np.zeros((B, T), np.int32)
    tokens[0, 0] = prompts[0, -1]
    tokens[1, :] = rng.randint(0, cfg.vocab_size, (T,))
    tokens[1, 0] = prompts[1, -1]
    qoff = jnp.full((B,), 6, jnp.int32)
    valid = jnp.asarray([1, 4], jnp.int32)
    x, vpool = G._paged_chunk_hidden(
        params, jnp.asarray(tokens), cfg, pool, tbl, qoff, valid)
    vlog = G.head_logits(G.epilogue(params, x, cfg), params, cfg)
    ref = np.asarray(jnp.argmax(vlog, axis=-1))
    out, accept, _, _ = G.serve_step_paged(
        params, jnp.asarray(tokens), vpool, tbl, qoff, valid, cfg)
    out, accept = np.asarray(out), np.asarray(accept)
    np.testing.assert_array_equal(out, ref)
    # host-side accept scan over the drafted slot
    a = 0
    while a < 3 and tokens[1, 1 + a] == ref[1, a]:
        a += 1
    assert accept[0] == 0 and accept[1] == a


# ---------------------------------------------------------------------------
# engine parity: the paged step against the dense-cache decode, byte-exact
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dense_greedy(tiny):
    """`gpt.generate` (dense cache, no paging, no engine) for one prompt;
    kept across the parametrised cases, which share their prompts."""
    cfg, params = tiny
    done = {}

    def greedy(prompt, max_new):
        key = (prompt.tobytes(), max_new)
        if key not in done:
            done[key] = np.asarray(G.generate(
                params, jnp.asarray(prompt)[None], cfg,
                max_new_tokens=max_new)[0])
        return done[key]
    return greedy


@pytest.mark.parametrize("spec_len", [0, 4], ids=["nospec", "spec4"])
@pytest.mark.parametrize("chunk", [None, 8], ids=["bucketed", "chunked"])
def test_greedy_byte_parity_with_dense_generate(tiny, dense_greedy, spec_len,
                                                chunk):
    """Acceptance bar: in every mode the engine emits, request by request,
    the dense-cache greedy decode's tokens (prefix cache + COW on), with
    exactly 1 decode-side compiled program."""
    cfg, params = tiny
    prompts = _mixed_prompts(cfg)
    eng = LLMEngine(params, cfg, num_slots=3, page_size=8,
                    max_model_len=64, prefill_chunk=chunk,
                    spec_len=spec_len)
    rids = [eng.add_request(p, max_new_tokens=10) for p in prompts]
    res = eng.run()
    for r, p in zip(rids, prompts):
        np.testing.assert_array_equal(res[r].tokens, dense_greedy(p, 10))
    st = eng.stats()
    eng.cache.check_invariants()
    assert st["pages_in_use"] == 0
    assert st["prefix_cached_tokens"] > 0       # the shared pair did share
    assert st["decode_executables"] + st["verify_executables"] == 1
    if spec_len:
        assert st["verify_steps"] > 0      # drafts rode the fused program
    if chunk is not None:
        assert st["prefill_executables"] == 0  # the chunk rode it too


def test_fused_mp2_parity_and_aot_program_count(tiny):
    """mp=2 tensor-parallel fused serving: byte-identical tokens vs mp=1,
    decode-side exactly ONE AOT-compiled program (exact count, not a
    dispatch-cache size)."""
    cfg, params = tiny
    prompts = _mixed_prompts(cfg)
    outs = {}
    for mp in (1, 2):
        eng = LLMEngine(params, cfg, num_slots=3, page_size=8,
                        max_model_len=64, prefill_chunk=8, spec_len=3,
                        mp=mp if mp > 1 else None)
        rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
        res = eng.run()
        outs[mp] = [list(res[r].tokens) for r in rids]
        st = eng.stats()
        assert st["decode_executables"] + st["verify_executables"] == 1
        if mp > 1:
            assert eng._decode_fn._cache_size() == 1   # AOT: exact count
    assert outs[1] == outs[2]


def test_fused_sampled_parity_fixed_key(tiny):
    """Sampled path: the on-device pick (shared `gpt.sample_token`, one split
    per decode dispatch) is a function of the seed alone — an engine built
    alike repeats the stream token for token, another seed does not."""
    cfg, params = tiny
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 12, 20)]
    outs = []
    for seed in (42, 42, 43):
        eng = LLMEngine(params, cfg, num_slots=3, page_size=8,
                        max_model_len=64, temperature=0.8, seed=seed,
                        spec_len=0)
        rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
        res = eng.run()
        outs.append([list(res[r].token_ids) for r in rids])
    assert outs[0] == outs[1]
    assert outs[0] != outs[2]
    # the same engine still honors the per-request greedy fast path
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    temperature=0.8, seed=42, spec_len=0)
    rg = eng.add_request(prompts[0], max_new_tokens=8, temperature=0.0)
    ref = G.generate(params, jnp.asarray(prompts[0])[None], cfg,
                     max_new_tokens=8)
    np.testing.assert_array_equal(eng.run()[rg].tokens, np.asarray(ref[0]))


# ---------------------------------------------------------------------------
# the one-dispatch claim, asserted from the step trace
# ---------------------------------------------------------------------------

def test_busy_step_dispatches_exactly_one_program(tiny):
    """Acceptance bar: a steady-state busy step — decode + interleaved
    prefill chunk + verify all active — dispatches exactly ONE program, and
    the v2 trace record says so (per-mode slot occupancy included)."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, num_slots=3, page_size=8, max_model_len=64,
                    prefill_chunk=8, spec_len=3)
    rng = np.random.RandomState(1)
    # repetitive prompt: decoding + drafting while the long prompt chunks
    eng.add_request(np.tile(np.asarray([7, 3, 9], np.int32), 4),
                    max_new_tokens=16)
    for _ in range(3):
        eng.step()
    eng.add_request(rng.randint(0, cfg.vocab_size, (30,)).astype(np.int32),
                    max_new_tokens=4)
    eng.run()
    busy = [r for r in eng.step_trace()
            if r["decode_batch"] > 0 and r["chunk"] and
            r["verify_dispatches"] > 0]
    assert busy, "no decode+chunk+verify step in the trace"
    for r in busy:
        assert r["v"] == 2
        assert r["dispatches"] == 1
        assert r["slots"]["chunk"] == 1
        assert r["slots"]["verify"] >= 1
        assert "sync_ms" in r
    # every decode-path step of the whole run was one dispatch
    assert all(r["dispatches"] <= 1 for r in eng.step_trace())


def test_double_buffer_token_lands_in_next_step(tiny):
    """Double-buffer ordering through the injectable clock: the fused
    dispatch of step n returns un-synced and its token is observed during
    step n+1 (the harvest inside step n+1's sample-sync span), while
    double_buffer=False keeps the synchronous schedule."""
    cfg, params = tiny

    class Clk:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    for db, after_step1 in ((True, 1), (False, 2)):
        clk = Clk()
        eng = LLMEngine(params, cfg, num_slots=2, page_size=8,
                        max_model_len=64, double_buffer=db, clock=clk)
        eng.add_request(np.arange(5, dtype=np.int32), max_new_tokens=4)
        clk.t = 1.0
        eng.step()      # admit + prefill (first token) + fused dispatch
        seq = next(iter(eng._running.values()))
        assert len(seq.generated) == after_step1
        trace = eng.step_trace()
        assert trace[-1]["tokens_emitted"] == after_step1 - 1
        clk.t = 2.0
        eng.step()      # db: harvest of step 1's dispatch lands HERE
        assert len(next(iter(eng._running.values())).generated) == \
            after_step1 + 1
        if db:
            assert eng.step_trace()[-1]["tokens_emitted"] == 1
        outs = eng.run()
        assert len(next(iter(outs.values())).token_ids) == 4
    # parity between the two schedules, token for token
    res = {}
    for db in (True, False):
        eng = LLMEngine(params, cfg, num_slots=2, page_size=8,
                        max_model_len=64, spec_len=3, prefill_chunk=8,
                        double_buffer=db)
        rids = [eng.add_request(p, max_new_tokens=8)
                for p in _mixed_prompts(cfg, seed=5, n_extra=2)]
        out = eng.run()
        res[db] = [list(out[r].tokens) for r in rids]
    assert res[True] == res[False]


def test_steady_state_fused_loop_transfer_guard_clean(tiny):
    """The warmed fused+double-buffered loop — harvest fetch included — runs
    under `jax.transfer_guard("disallow")`: every h2d is an explicit staged
    placement and the per-step d2h is the one O(B*K)-int harvest."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    num_pages=32, prefill_chunk=8, spec_len=3)
    rng = np.random.RandomState(0)
    for n in (5, 20):                   # warm chunk/decode/verify lanes
        eng.add_request(rng.randint(0, cfg.vocab_size, (n,))
                        .astype(np.int32), max_new_tokens=4)
    eng.run()
    eng.warm_decode()
    base = rng.randint(0, cfg.vocab_size, (13,)).astype(np.int32)
    eng.add_request(base, max_new_tokens=1)
    eng.run()                           # donor registers its prompt pages
    rids = [eng.add_request(rng.randint(0, cfg.vocab_size, (n,))
                            .astype(np.int32), max_new_tokens=5)
            for n in (7, 19)]
    rids.append(eng.add_request(np.concatenate([base, base[:4]]),
                                max_new_tokens=3))      # prefix hit + COW
    with jax.transfer_guard("disallow"):
        outs = eng.run()
    assert all(r in outs for r in rids)
    assert eng.stats()["prefix_cached_tokens"] > 0


def test_abort_mid_inflight_fused_batch_keeps_invariants(tiny):
    """check_invariants() after aborting a request whose fused batch is
    still in flight: the harvest-first abort keeps refcounts/partition
    exact, and the freed slot serves the next request with exact parity."""
    cfg, params = tiny
    rng = np.random.RandomState(2)
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    prefill_chunk=8, spec_len=4)
    prompt = np.tile(rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32), 4)
    r1 = eng.add_request(prompt, max_new_tokens=20)
    eng.step()
    eng.step()
    assert eng._inflight is not None    # a fused batch is in flight
    assert eng.abort(r1)
    assert eng._inflight is None        # abort harvested it first
    eng.cache.check_invariants()
    assert eng.cache.pages_in_use() == 0
    assert eng._outputs[r1].finish_reason == "abort"
    nxt = rng.randint(0, cfg.vocab_size, (9,)).astype(np.int32)
    r2 = eng.add_request(nxt, max_new_tokens=6)
    ref = G.generate(params, jnp.asarray(nxt)[None], cfg, max_new_tokens=6)
    np.testing.assert_array_equal(eng.run()[r2].tokens, np.asarray(ref[0]))
    eng.cache.check_invariants()

    # mid-chunk abort: the staged chunk slot resolves through the harvest
    eng2 = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                     prefill_chunk=8, spec_len=4)
    rl = eng2.add_request(rng.randint(0, cfg.vocab_size, (30,))
                          .astype(np.int32), max_new_tokens=4)
    eng2.step()                         # chunk 1 of 4 staged + dispatched
    assert eng2.abort(rl)
    eng2.cache.check_invariants()
    assert eng2.cache.pages_in_use() == 0 and not eng2.has_work


# ---------------------------------------------------------------------------
# bench + CI wiring
# ---------------------------------------------------------------------------

def test_bench_dispatches_per_step():
    """Acceptance bar (CPU smoke): the served stream shows dispatches_per_step
    <= 1.1 on a chunked, speculating, prefix-sharing stream, from one
    decode-side program."""
    from serve_stream import run_stream
    st = run_stream(num_requests=12, num_slots=2, page_size=8,
                    max_model_len=64, max_new_tokens=6,
                    prefill_chunk=16, shared_prefix_frac=0.5,
                    spec_len=4, seed=11)
    assert st["dispatches_per_step"] <= 1.1
    assert st["decode_executables"] + st["verify_executables"] == 1
    assert st["prefill_executables"] == 0       # chunk rides the fused batch
    assert st["accepted_per_step"] > 1.0        # spec still pays inside fusion


def test_program_budget_decode_side_one():
    """Satellite (CI wiring): the tightened budget — decode-side <= 1 — is
    declared once in analysis/registry.py and both measurement passes of
    check_program_count enforce it."""
    from paddle_tpu.analysis.registry import (SERVE_PROGRAM_BUDGET,
                                              SERVE_PROGRAM_BUDGET_MP)
    assert SERVE_PROGRAM_BUDGET["decode_side_executables"] == 1
    assert SERVE_PROGRAM_BUDGET_MP["decode_side_executables"] == 1
    import tools.check_program_count as cpc
    assert cpc.BUDGET is SERVE_PROGRAM_BUDGET          # declared ONCE
    assert cpc.BUDGET_MP is SERVE_PROGRAM_BUDGET_MP


def test_fused_jaxpr_audit_host_output_budget():
    """The fused executable's jaxpr passes JXP001-005 — in particular the
    host-visible output is O(B*K) ints — and a logits-returning variant is
    caught by the new JXP005 audit."""
    from paddle_tpu.analysis.jaxpr_checks import audit_jaxpr, serving_targets
    targets = [t for t in serving_targets(1) if "fused_step" in t[0]]
    assert targets, "fused executable missing from the jaxpr target set"
    name, fn, args, kw = targets[0]
    assert kw.get("host_output_budget")
    assert audit_jaxpr(name, fn, args, **kw) == []


# ---------------------------------------------------------------------------
# launch-ahead scheduling: program k+1 goes out before program k is read
# ---------------------------------------------------------------------------

from conftest import AHEAD_CASES, ahead_parity_case     # noqa: E402


@pytest.mark.parametrize("case", sorted(AHEAD_CASES))
def test_launch_ahead_serves_the_synchronous_schedules_tokens(tiny, case):
    """`double_buffer=True` launches the next fused program before it reads
    the last one's tokens wherever the next batch is predictable; what is
    served is the synchronous schedule's, token for token, in every mode."""
    cfg, params = tiny

    eng, outs = ahead_parity_case(cfg, params, case, cfg.vocab_size)
    st = eng.stats()
    share = st["fused_launched_ahead"] / st["decode_iterations"]
    if case == "steady_full_batch":
        # nothing to admit, nothing to draft: every launch but the first
        assert share >= 0.9 and st["fused_ahead_discarded_lanes"] == 0
        assert all(o.finish_reason == "length" for o in outs)
    elif case in ("eos_mid_batch", "chunked_eos"):
        # a lane is dropped exactly where the host could not foresee the end:
        # an EOS a fused program produced, short of the request's budget
        # (a chunked prompt's FIRST token is a fused program's too)
        first = 1 if case == "chunked_eos" else 2
        stops = [o for o in outs if o.finish_reason == "stop"]
        assert stops and st["fused_ahead_discarded_lanes"] == sum(
            first <= len(o.token_ids) < b for o, b in zip(
                outs, (12, 15)) if o.finish_reason == "stop")
    elif case == "spec3":
        assert st["spec_accepted_tokens"] > 0
    elif case == "optimistic_forced_preemption":
        assert st["preemptions"] >= 1
    elif case == "deadline_in_flight":
        assert [o.finish_reason for o in outs].count("timeout") == 1
        assert st["fused_ahead_discarded_lanes"] == 1
    elif case == "abort_in_flight":
        assert outs[0].finish_reason == "abort" and outs[0].token_ids
    if case not in ("spec3",):
        assert st["fused_launched_ahead"] > 0
    ring = eng.step_trace()
    assert sum(r["ahead"] for r in ring) == st["fused_launched_ahead"]
    assert all(r["turnaround_ms"] == 0.0 for r in ring if r["ahead"])
