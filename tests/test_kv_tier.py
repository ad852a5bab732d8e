"""KV tiering (ISSUE 15): device -> host prefix spill, one-scatter session
restore, the rolling-hash partial-page index, the disk level, fault
degradation, and the unified host-pool accounting.

The load-bearing bars:
- byte-exact greedy parity for a session resumed from the host tier (and
  from a `spill_dir` disk tier) vs the undisturbed engine AND vs the full
  re-prefill (`kv_tier=False`) baseline;
- the eviction cascade device -> host -> disk -> drop keeps
  `check_invariants` green with zero leaked pages at every level;
- `FaultPlan.fail_d2h` degrades spill -> drop and `fail_h2d` degrades
  restore -> re-prefill, both parity-lossless;
- `host_pool_room` counts spilled prefix pages against the same ceiling as
  preemption swap parking, and `tier_make_room` reclaims tier room for live
  victims;
- the multi-turn bench: returning-session prefill drops >= 50% and TTFT p50
  improves vs --no-kv-tier on the same stream, byte-exact parity, zero new
  compiled programs (spill/restore reuse the <= 2 swap bucket).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference.cache import PagedKVCache
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.inference.faults import FaultPlan
from paddle_tpu.models import gpt as G


@pytest.fixture(scope="module")
def cfg():
    return G.gpt_tiny(64)


@pytest.fixture(scope="module")
def params(cfg):
    return G.init_params(cfg, jax.random.key(0))


def _engine(params, cfg, **kw):
    base = dict(num_slots=2, page_size=8, num_pages=9, max_model_len=64,
                prefill_chunk=16, seed=3, swap_pool_pages=64)
    base.update(kw)
    return LLMEngine(params, cfg, **base)


def _session_stream(eng, rng_seed=7, churn=6):
    """Turn 1 of a session, distinct-prompt churn that evicts its pages,
    then the returning turn (prompt + reply + fresh tokens).  Returns
    (outputs keyed oldest-first, returning-turn output)."""
    rng = np.random.RandomState(rng_seed)
    shared = rng.randint(0, eng.config.vocab_size, (20,)).astype(np.int32)
    outs = {}
    r1 = eng.add_request(shared, max_new_tokens=5)
    outs.update(eng.run())
    for _ in range(churn):
        eng.add_request(rng.randint(0, eng.config.vocab_size, (30,))
                        .astype(np.int32), max_new_tokens=4)
    outs.update(eng.run())
    t2 = np.concatenate([shared, np.asarray(outs[r1].token_ids, np.int32),
                         rng.randint(0, eng.config.vocab_size, (4,))
                         .astype(np.int32)])
    r2 = eng.add_request(t2, max_new_tokens=5)
    outs.update(eng.run())
    return outs, outs[r2]


# ---------------------------------------------------------------------------
# resumed-from-host parity + counters
# ---------------------------------------------------------------------------

def test_host_restore_parity_and_counters(params, cfg):
    """A returning session whose pages were LRU-evicted restores from the
    host tier with ONE scatter: tokens byte-identical to both the
    drop-on-evict baseline (full re-prefill) and a direct `generate`, with
    the spill/restore counters moving and zero page leaks."""
    eng = _engine(params, cfg)
    outs, ret = _session_stream(eng)
    base_eng = _engine(params, cfg, kv_tier=False)
    base_outs, base_ret = _session_stream(base_eng)
    for a, b in zip(sorted(outs), sorted(base_outs)):
        assert outs[a].token_ids == base_outs[b].token_ids
    ref = G.generate(params, jnp.asarray(ret.prompt)[None], cfg,
                     max_new_tokens=5)
    np.testing.assert_array_equal(ret.tokens, np.asarray(ref[0]))

    st, base_st = eng.stats(), base_eng.stats()
    assert st["kv_tier"]["enabled"] and not base_st["kv_tier"]["enabled"]
    assert st["kv_tier"]["spills"] > 0
    assert st["kv_tier"]["restores"] >= 1
    assert st["kv_tier"]["restored_tokens"] >= 16     # >= 2 full pages
    assert base_st["kv_tier"]["spills"] == 0
    # the restored tokens were NOT re-prefilled: the tier pass computes less
    assert st["prefilled_tokens"] < base_st["prefilled_tokens"]
    # spill/restore reuse the two swap executables — nothing new compiles
    assert st["swap_executables"] <= 2
    assert st["decode_executables"] + st["verify_executables"] == 1
    eng.cache.check_invariants()
    assert eng.cache.swapped_page_count == 0


def test_restore_from_spill_dir_parity(params, cfg, tmp_path):
    """With a tight host budget and `spill_dir`, over-budget tier content
    cascades to disk and restores from there transparently — same tokens as
    the re-prefill baseline."""
    eng = _engine(params, cfg, swap_pool_pages=6, spill_dir=str(tmp_path))
    outs, ret = _session_stream(eng)
    base_eng = _engine(params, cfg, kv_tier=False)
    base_outs, _ = _session_stream(base_eng)
    for a, b in zip(sorted(outs), sorted(base_outs)):
        assert outs[a].token_ids == base_outs[b].token_ids
    st = eng.stats()
    assert st["kv_tier"]["disk_spills"] > 0
    assert st["kv_tier"]["restores"] >= 1
    assert st["kv_tier"]["pages_host"] <= 6           # budget respected
    eng.cache.check_invariants()


def test_eviction_cascade_to_drop_no_leaks(params, cfg, tmp_path):
    """device -> host -> disk -> drop: with a capped disk level the oldest
    spilled prefixes fall off the end; every level's accounting stays exact
    under check_invariants and nothing leaks."""
    eng = _engine(params, cfg, swap_pool_pages=4, spill_dir=str(tmp_path),
                  spill_disk_pages=3)
    rng = np.random.RandomState(11)
    for _ in range(10):
        eng.add_request(rng.randint(0, cfg.vocab_size, (30,))
                        .astype(np.int32), max_new_tokens=4)
        eng.run()
        eng.cache.check_invariants()
    st = eng.stats()["kv_tier"]
    assert st["pages_host"] <= 4
    assert st["pages_disk"] <= 3
    assert st["disk_spills"] > 0 and st["tier_drops"] > 0
    # drop really deletes the files
    import os
    assert len(os.listdir(str(tmp_path))) == eng.cache.tier_pages_disk
    eng.cache.check_invariants()


def test_no_tier_when_disabled_or_unbudgeted(params, cfg):
    """kv_tier=False, prefix_cache=False, and swap_pool_pages=0 all disable
    tiering cleanly: evictions drop as in PR 10, stats say so."""
    for kw in (dict(kv_tier=False), dict(prefix_cache=False),
               dict(swap_pool_pages=0)):
        eng = _engine(params, cfg, **kw)
        assert not eng.kv_tier
        rng = np.random.RandomState(1)
        for _ in range(4):
            eng.add_request(rng.randint(0, cfg.vocab_size, (30,))
                            .astype(np.int32), max_new_tokens=3)
        eng.run()
        st = eng.stats()["kv_tier"]
        assert st["spills"] == 0 and st["pages_host"] == 0
        eng.cache.check_invariants()


# ---------------------------------------------------------------------------
# rolling-hash partial-page index
# ---------------------------------------------------------------------------

def test_rolling_hash_partial_tail_of_full_page():
    """A prompt sharing only a partial tail of a cached FULL page COW-copies
    the matched fraction — the case the PR-2 exact-content index could never
    hit (it only matched pages registered under exactly that partial
    content)."""
    mgr = PagedKVCache(num_pages=16, page_size=4, num_slots=4,
                       max_pages_per_slot=8)
    tok = np.arange(12, dtype=np.int32)             # 3 full pages
    mgr.allocate_prefixed(0, 12, tok)
    mgr.register_prefix(0, tok, 12)
    # new prompt: first page + HALF the second page, then diverges
    div = np.concatenate([tok[:6], np.asarray([77, 77, 77, 77], np.int32)])
    row, m, cow = mgr.allocate_prefixed(1, 12, div)
    assert m == 6                                   # 4 full + 2 partial
    assert cow is not None and cow[0] == mgr.slot_pages(0)[1]
    # divergent tail beyond the verified prefix does not match
    bad = np.concatenate([tok[:4], np.asarray([9, 9, 9], np.int32)])
    _, m2, cow2 = mgr.allocate_prefixed(2, 8, bad)
    assert m2 == 4 and cow2 is None
    mgr.check_invariants()


def test_rolling_hash_engine_parity(params, cfg):
    """Engine-level: a request sharing a partial tail of a cached page is
    token-identical to `generate` (the COW'd fraction is real KV), and the
    partial_page_hits counter moves."""
    eng = _engine(params, cfg)
    rng = np.random.RandomState(5)
    donor = rng.randint(0, cfg.vocab_size, (24,)).astype(np.int32)
    eng.add_request(donor, max_new_tokens=3)
    eng.run()
    # shares donor's first 12 tokens: page 1 full + half of page 2
    probe = np.concatenate([donor[:12],
                            rng.randint(0, cfg.vocab_size, (6,))
                            .astype(np.int32)])
    rid = eng.add_request(probe, max_new_tokens=5)
    outs = eng.run()
    ref = G.generate(params, jnp.asarray(probe)[None], cfg, max_new_tokens=5)
    np.testing.assert_array_equal(outs[rid].tokens, np.asarray(ref[0]))
    assert outs[rid].cached_tokens == 12
    assert eng.stats()["kv_tier"]["partial_page_hits"] >= 1
    eng.cache.check_invariants()


# ---------------------------------------------------------------------------
# fault degradation: spill -> drop, restore -> re-prefill
# ---------------------------------------------------------------------------

def test_fail_d2h_degrades_spill_to_drop(params, cfg):
    """Every spill d2h copy fails: nodes drop from the index (no restores
    ever), outputs identical to the no-tier baseline, nothing leaks."""
    eng = _engine(params, cfg, fault_plan=FaultPlan(fail_d2h=1000))
    outs, _ = _session_stream(eng)
    base_eng = _engine(params, cfg, kv_tier=False)
    base_outs, _ = _session_stream(base_eng)
    for a, b in zip(sorted(outs), sorted(base_outs)):
        assert outs[a].token_ids == base_outs[b].token_ids
    st = eng.stats()["kv_tier"]
    assert st["spills"] == 0 and st["restores"] == 0
    assert st["pages_host"] == 0 and st["pages_disk"] == 0
    eng.cache.check_invariants()


def test_fail_h2d_degrades_restore_to_reprefill(params, cfg):
    """Spills land, but every restore h2d fails: the matched nodes drop and
    the request re-prefills — same tokens, no partial restore ever visible,
    zero leaks."""
    eng = _engine(params, cfg, fault_plan=FaultPlan(fail_h2d=1000))
    outs, _ = _session_stream(eng)
    base_eng = _engine(params, cfg, kv_tier=False)
    base_outs, _ = _session_stream(base_eng)
    for a, b in zip(sorted(outs), sorted(base_outs)):
        assert outs[a].token_ids == base_outs[b].token_ids
    st = eng.stats()["kv_tier"]
    assert st["spills"] > 0
    assert st["restores"] == 0 and st["restored_tokens"] == 0
    eng.cache.check_invariants()
    assert eng.cache.swapped_page_count == 0


# ---------------------------------------------------------------------------
# unified host pool: room accounting + reclamation for live victims
# ---------------------------------------------------------------------------

def test_host_pool_room_counts_tier_pages(params, cfg):
    """Spilled prefix pages consume the SAME budget as preemption swap
    parking: host_pool_room reflects them, and tier_make_room reclaims
    (drops, with no disk level) room on demand."""
    eng = _engine(params, cfg, swap_pool_pages=8)
    rng = np.random.RandomState(2)
    for _ in range(5):
        eng.add_request(rng.randint(0, cfg.vocab_size, (30,))
                        .astype(np.int32), max_new_tokens=3)
        eng.run()
    mgr = eng.cache
    held = mgr.tier_pages_host
    assert held > 0
    assert mgr.host_pool_room(8) == 8 - held
    freed = mgr.tier_make_room(2)
    assert freed == 2
    assert mgr.host_pool_room(8) == 8 - held + 2
    mgr.check_invariants()


def test_preemption_swap_reclaims_tier_room(params, cfg):
    """preempt="swap" with the host pool full of spilled prefixes: the
    victim still parks — live work evicts cached prefixes from the unified
    pool instead of degrading to recompute."""
    prompts = [np.arange(i * 7, i * 7 + 20, dtype=np.int32) % cfg.vocab_size
               for i in range(6)]
    eng = _engine(params, cfg, num_slots=6, prefill_chunk=8,
                  admission="optimistic", preempt="swap", swap_pool_pages=8)
    for p in prompts:
        eng.add_request(p.astype(np.int32), max_new_tokens=24)
    eng.run()
    st = eng.stats()
    assert st["preemptions"] > 0
    assert st["preempt_swaps"] > 0      # parking never starved by the tier
    eng.cache.check_invariants()
    assert eng.cache.swapped_page_count == 0


# ---------------------------------------------------------------------------
# the multi-turn bench: the ISSUE-15 acceptance bar
# ---------------------------------------------------------------------------

def test_bench_multi_turn_tier_acceptance(params, cfg):
    """CPU-smoke --multi-turn: returning-session prefilled tokens drop
    >= 50% and returning TTFT p50 improves vs --no-kv-tier on the same
    stream, with byte-exact greedy parity and zero new compiled programs
    (decode-side 1, swap bucket <= 2) — and the current-schema trajectory
    row built from the run passes schema + floors."""
    from bench_serve import run_serve_bench
    from tools.check_bench import bench_row, check_floors, validate_row

    kw = dict(config=cfg, params=params, num_requests=12, num_slots=4,
              page_size=8, max_model_len=64, max_new_tokens=6,
              prefill_chunk=8, multi_turn=3, seed=0)
    tier = run_serve_bench(kv_tier=True, **kw)
    base = run_serve_bench(kv_tier=False, **kw)
    assert tier["outputs_digest"] == base["outputs_digest"]
    assert tier["resume_hits"] > 0 and tier["resume_restored_tokens"] > 0
    drop = 1.0 - tier["returning_prefilled_tokens"] / \
        max(base["returning_prefilled_tokens"], 1)
    assert drop >= 0.5, (tier["returning_prefilled_tokens"],
                         base["returning_prefilled_tokens"])
    assert tier["returning_ttft_p50_ms"] < base["returning_ttft_p50_ms"]
    assert tier["decode_executables"] + tier["verify_executables"] == 1
    assert tier["swap_executables"] <= 2

    stats = dict(tier)
    stats["kv_tier_parity"] = \
        tier["outputs_digest"] == base["outputs_digest"]
    stats["returning_prefilled_drop"] = round(drop, 4)
    row = bench_row(stats)
    assert row["schema_version"] == 5
    assert validate_row(row) == []
    assert check_floors(row) == []
    assert row["mode"]["kv_tier"] is True and row["mode"]["multi_turn"] == 3
    assert row["parity"]["kv_tier_parity"] is True


def test_check_bench_v1_rows_still_parse():
    """Old trajectory rows (schema v1) keep validating against the v1 axis
    sets; unknown versions fail loudly."""
    from tools.check_bench import (MODE_AXES_V1, PERF_KEYS_V1, validate_row)
    v1 = {"schema_version": 1, "t": 1.0,
          "mode": {k: None for k in MODE_AXES_V1},
          "perf": {k: None for k in PERF_KEYS_V1},
          "parity": {}}
    v1["perf"]["decode_tokens_per_sec_per_chip"] = 100.0
    assert validate_row(v1) == []
    v9 = dict(v1, schema_version=9)
    assert any("schema_version" in e for e in validate_row(v9))


# ---------------------------------------------------------------------------
# ISSUE 31: only the evicted pages leave, their copies run beside the steps,
# and the engine takes the bytes when they have landed
# ---------------------------------------------------------------------------



def _narrow_pieces(monkeypatch, params, cfg, pages=2, **kw):
    """An engine whose pieces are `pages` wide, as a real model's page bytes
    make them (the tiny model's whole slot would fit one piece)."""
    from conftest import narrow_d2h_pieces
    narrow_d2h_pieces(monkeypatch, cfg, pages)
    eng = _engine(params, cfg, **kw)
    assert eng._swap_w == pages < eng.cache.max_pages_per_slot
    return eng


def _steps(eng):
    while eng.has_work:
        eng.step()
        eng.cache.check_invariants()
    return dict(eng._outputs)


def _cached_pages(eng):
    """{node id: {lane: the pool's bytes of its page}} of every cached
    prefix page an eviction could take."""
    pool = jax.device_get(eng._pool)
    return {nid: {lane: a[:, node.page].copy() for lane, a in pool.items()}
            for nid, node in eng.cache._lru.items()}


@pytest.mark.parametrize("when", ["landed", "in_flight"])
def test_spilled_prefix_restores_byte_for_byte(params, cfg, monkeypatch,
                                               held_worker, when):
    """A prefix restored after its copy landed, and one restored while the
    copy is still in flight (the engine waits at the restore, nowhere
    else), hold the bytes the pool held and give the tokens of no tiering."""
    held = held_worker
    # a bound of 32 pages: the churn's evictions stay under it, so that
    # nothing but the restore can come for a piece in flight
    eng = held.watch(_narrow_pieces(monkeypatch, params, cfg,
                                    max_model_len=128))
    rng = np.random.RandomState(7)
    shared = rng.randint(0, cfg.vocab_size, (20,)).astype(np.int32)
    prompts = [shared] + [rng.randint(0, cfg.vocab_size, (30,))
                          .astype(np.int32) for _ in range(3)]
    r1 = eng.add_request(shared, max_new_tokens=5)
    outs = _steps(eng)
    before = _cached_pages(eng)
    for p in prompts[1:]:
        eng.add_request(p, max_new_tokens=4)
        outs.update(_steps(eng))
        before.update(_cached_pages(eng))
    # every copy is still held: nothing has landed, nothing was waited for
    assert eng._pending_d2h and held.waited_for == 0
    assert eng.stats()["swap_d2h_fetches"] == 0
    assert eng.stats()["swap_d2h_inflight_pages"] > 0
    if when == "landed":
        held.gate.set()
        held.settle(eng)
        eng.drain()
        assert not eng._pending_d2h and eng._d2h_inflight == 0
    tier = eng.cache._tier
    turn2 = np.concatenate([shared, np.asarray(outs[r1].token_ids, np.int32),
                            rng.randint(0, cfg.vocab_size, (4,))
                            .astype(np.int32)])
    r2 = eng.add_request(turn2, max_new_tokens=5)
    outs.update(_steps(eng))
    held.settle(eng)
    eng.drain()
    st = eng.stats()
    assert st["kv_tier"]["restores"] >= 1
    assert st["kv_tier"]["restored_tokens"] >= 16
    assert (held.waited_for > 0) == (when == "in_flight")
    assert st["swap_d2h_landed_free"] == \
        st["swap_d2h_fetches"] - held.waited_for
    assert st["swap_d2h_inflight_pages"] == 0
    # what sits in the host tier is what the pool held, byte for byte
    parked = [nid for nid in tier._host if nid in before]
    assert parked
    for nid in parked:
        for lane, a in tier._host[nid].items():
            np.testing.assert_array_equal(a, before[nid][lane])
    # and the tokens are those of an engine that never tiers
    base = _engine(params, cfg, kv_tier=False, max_model_len=128)
    for p in prompts:
        base.add_request(p, max_new_tokens=5 if p is shared else 4)
        base.run()
    rb = base.add_request(turn2, max_new_tokens=5)
    base_outs = base.run()
    assert outs[r2].token_ids == base_outs[rb].token_ids
    assert [outs[r].token_ids for r in sorted(outs)] == \
        [base_outs[r].token_ids for r in sorted(base_outs)]


@pytest.fixture(scope="module")
def narrow(params, cfg):
    """One warmed engine with 2-page pieces for the width cases."""
    mp = pytest.MonkeyPatch()
    eng = _narrow_pieces(mp, params, cfg, num_pages=17)
    mp.undo()
    eng.add_request(np.arange(60, dtype=np.int32) % cfg.vocab_size,
                    max_new_tokens=2)
    eng.run()
    eng.warm_swap()
    return eng


@pytest.mark.parametrize("n", range(1, 9))
def test_gather_moves_n_pages_rounded_up_to_a_piece(narrow, n):
    """For every page count a slot can hold: what crosses is at most n + W
    - 1 pages, what lands is the pool's content, and no program is added
    to the two `warm_swap()` compiled."""
    eng = narrow
    assert eng.cache.max_pages_per_slot == 8
    eng.reset_counters()
    execs = eng.stats()["swap_executables"]
    pages = list(range(1, n + 1))
    pieces = eng._gather_d2h(pages)
    assert len(pieces) == -(-n // eng._swap_w)
    got = [page for piece in pieces for page in eng._take_piece(piece)]
    st, pb = eng.stats(), eng._kv_page_bytes
    assert st["swap_d2h_useful_bytes"] == n * pb
    assert n * pb <= st["swap_d2h_bytes"] <= (n + eng._swap_w - 1) * pb
    assert st["swap_d2h_fetches"] == len(pieces)
    assert st["swap_executables"] == execs == 2
    assert eng._d2h_inflight == 0
    pool = jax.device_get(eng._pool)
    assert len(got) == n
    for page, data in zip(pages, got):
        for lane, a in data.items():
            np.testing.assert_array_equal(a, pool[lane][:, page])


def test_d2h_fault_with_copies_in_flight_drops_and_leaks_nothing(
        params, cfg, monkeypatch, held_worker):
    """Every spill's copy is in flight when its fault fires: the nodes drop
    from the index, the pieces are let go, outputs are those of no tiering."""
    held = held_worker
    eng = held.watch(_narrow_pieces(
        monkeypatch, params, cfg, fault_plan=FaultPlan(fail_d2h=1000)))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, (30,)).astype(np.int32)
               for _ in range(4)]
    for p in prompts:
        eng.add_request(p, max_new_tokens=4)
    outs = _steps(eng)
    assert eng._pending_d2h and eng._d2h_inflight > 0
    eng.drain()
    held.gate.set()
    st = eng.stats()
    assert not eng._pending_d2h and st["swap_d2h_inflight_pages"] == 0
    assert st["swap_d2h_fetches"] == 0 and held.waited_for == 0
    assert st["kv_tier"]["spills"] == 0 and st["kv_tier"]["pages_host"] == 0
    eng.cache.check_invariants()
    base = _engine(params, cfg, kv_tier=False)
    for p in prompts:
        base.add_request(p, max_new_tokens=4)
    base_outs = base.run()
    assert [outs[r].token_ids for r in sorted(outs)] == \
        [base_outs[r].token_ids for r in sorted(base_outs)]


def test_backpressure_holds_a_gather_at_two_slots_width(params, cfg,
                                                        monkeypatch,
                                                        held_worker):
    """Gathered pages in flight never pass two slots' width: the gather that
    would is held until the oldest piece has landed, and counted."""
    held = held_worker
    eng = held.watch(_narrow_pieces(monkeypatch, params, cfg))
    assert eng._d2h_bound == 2 * eng.cache.max_pages_per_slot == 16
    rng = np.random.RandomState(3)
    for _ in range(8):
        eng.add_request(rng.randint(0, cfg.vocab_size, (30,))
                        .astype(np.int32), max_new_tokens=4)
    peak = 0
    while eng.has_work:
        eng.step()
        peak = max(peak, eng._d2h_inflight)
        assert eng._d2h_inflight <= eng._d2h_bound
    st = eng.stats()
    assert st["swap_d2h_backpressure_waits"] >= 1
    assert held.waited_for >= st["swap_d2h_backpressure_waits"]
    assert st["swap_d2h_blocked_ms"] >= 0.0
    assert peak > 0
    eng.drain()
    assert eng.stats()["swap_d2h_inflight_pages"] == 0
    # nothing was lost to the bound: every accepted page landed
    assert eng.stats()["kv_tier"]["spills"] == eng.cache.tier_pages_host > 0
    eng.cache.check_invariants()


@pytest.mark.parametrize("seam", ["run", "drain", "stop_loop",
                                  "reset_counters", "export_prefix"])
def test_nothing_stays_in_flight_past(params, cfg, monkeypatch, held_worker,
                                      tmp_path, seam):
    """Where the engine rests or hands its pages on, every copy has landed
    in the tier: no record pending, no gathered page in flight."""
    held = held_worker
    eng = held.watch(_narrow_pieces(monkeypatch, params, cfg,
                                    max_model_len=128,
                                    spill_dir=str(tmp_path)))
    rng = np.random.RandomState(5)
    first = rng.randint(0, cfg.vocab_size, (30,)).astype(np.int32)
    eng.add_request(first, max_new_tokens=4)
    for _ in range(3):
        eng.add_request(rng.randint(0, cfg.vocab_size, (30,))
                        .astype(np.int32), max_new_tokens=4)
    _steps(eng)
    assert eng._pending_d2h and eng._d2h_inflight > 0
    if seam == "export_prefix":
        eng.export_prefix(first)
    else:
        getattr(eng, seam)()
    assert not eng._pending_d2h and eng._d2h_inflight == 0
    assert held.waited_for > 0
    tier = eng.cache._tier
    assert not any(tier.is_pending(nid) for nid in list(tier._host))
    if seam == "stop_loop":
        assert eng._d2h_worker is None
    if seam != "reset_counters":
        assert eng.stats()["kv_tier"]["spills"] > 0
    eng.cache.check_invariants()


# ---------------------------------------------------------------------------
# the gather itself (PR 38): a page is taken where it lies, a
# `dynamic_slice` each — every lane of every kind of pool, bit for bit
# ---------------------------------------------------------------------------

def _xing4_tiny():
    """The latent configuration of the benchmark's tiny Xing4.0 cell
    (`benchmarks/checks/tiny_xing4`: one latent lane, 128 wide, three
    layers of two mixers), as its driver derives it."""
    import json
    import pathlib

    from benchmarks.drivers import serve_xing4
    root = pathlib.Path(__file__).resolve().parents[1]
    conf = json.loads((root / "benchmarks/checks/tiny_xing4/configs/"
                       "xing4-tiny.json").read_text())
    return serve_xing4.program_config(serve_xing4.model_of(conf))


def _pool_of(kind, pages=11, page=8):
    """A pool of `kind` with seeded content in every lane, as numpy."""
    from paddle_tpu.models import hybrid
    if kind == "latent":
        pool = hybrid.init_paged_cache(_xing4_tiny(), pages, page, 2)
    else:
        pool = G.init_paged_cache(G.gpt_tiny(64), pages, page,
                                  kv_dtype="int8" if kind == "int8" else None)
    rng = np.random.default_rng(0)
    return {n: (rng.integers(-128, 128, a.shape).astype(a.dtype)
                if jnp.issubdtype(a.dtype, jnp.integer)
                else rng.standard_normal(a.shape).astype(a.dtype))
            for n, a in pool.items()}


_GATHER_IDS = {
    "null_padded": [3, 7, 1, 0, 0, 0, 0, 0],
    "repeats": [5, 5, 2, 5, 2, 2, 5, 5],
    "last_page": [10, 0, 10, 9, 1, 10, 0, 0],
}


@pytest.mark.parametrize("ids", list(_GATHER_IDS))
@pytest.mark.parametrize("kind", ["dense", "latent", "int8"])
def test_swap_out_pages_is_numpy_indexing_bit_for_bit(kind, ids):
    """One piece of the gather against `a[:, ids]` of the same pool on the
    host: a dense {"k","v"} pool, the one latent lane, an int8 pool with
    its float32 scale lanes; ids padded with the null page, repeated, and
    holding the pool's last page."""
    host = _pool_of(kind)
    assert set(host) == {"dense": {"k", "v"}, "latent": {"c"},
                         "int8": {"k", "v", "k_scale", "v_scale"}}[kind]
    page_ids = np.asarray(_GATHER_IDS[ids], np.int32)
    got = jax.jit(G.swap_out_pages)(
        {n: jnp.asarray(a) for n, a in host.items()}, jnp.asarray(page_ids))
    assert set(got) == set(host)
    for n, a in host.items():
        piece = np.asarray(got[n])
        assert piece.dtype == a.dtype
        assert piece.shape == (a.shape[0], page_ids.size) + a.shape[2:]
        np.testing.assert_array_equal(
            piece.view(np.uint8), a[:, page_ids].view(np.uint8))


def test_latent_pages_spilled_in_pieces_come_back_as_they_left(monkeypatch):
    """A spill -> restore round trip on the tiny Xing4.0 configuration with
    two-page pieces: what the host tier holds is what the pool held, and
    the pages the restore scatters into hold it again, bit for bit."""
    from paddle_tpu.inference import engine as E
    from paddle_tpu.models import hybrid
    cfg = _xing4_tiny()
    monkeypatch.setattr(E, "_D2H_PIECE_BYTES", 2 * cfg.page_bytes(8))
    eng = LLMEngine(hybrid.init_params(cfg, jax.random.key(1)), cfg,
                    num_slots=2, page_size=8, num_pages=9, max_model_len=64,
                    prefill_chunk=16, swap_pool_pages=64)
    assert eng.kv_tier and set(eng._pool) == {"c"}
    assert eng._swap_w == 2 < eng.cache.max_pages_per_slot
    rng = np.random.RandomState(7)
    shared = rng.randint(0, cfg.vocab_size, (20,)).astype(np.int32)
    r1 = eng.add_request(shared, max_new_tokens=5)
    outs = _steps(eng)
    left = _cached_pages(eng)
    for _ in range(6):
        eng.add_request(rng.randint(0, cfg.vocab_size, (30,))
                        .astype(np.int32), max_new_tokens=4)
    outs.update(_steps(eng))
    eng.drain()
    parked = {nid: d for nid, d in eng.cache._tier._host.items()
              if nid in left}
    assert parked and eng.stats()["swap_d2h_fetches"] > 0
    for nid, d in parked.items():
        np.testing.assert_array_equal(d["c"], left[nid]["c"])
    back = {}
    restore = eng._tier_restore

    def restore_and_look(slot, plan, rid):
        ok = restore(slot, plan, rid)
        lane = np.asarray(eng._pool["c"])
        back.update({node.node_id: lane[:, dst].copy()
                     for dst, node, _ in plan})
        return ok

    monkeypatch.setattr(eng, "_tier_restore", restore_and_look)
    eng.add_request(np.concatenate(
        [shared, np.asarray(outs[r1].token_ids, np.int32),
         rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)]),
        max_new_tokens=5)
    _steps(eng)
    assert eng.stats()["kv_tier"]["restores"] >= 1
    assert back and set(back) <= set(left)
    for nid, page in back.items():
        np.testing.assert_array_equal(page, left[nid]["c"])
