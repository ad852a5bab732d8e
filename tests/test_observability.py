"""Serving observability: metrics registry (Counter/Gauge/Histogram +
Prometheus/JSON export), request lifecycle latency tracking, and engine step
tracing (ref `python/paddle/profiler/profiler.py` + `fluid/platform/profiler/`
span tree / chrome export; Orca OSDI'22 + vLLM SOSP'23 serving metrics)."""
import json

import numpy as np
import pytest

import jax

from paddle_tpu.models import gpt as G
from paddle_tpu.inference.engine import ENGINE_SPANS, LLMEngine
from paddle_tpu.inference.faults import FaultPlan
from paddle_tpu.inference.metrics import (Counter, FleetMetrics, Gauge,
                                          Histogram, MetricsRegistry,
                                          log_buckets)
from paddle_tpu.inference.spec import NgramProposer
from paddle_tpu.inference.tracing import RequestTrace


# ---------------------------------------------------------------------------
# metrics primitives (pure host, no jax)
# ---------------------------------------------------------------------------

def test_log_buckets_geometric_cover():
    edges = log_buckets(0.001, 1.0, per_decade=3)
    assert edges[0] == pytest.approx(0.001)
    assert edges[-1] >= 1.0
    ratios = [b / a for a, b in zip(edges, edges[1:])]
    assert all(r == pytest.approx(10 ** (1 / 3)) for r in ratios)
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        log_buckets(1.0, 0.5)


def test_histogram_bucket_edges_le_semantics():
    """A value exactly on an edge lands in that edge's bucket (le semantics);
    past the last edge it lands in overflow but count/sum/max stay exact."""
    h = Histogram("x", buckets=[1.0, 2.0, 4.0, 8.0])
    for v in (1.0, 1.5, 2.0, 2.0001, 9.0):
        h.observe(v)
    assert h.counts == [1, 2, 1, 0]
    assert h.overflow == 1
    assert h.count == 5
    assert h.sum == pytest.approx(1.0 + 1.5 + 2.0 + 2.0001 + 9.0)
    assert h.min == 1.0 and h.max == 9.0


def test_histogram_percentile_interpolation_exact():
    """Percentiles interpolate linearly inside the covering bucket — checked
    against hand-computed values, clamped to the observed envelope."""
    h = Histogram("x", buckets=[1.0, 2.0, 4.0])
    for _ in range(5):
        h.observe(1.0)          # bucket (0, 1]
    for _ in range(5):
        h.observe(4.0)          # bucket (2, 4]
    # p50: rank 5 covered by the first bucket -> 0 + 1 * 5/5 = 1.0
    assert h.percentile(50) == pytest.approx(1.0)
    # p90: rank 9 -> second occupied bucket: 2 + (4-2) * (9-5)/5 = 3.6
    assert h.percentile(90) == pytest.approx(3.6)
    # p99: rank 9.9 -> 2 + 2 * 4.9/5 = 3.96
    assert h.percentile(99) == pytest.approx(3.96)
    assert h.percentile(0) == 1.0           # envelope, not bucket edge
    assert h.percentile(100) == 4.0
    assert h.percentile(50) <= h.percentile(90) <= h.percentile(99)
    with pytest.raises(ValueError):
        h.percentile(101)


def test_histogram_overflow_and_clamp():
    h = Histogram("x", buckets=[1.0, 2.0])
    h.observe(100.0)            # overflow bucket
    h.observe(1.5)
    assert h.percentile(99) == 100.0        # overflow reports observed max
    # a lone observation in a wide bucket must not interpolate below itself
    g = Histogram("y", buckets=[0.001, 100.0])
    g.observe(50.0)
    assert g.percentile(1) == 50.0
    assert g.percentile(99) == 50.0
    empty = Histogram("z", buckets=[1.0])
    assert empty.percentile(50) == 0.0 and empty.min == 0.0


def test_counter_monotone_and_registry_dedup():
    reg = MetricsRegistry(namespace="t")
    c = reg.counter("events")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    assert reg.counter("events") is c       # idempotent factory
    with pytest.raises(TypeError):
        reg.gauge("events")                 # name/type conflict
    g = reg.gauge("level", lambda: 7)
    assert g.value == 7
    with pytest.raises(ValueError):
        g.set(3.0)                          # callback gauges are read-only
    h = reg.histogram("lat", buckets=[1.0, 2.0])
    h.observe(1.5)
    reg.reset()
    assert c.value == 0 and h.count == 0
    assert g.value == 7                     # callback gauges read live state


def test_registry_clock_injection_and_snapshot_json():
    t = [41.5]
    reg = MetricsRegistry(clock=lambda: t[0])
    assert reg.now() == 41.5
    t[0] = 43.25
    assert reg.now() == 43.25
    reg.counter("c").inc(2)
    reg.histogram("h", buckets=[1.0]).observe(0.5)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["counters"]["c"] == 2
    assert snap["histograms"]["h"]["count"] == 1


def test_prometheus_exposition_parses():
    """The text exposition validates under the same checker CI runs
    (tools/check_metrics.py): well-formed lines, cumulative buckets ending
    at +Inf == _count, sum/count samples present."""
    from tools.check_metrics import check_exposition, parse_prometheus
    reg = MetricsRegistry(namespace="llm_engine")
    reg.counter("decode_tokens", "tokens").inc(7)
    reg.gauge("queued", lambda: 3, "depth")
    h = reg.histogram("ttft_seconds", buckets=[0.1, 1.0, 10.0], help="ttft")
    for v in (0.05, 0.5, 0.5, 20.0):
        h.observe(v)
    text = reg.to_prometheus()
    errors = []
    check_exposition(text, errors)
    assert not errors, errors
    samples = parse_prometheus(text)
    assert samples["llm_engine_decode_tokens_total"][0][1] == 7
    assert samples["llm_engine_queued"][0][1] == 3
    buckets = dict(samples["llm_engine_ttft_seconds_bucket"])
    assert buckets['{le="0.1"}'] == 1       # cumulative
    assert buckets['{le="1"}'] == 3
    assert buckets['{le="10"}'] == 3
    assert buckets['{le="+Inf"}'] == 4
    assert samples["llm_engine_ttft_seconds_count"][0][1] == 4


def test_ngram_proposer_telemetry():
    p = NgramProposer(max_ngram=2)
    ctx = np.array([5, 6, 7, 5, 6], np.int32)
    assert p.propose(ctx, 2) is not None    # trailing (5,6) recurs
    assert p.propose(np.arange(8, dtype=np.int32), 2) is None
    st = p.stats()
    assert st["propose_calls"] == 2 and st["propose_hits"] == 1
    assert st["tokens_proposed"] >= 1 and st["hit_rate"] == 0.5
    p.reset_stats()
    assert p.stats()["propose_calls"] == 0


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = G.gpt_tiny(64)
    return cfg, G.init_params(cfg, jax.random.key(0))


@pytest.fixture(scope="module")
def spec_eng(tiny):
    """Shared chunked + speculative engine with a pool small enough to force
    LRU eviction — counters only ever grow across the tests that share it."""
    cfg, params = tiny
    return LLMEngine(params, cfg, num_slots=2, page_size=8, num_pages=9,
                     max_model_len=64, prefill_chunk=16, spec_len=3, seed=3)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def test_request_lifecycle_fake_clock(tiny):
    """Deterministic lifecycle math through the injectable clock: queue time,
    TTFT, TPOT and e2e land exactly where the clock was set, in both the
    per-request record and the engine histograms."""
    cfg, params = tiny
    clk = FakeClock(10.0)
    # double_buffer=False: this test pins exact per-step stamp math, which
    # needs tokens observed in the step that dispatched them (the deferred-
    # harvest ordering has its own test in test_fused_step.py)
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    clock=clk, double_buffer=False)
    rid = eng.add_request(np.arange(5, dtype=np.int32), max_new_tokens=3)
    clk.t = 12.0
    # one step() = admit + bucketed prefill (first token) + a decode
    # iteration (second token), all stamped at t=12
    assert eng.step() == []
    clk.t = 15.5
    outs = eng.step()           # third token -> finish
    assert [o.request_id for o in outs] == [rid]
    m = outs[0].metrics
    assert m.t_enqueue == 10.0 and m.t_admit == 12.0
    assert m.queue_s == pytest.approx(2.0)
    assert m.ttft_s == pytest.approx(2.0) and outs[0].ttft_s == m.ttft_s
    assert m.t_first_token == 12.0 and m.t_finish == 15.5
    assert m.e2e_s == pytest.approx(5.5)
    assert m.tpot_s == pytest.approx((15.5 - 12.0) / 2)
    assert m.n_generated == 3
    lat = eng.stats()["latency"]
    assert lat["queue_s"]["count"] == 1
    assert lat["queue_s"]["sum"] == pytest.approx(2.0)
    assert lat["ttft_s"]["max"] == pytest.approx(2.0)
    assert lat["e2e_s"]["sum"] == pytest.approx(5.5)
    assert lat["tpot_s"]["mean"] == pytest.approx(1.75)


def test_lifecycle_covers_abort_and_prefix_hit(tiny):
    """The abort path closes the record (with its own counter, not the
    latency histograms); a prefix-hit admission carries cached_tokens into
    the record."""
    cfg, params = tiny
    clk = FakeClock(100.0)
    eng = LLMEngine(params, cfg, num_slots=1, page_size=8, num_pages=17,
                    max_model_len=64, prefill_chunk=8, clock=clk)
    prompt = (np.arange(20, dtype=np.int32) * 7 + 1) % cfg.vocab_size
    rid = eng.add_request(prompt, max_new_tokens=4)
    eng.run()
    # same prompt again: admission maps the cached prefix
    rid2 = eng.add_request(prompt, max_new_tokens=4)
    eng.step()
    out2 = eng.run()[rid2]
    assert out2.metrics.cached_tokens > 0
    assert out2.cached_tokens == out2.metrics.cached_tokens
    # queued abort: never admitted -> no admission stamp, reason recorded
    blocker = eng.add_request(prompt[:9], max_new_tokens=40)
    clk.t = 101.0
    waiting = eng.add_request(prompt[:5], max_new_tokens=4)
    eng.step()
    e2e_before = eng.stats()["latency"]["e2e_s"]["count"]
    clk.t = 103.0
    assert eng.abort(waiting)           # still queued: slot held by blocker
    assert eng.abort(blocker)           # running
    out = eng.run()[waiting]
    assert out.finish_reason == "abort"
    assert out.metrics.t_admit is None and out.metrics.queue_s is None
    assert out.metrics.e2e_s == pytest.approx(2.0)
    st = eng.stats()
    assert st["aborted_requests"] == 2
    assert st["latency"]["e2e_s"]["count"] == e2e_before  # aborts excluded


def test_counters_monotonic_across_abort_and_eviction(spec_eng):
    """No counter ever decreases while the engine churns through prefix
    hits, LRU eviction and a mid-flight abort; the page partition stays
    consistent afterwards."""
    eng = spec_eng
    rng = np.random.RandomState(5)
    shared = rng.randint(0, eng.config.vocab_size, (20,)).astype(np.int32)
    rids = []
    for i in range(8):
        if i % 3 == 0:
            tail = rng.randint(0, eng.config.vocab_size, (i,)).astype(np.int32)
            prompt = np.concatenate([shared, tail]) if i else shared.copy()
        else:
            prompt = rng.randint(0, eng.config.vocab_size,
                                 (int(rng.randint(4, 40)),)).astype(np.int32)
        rids.append(eng.add_request(prompt, max_new_tokens=6))
    prev = eng.metrics.snapshot()["counters"]
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
        if steps == 3:
            assert eng.abort(rids[-1])
        cur = eng.metrics.snapshot()["counters"]
        for k, v in cur.items():
            # lazily registered counters (per-priority goodput) appear
            # mid-run at 0 — appearing is fine, decreasing is not
            assert v >= prev.get(k, 0), \
                f"counter {k} decreased: {prev.get(k, 0)} -> {v}"
        prev = cur
    st = eng.stats()
    assert st["aborted_requests"] >= 1
    assert st["prefix_evictions"] >= 1          # pool pressure hit the LRU
    assert st["prefix_evictions"] == prev["prefix_evictions"]  # mirror synced
    assert st["spec_events"] > 0
    eng.cache.check_invariants()


def test_stats_spec_events_recompute_acceptance(spec_eng):
    """Satellite: spec_events is reported, so accepted_per_step is
    recomputable from the stats dict alone."""
    st = spec_eng.stats()
    assert st["spec_events"] > 0
    assert st["accepted_per_step"] == pytest.approx(
        st["spec_emitted_tokens"] / st["spec_events"])


def test_chrome_trace_and_step_timeline(spec_eng, tmp_path):
    """engine.trace(dir) exports a valid chrome trace holding the engine's
    host-phase span names, the step-timeline ring, and a metrics snapshot."""
    eng = spec_eng
    td = tmp_path / "trace"
    with eng.trace(str(td), device=False):
        rng = np.random.RandomState(9)
        for n in (5, 18, 30):
            eng.add_request(rng.randint(0, eng.config.vocab_size,
                                        (n,)).astype(np.int32),
                            max_new_tokens=4)
        eng.run()
    host = json.loads((td / "host_trace.json").read_text())
    names = {e["name"] for e in host["traceEvents"]}
    # acceptance is on the device, so the harvest's host loop is engine.emit
    assert {"engine.step", "engine.turnaround", "engine.emit", "engine.admit",
            "engine.batch.build", "engine.fused.dispatch", "engine.fused.h2d",
            "engine.spec.propose", "engine.sample.sync"} <= names
    assert names <= set(ENGINE_SPANS)
    for e in host["traceEvents"]:
        assert e["ph"] == "X" and e["dur"] >= 0
    timeline = json.loads((td / "step_timeline.json").read_text())
    assert timeline and timeline[-1]["step"] >= len(timeline)
    for key in ("decode_batch", "chunk", "verify_dispatches",
                "tokens_emitted", "pages_in_use", "pages_free",
                "pages_evictable", "queued", "running", "prefilling",
                "v", "dispatches", "sync_ms", "slots",
                "turnaround_ms", "d2h_ms", "pages_walked", "ahead"):
        assert key in timeline[-1]
    assert any(r["tokens_emitted"] > 0 for r in timeline)
    snap = json.loads((td / "metrics.json").read_text())
    assert snap["counters"]["decode_tokens"] > 0
    assert snap["proposer"]["propose_calls"] > 0
    # spans are recorded only inside a trace window
    n_before = len(eng.step_trace())
    eng.add_request(np.arange(4, dtype=np.int32), max_new_tokens=2)
    eng.run()
    assert len(eng.step_trace()) > n_before


def test_trace_rides_outer_profiler(spec_eng, tmp_path):
    """engine.trace() nested inside a user Profiler must not wipe the outer
    event buffer or stop the outer recording — it rides it and snapshots."""
    from paddle_tpu.profiler import Profiler, RecordEvent, is_recording
    from paddle_tpu.profiler import profiler as prof_mod
    eng = spec_eng
    with Profiler(timer_only=True):
        with RecordEvent("outer.before"):
            pass
        with eng.trace(str(tmp_path / "t"), device=False):
            eng.add_request(np.arange(4, dtype=np.int32), max_new_tokens=2)
            eng.run()
        assert is_recording()           # outer recording still live
        with RecordEvent("outer.after"):
            pass
        names = {e.name for e in prof_mod._events}
        assert {"outer.before", "engine.step", "outer.after"} <= names
    host = json.loads((tmp_path / "t" / "host_trace.json").read_text())
    snap_names = {e["name"] for e in host["traceEvents"]}
    assert "engine.step" in snap_names and "outer.before" in snap_names


def test_step_trace_ring_bounded(tiny, monkeypatch):
    cfg, params = tiny
    monkeypatch.setattr(LLMEngine, "TRACE_RING", 4)
    eng = LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=64)
    eng.add_request(np.arange(3, dtype=np.int32), max_new_tokens=10)
    eng.run()
    trace = eng.step_trace()
    assert len(trace) == 4                      # ring capped
    assert trace[-1]["step"] > 4                # but steps kept counting
    eng.reset_counters()
    assert eng.step_trace() == []
    assert eng.stats()["decode_tokens"] == 0


@pytest.mark.parametrize("mode", ["bucketed", "chunked", "bucketed_spec"])
def test_pages_walked_counts_what_the_programs_were_handed(tiny, mode):
    """`pages_walked` (ring) and `paged_pages_walked` / `paged_table_entries`
    (stats, /metrics) equal sum ceil((q_offset + valid) / page) over the
    non-null rows of every dispatch that holds the paged prefill kernel -
    recomputed here from the arrays the programs actually received - against
    rows x table width; by hand for the first step of the plain engine."""
    cfg, params = tiny
    kw = {"bucketed": {}, "chunked": {"prefill_chunk": 16},
          "bucketed_spec": {"spec_len": 3}}[mode]
    eng = LLMEngine(params, cfg, num_slots=3, page_size=8, max_model_len=64,
                    seed=1, **kw)
    seen = []

    def spy(fn, t_at, q_at, v_at):
        def call(*args, **kwargs):
            t, q, v = (np.asarray(args[i]) for i in (t_at, q_at, v_at))
            seen.append((int(np.sum(-(-(q + v) // 8), where=t[:, 0] != 0)),
                         t.size))
            return fn(*args, **kwargs)
        return call

    eng._decode_fn = spy(eng._decode_fn, 3, 4, 5)
    if eng._chunk_fn is not None:
        eng._chunk_fn = spy(eng._chunk_fn, 3, 4, 5)
    rng = np.random.RandomState(2)
    for n in (18, 5, 30):
        eng.add_request(rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32),
                        max_new_tokens=9)
    eng.run()
    st, ring = eng.stats(), eng.step_trace()
    assert seen and st["paged_pages_walked"] == sum(w for w, _ in seen)
    assert st["paged_table_entries"] == sum(e for _, e in seen)
    assert sum(r["pages_walked"] for r in ring) == st["paged_pages_walked"]
    assert 0 < st["paged_pages_walked"] < st["paged_table_entries"]
    if mode == "bucketed":
        # bucketed admission prefills through flash; the first fused step
        # decodes all three slots at q_offset = prompt length, valid = 1:
        # ceil(19/8) + ceil(6/8) + ceil(31/8) pages of 3 x 8 entries
        first = next(r for r in ring if r["pages_walked"])
        assert first["pages_walked"] == 3 + 1 + 4 and seen[0] == (8, 24)
    snap = eng.metrics.snapshot()["counters"]
    assert snap["paged_pages_walked"] == st["paged_pages_walked"]
    assert "paged_table_entries" in eng.metrics.to_prometheus()
    eng.reset_counters()
    assert eng.stats()["paged_pages_walked"] == 0


def test_stats_execs_fallback_attribute_error_only(spec_eng, monkeypatch):
    """Satellite: a missing _cache_size falls back to the tracked count, but
    a REAL failure inside _cache_size propagates instead of being silently
    absorbed into the fallback number."""
    class _NoSize:
        pass

    class _Boom:
        def _cache_size(self):
            raise RuntimeError("bug inside the executable cache")

    monkeypatch.setattr(spec_eng, "_decode_fn", _NoSize())
    st = spec_eng.stats()       # fallback path: tracked approximation
    assert st["decode_executables"] in (0, 1)
    monkeypatch.setattr(spec_eng, "_decode_fn", _Boom())
    with pytest.raises(RuntimeError, match="bug inside"):
        spec_eng.stats()


GOLDEN_STATS_KEYS = frozenset({
    # frozen pre-observability surface (PRs 1-4): benches and tests consume
    # these — removing or renaming any of them is an API break
    "decode_executables", "verify_executables", "prefill_executables",
    "copy_executables", "buckets", "prefill_chunk", "spec_len", "mp",
    "decode_iterations", "decode_tokens", "verify_steps",
    "spec_drafted_tokens", "spec_accepted_tokens", "spec_emitted_tokens",
    "spec_backoffs", "accepted_per_step", "prefill_chunks",
    "prefilled_tokens", "prefix_cached_tokens", "prefix_hit_requests",
    "prefix_hit_rate", "cow_page_copies", "pages_in_use", "pages_free",
    "pages_evictable", "prefix_evictions", "kv_token_capacity",
    "dense_token_footprint", "queued", "prefilling", "running",
})
NEW_STATS_KEYS = frozenset({
    # added by the observability PR
    "engine_steps", "spec_events", "finished_requests", "aborted_requests",
    "latency",
}) | frozenset({
    # added by the oversubscription PR (overload surface)
    "swap_executables", "admission", "preempt", "preemptions",
    "preempt_swaps", "preempt_recomputes", "swapped_pages", "swap_ms",
    "recomputed_tokens", "timeouts", "rejected_requests", "swapped",
    "kv_pages_swapped", "kv_pool_pressure",
}) | frozenset({
    # added by the quantized-serving PR (weight/kv int8 + intake admission)
    "weight_dtype", "kv_dtype", "kv_pool_bytes", "intake_swap_rejects",
}) | frozenset({
    # added by the observability-plane PR (SLO block: deadline attainment +
    # per-priority-class goodput — the router's SLO layer input)
    "slo",
}) | frozenset({
    # added by the health & signals PR: windowed rates, the folded health
    # state, and the steady-state recompile count
    "rates", "health", "steady_state_recompiles",
}) | frozenset({
    # added by the KV tiering PR: per-tier occupancy + spill/restore traffic
    # + the rolling-hash partial-index hit counter
    "kv_tier",
}) | frozenset({
    # added by the tracing PR (ISSUE 26): what crossed at the two swap
    # boundaries against what was wanted, and the host turnaround
    "swap_d2h_fetches", "swap_d2h_bytes", "swap_d2h_useful_bytes",
    "swap_h2d_bytes", "swap_h2d_useful_bytes", "turnaround_ms",
}) | frozenset({
    # added by the disaggregated-serving PR: the engine's fleet role
    # (None / "prefill" / "decode") so health and routing can label it
    "role",
}) | frozenset({
    # added by the hybrid PR (ISSUE 28): the expert layers' routing account
    # and the recurrent state lanes (all 0 for a dense configuration)
    "moe_pairs_here", "moe_pairs_away", "moe_experts_touched", "moe_load_max",
    "ssm_slots_live", "ssm_state_resets", "ssm_state_bytes",
    "ssm_state_pool_bytes", "prefix_lookups_skipped_no_state",
}) | frozenset({
    # added by the latent-pages PR (ISSUE 32): rows absorbed attention read,
    # its query tokens, and the latent lane's bytes a page (all 0 for a
    # configuration without latent attention)
    "latent_tokens_written", "mla_absorbed_rows", "latent_page_bytes",
}) | frozenset({
    # added by the sparse-attention PR (ISSUE 39): rows the indexer scored
    # and the sparse attention read, of all programs and of the fused steps,
    # and the index lane's bytes a page (all 0 without DSA)
    "dsa_rows_scored", "dsa_rows_selected", "dsa_step_rows_scored",
    "dsa_step_rows_selected", "index_page_bytes",
}) | frozenset({
    # the decode top-k by counting: query tokens whose selection needed the
    # search, of all programs and of the fused steps (both 0 without DSA)
    "dsa_rows_searched", "dsa_step_rows_searched",
}) | frozenset({
    # the long DSA pass expanded: query tokens whose sparse attention ran
    # with per-head keys and values (0 without DSA)
    "dsa_expanded_tokens",
}) | frozenset({
    # the long DSA pass's compact expert layer: held pairs it computed and
    # its chunks past the first (0 for fused steps and without DSA)
    "moe_pairs_compacted", "moe_compact_extra_chunks",
}) | frozenset({
    # added by the paged-walk PR (ISSUE 29): pages the paged kernel walks
    # against the table entries its programs were handed
    "paged_pages_walked", "paged_table_entries",
}) | frozenset({
    # added by the spill-fetch PR (ISSUE 31): the copies run beside the
    # engine thread — what it still waited for, what had landed, what is in
    # flight
    "swap_d2h_blocked_ms", "swap_d2h_landed_free",
    "swap_d2h_backpressure_waits", "swap_d2h_inflight_pages",
}) | frozenset({
    # added by the launch-ahead PR (ISSUE 33): fused launches made before
    # the previous result was read, and their lanes dropped at harvest
    "fused_launched_ahead", "fused_ahead_discarded_lanes",
}) | frozenset({
    # added by the admission-account PR (ISSUE 36): launches ahead that
    # found the program in flight finished, and the serial steps by reason
    "fused_ahead_late", "fused_serial_steps",
})


def test_stats_keyset_backcompat_golden(spec_eng):
    """Every pre-observability stats() key survives byte-for-byte, and the
    full key set is exactly golden + the documented additions — an
    accidental key (or a dropped one) fails here before a bench does."""
    keys = set(spec_eng.stats())
    assert GOLDEN_STATS_KEYS <= keys
    assert keys == GOLDEN_STATS_KEYS | NEW_STATS_KEYS
    lat = spec_eng.stats()["latency"]
    assert set(lat) == {"queue_s", "ttft_s", "tpot_s", "e2e_s", "step_s"}
    for summ in lat.values():
        assert set(summ) == {"count", "sum", "mean", "min", "max",
                             "p50", "p90", "p99"}


# ---------------------------------------------------------------------------
# per-request tracing: chrome export + exemplar round-trip (ISSUE 12)
# ---------------------------------------------------------------------------

def test_request_trace_chrome_export_phases():
    """Pure-host chrome rendering: lifecycle stamps become the root span +
    queued/prefill/decode phase children with exact (relative-us) geometry;
    every raw event rides along as an instant."""
    tr = RequestTrace(7)
    tr.event(1.0, "enqueue", prompt_len=4)
    tr.event(2.0, "admit", slot=0)
    tr.event(3.0, "first_token")
    tr.event(5.0, "finish", reason="stop", n_generated=2)
    tree = tr.to_chrome()
    json.dumps(tree)                            # serializable as-is
    evs = tree["traceEvents"]
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert spans["request/7"]["dur"] == pytest.approx(4e6)
    assert spans["queued"]["ts"] == 0.0
    assert spans["queued"]["dur"] == pytest.approx(1e6)
    assert spans["prefill"]["ts"] == pytest.approx(1e6)
    assert spans["prefill"]["dur"] == pytest.approx(1e6)
    assert spans["decode"]["ts"] == pytest.approx(2e6)
    assert spans["decode"]["dur"] == pytest.approx(2e6)
    instants = [e for e in evs if e["ph"] == "i"]
    assert len(instants) == len(tr.events)
    assert instants[0]["args"] == {"prompt_len": 4}
    assert all(e["tid"] == 7 for e in evs)      # one track per request
    # a phase never reached is absent: abort while queued has only "queued"
    tr2 = RequestTrace(8)
    tr2.event(1.0, "enqueue")
    tr2.event(2.0, "finish", reason="abort")
    names = {e["name"] for e in tr2.to_chrome()["traceEvents"]
             if e["ph"] == "X"}
    assert names == {"request/8", "queued"}
    # empty timeline renders a valid empty tree (never KeyErrors)
    assert RequestTrace(9).to_chrome() == {"traceEvents": [],
                                           "displayTimeUnit": "ms"}


def test_exemplar_roundtrip_exposition_to_request(tiny):
    """observe -> exposition -> parse -> rid: every exemplar in the live
    exposition carries the obs-server handle and resolves through
    export_request_trace to the request's own span tree."""
    from tools.check_metrics import check_exposition, parse_prometheus_full
    cfg, params = tiny
    clk = FakeClock(5.0)
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    clock=clk)
    rid = eng.add_request(np.arange(6, dtype=np.int32), max_new_tokens=3)
    clk.t = 6.0
    out = eng.run()[rid]
    names = [e["name"] for e in out.trace.events]
    assert names[0] == "enqueue" and names[-1] == "finish"
    assert "admit" in names and "first_token" in names
    text = eng.metrics.to_prometheus(exemplars=True)
    errs = []
    check_exposition(text, errs)
    assert not errs, errs
    _, exemplars = parse_prometheus_full(text)
    assert exemplars, "no exemplar in the exposition"
    for (name, _), (lbls, _v) in exemplars.items():
        assert name.endswith("_bucket")
        assert lbls["trace"] == f'/requests/{lbls["request_id"]}'
        tree = eng.export_request_trace(int(lbls["request_id"]))
        assert tree is not None and tree["traceEvents"]
    assert rid in {int(l["request_id"]) for l, _ in exemplars.values()}
    # the resolved tree is the chrome rendering of the same timeline
    tnames = {e["name"]
              for e in eng.export_request_trace(rid)["traceEvents"]}
    assert {f"request/{rid}", "queued", "prefill", "decode",
            "enqueue", "finish"} <= tnames
    # exemplars follow the dialect by default: the `# {...}` suffix is
    # OpenMetrics-only syntax, so a bare to_prometheus() is pure 0.0.4 a
    # stock parser can scrape, and explicit exemplars=False strips them
    # from any dialect
    assert " # {" not in eng.metrics.to_prometheus()
    assert " # {" in eng.metrics.to_prometheus(openmetrics=True)
    assert " # {" not in eng.metrics.to_prometheus(openmetrics=True,
                                                   exemplars=False)


def test_trace_retention_bounds_retired_timelines(tiny):
    """`trace_retention` caps how many RETIRED timelines the output ledger
    holds: past the cap the oldest retired trace drops (its RequestOutput
    keeps its tokens and metrics), newer ones keep resolving — the bound
    that keeps an always-on plane from growing host memory forever on a
    long-running server.  None retains everything."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    trace_retention=2)
    rids = [eng.add_request(np.arange(4 + i, dtype=np.int32),
                            max_new_tokens=2) for i in range(3)]
    outs = eng.run()
    # 3 retirements, cap 2: the oldest timeline dropped, the rest resolve
    assert eng.export_request_trace(rids[0]) is None
    assert eng.export_request_trace(rids[1])["traceEvents"]
    assert eng.export_request_trace(rids[2])["traceEvents"]
    # the evicted request's OUTPUT survives, tokens intact
    assert outs[rids[0]].finish_reason in ("stop", "length")
    assert outs[rids[0]].trace is None
    assert len(outs[rids[0]].token_ids) >= 1
    eng2 = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                     trace_retention=None)
    r2 = [eng2.add_request(np.arange(4, dtype=np.int32), max_new_tokens=2)
          for _ in range(3)]
    eng2.run()
    assert all(eng2.export_request_trace(x) is not None for x in r2)
    with pytest.raises(ValueError, match="trace_retention"):
        LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=64,
                  trace_retention=-1)


@pytest.mark.parametrize("mode", ["swap", "recompute"])
def test_timeline_exact_across_preempt_resume(tiny, mode):
    """Fake-clock exactness through a forced preempt/resume cycle, both
    eviction policies: a swap victim restores in place (swap_out -> swap_in,
    no re-admission), a recompute victim re-enters through a second
    admit(resume=True); stamps ride the engine clock monotonically and the
    survivor's timeline stays preemption-free."""
    cfg, params = tiny
    clk = FakeClock(100.0)
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    prefill_chunk=8, admission="optimistic", preempt=mode,
                    clock=clk, fault_plan=FaultPlan(pressure_steps=(4,)))
    lo = eng.add_request(np.arange(4, dtype=np.int32), max_new_tokens=20,
                         priority=0)
    hi = eng.add_request(np.arange(4, 6, dtype=np.int32), max_new_tokens=20,
                         priority=1)
    while eng.has_work:
        clk.t += 1.0
        eng.step()
    st = eng.stats()
    assert st["preemptions"] >= 1
    ev = eng._outputs[lo].trace.events
    names = [e["name"] for e in ev]
    assert names[0] == "enqueue" and ev[0]["t"] == 100.0
    assert names[-1] == "finish" and ev[-1]["reason"] == "length"
    assert ev[-1]["n_generated"] == len(eng._outputs[lo].token_ids)
    for key in ("grow_fail", "preempt", "first_token"):
        assert key in names, f"missing {key}: {names}"
    assert ev[names.index("preempt")]["kind"] == mode
    if mode == "swap":
        assert st["preempt_swaps"] >= 1
        assert "swap_out" in names and "swap_in" in names
        assert names.index("preempt") < names.index("swap_out") \
            < names.index("swap_in")
        assert "slot" in ev[names.index("swap_in")]
        assert names.count("admit") == 1    # in-place restore, no re-admit
    else:
        assert "swap_out" not in names and "swap_in" not in names
        assert names.count("admit") == 2    # first admission + replay
        admits = [e for e in ev if e["name"] == "admit"]
        assert admits[0]["resume"] is False and admits[1]["resume"] is True
        assert names.index("preempt") < names.index("admit", 1 +
                                                    names.index("admit"))
    ts = [e["t"] for e in ev]
    assert ts == sorted(ts)                 # engine clock is the only stamp
    # survivor: admitted once, never preempted
    hi_names = [e["name"] for e in eng._outputs[hi].trace.events]
    assert "preempt" not in hi_names and hi_names.count("admit") == 1
    # post-retirement resolution still works (trace rides the output)
    assert eng.export_request_trace(lo)["traceEvents"]


def test_timeline_and_slo_across_timeout(tiny):
    """Deadline expiry: the timeline closes with finish(reason=timeout)
    stamped at the expiry-scan clock; SLO accounting lands the miss in the
    attainment denominator while the latency histograms keep excluding it;
    goodput credits final tokens to the finisher's priority class only."""
    cfg, params = tiny
    clk = FakeClock(10.0)
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, num_pages=17,
                    max_model_len=64, clock=clk, double_buffer=False)
    ok = eng.add_request(np.arange(5, dtype=np.int32), max_new_tokens=3,
                         priority=1, deadline_s=1000.0)
    clk.t = 11.0
    eng.run()
    late = eng.add_request(np.arange(7, dtype=np.int32), max_new_tokens=50,
                           deadline_s=5.0)
    eng.step()                              # admitted, decoding
    e2e_before = eng.stats()["latency"]["e2e_s"]["count"]
    clk.t = 40.0                            # far past enqueue + 5s
    eng.step()
    out = eng._outputs[late]
    assert out.finish_reason == "timeout"
    fin = out.trace.events[-1]
    assert fin["name"] == "finish" and fin["reason"] == "timeout"
    assert fin["t"] == 40.0
    slo = eng.stats()["slo"]
    assert slo["deadline_requests"] == 2 and slo["deadline_met"] == 1
    assert slo["deadline_attainment"] == pytest.approx(0.5)
    assert slo["goodput_tokens_by_priority"] == {1: 3}
    # timeouts stay excluded from the latency SLO histograms
    assert eng.stats()["latency"]["e2e_s"]["count"] == e2e_before


def test_reset_counters_mid_trace_window(tiny, tmp_path):
    """The audited reset-vs-open-capture contract (engine.reset_counters
    docstring): a reset inside an engine.trace window neither corrupts the
    chrome export nor leaves a stale exemplar handle — cleared exemplars
    vanish from the exposition, and post-reset observations re-attach
    handles that resolve."""
    from tools.check_metrics import parse_prometheus_full
    cfg, params = tiny
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64)
    td = tmp_path / "trace"
    with eng.trace(str(td), device=False):
        eng.add_request(np.arange(5, dtype=np.int32), max_new_tokens=3)
        eng.run()
        _, exemplars = parse_prometheus_full(
            eng.metrics.to_prometheus(exemplars=True))
        assert exemplars                    # attached pre-reset
        eng.reset_counters()
        # exemplars cleared WITH the counts: no handle survives a reset
        _, exemplars = parse_prometheus_full(
            eng.metrics.to_prometheus(exemplars=True))
        assert not exemplars
        rid2 = eng.add_request(np.arange(7, dtype=np.int32),
                               max_new_tokens=3)
        eng.run()
    # the chrome export survived the mid-window reset
    host = json.loads((td / "host_trace.json").read_text())
    assert host["traceEvents"]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in host["traceEvents"])
    # step timeline holds only post-reset records (warmup-exclusion
    # semantics), and stays valid JSON
    timeline = json.loads((td / "step_timeline.json").read_text())
    assert timeline and all("step" in r for r in timeline)
    # post-reset exemplars point at post-reset requests only, and resolve
    _, exemplars = parse_prometheus_full(
        eng.metrics.to_prometheus(exemplars=True))
    rids = {int(l["request_id"]) for l, _ in exemplars.values()}
    assert rids == {rid2}
    assert eng.export_request_trace(rid2)["traceEvents"]


# ---------------------------------------------------------------------------
# fleet aggregation: merge math + labeled re-exposition (ISSUE 12)
# ---------------------------------------------------------------------------

def test_registry_merge_counter_histogram_goldens():
    """merge() vs hand-computed goldens: counter sum, gauge fold by its
    declared agg (sum for levels, MAX for ratio gauges — a sum of
    per-replica fractions would read >100% on a healthy fleet), histogram
    bucket-wise add with min/max/count/sum folded and last-merged exemplar
    per bucket; disjoint names union; empty merges are identities."""
    a = MetricsRegistry(namespace="m")
    b = MetricsRegistry(namespace="m")
    a.counter("c").inc(3)
    b.counter("c").inc(4)
    b.counter("only_b").inc(5)
    a.gauge("g").set(2.0)
    b.gauge("g").set(0.5)
    a.gauge("pressure", agg="max").set(0.3)
    b.gauge("pressure", agg="max").set(0.7)
    ha = a.histogram("h", buckets=[1.0, 2.0])
    hb = b.histogram("h", buckets=[1.0, 2.0])
    ha.observe(0.5, exemplar={"request_id": "1"})
    ha.observe(1.5)
    hb.observe(1.7, exemplar={"request_id": "9"})
    hb.observe(9.0)
    agg = MetricsRegistry(namespace="m").merge(a).merge(b)
    snap = agg.snapshot()
    assert snap["counters"] == {"c": 7, "only_b": 5}
    assert snap["gauges"]["g"] == pytest.approx(2.5)
    assert snap["gauges"]["pressure"] == pytest.approx(0.7)   # max, not 1.0
    with pytest.raises(ValueError, match="agg"):
        MetricsRegistry().gauge("bad", agg="mean")
    h = agg.get("h")
    assert h.counts == [1, 2] and h.overflow == 1
    assert h.count == 4 and h.sum == pytest.approx(0.5 + 1.5 + 1.7 + 9.0)
    assert h.min == 0.5 and h.max == 9.0
    assert h.exemplars[0] == ({"request_id": "1"}, 0.5)
    assert h.exemplars[1] == ({"request_id": "9"}, 1.7)   # last-merged wins
    assert h.exemplars[2] is None
    # empty-registry identities, both directions
    empty = MetricsRegistry(namespace="m")
    assert empty.merge(MetricsRegistry(namespace="m")).snapshot() == \
        MetricsRegistry(namespace="m").snapshot()
    assert MetricsRegistry(namespace="m").merge(a).snapshot() == a.snapshot()
    before = agg.snapshot()
    assert agg.merge(MetricsRegistry(namespace="m")).snapshot() == before


def test_exemplar_label_escape_roundtrip():
    """Label values survive exposition escaping byte-for-byte — including
    the adversarial cases for ordered .replace unescaping (a literal
    backslash before 'n', escaped quotes, real newlines)."""
    from tools.check_metrics import parse_prometheus_full
    tricky = 'back\\slash "quote" bs-n\\nreal\nnewline'
    reg = MetricsRegistry()
    reg.histogram("h", buckets=[1.0]).observe(0.5, exemplar={"v": tricky})
    _, exemplars = parse_prometheus_full(reg.to_prometheus(exemplars=True))
    (labels, value), = exemplars.values()
    assert labels == {"v": tricky}
    assert value == 0.5


def test_labelled_counters_are_one_family_and_merge_by_label():
    """A counter with a constant label set is a sibling of the others of its
    name: one family in the exposition (one HELP/TYPE, a sample a sibling,
    the labels beside a fleet's `engine` label), keyed `name{k="v"}` in the
    registry and the snapshot, summed label by label in a merge."""
    from tools.check_metrics import check_exposition
    regs = []
    for x, y in ((1, 2), (10, 20)):
        reg = MetricsRegistry("llm_engine")
        reg.counter("plain", "no labels").inc(5)
        reg.counter("fam", "by reason", labels={"reason": "x"}).inc(x)
        reg.counter("other").inc(1)         # siblings need not be adjacent
        reg.counter("fam", "by reason", labels={"reason": "y"}).inc(y)
        assert reg.counter("fam", labels={"reason": "x"}) is \
            reg.counter("fam", labels={"reason": "x"})
        assert reg.counter("fam", labels={"reason": "x"}) is not \
            reg.counter("fam", labels={"reason": "y"})
        regs.append(reg)
    a, b = regs
    assert a.snapshot()["counters"] == {
        "plain": 5, 'fam{reason="x"}': 1, "other": 1, 'fam{reason="y"}': 2}
    for openmetrics in (False, True):
        text = a.to_prometheus(openmetrics=openmetrics)
        errors = []
        check_exposition(text, errors)
        assert not errors
        fam = "llm_engine_fam" + ("" if openmetrics else "_total")
        assert text.count(f"# TYPE {fam} counter\n") == 1
        assert text.count(f"# HELP {fam} by reason\n") == 1
        assert f'# TYPE {fam} counter\n' \
            'llm_engine_fam_total{reason="x"} 1\n' \
            'llm_engine_fam_total{reason="y"} 2\n' in text
        assert "llm_engine_plain_total 5\n" in text
    merged = MetricsRegistry("llm_fleet").merge(a).merge(b)
    assert merged.snapshot()["counters"] == {
        "plain": 10, 'fam{reason="x"}': 11, "other": 2,
        'fam{reason="y"}': 22}
    fleet = FleetMetrics().add("e0", a).add("e1", b)
    text = fleet.to_prometheus()
    errors = []
    check_exposition(text, errors)
    assert not errors
    assert text.count("# TYPE llm_engine_fam_total counter\n") == 1
    for line in ('llm_engine_fam_total{engine="e0",reason="x"} 1',
                 'llm_engine_fam_total{engine="e1",reason="y"} 20',
                 'llm_fleet_fam_total{reason="x"} 11'):
        assert line + "\n" in text
    a.reset()
    assert not any(a.snapshot()["counters"].values())


def test_registry_merge_conflicts_raise():
    """Mismatched bucket edges, name/type conflicts and a callback gauge on
    the aggregate side all refuse loudly instead of merging garbage."""
    a = MetricsRegistry()
    a.histogram("h", buckets=[1.0, 2.0]).observe(0.5)
    bad_edges = MetricsRegistry()
    bad_edges.histogram("h", buckets=[1.0, 3.0])
    with pytest.raises(ValueError, match="bucket edges differ"):
        bad_edges.merge(a)
    bad_type = MetricsRegistry()
    bad_type.gauge("h").set(1.0)
    with pytest.raises(TypeError):
        bad_type.merge(a)
    live = MetricsRegistry()
    live.gauge("g", lambda: 7)              # callback gauge: read-only
    src = MetricsRegistry()
    src.gauge("g").set(1.0)
    with pytest.raises(ValueError):
        live.merge(src)
    # but a callback gauge on the SOURCE side merges by value
    agg = MetricsRegistry()
    agg.merge(live)
    assert agg.get("g").value == 7


def test_fleet_metrics_exposition_and_snapshot():
    """FleetMetrics over two registries (one disjoint metric): per-engine
    labeled series grouped per family, llm_fleet_* totals equal to the
    member sums, and the whole exposition passes the CI checker."""
    from tools.check_metrics import check_exposition, parse_prometheus
    r0 = MetricsRegistry(namespace="llm_engine")
    r1 = MetricsRegistry(namespace="llm_engine")
    r0.counter("decode_tokens").inc(10)
    r1.counter("decode_tokens").inc(32)
    r1.counter("only_e1").inc(2)
    r0.histogram("ttft_seconds", buckets=[0.1, 1.0]).observe(
        0.05, exemplar={"request_id": "3", "trace": "/requests/3"})
    r1.histogram("ttft_seconds", buckets=[0.1, 1.0]).observe(0.5)
    fleet = FleetMetrics().add("e0", r0).add("e1", r1)
    text = fleet.to_prometheus(exemplars=True)
    errs = []
    check_exposition(text, errs)
    assert not errs, errs
    samples = parse_prometheus(text)
    per = dict(samples["llm_engine_decode_tokens_total"])
    assert per == {'{engine="e0"}': 10, '{engine="e1"}': 32}
    assert samples["llm_fleet_decode_tokens_total"][0][1] == 42
    assert dict(samples["llm_engine_only_e1_total"]) == {'{engine="e1"}': 2}
    assert samples["llm_fleet_only_e1_total"][0][1] == 2
    assert samples["llm_fleet_ttft_seconds_count"][0][1] == 2
    # member exemplars survive the labeled re-exposition, with the trace
    # handle scoped to the member (request ids are per-engine counters)
    assert 'request_id="3"' in text
    assert 'trace="/requests/3?engine=e0"' in text
    # and the default fleet exposition follows the dialect: no exemplars
    assert " # {" not in fleet.to_prometheus()
    snap = fleet.snapshot()
    assert set(snap) == {"fleet", "engines"}
    assert set(snap["engines"]) == {"e0", "e1"}
    assert snap["fleet"]["counters"]["decode_tokens"] == 42
    assert snap["engines"]["e0"]["counters"]["decode_tokens"] == 10
    with pytest.raises(TypeError):
        FleetMetrics().add("x", object())


# ---------------------------------------------------------------------------
# HTTP observability plane + postmortem debug bundle (ISSUE 12)
# ---------------------------------------------------------------------------

def _http_get(url, accept=None):
    import urllib.error
    import urllib.request
    req = urllib.request.Request(
        url, headers={"Accept": accept} if accept else {})
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, r.read().decode("utf-8")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8")


def test_obs_server_endpoint_smoke(tiny):
    """All five routes over a real loopback socket on an ephemeral port:
    /metrics parses with exemplars, /stats carries the SLO block,
    /requests/<rid> serves the span tree (404 unknown, 400 malformed),
    /debug is a valid bundle, /healthz answers — and close() actually tears
    the daemon-thread listener down."""
    import urllib.error

    from paddle_tpu.inference.obs_server import ObservabilityServer
    from tools.check_metrics import (REQUIRED_DEBUG_BUNDLE_KEYS,
                                     check_exposition, parse_prometheus_full)
    cfg, params = tiny
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64)
    rid = eng.add_request(np.arange(6, dtype=np.int32), max_new_tokens=3)
    eng.run()
    with ObservabilityServer(eng) as srv:
        assert srv.port > 0 and srv.url.startswith("http://127.0.0.1:")
        # OpenMetrics negotiation: exemplars + # EOF on the wire
        code, text = _http_get(srv.url + "/metrics",
                               accept="application/openmetrics-text")
        assert code == 200 and text.endswith("# EOF\n")
        errs = []
        check_exposition(text, errs)
        assert not errs, errs
        assert parse_prometheus_full(text)[1]       # exemplars on the wire
        # plain scrape: 0.0.4 text, exemplar-free (stock Prometheus rejects
        # the suffix outside openmetrics mode)
        code, plain = _http_get(srv.url + "/metrics")
        assert code == 200 and " # {" not in plain
        assert "# EOF" not in plain
        errs = []
        check_exposition(plain, errs)
        assert not errs, errs
        code, text = _http_get(srv.url + "/stats")
        assert code == 200 and "slo" in json.loads(text)
        code, text = _http_get(srv.url + f"/requests/{rid}")
        assert code == 200 and json.loads(text)["traceEvents"]
        assert _http_get(srv.url + "/requests/424242")[0] == 404
        assert _http_get(srv.url + "/requests/nope")[0] == 400
        assert _http_get(srv.url + "/nosuch")[0] == 404
        code, text = _http_get(srv.url + "/healthz")
        health = json.loads(text)
        assert code == 200 and health["state"] in ("ok", "degraded")
        assert "signals" in health and "reasons" in health  # not the old stub
        # the 404 route list advertises exactly the served routes
        code, text = _http_get(srv.url + "/nosuch")
        assert code == 404
        assert set(json.loads(text)["routes"]) == {
            "/metrics", "/stats", "/requests/<rid>", "/debug", "/healthz"}
        code, text = _http_get(srv.url + "/debug")
        assert code == 200
        assert REQUIRED_DEBUG_BUNDLE_KEYS <= set(json.loads(text))
        url = srv.url
    with pytest.raises((ConnectionError, urllib.error.URLError)):
        _http_get(url + "/healthz")


def test_obs_server_fleet_mode(tiny):
    """Fleet mode: /metrics re-exposes members under engine labels plus
    llm_fleet totals, /stats and /debug key by member label, and
    /requests/<rid> disambiguates colliding per-engine request ids —
    ?engine= (what fleet exemplar handles carry) scopes the lookup, a bare
    colliding rid gets 300 with the candidate handles instead of an
    arbitrary member's timeline.  Constructor rejects ambiguous
    engine+fleet wiring."""
    from paddle_tpu.inference.obs_server import ObservabilityServer
    cfg, params = tiny
    e0 = LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=64)
    e1 = LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=64)
    # SAME rid on both members: per-engine counters both start at 0
    rid0 = e0.add_request(np.arange(7, dtype=np.int32), max_new_tokens=2)
    e0.run()
    rid = e1.add_request(np.arange(5, dtype=np.int32), max_new_tokens=2)
    e1.run()
    assert rid0 == rid
    fleet = FleetMetrics().add("e0", e0).add("e1", e1)
    with ObservabilityServer(fleet=fleet) as srv:
        code, text = _http_get(srv.url + "/metrics",
                               accept="application/openmetrics-text")
        assert code == 200
        assert 'engine="e0"' in text and 'engine="e1"' in text
        assert "llm_fleet_" in text
        # fleet exemplar handles are member-scoped, and resolve as served
        assert f'trace="/requests/{rid}?engine=e1"' in text
        def enqueue_prompt_len(tree):
            enq = [e for e in tree["traceEvents"] if e["name"] == "enqueue"]
            return enq[0]["args"]["prompt_len"]

        code, text = _http_get(srv.url + f"/requests/{rid}?engine=e1")
        assert code == 200 and enqueue_prompt_len(json.loads(text)) == 5
        code, text = _http_get(srv.url + f"/requests/{rid}?engine=e0")
        assert code == 200 and enqueue_prompt_len(json.loads(text)) == 7
        # a bare colliding rid is ambiguous: candidates, not a silent guess
        code, text = _http_get(srv.url + f"/requests/{rid}")
        assert code == 300
        body = json.loads(text)
        assert body["engines"] == ["e0", "e1"]
        assert f"/requests/{rid}?engine=e1" in body["handles"]
        assert _http_get(srv.url + f"/requests/{rid}?engine=nosuch")[0] == 404
        code, text = _http_get(srv.url + "/stats")
        st = json.loads(text)
        assert code == 200 and set(st) == {"e0", "e1"}
        assert st["e1"]["finished_requests"] == 1
        code, text = _http_get(srv.url + "/debug")
        assert code == 200 and set(json.loads(text)) == {"e0", "e1"}
    with pytest.raises(ValueError):
        ObservabilityServer(e0, fleet=fleet)
    with pytest.raises(ValueError):
        ObservabilityServer()


def test_debug_bundle_valid_after_forced_fault_crash(tiny, tmp_path):
    """A crash postmortem at the engine API: a hard (non-
    degradable) fault escapes step() mid-flight with rich scheduler state,
    and dump_debug_bundle still writes a valid, schema-complete JSON
    postmortem — request states with timelines, step ring, pool levels."""
    from tools.check_metrics import REQUIRED_DEBUG_BUNDLE_KEYS
    cfg, params = tiny

    class _HardFault(FaultPlan):
        # a non-FaultInjected error cannot be degraded to recompute: it
        # escapes the engine exactly like a real d2h wreck would
        def d2h(self):
            raise RuntimeError("hard d2h crash")

    eng = LLMEngine(params, cfg, num_slots=6, page_size=8, num_pages=9,
                    max_model_len=64, prefill_chunk=8,
                    admission="optimistic", preempt="swap",
                    fault_plan=_HardFault(pressure_steps=(3,)))
    rng = np.random.RandomState(7)
    for n in (5, 9, 14, 20, 6, 11):
        eng.add_request(rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32),
                        max_new_tokens=24)
    with pytest.raises(RuntimeError, match="hard d2h crash"):
        while eng.has_work:
            eng.step()
    path = eng.dump_debug_bundle(str(tmp_path / "bundle"))
    with open(path) as f:
        bundle = json.load(f)
    assert REQUIRED_DEBUG_BUNDLE_KEYS <= set(bundle)
    reqs = bundle["requests"]
    assert reqs, "no request states in the postmortem"
    states = {r["state"] for r in reqs.values()}
    assert states <= {"queued", "prefilling", "running", "finished"}
    assert any(r["events"] for r in reqs.values())
    assert bundle["step_trace"] and isinstance(bundle["step_trace"], list)
    assert isinstance(bundle["pool"]["pages_in_use"], int)
    assert "slo" in bundle["stats"]
    assert bundle["metrics"]["counters"]["preemptions"] >= 1


# ---------------------------------------------------------------------------
# health & perf signal plane (ISSUE 13): windowed rates, burn-rate health,
# steady-state recompiles
# ---------------------------------------------------------------------------

def test_rate_window_golden_values():
    """RateWindow math is exact under an injectable clock: empty ring,
    single sample, live right-edge reads, young-ring oldest-sample
    reference, in-window reference selection, and idle decay to 0.0."""
    from paddle_tpu.inference.metrics import RateWindow
    t = [0.0]
    v = [0]
    rw = RateWindow("r", lambda: v[0], lambda: t[0],
                    (("10s", 10.0), ("1m", 60.0)), min_interval_s=0.0)
    assert rw.rate(10.0) == 0.0                 # empty ring: no reference
    rw.sample()                                 # (0, 0)
    assert rw.rate(10.0) == 0.0                 # single sample, zero elapsed
    t[0], v[0] = 5.0, 50
    # live read against the ring: (50 - 0) / (5 - 0) — no sample needed
    assert rw.rate(10.0) == pytest.approx(10.0)
    rw.sample()                                 # (5, 50)
    t[0], v[0] = 8.0, 80
    # ring younger than the window: the OLDEST sample is the reference
    assert rw.rate(10.0) == pytest.approx(10.0)     # 80 / 8
    assert rw.delta(10.0) == pytest.approx(80.0)
    rw.sample()                                 # (8, 80)
    t[0] = 16.0
    # newest sample at or before now-10 = (5, 50): (80-50)/(16-5)
    assert rw.rate(10.0) == pytest.approx(30.0 / 11.0)
    assert rw.delta(10.0) == pytest.approx(30.0)
    # the 1m window still spans everything: 80 events over 16 s
    assert rw.rate(60.0) == pytest.approx(5.0)
    # idle decay: the counter stopped, so every window reads exactly 0.0
    # with no further samples
    t[0] = 100.0
    assert rw.rate(10.0) == 0.0
    assert rw.rate(60.0) == 0.0
    assert rw.rates() == {"10s": 0.0, "1m": 0.0}
    with pytest.raises(ValueError):
        RateWindow("bad", lambda: 0, lambda: 0.0, (("w", -1.0),))


def test_rate_window_reset_and_pruning():
    """A counter observed DECREASING (reset underneath the ring) restarts
    the window instead of reporting a negative rate; pruning keeps exactly
    one reference sample beyond the horizon; registry reset clears rings."""
    from paddle_tpu.inference.metrics import MetricsRegistry, RateWindow
    t = [0.0]
    v = [0]
    rw = RateWindow("r", lambda: v[0], lambda: t[0], (("10s", 10.0),),
                    min_interval_s=0.0)
    rw.sample()
    t[0], v[0] = 5.0, 50
    rw.sample()
    v[0] = 3                                    # counter reset mid-window
    assert rw.rate(10.0) == 0.0                 # never negative
    assert not rw._samples                      # ring restarted
    rw.sample()                                 # (5, 3): fresh baseline
    t[0], v[0] = 7.0, 13
    assert rw.rate(10.0) == pytest.approx(5.0)  # (13-3)/2 post-reset only
    # sample() detects the reset too (no rate() call needed)
    v[0] = 0
    rw.sample()
    assert list(rw._samples) == [(7.0, 0.0)]
    # pruning: samples past the horizon drop, keeping the newest one at or
    # beyond it as the exact reference for the largest window
    for i in range(1, 8):
        t[0], v[0] = 7.0 + 2.0 * i, 10 * i
        rw.sample()
    assert all(tt > t[0] - 10.0 for tt, _ in list(rw._samples)[1:])
    assert rw._samples[0][0] <= t[0] - 10.0     # the kept reference
    # forced samples anchor eventful bursts WITHOUT growing the ring:
    # inside the throttle interval they slide the newest entry forward
    # (when it is itself within the interval of its predecessor)
    rw3 = RateWindow("f", lambda: v[0], lambda: t[0], (("10s", 10.0),),
                     min_interval_s=1.0)
    t[0], v[0] = 100.0, 0
    rw3.sample()
    t[0], v[0] = 100.2, 2
    rw3.sample(force=True)              # appended (lone predecessor)
    t[0], v[0] = 100.4, 4
    rw3.sample(force=True)              # slides the 100.2 anchor
    t[0], v[0] = 100.6, 6
    rw3.sample(force=True)              # slides again: ring stays at 2
    assert list(rw3._samples) == [(100.0, 0.0), (100.6, 6.0)]
    t[0] = 100.8
    rw3.sample()                        # unforced inside the interval: no-op
    assert list(rw3._samples) == [(100.0, 0.0), (100.6, 6.0)]
    # the anchor is exact: once the window passes the burst, rate reads 0
    t[0] = 200.0
    assert rw3.rate(10.0) == 0.0
    # registry wiring: per-window pull gauges + reset clears the ring
    reg = MetricsRegistry(clock=lambda: t[0])
    c = reg.counter("events")
    rw2 = reg.rate_window("events_per_sec", lambda: c.value,
                          (("10s", 10.0),), min_interval_s=0.0)
    assert reg.rate_window("events_per_sec", lambda: -1) is rw2  # idempotent
    t0 = t[0]
    reg.sample_rates()
    c.inc(40)
    t[0] = t0 + 4.0
    assert reg.snapshot()["gauges"]["events_per_sec_10s"] == \
        pytest.approx(10.0)
    assert "events_per_sec_10s" in reg.to_prometheus()
    reg.reset()
    assert not rw2._samples and c.value == 0


def test_engine_rates_exact_under_fake_clock(tiny):
    """stats()['rates'] golden values through the engine: the reset-time
    seed sample makes a young window read exactly events-since-reset over
    elapsed-since-reset; idle decay and the reset_counters contract hold."""
    cfg, params = tiny
    clk = FakeClock(50.0)
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    clock=clk, double_buffer=False)
    eng.add_request(np.arange(5, dtype=np.int32), max_new_tokens=4)
    while eng.has_work:
        clk.t += 1.0
        eng.step()
    st = eng.stats()
    elapsed = clk.t - 50.0
    tokens = st["decode_tokens"]            # decode-emitted (first token is
    assert tokens >= 3                      # prefill's, not counted here)
    for w in ("10s", "1m", "5m"):
        # span < every window: the seed sample at t=50 is the reference
        assert st["rates"]["tokens_per_sec"][w] == \
            pytest.approx(tokens / elapsed)
    assert st["rates"]["admits_per_sec"]["5m"] == pytest.approx(1 / elapsed)
    assert st["rates"]["preemptions_per_sec"]["10s"] == 0.0
    # the same numbers ride the exposition as pull gauges
    snap = eng.metrics.snapshot()["gauges"]
    assert snap["tokens_per_sec_10s"] == pytest.approx(tokens / elapsed)
    # idle decay: the engine stops, rates fall to exactly 0.0 untouched
    clk.t += 400.0
    assert eng.stats()["rates"]["tokens_per_sec"]["5m"] == 0.0
    # reset mid-life: rings restart with the counters (the PR-12 reset
    # contract extended) — post-reset rates count post-reset events only
    eng.reset_counters()
    t_reset = clk.t
    eng.add_request(np.arange(3, dtype=np.int32), max_new_tokens=2)
    while eng.has_work:
        clk.t += 2.0
        eng.step()
    st = eng.stats()
    assert st["decode_tokens"] >= 1
    assert st["rates"]["tokens_per_sec"]["5m"] == \
        pytest.approx(st["decode_tokens"] / (clk.t - t_reset))


def test_slo_burn_rates_and_health_under_clock_skew(tiny):
    """Burn-rate edges under the fake clock: no deadline traffic burns 0
    (ok); on-time finishes burn 0; FaultPlan clock skew forcing timeouts
    sends the fast burn over the overload threshold with the slow window
    confirming — engine_health goes overloaded with slo_burn named in the
    reasons — and the window aging out recovers it to ok."""
    cfg, params = tiny
    clk = FakeClock(10.0)
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, num_pages=17,
                    max_model_len=64, clock=clk, double_buffer=False)
    h = eng.health()
    assert h["state"] == "ok" and h["burn_rates"]["1m"] == 0.0
    ok = eng.add_request(np.arange(5, dtype=np.int32), max_new_tokens=3,
                         deadline_s=1000.0)
    clk.t = 11.0
    eng.run()
    assert eng._outputs[ok].finish_reason in ("stop", "length")
    h = eng.health()
    assert h["state"] == "ok"
    assert h["burn_rates"]["1m"] == 0.0         # met on time: nothing burns
    assert eng.stats()["health"]["state"] == "ok"
    # injected clock skew: deadline evaluation sees now + 10000 s, so the
    # request times out on its first step — a 100% in-window miss rate over
    # the 1% error budget = burn 100 on both windows
    eng2 = LLMEngine(params, cfg, num_slots=2, page_size=8, num_pages=17,
                     max_model_len=64, clock=clk, double_buffer=False,
                     fault_plan=FaultPlan(skew_s=10_000.0))
    late = eng2.add_request(np.arange(5, dtype=np.int32), max_new_tokens=3,
                            deadline_s=5.0)
    clk.t += 1.0
    eng2.step()
    assert eng2._outputs[late].finish_reason == "timeout"
    clk.t += 1.0
    eng2.step()                                 # sample the rings post-miss
    h = eng2.health()
    assert h["burn_rates"]["1m"] == pytest.approx(100.0)
    assert h["burn_rates"]["5m"] == pytest.approx(100.0)
    assert h["state"] == "overloaded"
    assert h["signals"]["slo_burn"]["state"] == "overloaded"
    assert any(r.startswith("slo_burn") for r in h["reasons"])
    assert eng2._health_code() == 2.0
    # timeouts are also admission saturation: the signal fires on its own
    assert h["signals"]["admission"]["state"] != "ok"
    # recovery: the miss ages past every window — burn and rates decay to
    # exactly 0 and health folds back to ok without any reset
    clk.t += 400.0
    h = eng2.health()
    assert h["burn_rates"] == {"10s": 0.0, "1m": 0.0, "5m": 0.0}
    assert h["state"] == "ok" and h["reasons"] == []


def test_healthz_503_roundtrip_forced_pressure(tiny):
    """Acceptance bar: over a real socket, FaultPlan-forced pool pressure
    drives /healthz to 503 with a structured reason, the fleet rollup is
    worst-of, and the window aging out (fake clock) recovers it to 200 —
    deterministically."""
    from paddle_tpu.inference.obs_server import ObservabilityServer
    cfg, params = tiny
    clk = FakeClock(0.0)
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                    prefill_chunk=8, admission="optimistic",
                    preempt="recompute", clock=clk, double_buffer=False,
                    fault_plan=FaultPlan(pressure_steps=(2, 3, 4, 5, 6, 7)))
    eng.add_request(np.arange(4, dtype=np.int32), max_new_tokens=20,
                    priority=0)
    eng.add_request(np.arange(4, 6, dtype=np.int32), max_new_tokens=20,
                    priority=1)
    steps = 0
    while eng.has_work and eng.stats()["preemptions"] < 3 and steps < 100:
        clk.t += 0.1
        eng.step()
        steps += 1
    st = eng.stats()
    assert st["preemptions"] >= 3
    # >= 3 preemptions inside ~a second of engine time: far over the 1/s
    # overload threshold on the 10s window
    assert st["rates"]["preemptions_per_sec"]["10s"] >= 1.0
    healthy = LLMEngine(params, cfg, num_slots=1, page_size=8,
                        max_model_len=64, clock=clk)
    fleet = FleetMetrics().add("sick", eng).add("fine", healthy)
    with ObservabilityServer(eng) as srv, \
            ObservabilityServer(fleet=fleet) as fsrv:
        code, text = _http_get(srv.url + "/healthz")
        body = json.loads(text)
        assert code == 503
        assert body["state"] == "overloaded"
        assert body["signals"]["preemption"]["state"] == "overloaded"
        assert any(r.startswith("preemption") for r in body["reasons"])
        # fleet mode: worst-of rollup + per-engine detail
        code, text = _http_get(fsrv.url + "/healthz")
        fb = json.loads(text)
        assert code == 503 and fb["state"] == "overloaded"
        assert fb["engines"]["sick"]["state"] == "overloaded"
        assert fb["engines"]["fine"]["state"] == "ok"
        # recovery: the preemption burst ages past the window — 200/ok
        # again with zero resets, on both surfaces
        clk.t += 400.0
        code, text = _http_get(srv.url + "/healthz")
        assert code == 200 and json.loads(text)["state"] == "ok"
        code, text = _http_get(fsrv.url + "/healthz")
        fb = json.loads(text)
        assert code == 200 and fb["state"] == "ok"
        # a wedged engine (health evaluation raises) is 503, never 200 —
        # the bug the hardcoded {"ok": true} stub had
        eng._rw_preemptions = None              # wreck it
        code, text = _http_get(srv.url + "/healthz")
        body = json.loads(text)
        assert code == 503 and body["state"] == "error"
        assert "health evaluation failed" in body["reasons"][0]
        # error payloads keep the report shape probes read (code/signals)
        assert body["code"] == 3 and body["signals"] == {}
        # the postmortem surfaces survive the wrecked signal plane too:
        # stats() degrades to an error health entry instead of raising,
        # so the debug bundle (which embeds it) still assembles
        st_err = eng.stats()
        assert st_err["health"]["state"] == "error"
        assert "health evaluation failed" in st_err["health"]["reasons"][0]
        assert "requests" in eng.debug_bundle()
    # drain what's left so the fixture engines don't leak state
    eng._rw_preemptions = eng.metrics._rate_windows["preemptions_per_sec"]
    while eng.has_work:
        clk.t += 0.1
        eng.step()


def test_health_gauge_fleet_merge_worst_of():
    """The engine_health gauge declares agg='max': a fleet with a degraded
    (1) and an overloaded (2) member reads 2 — worst-of, not the
    nonsensical sum 3."""
    from paddle_tpu.inference.metrics import FleetMetrics, MetricsRegistry
    a, b = MetricsRegistry(namespace="llm_engine"), \
        MetricsRegistry(namespace="llm_engine")
    a.gauge("engine_health", agg="max").set(1.0)
    b.gauge("engine_health", agg="max").set(2.0)
    fleet = FleetMetrics().add("e0", a).add("e1", b)
    assert fleet.merged().get("engine_health").value == 2.0
    snap = fleet.snapshot()
    assert snap["fleet"]["gauges"]["engine_health"] == 2.0
    assert snap["engines"]["e0"]["gauges"]["engine_health"] == 1.0


def test_steady_state_recompile_anomaly(tiny, monkeypatch):
    """The steady-state recompile counter stays 0 over a real run (fixed
    shapes), moves exactly on executable-count growth after the baseline
    step and degrades health; reset_counters re-seeds it."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64)
    eng.warm_decode()
    eng.add_request(np.arange(6, dtype=np.int32), max_new_tokens=4)
    eng.run()
    assert eng.stats()["steady_state_recompiles"] == 0  # fixed shapes: never

    class _Growing:
        n = 1

        def _cache_size(self):
            return self.n

    fake = _Growing()
    monkeypatch.setattr(eng, "_decode_fn", fake)
    eng._exec_baseline = None
    eng._note_recompiles()                  # baseline fixed at 1
    assert eng._ss_recompiles.value == 0
    fake.n = 3
    eng._note_recompiles()                  # grew after warm: anomaly
    assert eng._ss_recompiles.value == 2
    assert eng.stats()["steady_state_recompiles"] == 2
    h = eng.health()
    assert h["signals"]["recompiles"]["state"] == "degraded"
    assert h["state"] != "ok"
    assert any(r.startswith("recompiles") for r in h["reasons"])
    # the reset contract: the counter and the baseline re-seed
    eng.reset_counters()
    assert eng.stats()["steady_state_recompiles"] == 0
    assert eng._exec_baseline is None


def test_check_metrics_tool(tmp_path):
    """Satellite (CI wiring): the metrics schema guard passes on the live
    engine and its parser rejects malformed exposition text."""
    import tools.check_metrics as cm
    errors = []
    eng, st = cm.run_smoke(errors)
    assert not errors, errors
    assert cm.REQUIRED_STATS_KEYS <= set(st)
    check_errors = []
    cm.check_exposition(eng.metrics.to_prometheus(), check_errors)
    assert not check_errors, check_errors
    with pytest.raises(ValueError, match="malformed sample"):
        cm.parse_prometheus("bad metric line {")
    broken = ('m_bucket{le="1"} 5\nm_bucket{le="+Inf"} 3\n'
              'm_sum 1.0\nm_count 3\n')
    errs = []
    cm.check_exposition(broken, errs)
    assert any("cumulative" in e for e in errs)
