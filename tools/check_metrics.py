#!/usr/bin/env python
"""CI guard: the serving observability schema.

The metrics surface is an API: dashboards scrape `to_prometheus()`, benches
read `stats()`, and the ROADMAP's preemption/router work will consume the
page gauges and step timeline.  This script re-measures the contract on every
run so a future PR cannot silently drop a key, break the exposition format,
or make a "counter" go backwards:

- **stats() schema** — every key in REQUIRED_STATS_KEYS present (the frozen
  serving-stats surface, including the latency histogram block and the SLO
  block: deadline attainment + per-priority goodput);
- **registry schema** — required counters/gauges/histograms present in
  `metrics.snapshot()`;
- **exposition** — `to_prometheus()` parses line-by-line against the
  Prometheus text format: HELP/TYPE comments only, well-formed samples
  (general label sets accepted), `_bucket` series cumulative and ending at
  `+Inf` == `_count`, and OpenMetrics `# {...} value` exemplars syntactically
  valid with the exemplar value inside its bucket's `le` bound;
- **exemplar round-trip** — the smoke engine's exposition carries >= 1
  exemplar whose `request_id` resolves through
  `engine.export_request_trace()` to a non-empty chrome-trace span tree (the
  p99-to-request lookup the tracing layer exists for);
- **merged-registry schema** — `MetricsRegistry.merge()` counter/histogram
  math against hand-computed goldens, and a two-member `FleetMetrics`
  exposition that parses with per-engine labels plus `llm_fleet_*` totals
  equal to the member sums;
- **obs-server smoke** — `ObservabilityServer` over the live smoke engine on
  an ephemeral loopback port: /metrics parses under this same checker,
  /stats carries the required keys, /requests/<rid> serves the exemplar's
  span tree, /debug is valid JSON with the bundle schema;
- **health & signals schema** — `stats()` carries the windowed-rate block
  (every family over every window), a folded `health` state from the known
  set with burn rates, and the complete `roofline` account; the exposition
  carries the rate/burn/health/roofline gauge families; `/healthz` serves
  the REAL health evaluation (structured state + per-signal detail, 200 for
  ok/degraded, 503 for overloaded — never the old hardcoded stub); and the
  `engine_health` gauge fleet-merges WORST-OF (max), not sum;
- **front-door smoke** — the serving front door (`inference.frontend
  .ServingFrontend`) over a 2-replica dp `EngineFleet` on a real loopback
  socket: the obs routes served THROUGH the door (one server, `/v1/*` next
  to `/metrics`) carry the fleet exposition — per-``{engine=...}`` series
  for every replica plus `llm_fleet_*` merged totals equal to the member
  sums — `/stats` is the per-label map, `/healthz` is the worst-of fleet
  rollup (503 the moment any member reads overloaded), and the 404 route
  list advertises the inference endpoints;
- **disagg smoke** — a 1P:1D role fleet over the durable tier store: the
  `kv_handoff_*` counters move on the prefill replica and `kv_tier_restores`
  on the decode replica, the prefill request's timeline carries the
  `handoff` event, and `/healthz` served through the front door labels every
  per-engine entry with its role;
- **monotonicity** — across a CPU-smoke engine loop that exercises admission,
  chunked prefill, speculative verify, prefix hits, LRU eviction AND abort,
  no counter ever decreases between steps;
- **program budget** — decode-side compiled programs within the budget
  declared in paddle_tpu/analysis/registry.py with metrics enabled
  (observability — tracing and exemplars included — is host-only; see
  tools/check_program_count.py for the full per-mesh budget).

Exits non-zero with a diff on violation.  Usage:
    JAX_PLATFORMS=cpu python tools/check_metrics.py
"""
from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the `reason` label of `fused_serial_steps` (engine.SERIAL_REASONS, frozen
# here like every other name of the schema)
SERIAL_REASONS = ("idle_start", "draft", "prefilling", "admission_due",
                  "budget_end", "pages")
REQUIRED_STATS_KEYS = frozenset({
    "decode_executables", "verify_executables", "prefill_executables",
    "copy_executables", "swap_executables", "buckets", "prefill_chunk",
    "spec_len", "mp",
    "engine_steps", "decode_iterations", "decode_tokens", "verify_steps",
    "spec_events", "spec_drafted_tokens", "spec_accepted_tokens",
    "spec_emitted_tokens", "spec_backoffs", "accepted_per_step",
    "prefill_chunks", "prefilled_tokens", "prefix_cached_tokens",
    "prefix_hit_requests", "prefix_hit_rate", "cow_page_copies",
    "pages_in_use", "pages_free", "pages_evictable", "prefix_evictions",
    "kv_token_capacity", "dense_token_footprint", "queued", "prefilling",
    "running", "finished_requests", "aborted_requests", "latency",
    # overload surface (oversubscription PR): admission/preempt modes + the
    # preemption/swap/deadline counters the bench and dashboards consume
    "admission", "preempt", "preemptions", "preempt_swaps",
    "preempt_recomputes", "swapped_pages", "swap_ms", "recomputed_tokens",
    "timeouts", "rejected_requests", "swapped", "kv_pages_swapped",
    "kv_pool_pressure",
    # quantized serving (ISSUE 11): the quantization knobs, the at-rest pool
    # bytes the capacity math keys on, and the swap-pool intake gate counter
    "weight_dtype", "kv_dtype", "kv_pool_bytes", "intake_swap_rejects",
    # observability-plane PR (ISSUE 12): the SLO block (deadline attainment
    # + per-priority-class goodput) the router's SLO layer consumes
    "slo",
    # health & signals PR (ISSUE 13): windowed rates, the folded health
    # state, and the live roofline (predicted/measured/drift/anomalies)
    "rates", "health", "roofline",
    # KV tiering PR (ISSUE 15): per-tier occupancy + spill/restore traffic
    # + the rolling-hash partial-index hit counter
    "kv_tier",
    # tracing PR (ISSUE 26): bytes moved against bytes wanted at the two
    # swap boundaries, and the host turnaround between fused programs
    "swap_d2h_fetches", "swap_d2h_bytes", "swap_d2h_useful_bytes",
    "swap_h2d_bytes", "swap_h2d_useful_bytes", "turnaround_ms",
    # spill-fetch PR (ISSUE 31): the copies run beside the engine thread —
    # what it still waited for, what had landed, what is in flight
    "swap_d2h_blocked_ms", "swap_d2h_landed_free",
    "swap_d2h_backpressure_waits", "swap_d2h_inflight_pages",
    # hybrid PR (ISSUE 28): the expert layers' routing account and the
    # recurrent state lanes (0 for a dense configuration)
    "moe_pairs_here", "moe_pairs_away", "moe_experts_touched", "moe_load_max",
    "ssm_slots_live", "ssm_state_resets", "ssm_state_bytes",
    "ssm_state_pool_bytes", "prefix_lookups_skipped_no_state",
    # paged-walk PR (ISSUE 29): pages the paged kernel walks against the
    # table entries its programs were handed
    "paged_pages_walked", "paged_table_entries",
    # latent-pages PR (ISSUE 32): rows absorbed attention read and its query
    # tokens, and the latent lane's bytes a page (0 without latent layers)
    "latent_tokens_written", "mla_absorbed_rows", "latent_page_bytes",
    # sparse-attention PR (ISSUE 39): rows the indexer scored and the sparse
    # attention read (one DSA layer's worth; all programs, then the fused
    # steps alone), and the index lane's bytes a page (0 without DSA)
    "dsa_rows_scored", "dsa_rows_selected", "dsa_step_rows_scored",
    "dsa_step_rows_selected", "index_page_bytes",
    # the decode top-k by counting: query tokens whose selection needed the
    # search (more than index_topk candidates), all programs and fused steps
    "dsa_rows_searched", "dsa_step_rows_searched",
    # the long pass expanded: query tokens whose sparse attention ran with
    # per-head keys and values (0 for fused steps and without DSA)
    "dsa_expanded_tokens",
    # the long pass's compact expert layer: held pairs it computed and the
    # chunks past the first (0 for fused steps and without DSA)
    "moe_pairs_compacted", "moe_compact_extra_chunks",
    # launch-ahead PR (ISSUE 33): fused launches made before the previous
    # program's result was read, and the lanes of those dropped at harvest
    "fused_launched_ahead", "fused_ahead_discarded_lanes",
    # admission-account PR (ISSUE 36): launches ahead that found the program
    # in flight already finished, and the serial steps by reason
    "fused_ahead_late", "fused_serial_steps",
})
REQUIRED_KV_TIER_KEYS = frozenset({
    "enabled", "spill_dir", "pages_host", "pages_disk", "spills",
    "restores", "restored_tokens", "partial_page_hits", "disk_spills",
    "disk_restores", "tier_drops",
    # disaggregated serving PR (ISSUE 17): the durable store + cross-engine
    # handoff surface
    "store", "handoff_exports", "handoff_pages", "handoff_tokens",
    "store_nodes_restored",
})
REQUIRED_SLO_KEYS = frozenset({
    "deadline_requests", "deadline_met", "deadline_attainment",
    "goodput_tokens_by_priority",
})
# stats()["rates"] families x window labels (inference.metrics.RATE_WINDOWS);
# each (family, window) pair is ALSO a pull gauge in the exposition
RATE_FAMILIES = ("tokens_per_sec", "admits_per_sec", "preemptions_per_sec",
                 "timeouts_per_sec", "rejects_per_sec")
RATE_WINDOW_LABELS = ("10s", "1m", "5m")
REQUIRED_HEALTH_KEYS = frozenset({"state", "code", "reasons", "burn_rates"})
REQUIRED_ROOFLINE_KEYS = frozenset({
    "predicted_step_ms", "measured_step_ms", "drift", "drift_alerts",
    "steady_state_recompiles",
})
HEALTH_STATES = ("ok", "degraded", "overloaded")
REQUIRED_LATENCY_KEYS = frozenset(
    {"queue_s", "ttft_s", "tpot_s", "e2e_s", "step_s"})
REQUIRED_COUNTERS = frozenset({
    "decode_iterations", "decode_tokens", "prefill_chunks",
    "prefilled_tokens", "prefix_cached_tokens", "prefix_hit_requests",
    "cow_page_copies", "verify_steps", "spec_events", "spec_drafted_tokens",
    "spec_accepted_tokens", "spec_emitted_tokens", "spec_backoffs",
    "finished_requests", "aborted_requests", "prefix_evictions",
    "preemptions", "preempt_swaps", "preempt_recomputes", "swapped_pages",
    "swap_ms", "recomputed_tokens", "timeouts", "rejected_requests",
    "intake_swap_rejects", "deadline_requests", "deadline_met",
    # health & signals PR: admission-rate numerator + anomaly counters
    "admitted_requests", "roofline_drift_alerts", "steady_state_recompiles",
    # KV tiering PR: spill/restore traffic + rolling-hash partial hits
    "kv_tier_spills", "kv_tier_restores", "kv_tier_restored_tokens",
    "partial_page_hits",
    # disaggregated serving PR: prefill->decode handoffs through the store
    "kv_handoff_exports", "kv_handoff_pages", "kv_handoff_tokens",
    # tracing PR: the swap boundaries' byte account + host turnaround
    "swap_d2h_fetches", "swap_d2h_bytes", "swap_d2h_useful_bytes",
    "swap_h2d_bytes", "swap_h2d_useful_bytes", "turnaround_ms",
    # spill-fetch PR (ISSUE 31): the engine thread's wait for spill bytes
    "swap_d2h_blocked_ms", "swap_d2h_landed_free",
    "swap_d2h_backpressure_waits",
    # hybrid PR: expert routing and recurrent state
    "moe_pairs_here", "moe_pairs_away", "moe_experts_touched",
    "ssm_slots_live", "ssm_state_resets", "ssm_state_bytes",
    "prefix_lookups_skipped_no_state",
    # paged-walk PR (ISSUE 29): how far the kernel's bounded walk engages
    "paged_pages_walked", "paged_table_entries",
    # launch-ahead PR (ISSUE 33)
    "fused_launched_ahead", "fused_ahead_discarded_lanes",
    # sparse-attention PR (ISSUE 39): the indexer's rows and the chosen ones
    "dsa_rows_scored", "dsa_rows_selected",
    # the decode top-k by counting: the tokens whose selection was searched
    "dsa_rows_searched",
    # the long pass expanded: its query tokens
    "dsa_expanded_tokens",
    # the long pass's compact expert layer: its pairs and extra chunks
    "moe_pairs_compacted", "moe_compact_extra_chunks",
    # admission-account PR (ISSUE 36): one labelled family, a sample a reason
    "fused_ahead_late",
}) | frozenset(f'fused_serial_steps{{reason="{why}"}}'
              for why in SERIAL_REASONS)
# the v2 step-ring record (`step_trace()`, /debug's "step_trace")
REQUIRED_STEP_RECORD_KEYS = frozenset({
    "v", "step", "t", "dur_s", "queued", "prefilling", "running",
    "decode_batch", "chunk", "verify_dispatches", "tokens_emitted",
    "finished", "pages_in_use", "pages_free", "pages_evictable",
    "dispatches", "sync_ms", "turnaround_ms", "d2h_ms", "slots", "preempted",
    "pool_pressure", "moe_pairs_here", "moe_pairs_away", "moe_experts_touched",
    "pages_walked", "latent_tokens_written", "ahead", "late",
    "serial_reason",
})
REQUIRED_DEBUG_BUNDLE_KEYS = frozenset({
    "version", "t", "engine", "pool", "requests", "step_trace", "stats",
    "metrics",
})
REQUIRED_GAUGES = frozenset({
    "queued", "prefilling", "running", "kv_pages_in_use", "kv_pages_free",
    "kv_pages_evictable", "prefix_cached_pages", "kv_pages_swapped",
    "kv_pool_pressure", "kv_pool_bytes",
    # health & signals PR: the folded health code (worst-of fleet merge),
    # the live roofline pair, and the SLO burn-rate pair
    "engine_health", "measured_step_ms", "roofline_drift",
    "slo_burn_rate_1m", "slo_burn_rate_5m",
    # KV tiering PR: per-tier-level occupancy
    "kv_tier_pages_host", "kv_tier_pages_disk",
    # spill-fetch PR (ISSUE 31): gathered pages whose bytes are in flight
    "swap_d2h_inflight_pages",
}) | frozenset(
    # windowed-rate pull gauges: one per (family, window)
    f"{fam}_{w}" for fam in RATE_FAMILIES for w in RATE_WINDOW_LABELS)
REQUIRED_HISTOGRAMS = frozenset({
    "queue_time_seconds", "ttft_seconds", "tpot_seconds",
    "e2e_latency_seconds", "step_seconds",
})

# general Prometheus label set: {k="v",...} with escaped quotes/backslashes
_LABELSET = r'\{(?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"' \
            r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*)?\}'
_NUM = r"(?:-?(?:[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?|Inf|NaN)|\+Inf)"
_SAMPLE = re.compile(
    rf"^([a-zA-Z_:][a-zA-Z0-9_:]*)"             # metric name
    rf"({_LABELSET})?"                          # optional label set
    rf" ({_NUM})"                               # sample value
    rf"(?: # ({_LABELSET}) ({_NUM})(?: ({_NUM}))?)?$")  # OpenMetrics exemplar
_COMMENT = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+$")
_LABEL_ITEM = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_labels(labelset):
    """`{k="v",...}` (or ""/None) -> dict, unescaping values.  Unescaping is
    a single left-to-right pass (each backslash consumes exactly the next
    char) — sequential .replace calls would mis-decode a literal backslash
    followed by 'n' or a quote."""
    out = {}
    for k, v in _LABEL_ITEM.findall(labelset or ""):
        out[k] = re.sub(r"\\(.)",
                        lambda m: "\n" if m.group(1) == "n" else m.group(1),
                        v)
    return out


def series_key(labelset):
    """Grouping key for a sample's label set with the `le` bucket label
    removed: PARSED and re-serialized sorted, not regex-stripped — a label
    KEY that merely ends in "le" (``module=...``) must survive, and bucket
    rows must key identically to their `_count`/`_sum` rows regardless of
    label order."""
    items = sorted((k, v) for k, v in parse_labels(labelset).items()
                   if k != "le")
    return "{%s}" % ",".join(f'{k}="{v}"' for k, v in items) if items else ""


def parse_prometheus_full(text):
    """Exposition parser: returns `(samples, exemplars)` where samples is
    {name: [(labels, value)]} and exemplars is {(name, labels): (exemplar
    label dict, exemplar value)} for every sample carrying an OpenMetrics
    `# {...} value [ts]` exemplar suffix.  Raises ValueError on any
    malformed line — including a malformed exemplar, which the pre-exemplar
    parser would have rejected wholesale and a naive split would ignore."""
    samples = {}
    exemplars = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line == "# EOF":        # OpenMetrics terminator (obs server)
            continue
        if line.startswith("#") and not line.startswith("# {"):
            if not _COMMENT.match(line):
                raise ValueError(f"malformed comment line: {line!r}")
            continue
        m = _SAMPLE.match(line)
        if m is None:
            raise ValueError(f"malformed sample line: {line!r}")
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        samples.setdefault(name, []).append((labels, float(value)))
        if m.group(4) is not None:
            if not (name.endswith("_bucket") or name.endswith("_total")):
                raise ValueError(
                    f"exemplar on a non-bucket/counter sample: {line!r}")
            exemplars[(name, labels)] = (parse_labels(m.group(4)),
                                         float(m.group(5)))
    return samples, exemplars


def parse_prometheus(text):
    """Minimal exposition-format checker: returns {name: [(labels, value)]},
    raising ValueError on any malformed line (exemplar-tolerant; use
    parse_prometheus_full to read the exemplars too)."""
    return parse_prometheus_full(text)[0]


def check_exposition(text, errors):
    try:
        samples, exemplars = parse_prometheus_full(text)
    except ValueError as e:
        errors.append(str(e))
        return
    for base in (n[:-len("_bucket")] for n in samples if n.endswith("_bucket")):
        buckets = samples[base + "_bucket"]
        # fleet expositions carry one series per {engine=...} label set:
        # cumulative/+Inf/_count checks apply per series, keyed on the
        # labels with `le` stripped
        series = {}
        for labels, v in buckets:
            series.setdefault(series_key(labels), []).append((labels, v))
        for key, rows in series.items():
            counts = [v for _, v in rows]
            tag = f"{base}_bucket{key or ''}"
            if counts != sorted(counts):
                errors.append(f"{tag} series is not cumulative: {counts}")
            if 'le="+Inf"' not in rows[-1][0]:
                errors.append(f"{tag} does not end at le=+Inf")
            count = [v for lbl, v in samples.get(base + "_count", ())
                     if series_key(lbl) == key]
            if not count:
                errors.append(f"{base}_count sample missing for {key or '{}'}")
            elif count[0] != counts[-1]:
                errors.append(f"{tag}: +Inf bucket {counts[-1]} != "
                              f"_count {count[0]}")
        if base + "_sum" not in samples:
            errors.append(f"{base}_sum sample missing")
    # exemplar semantics: a bucket's exemplar value must sit within its le
    # bound (our histograms attach the exemplar to the bucket the value
    # landed in, so a violation means attachment or emission broke)
    for (name, labels), (ex_labels, ex_value) in exemplars.items():
        if not name.endswith("_bucket"):
            continue
        le = parse_labels(labels).get("le")
        if le is None:
            errors.append(f"exemplar on a bucket without le: {name}{labels}")
            continue
        bound = float("inf") if le == "+Inf" else float(le)
        if ex_value > bound:
            errors.append(f"exemplar value {ex_value} above its bucket "
                          f'bound le="{le}" on {name}{labels}')


def run_smoke(errors):
    """Drive every scheduler lane on a tiny engine, asserting per-step that
    no counter decreases; returns the final stats()/snapshot pair."""
    import jax
    import numpy as np

    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models import gpt as G

    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(0))
    # 8-page pool under 2 slots: retiring requests park prefixes in the LRU
    # and later distinct prompts evict them (the eviction counter must move)
    # swap_pool_pages sized up so LRU-evicted prefixes SPILL to the host
    # tier (default-on tiering) instead of churning out of the budget —
    # the re-request below then restores from the tier (the restore lane)
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, num_pages=9,
                    max_model_len=64, prefill_chunk=16, spec_len=3, seed=11,
                    swap_pool_pages=64)
    rng = np.random.RandomState(11)
    shared = rng.randint(0, cfg.vocab_size, (20,)).astype(np.int32)
    rids = []
    for i in range(10):
        if i % 3 == 0:      # shared-prefix family: prefix hits + COW
            tail = rng.randint(0, cfg.vocab_size, (i,)).astype(np.int32)
            prompt = np.concatenate([shared, tail]) if i else shared.copy()
        else:               # distinct prompts: forces LRU eviction churn
            prompt = rng.randint(0, cfg.vocab_size,
                                 (int(rng.randint(4, 40)),)).astype(np.int32)
        rids.append(eng.add_request(prompt, max_new_tokens=6))
    prev = eng.metrics.snapshot()["counters"]
    aborted = False
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
        if steps == 4 and not aborted:      # mid-flight abort lane
            aborted = eng.abort(rids[-1])
        cur = eng.metrics.snapshot()["counters"]
        for k, v in cur.items():
            if v < prev.get(k, 0):
                errors.append(f"counter {k} decreased: "
                              f"{prev[k]} -> {v} at step {steps}")
        prev = cur
    if not aborted:
        errors.append("abort lane never exercised")
    # tier restore lane: re-submit the shared-family prompt AFTER the
    # distinct-prompt churn evicted (= spilled) its pages — admission must
    # map the prefix from the host tier with one scatter
    eng.add_request(np.concatenate(
        [shared, rng.randint(0, cfg.vocab_size, (5,)).astype(np.int32)]),
        max_new_tokens=4)
    while eng.has_work:
        eng.step()
        cur = eng.metrics.snapshot()["counters"]
        for k, v in cur.items():
            if v < prev.get(k, 0):
                errors.append(f"counter {k} decreased: "
                              f"{prev[k]} -> {v} in the restore lane")
        prev = cur
    st = eng.stats()
    if st["prefix_evictions"] < 1:
        errors.append("eviction lane never exercised "
                      f"(prefix_evictions={st['prefix_evictions']})")
    if st["spec_events"] < 1:
        errors.append("speculative lane never exercised (spec_events=0)")
    if st["prefix_hit_requests"] < 1:
        errors.append("prefix-hit lane never exercised")
    if st["kv_tier"]["spills"] < 1:
        errors.append("KV-tier spill lane never exercised "
                      f"(kv_tier={st['kv_tier']})")
    if st["kv_tier"]["restores"] < 1:
        errors.append("KV-tier restore lane never exercised "
                      f"(kv_tier={st['kv_tier']})")
    if st["kv_tier"]["partial_page_hits"] < 1:
        errors.append("rolling-hash partial-page lane never exercised")
    return eng, st


def check_exemplar_roundtrip(eng, errors):
    """>= 1 exemplar in the live exposition, and its request_id resolves
    through export_request_trace to a non-empty chrome span tree — the
    aggregate-to-request lookup the tracing layer exists for.  Returns the
    resolved rid (for the obs-server smoke) or None."""
    try:
        _, exemplars = parse_prometheus_full(
            eng.metrics.to_prometheus(exemplars=True))
    except ValueError as e:
        errors.append(f"exposition with exemplars failed to parse: {e}")
        return None
    rids = sorted({ex[0]["request_id"] for ex in exemplars.values()
                   if "request_id" in ex[0]})
    if not rids:
        errors.append("no request_id exemplar in the smoke exposition "
                      "(request tracing defaulted off, or attachment broke)")
        return None
    rid = int(rids[0])
    tree = eng.export_request_trace(rid)
    if not (isinstance(tree, dict) and tree.get("traceEvents")):
        errors.append(f"exemplar request {rid} did not resolve to a "
                      f"chrome-trace span tree (got {type(tree).__name__})")
        return None
    names = {e.get("name") for e in tree["traceEvents"]}
    if f"request/{rid}" not in names or "enqueue" not in names:
        errors.append(f"request {rid} span tree missing root/enqueue: "
                      f"{sorted(names)}")
    return rid


def check_merge_and_fleet(eng, errors):
    """MetricsRegistry.merge math vs hand-computed goldens + a two-member
    FleetMetrics exposition (per-engine labels, llm_fleet_* totals == member
    sums) parsed under this file's own checker."""
    from paddle_tpu.inference.metrics import FleetMetrics, MetricsRegistry

    a, b = MetricsRegistry(namespace="m"), MetricsRegistry(namespace="m")
    a.counter("c").inc(3)
    b.counter("c").inc(4)
    b.counter("only_b").inc(5)                  # disjoint-name passthrough
    ha = a.histogram("h", [1.0, 2.0])
    hb = b.histogram("h", [1.0, 2.0])
    ha.observe(0.5, exemplar={"request_id": "1"})
    hb.observe(1.5)
    hb.observe(9.0)
    agg = MetricsRegistry(namespace="agg").merge(a).merge(b)
    snap = agg.snapshot()
    if snap["counters"].get("c") != 7 or snap["counters"].get("only_b") != 5:
        errors.append(f"counter merge != golden: {snap['counters']}")
    h = agg.get("h")
    if h.counts != [1, 1] or h.overflow != 1 or h.count != 3 or \
            h.sum != 11.0 or h.min != 0.5 or h.max != 9.0:
        errors.append(f"histogram merge != golden: counts={h.counts} "
                      f"overflow={h.overflow} count={h.count} sum={h.sum}")
    # fleet: the same engine twice => per-engine labels + exactly-2x totals
    fleet = FleetMetrics().add("e0", eng).add("e1", eng)
    text = fleet.to_prometheus()
    check_exposition(text, errors)
    try:
        samples = parse_prometheus(text)
    except ValueError as e:
        errors.append(f"fleet exposition failed to parse: {e}")
        return
    per = {lbl: v
           for lbl, v in samples.get("llm_engine_decode_tokens_total", ())}
    if set(per) != {'{engine="e0"}', '{engine="e1"}'}:
        errors.append(f"fleet per-engine labels wrong: {sorted(per)}")
    total = samples.get("llm_fleet_decode_tokens_total", [("", -1)])[0][1]
    if total != sum(per.values()) or total != \
            2 * eng.stats()["decode_tokens"]:
        errors.append(f"fleet merged total {total} != member sum "
                      f"{sum(per.values())}")
    # exemplar-carrying fleet text still parses, and every PER-ENGINE series
    # exemplar scopes its trace handle with ?engine= — request ids are
    # per-engine counters, so an unscoped handle is ambiguous fleet-wide
    # (the llm_fleet_* merged series keep the member's bare handle: the obs
    # server answers those with the candidate list rather than guessing)
    try:
        _, fex = parse_prometheus_full(fleet.to_prometheus(exemplars=True))
    except ValueError as e:
        errors.append(f"fleet exposition with exemplars failed to parse: {e}")
        return
    if not fex:
        errors.append("fleet exposition carries no exemplar")
    unscoped = [(name, labels) for (name, labels), ex in fex.items()
                if 'engine="' in labels and "trace" in ex[0]
                and "?engine=" not in ex[0]["trace"]]
    if unscoped:
        errors.append(f"fleet per-engine exemplar trace handles missing "
                      f"?engine= scope: {unscoped[:3]}")
    # health gauge fleet fold: a fleet with one degraded (1) and one
    # overloaded (2) member must merge WORST-OF (2) — a sum (3) would
    # invent a state past "overloaded" and a healthy+sick pair would read
    # sick twice as hard as it is
    ha_, hb_ = MetricsRegistry(namespace="m"), MetricsRegistry(namespace="m")
    ha_.gauge("engine_health", agg="max").set(1.0)
    hb_.gauge("engine_health", agg="max").set(2.0)
    merged_h = FleetMetrics().add("e0", ha_).add("e1", hb_).merged()
    got = merged_h.get("engine_health").value
    if got != 2.0:
        errors.append(f"engine_health fleet merge is not worst-of: "
                      f"max(1, 2) merged to {got} (sum semantics leaked in)")
    # and the live engine's own health gauge max-folds with itself
    same = FleetMetrics().add("a", eng).add("b", eng).merged()
    one = eng.metrics.get("engine_health").value
    if same.get("engine_health").value != one:
        errors.append(f"engine_health self-merge {same.get('engine_health').value} "
                      f"!= member value {one} (agg must be max)")


def check_obs_server(eng, rid, errors):
    """Endpoint smoke over a real loopback socket (ephemeral port, daemon
    thread): /metrics parses, /stats carries the stats schema, /requests/
    <rid> serves the exemplar's span tree, /debug is a valid bundle, and an
    unknown rid is a clean 404."""
    import urllib.error
    import urllib.request

    from paddle_tpu.inference.obs_server import ObservabilityServer

    def get(srv, route, accept=None):
        req = urllib.request.Request(
            srv.url + route,
            headers={"Accept": accept} if accept else {})
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, r.read().decode("utf-8")
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode("utf-8")

    with ObservabilityServer(eng) as srv:
        # OpenMetrics negotiation carries the exemplars...
        status, text = get(srv, "/metrics",
                           accept="application/openmetrics-text")
        if status != 200:
            errors.append(f"/metrics -> {status}")
        check_exposition(text, errors)
        if not parse_prometheus_full(text)[1]:
            errors.append("/metrics (openmetrics) carries no exemplar")
        # ...while a plain 0.0.4 scrape must get exemplar-free text (stock
        # Prometheus text-format parsers reject the suffix)
        status, plain = get(srv, "/metrics")
        if status != 200:
            errors.append(f"/metrics (plain) -> {status}")
        check_exposition(plain, errors)
        if " # {" in plain:
            errors.append("plain /metrics scrape leaked exemplar syntax")
        status, text = get(srv, "/stats")
        st = json.loads(text) if status == 200 else {}
        missing = REQUIRED_STATS_KEYS - set(st)
        if status != 200 or missing:
            errors.append(f"/stats -> {status}, missing {sorted(missing)}")
        if rid is not None:
            status, text = get(srv, f"/requests/{rid}")
            if status != 200 or not json.loads(text).get("traceEvents"):
                errors.append(f"/requests/{rid} -> {status} (no span tree)")
        status, text = get(srv, "/requests/1234567")
        if status != 404:
            errors.append(f"/requests/<unknown> -> {status}, want 404")
        status, text = get(srv, "/debug")
        bundle = json.loads(text) if status == 200 else {}
        missing = REQUIRED_DEBUG_BUNDLE_KEYS - set(bundle)
        if status != 200 or missing:
            errors.append(f"/debug -> {status}, missing {sorted(missing)}")
        ring = bundle.get("step_trace") or [{}]
        missing = REQUIRED_STEP_RECORD_KEYS - set(ring[-1])
        if missing:
            errors.append(f"/debug step_trace record missing "
                          f"{sorted(missing)}")
        # /healthz is the REAL health evaluation now: a structured state
        # with per-signal detail, never the old hardcoded {"ok": true}
        status, text = get(srv, "/healthz")
        health = json.loads(text)
        if set(health) == {"ok"}:
            errors.append("/healthz is still the hardcoded liveness stub")
        if health.get("state") not in HEALTH_STATES:
            errors.append(f"/healthz state {health.get('state')!r} unknown")
        if status not in (200, 503) or \
                (status == 503) != (health.get("state") == "overloaded"):
            errors.append(f"/healthz -> {status} with state "
                          f"{health.get('state')!r} (want 200 for "
                          f"ok/degraded, 503 for overloaded)")
        if "signals" not in health:
            errors.append("/healthz carries no per-signal detail")


def check_front_door(errors):
    """ONE door: a 2-replica dp fleet served by `ServingFrontend`, with the
    obs plane mounted on the same socket as `/v1/*`.  Asserts the door's
    `/metrics` is the FLEET exposition (per-engine series + `llm_fleet_*`
    merges equal to member sums), `/stats` maps per label, `/healthz` is
    the worst-of rollup (flips to 503 when one member goes overloaded),
    inference requests round-trip 200, and the 404 route list advertises
    the `/v1` endpoints next to the obs routes."""
    import urllib.error
    import urllib.request

    import jax
    import numpy as np

    from paddle_tpu.inference.frontend import ServingFrontend
    from paddle_tpu.inference.router import EngineFleet
    from paddle_tpu.models import gpt as G

    def get(url, accept=None):
        req = urllib.request.Request(
            url, headers={"Accept": accept} if accept else {})
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, r.read().decode("utf-8")
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode("utf-8")

    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(1))
    fleet = EngineFleet(params, cfg, replicas=2,
                        engine_kwargs=dict(num_slots=2, page_size=8,
                                           max_model_len=64,
                                           prefill_chunk=16, seed=0))
    if not fleet.shared_executables():
        errors.append("front-door fleet replicas did not adopt the "
                      "leader's compiled executables")
    fleet.start()
    door = ServingFrontend(fleet).start()
    try:
        # land one request on EACH replica (round-robin) so every per-engine
        # series carries real traffic, then one through the HTTP door itself
        rng = np.random.RandomState(3)
        for label in fleet.engines:
            h = fleet.submit(rng.randint(0, cfg.vocab_size, (12,)),
                             session=label, policy="round_robin",
                             max_new_tokens=3)
            if fleet.result(h, timeout=60.0) is None:
                errors.append(f"front-door warm request on {label} "
                              f"timed out")
        body = json.dumps({
            "prompt": [int(x) for x in rng.randint(0, cfg.vocab_size, (8,))],
            "max_tokens": 3}).encode("utf-8")
        req = urllib.request.Request(
            door.url + "/v1/completions", data=body,
            headers={"Content-Type": "application/json"}, method="POST")
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                out = json.loads(r.read())
                if not out.get("choices", [{}])[0].get("token_ids"):
                    errors.append(f"front-door completion carried no "
                                  f"tokens: {out}")
        except urllib.error.HTTPError as e:
            errors.append(f"POST /v1/completions through the door -> "
                          f"{e.code}: {e.read()[:200]}")

        # /metrics THROUGH the door == the fleet exposition
        status, text = get(door.url + "/metrics")
        if status != 200:
            errors.append(f"front-door /metrics -> {status}")
        check_exposition(text, errors)
        try:
            samples = parse_prometheus(text)
        except ValueError as e:
            errors.append(f"front-door /metrics failed to parse: {e}")
            samples = {}
        per = {parse_labels(lbl).get("engine"): v for lbl, v in
               samples.get("llm_engine_decode_tokens_total", ())}
        if set(per) != set(fleet.engines):
            errors.append(f"front-door /metrics per-engine series "
                          f"{sorted(per)} != replicas "
                          f"{sorted(fleet.engines)}")
        elif min(per.values()) <= 0:
            errors.append(f"a replica served traffic but its per-engine "
                          f"decode_tokens series is empty: {per}")
        total = samples.get("llm_fleet_decode_tokens_total",
                            [("", -1)])[0][1]
        if total != sum(per.values()):
            errors.append(f"front-door llm_fleet_decode_tokens_total "
                          f"{total} != member sum {sum(per.values())}")

        status, text = get(door.url + "/stats")
        st = json.loads(text) if status == 200 else {}
        if status != 200 or set(st) != set(fleet.engines):
            errors.append(f"front-door /stats -> {status}, labels "
                          f"{sorted(st)}")
        status, text = get(door.url + "/healthz")
        health = json.loads(text)
        if status != 200 or health.get("state") not in HEALTH_STATES or \
                set(health.get("engines", {})) != set(fleet.engines):
            errors.append(f"front-door /healthz -> {status}: {health}")
        # worst-of: wedge ONE member into overloaded — the fleet rollup
        # must flip to 503/overloaded while the other member stays ok
        eng1 = fleet.engines["engine1"]
        real_health = eng1.health
        eng1.health = lambda: {"state": "overloaded", "code": 2,
                               "reasons": ["forced by check_metrics"],
                               "signals": {}, "burn_rates": {}}
        try:
            status, text = get(door.url + "/healthz")
            health = json.loads(text)
            if status != 503 or health.get("state") != "overloaded":
                errors.append(f"front-door /healthz is not worst-of: one "
                              f"overloaded member -> {status} "
                              f"{health.get('state')!r} (want 503 "
                              f"overloaded)")
        finally:
            eng1.health = real_health

        status, text = get(door.url + "/no-such-route")
        routes = json.loads(text).get("routes", []) if status == 404 else []
        if status != 404 or "POST /v1/completions" not in routes or \
                "/metrics" not in routes:
            errors.append(f"front-door 404 route list does not advertise "
                          f"both planes: {status} {routes}")
    finally:
        door.close()
        fleet.stop()


def check_disagg(errors):
    """Disaggregated-serving observability (ISSUE 17): a 1P:1D role fleet
    serving a returning conversation must move the `kv_handoff_*` counters
    on the prefill replica and `kv_tier_restores` on the decode replica,
    stamp a `handoff` event on the prefill request's timeline, and expose
    role-labeled per-engine health through the serving front door."""
    import urllib.error
    import urllib.request

    import jax
    import numpy as np

    from paddle_tpu.inference.frontend import ServingFrontend
    from paddle_tpu.inference.router import EngineFleet
    from paddle_tpu.models import gpt as G

    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(2))
    fleet = EngineFleet(params, cfg, roles="P:D",
                        engine_kwargs=dict(num_slots=2, page_size=8,
                                           max_model_len=64,
                                           prefill_chunk=16, seed=2))
    fleet.warm()
    fleet.start()
    door = ServingFrontend(fleet).start()
    try:
        rng = np.random.RandomState(5)
        conv = list(rng.randint(0, cfg.vocab_size, (20,)).astype(np.int32))
        for _turn in range(2):
            h = fleet.submit(np.asarray(conv, np.int32), session="s0",
                             max_new_tokens=4)
            out = fleet.result(h, timeout=120.0)
            if out is None:
                errors.append("disagg smoke turn timed out")
                return
            conv = conv + list(out.token_ids)
        pe = fleet.engines[fleet.prefill_pool[0]]
        de = fleet.engines[fleet.decode_pool[0]]
        pc = pe.metrics.snapshot()["counters"]
        for k in ("kv_handoff_exports", "kv_handoff_pages",
                  "kv_handoff_tokens"):
            if pc.get(k, 0) < 1:
                errors.append(f"disagg smoke: prefill counter {k} never "
                              f"moved ({pc.get(k, 0)})")
        if de.stats()["kv_tier"]["restores"] < 1:
            errors.append("disagg smoke: decode replica never tier-restored "
                          "a handed-off prefix")
        if fleet.stats()["disagg"]["handoffs"] < 1:
            errors.append("disagg smoke: fleet recorded no handoff")
        # the prefill request's timeline carries the handoff event (stamped
        # post-retirement, so it must land on the RETIRED trace)
        names = set()
        for rid in range(12):
            tree = pe.export_request_trace(rid)
            if isinstance(tree, dict):
                names |= {e.get("name") for e in tree.get("traceEvents", ())}
        if "handoff" not in names:
            errors.append(f"disagg smoke: no 'handoff' timeline event on "
                          f"any prefill request trace (saw {sorted(names)})")
        # role-labeled health through the front door
        try:
            with urllib.request.urlopen(door.url + "/healthz",
                                        timeout=10) as r:
                health = json.loads(r.read())
        except urllib.error.HTTPError as e:
            health = json.loads(e.read())
        per = health.get("engines", {})
        got = {l: per.get(l, {}).get("role") for l in fleet.engines}
        want = {l: fleet.engines[l].role for l in fleet.engines}
        if got != want:
            errors.append(f"front-door /healthz per-engine roles {got} != "
                          f"{want}")
    finally:
        door.close()
        fleet.stop()


def main() -> int:
    errors = []
    eng, st = run_smoke(errors)

    missing = REQUIRED_STATS_KEYS - set(st)
    if missing:
        errors.append(f"stats() missing keys: {sorted(missing)}")
    if not missing:
        lat_missing = REQUIRED_LATENCY_KEYS - set(st["latency"])
        if lat_missing:
            errors.append(f"stats()['latency'] missing: {sorted(lat_missing)}")
        slo_missing = REQUIRED_SLO_KEYS - set(st["slo"])
        if slo_missing:
            errors.append(f"stats()['slo'] missing: {sorted(slo_missing)}")
        # health & signals PR: the rates block carries every family over
        # every window, health folds to a known state, roofline is complete
        rates = st["rates"]
        miss = set(RATE_FAMILIES) - set(rates)
        if miss:
            errors.append(f"stats()['rates'] missing families: {sorted(miss)}")
        for fam in RATE_FAMILIES:
            wmiss = set(RATE_WINDOW_LABELS) - set(rates.get(fam, {}))
            if wmiss:
                errors.append(f"stats()['rates'][{fam!r}] missing windows: "
                              f"{sorted(wmiss)}")
        hmiss = REQUIRED_HEALTH_KEYS - set(st["health"])
        if hmiss:
            errors.append(f"stats()['health'] missing: {sorted(hmiss)}")
        elif st["health"]["state"] not in HEALTH_STATES:
            errors.append(f"unknown health state {st['health']['state']!r}")
        rmiss = REQUIRED_ROOFLINE_KEYS - set(st["roofline"])
        if rmiss:
            errors.append(f"stats()['roofline'] missing: {sorted(rmiss)}")
        tmiss = REQUIRED_KV_TIER_KEYS - set(st["kv_tier"])
        if tmiss:
            errors.append(f"stats()['kv_tier'] missing: {sorted(tmiss)}")

    snap = eng.metrics.snapshot()
    for section, required in (("counters", REQUIRED_COUNTERS),
                              ("gauges", REQUIRED_GAUGES),
                              ("histograms", REQUIRED_HISTOGRAMS)):
        miss = required - set(snap.get(section, {}))
        if miss:
            errors.append(f"snapshot()[{section!r}] missing: {sorted(miss)}")
    try:
        json.dumps(snap)
    except TypeError as e:
        errors.append(f"snapshot() is not JSON-serializable: {e}")

    check_exposition(eng.metrics.to_prometheus(), errors)
    rid = check_exemplar_roundtrip(eng, errors)
    check_merge_and_fleet(eng, errors)
    check_obs_server(eng, rid, errors)
    check_front_door(errors)
    check_disagg(errors)

    # observability must be free of compiled programs: decode-side budget
    # unchanged — the bound comes from the registry (declared ONCE) so this
    # guard cannot drift from check_program_count's
    from paddle_tpu.analysis.registry import SERVE_PROGRAM_BUDGET
    bound = SERVE_PROGRAM_BUDGET["decode_side_executables"]
    decode_side = st["decode_executables"] + st["verify_executables"]
    if decode_side > bound:
        errors.append(f"decode-side executables {decode_side} > {bound} with "
                      f"metrics enabled — instrumentation leaked into a "
                      f"compiled program")

    report = {"metric": "serve_metrics_schema", "ok": not errors,
              "decode_side_executables": decode_side,
              "prefix_evictions": st["prefix_evictions"],
              "spec_events": st["spec_events"],
              "aborted_requests": st["aborted_requests"],
              "exemplar_rid": rid,
              "errors": errors}
    for e in errors:
        print(f"FAIL: {e}", file=sys.stderr)
    print(json.dumps(report))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
