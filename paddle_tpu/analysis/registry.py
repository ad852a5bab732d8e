"""Central compiled-program registry: the declared source-of-truth for every
`jax.jit`/`pjit`/`shard_map` call site in the tree and for the serving
engine's program-count budget.

Three consumers keep each other honest:

- **TPL002** (`tools/tpu_lint.py`): a jit/shard_map call site not declared
  here is a lint failure — new program sources cannot appear silently; a
  declared site with no remaining code is flagged as stale.
- **`tools/check_program_count.py`**: re-measures the live serving program
  counts against `SERVE_PROGRAM_BUDGET[_MP]` below — the budget is declared
  ONCE here, so the runtime guard and the static guard cannot drift apart.
- **`analysis/jaxpr_checks.py`**: level-2 targets reference the serving
  entries' budget buckets when auditing donation/transfer/dtype discipline.
- **`tools/tpu_cost.py`**: re-measures the serving executables' static
  resource account (at-rest HBM, liveness peak, collective bytes/step)
  against `SERVE_RESOURCE_BUDGET` below — memory and communication budgets
  are declared ONCE here, next to the program-count budget they extend.

Granularity is (repo-relative path, enclosing function qualname): one entry
covers every jit call textually inside that function (lambdas fold into their
enclosing def).  That matches how program sources actually cluster — e.g.
`LLMEngine.__init__` builds all five serving executables through one wrapper.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# serving program budget (consumed by tools/check_program_count.py and README)
# ---------------------------------------------------------------------------

# Continuous batching is only viable on TPU because the engine runs a FIXED
# set of executables regardless of traffic shape.  Since the one-dispatch
# refactor the decode side is a SINGLE fused program
# (`models/gpt.py::serve_step_paged`, built through `LLMEngine.__init__`'s
# jit_ wrapper as `_decode_fn`): vanilla decode, spec verify and the
# interleaved prefill chunk all ride it, with sampling and the accept scan on
# device.  The prefill budget covers the cold paths (bucketed one-shot +
# prefix-tail chunk in bucketed mode; zero programs in chunked mode, where
# the chunk rides the fused batch), plus one COW page copy.  The swap budget
# covers the two KV-copy executables — ONE fixed-shape gather
# (`swap_out_pages` over page ids padded to the slot capacity: one
# `dynamic_slice` a page, so the pool is read only where a page lies, its
# output split into pieces of `LLMEngine._swap_w` pages of which only the
# wanted ones are fetched) and ONE scatter (`swap_in_pages`, page ids padded to
# the slot capacity) — shared by BOTH host-copy paths: preemption swap
# parking (oversubscription PR) and the KV tier's prefix spill/restore
# (tiering PR), which reuse the same programs so tiering adds ZERO
# executables.  They compile only when a swap or spill actually fires
# (warmed by `warm_swap` on engines that can reach them).
SERVE_PROGRAM_BUDGET: Dict[str, int] = {
    "decode_side_executables": 1,   # THE fused serve_step_paged program
    "prefill_executables": 2,
    "copy_executables": 1,
    "swap_executables": 2,          # preemption swap-out gather + swap-in scatter
    "total_executables": 6,
}

# Per-mesh-config budget under tensor parallelism: the AOT path keeps counts
# exact; the contract since the one-dispatch refactor is decode-side <= 1 at
# EVERY mesh config (the fused program partitions, it does not fork).
SERVE_PROGRAM_BUDGET_MP: Dict[str, int] = {
    "decode_side_executables": 1,
    "prefill_executables": 2,
    "copy_executables": 1,
    "swap_executables": 2,
    "total_executables": 6,
}

# ---------------------------------------------------------------------------
# serving resource budget (consumed by tools/tpu_cost.py --ci and tests)
# ---------------------------------------------------------------------------

# Static HBM/collective ceilings over the SAME tiny audit engines the jaxpr
# checks trace (`jaxpr_checks._build_engine`: gpt_tiny(64), 2 slots, page 8,
# chunk 8, spec 2, and its bucketed twin for the standalone chunk program —
# mp1, mp2 AND mp4, the mesh size where the sharded-head win compounds).
# Units are cost-model bytes (traced aval bytes, `analysis/cost_model.py` —
# deterministic across backends, no XLA padding).
# These are the repo's memory yardstick: the quantized-KV arc shrank the
# pool term, the vocab-sharded-head arc moved `wte` out of the replicated
# set — both show up HERE before any TPU run.
SERVE_RESOURCE_BUDGET: Dict[str, object] = {
    # Per-buffer ceiling on bytes REPLICATED on every chip under mp (JXP006).
    # RATCHETED with the vocab-sharded head (ISSUE-18): `wte` (256 x 64 fp32
    # = 64 KiB, the former ceiling-setter and 70B blocker) now lives in the
    # SHARDED column, and the largest replicated leaf left is a 512 B
    # norm/bias vector — 4096 is 8x headroom over that while any replicated
    # matrix (a re-replicated head at 64 KiB, even the tiny-config wte)
    # fails immediately.  At GPT-3 vocab the retired ceiling was
    # 50304 x D x 2 bytes PER CHIP no matter how large the mesh.
    "replicated_bytes_ceiling": 4_096,
    # Per-executable modeled peak HBM (JXP008): argument bytes + the
    # donation-aware liveness watermark, in which an in-place update of a
    # buffer the program owns allocates nothing (`cost_model._jaxpr_walk`:
    # the paged passes carry the donated pool through the layer scan and
    # scatter into it).  Measured 2026-10 at mp1/mp2 (fused 648k/655k,
    # chunk 856k/856k at the bucketed engine's tail width of 64 tokens,
    # bucketed 607k/607k, cow 82k/82k; mp4 = mp2) + ~10% headroom for jax
    # tracing drift.  The audit pool is 74k (37k a lane): a pool that is
    # copied — not donated, read again after its update, or scanned over and
    # re-stacked — or a second materialized logits buffer blows through it.
    "peak_hbm_bytes": {
        "fused_step": 720_000,
        "chunk_prefill": 940_000,
        "bucketed_prefill": 667_000,
        "cow_copy": 90_000,
        # preemption KV swap copies (oversubscription PR): the gather holds
        # pool + one slot-capacity staging buffer; the scatter holds pool +
        # the staging uploads and writes the donated pool in place.
        # Measured 2026-10 (swap_out 172k/172k mp1/mp2 since PR 38 takes a
        # page a `dynamic_slice` — the walk counts the slices beside their
        # concatenation — 139k/172k before; swap_in 139k/172k;
        # collective-free at mp2 — the page axis is unsharded) + ~10%.
        "swap_out": 190_000,
        "swap_in": 190_000,
        # quantized fused step (weight+kv int8): int8 at-rest args shrink
        # the account to LESS than the fp program — measured 2026-10
        # 307k/307k mp1/mp2 (+10% headroom).  A dequant that materializes
        # the whole fp weight stack (instead of one block inside the layer
        # scan) or an fp KV pool copy blows through this immediately.
        "fused_step_int8": 337_000,
    },
    # Per-executable collective bytes per step (JXP007), keyed by the FULL
    # target name: only the mp>1 programs may communicate at all.  The
    # declared traffic per step is (a) the Megatron row-parallel all-reduces
    # (proj + fc2, 2/layer), (b) the vocab-parallel embed's ONE hidden-sized
    # psum (ISSUE-18 — the price of never holding a replicated wte), and
    # (c) the sharded-argmax merge: one (value, index) scalar PAIR per row
    # (pmax + pmin, 2 x 4 B x rows) — NEVER logits-sized.  Measured 2026-08
    # on the audit config (L=2, f32): fused 20608 B/step (16384 layer
    # psums + 4096 embed psum + 128 argmax pair), bucketed 10248; the chunk
    # program at the bucketed engine's tail width (64 tokens, 2026-10) 49160
    # — budgets are measured + ~20% headroom, so a logits-wide allgather
    # (32 KiB at even this toy vocab) fails immediately.  Collective payloads are LOGICAL bytes, so mp2 and
    # mp4 share one measured account (per-chip shards halve, the summed
    # traffic does not).  An mp1 program with ANY collective, or an mp>1
    # program absent from this table, is undeclared traffic and fails CI.
    "collective_bytes_per_step": {
        "serve.mp2.fused_step": 24_576,
        # dequant is chip-local (scales shard with their weights/pages), so
        # the quantized fused step carries exactly the fp program's traffic
        "serve.mp2.fused_step_int8": 24_576,
        "serve.mp2.chunk_prefill": 59_000,
        "serve.mp2.bucketed_prefill": 12_288,
        # the mp4 audit pass (same logical payloads, see above)
        "serve.mp4.fused_step": 24_576,
        "serve.mp4.fused_step_int8": 24_576,
        "serve.mp4.chunk_prefill": 59_000,
        "serve.mp4.bucketed_prefill": 12_288,
    },
    # UNIFIED host-pool ceiling (JXP009): the bound
    # `LLMEngine.host_pool_bytes()` declares for EVERYTHING parked in host
    # memory — preempt="swap" victim KV AND the kv_tier spilled-prefix store
    # share this one `swap_pool_pages` budget (disk-tier pages are
    # off-budget; intake admission and the preempt decision both count
    # against it via `PagedKVCache.host_pool_room`).  Audit engine: 8 pages
    # x (2 layers x 8 tok x 4 KVH x 16 hd x 4 B x k+v) = 64 KiB, checked
    # exactly (the host pool is sized, not traced).  The yardstick for the
    # quantized-KV arc: halving page bytes must halve this ceiling too.
    "host_pool_bytes": 65_536,
    # ---- quantized serving (weight_dtype="int8" + kv_dtype="int8") --------
    # The quantized audit engine (same gpt_tiny(64) geometry, 9-page pool) is
    # accounted alongside the fp one each pass; all four numbers below are
    # the declared side of the ISSUE-11 acceptance bars:
    # - int8 replicated per-buffer ceiling (JXP006 on the quantized at-rest
    #   account): ratcheted with the fp ceiling (ISSUE-18) — wte_q/wte_scale
    #   shard with the vocab axis, so the quantized replicated remainder is
    #   the same 512 B norm/bias vectors plus tiny fp32 scale leaves.  A
    #   quantized embedding re-materializing replicated (16 KiB int8, 64 KiB
    #   fp) blows through 4096 immediately.
    "replicated_bytes_ceiling_int8": 4_096,
    # - int8 pool at-rest ceiling + minimum shrink ratio (JXP010): the fp
    #   pool is 72 KiB (2 x [2,9,8,4,16] f32), the int8 pool 22.5 KiB
    #   (int8 pages + per-token f32 scale lanes) — measured ratio 3.2x,
    #   declared floor 2.0x (the "~2x smaller at kv_dtype=int8, same pool
    #   geometry" acceptance bar, met with margin at fp32; bf16 pools land
    #   at ~1.9x which is why the floor is 2.0 on the f32 audit config, not
    #   a universal constant).
    "quantized_pool_bytes": 24_576,
    "quantized_pool_min_ratio": 2.0,
    # - int8 unified host-pool ceiling (JXP009 extended): int8 pages park
    #   as int8 — spill and swap alike — 8 pages x 2.5 KiB/page (k+v int8 +
    #   scale lanes) = 20 KiB, checked exactly like the fp bound (3.2x
    #   under the fp 64 KiB).
    "host_pool_bytes_int8": 20_480,
}


# ---------------------------------------------------------------------------
# serving SLO + health thresholds (consumed by inference/health.py, the obs
# server's /healthz and tools/check_metrics.py)
# ---------------------------------------------------------------------------

# The engine's health evaluation folds the live signal plane — multi-window
# SLO burn rates, pool pressure, admission saturation (timeout/reject rates),
# preemption rate, steady-state recompile anomalies — into ONE
# ok/degraded/overloaded state with per-signal reasons, against the targets
# declared HERE (and only here: the /healthz probe, stats()["health"], the
# `engine_health` gauge and the health tests all read this dict).  The
# numbers are the audit/CPU-smoke config's yardstick, same convention as
# SERVE_RESOURCE_BUDGET; a real deployment re-declares them for its traffic.
SERVE_SLO: Dict[str, object] = {
    # deadline-attainment target: the SLO the burn rates measure against.
    # Burn = (windowed miss fraction) / (1 - target): burn 1.0 consumes the
    # error budget exactly as fast as allowed, >1 is on track to violate.
    "deadline_attainment_target": 0.99,
    # latency bounds on the engine-side lifecycle histograms (p99, ms):
    # crossing one degrades health (the engine still serves; a router should
    # prefer other replicas).  Sized for the CPU-smoke/audit config — a cold
    # compile inside a first request's TTFT legitimately trips it.
    "ttft_p99_ms": 2000.0,
    "tpot_p99_ms": 500.0,
    # device KV pool pressure (pages in use / usable pages) at or above this
    # fraction degrades health: admission is about to stall and preemption
    # is imminent — the router should stop sending work here first.
    "pressure_ceiling": 0.95,
    # multi-window burn: page only when the FAST window burns hot while the
    # SLOW window confirms it is not a blip (the classic two-window rule).
    # Labels index inference.metrics.RATE_WINDOWS.
    "burn_window_fast": "1m",
    "burn_window_slow": "5m",
    "burn_degraded": 1.0,       # either window at 1.0 = budget-speed burn
    "burn_overloaded": 10.0,    # fast >= 10 x budget AND slow confirming
    # preemption churn (preemptions/s over the fast 10s window): sustained
    # preemption means live tokens exceed pool capacity — degraded at the
    # first trickle, overloaded when victims are evicted every second.
    "preempt_rate_degraded": 0.1,
    "preempt_rate_overloaded": 1.0,
    # admission saturation: ANY deadline timeout or intake rejection inside
    # the fast 10s window degrades; timeouts at or above this rate mean the
    # engine is shedding load faster than it serves — overloaded.
    "timeout_rate_overloaded": 1.0,
    # acceptable band for measured/predicted step time (the live roofline
    # drift gauge).  Wide because it must hold on CPU-smoke hosts where
    # dispatch overhead dominates; on TPU the ratio sits near 1 and a
    # tighter operational band belongs in the deployment's alert config.
    # Excursions count alert TRANSITIONS (roofline_drift_alerts counter),
    # they do not fold into engine_health (a slow host is not an overload).
    "roofline_drift_band": (0.02, 50.0),
}

# ---------------------------------------------------------------------------
# serving-bench perf floors (consumed by tools/check_bench.py --ci)
# ---------------------------------------------------------------------------

# The serving-bench trajectory (`BENCH_SERVE.jsonl`, appended by
# bench_serve.py / tools/check_bench.py) is CI-enforced the same way the
# HBM/program budgets are: floors declared ONCE here, re-measured on a fresh
# CPU-smoke bench run by `tools/check_bench.py --ci`.  Wall-clock numbers on
# a shared CI box swing +-10%, so the floors bind the DETERMINISTIC side of
# the bench (byte parity, dispatch counts, the stamp-count tracing account)
# tightly and the wall-clock ratios loosely.
SERVE_PERF_FLOORS: Dict[str, object] = {
    "schema_version": 5,
    # every parity flag a bench run reports must be True — byte-exact greedy
    # parity is the one bar noise cannot excuse (kv_tier_parity: tier
    # restores must be bit-exact vs the --no-kv-tier re-prefill;
    # fleet_parity: routing a session stream across dp replicas must emit
    # the same tokens as one engine serving it alone; disagg_parity: the
    # prefill->store->decode handoff AND the engine-restart restore must
    # both reproduce the colocated single-engine stream byte-for-byte)
    "parity_flags": ("spec_parity", "oversubscribe_parity",
                     "tracing_parity", "kv_tier_parity", "fleet_parity",
                     "disagg_parity"),
    # the one-dispatch claim in numbers: a fused busy step dispatches
    # exactly ONE decode-side program — tied to the program budget above so
    # the two guards cannot drift apart
    "dispatches_per_step_max": float(
        SERVE_PROGRAM_BUDGET["decode_side_executables"]),
    # the always-on tracing plane's deterministic stamp-count x unit-cost
    # account (bench `tracing_overhead_measured`) must stay under 2%
    "tracing_overhead_max": 0.02,
    # roofline sanity: model_error (measured/predicted step ms) must exist
    # and be a positive finite ratio.  On TPU it is meaningful (~1-3); the
    # CPU smoke is host-scheduling-bound so the ceiling only catches a
    # broken prediction (zero, negative, or absurd), not slow hosts.
    "model_error_max": 1.0e5,
    # a bench run that emitted nothing has no trajectory row to contribute
    "tokens_per_sec_min": 1.0,
    # the KV-tier capacity claim, deterministic on any multi-turn row that
    # ran the --no-kv-tier comparison: returning sessions must re-prefill
    # at most half the tokens the drop-on-evict baseline pays (the measured
    # CPU smoke sits ~0.7-0.85; token counts are scheduling-exact, so this
    # floor is noise-free)
    "returning_prefilled_drop_min": 0.5,
    # the affinity-routing claim (dp fleet PR), deterministic on any
    # `--replicas > 1` row: the returning-turn prefix-hit odds ratio
    # (1 + affinity_hit) / (1 + round_robin_hit) on the identical session
    # stream must be >= 1 — cache-aware routing never hits LESS than the
    # cache-blind round-robin baseline (the measured CPU smoke sits ~1.45;
    # hit rates are token-count-exact, so this floor is noise-free).  The
    # TTFT side of the A/B is wall-clock and stays report-only.
    "affinity_prefix_hit_ratio_min": 1.0,
    # the disaggregation handoff ceiling (disagg rows): p99 wall latency of
    # a prefill->store->decode handoff (prefill submit through decode index
    # refresh).  Wall-clock on a shared CPU smoke, so the ceiling is set to
    # catch only a collapse (a handoff path that re-prefills, blocks on a
    # lock, or re-reads the whole store); measured CPU-smoke handoffs sit
    # in the tens of ms.  disagg_parity carries the deterministic side.
    "handoff_p99_ms_max": 5000.0,
    # the vocab-sharded-head claim (schema v5, deterministic — leaf-shape
    # arithmetic, no wall clock): on any mp >= 2 row the per-device
    # replicated param bytes must sit STRICTLY below the fp `wte` size the
    # row also reports — i.e. the embedding/head genuinely left the
    # replicated column (a re-replicated head makes replicated >= wte
    # by definition).  The JXP006 ratchet enforces the same invariant on
    # the audit engines; this floor enforces it on every bench row.
    "replicated_below_wte": True,
}


@dataclasses.dataclass(frozen=True)
class ProgramSource:
    """One declared jit/shard_map site cluster.

    `budget` names the SERVE_PROGRAM_BUDGET bucket these programs count
    against (None for non-serving sources: training steps, export paths,
    test-only helpers).  `note` says what compiles there and why its count is
    bounded — the registry doubles as the program-inventory document."""
    path: str                           # repo-relative, '/'-separated
    qualname: str                       # enclosing def ("" = module level)
    budget: Optional[str] = None
    note: str = ""


PROGRAM_SOURCES: Tuple[ProgramSource, ...] = (
    # ---- serving engine (the budgeted set) --------------------------------
    ProgramSource(
        "paddle_tpu/inference/engine.py", "_AotCache.__init__",
        budget="total_executables",
        note="mp-mode AOT wrapper: one lower().compile() per signature; the "
             "wrapper IS how the mp program count stays exact"),
    ProgramSource(
        "paddle_tpu/inference/engine.py", "LLMEngine.__init__",
        budget="total_executables",
        note="the serving executables built through the jit_ wrapper, fixed "
             "shapes per engine: serve_step_paged — THE "
             "one-dispatch step (decode + verify + interleaved chunk in one "
             "[B, max(K+1, chunk)] batch, on-device sampling/acceptance, "
             "O(B*K)-int host output) — plus the cold prefill paths, the "
             "COW copy and the two KV-swap copies (swap_out gather / "
             "swap_in scatter — shared by preemption swap parking AND the "
             "KV tier's prefix spill/restore, compiled when either path "
             "fires)"),
    # ---- model core -------------------------------------------------------
    ProgramSource(
        "paddle_tpu/models/gpt.py", "generate",
        note="legacy one-shot generate: one program per (config, B, Tp, "
             "max_new) shape, LRU-bounded by GENERATE_CACHE_MAX"),
    ProgramSource(
        "paddle_tpu/models/gpt.py", "prefill_paged",
        note="bucketed prefill's dense flash attention shard_mapped over mp "
             "(inside the serving prefill executable, no standalone program)"),
    ProgramSource(
        "paddle_tpu/models/gpt.py", "_embed",
        note="vocab-parallel serving embed: masked local take + psum over "
             "the vocab-sharded wte (inside the serving executables, no "
             "standalone program)"),
    ProgramSource(
        "paddle_tpu/models/gpt.py", "sharded_argmax",
        note="sharded argmax merge over vocab-sharded logits — per-chip "
             "(value, global index) pair + pmax/pmin tie-break (inside the "
             "serving executables, no standalone program)"),
    ProgramSource(
        "paddle_tpu/models/gpt.py", "sample_token",
        note="sharded temperature/top-k pick: local top-k + k*mp all-gather "
             "threshold + gumbel-argmax merge (inside the serving "
             "executables, no standalone program)"),
    # ---- parallel trainers ------------------------------------------------
    ProgramSource(
        "paddle_tpu/parallel/ring_attention.py", "ring_attention",
        note="context-parallel ring attention body"),
    ProgramSource(
        "paddle_tpu/parallel/hybrid.py", "_moe_ffn_ep",
        note="expert-parallel MoE body (one program inside the train step)"),
    ProgramSource(
        "paddle_tpu/parallel/hybrid.py", "_cp_loss",
        note="context-parallel loss shard_map (ring attention lane)"),
    ProgramSource(
        "paddle_tpu/parallel/hybrid.py", "_vp_embed",
        note="vocab-parallel embedding shard_map"),
    ProgramSource(
        "paddle_tpu/parallel/hybrid.py", "_vp_ce",
        note="vocab-parallel cross-entropy shard_map"),
    ProgramSource(
        "paddle_tpu/parallel/hybrid.py", "_pp_loss",
        note="pipeline-parallel GPipe loop shard_map"),
    ProgramSource(
        "paddle_tpu/parallel/hybrid.py", "HybridParallelTrainer.__init__",
        note="param/optimizer init programs (one each per trainer)"),
    ProgramSource(
        "paddle_tpu/parallel/hybrid.py", "HybridParallelTrainer._build_step",
        note="THE train step: one program per trainer config"),
    ProgramSource(
        "paddle_tpu/parallel/hybrid.py", "HybridParallelTrainer.eval_loss",
        note="jitted eval loss, compiled once (test_eval_loss_jitted_once)"),
    # ---- kernels ----------------------------------------------------------
    ProgramSource(
        "paddle_tpu/incubate/kernels/flash_attention.py", "_per_shard",
        note="flash fwd/bwd kernels per shard of a partitioned train step "
             "(inside the step's custom_vjp halves, no standalone program)"),
    ProgramSource(
        "paddle_tpu/incubate/kernels/paged_attention.py",
        "paged_prefill_attention_mp",
        note="paged attention per-shard under the serving mp mesh"),
    # ---- export / static-graph paths --------------------------------------
    ProgramSource(
        "paddle_tpu/jit/api.py", "save",
        note="StableHLO export: one program per saved InputSpec signature"),
    ProgramSource(
        "paddle_tpu/jit/program.py", "ConcreteProgram.__init__",
        note="dy2static captured forward"),
    ProgramSource(
        "paddle_tpu/jit/program.py", "ConcreteProgram.run",
        note="dy2static captured backward (built on first .backward)"),
    ProgramSource(
        "paddle_tpu/static/__init__.py", "save_inference_model",
        note="static-mode export program"),
    # ---- distributed facades ----------------------------------------------
    ProgramSource(
        "paddle_tpu/distributed/communication/ops.py", "_replicated_jit",
        note="eager collective facade: one tiny program per op/mesh"),
    ProgramSource(
        "paddle_tpu/distributed/auto_parallel/engine.py", "Engine.predict",
        note="auto-parallel predictor forward"),
)

_BY_KEY: Dict[Tuple[str, str], ProgramSource] = {
    (s.path, s.qualname): s for s in PROGRAM_SOURCES}


def lookup(path: str, qualname: str) -> Optional[ProgramSource]:
    """The declared source covering a jit site at (path, enclosing qualname).
    Falls back to walking qualname prefixes so a site inside a nested def
    (`LLMEngine.__init__.fused_impl`) is covered by its enclosing entry."""
    parts = qualname.split(".") if qualname else []
    for i in range(len(parts), -1, -1):
        hit = _BY_KEY.get((path, ".".join(parts[:i])))
        if hit is not None:
            return hit
    return None


def for_path(path: str) -> List[ProgramSource]:
    return [s for s in PROGRAM_SOURCES if s.path == path]
