"""Continuous-batching LLM serving engine.

Reference lineage: the reference repo serves via `fluid/inference`'s
AnalysisPredictor + PaddleNLP `generation` — a one-shot, whole-batch API.  For
"heavy traffic from millions of users" (ROADMAP north star) that shape is
wrong: every (batch, prompt_len, max_new) combination compiles a fresh
program, cache memory is dense `B x max_seq_len`, and a long request blocks
the batch.  This engine follows vLLM's paged KV cache (Kwon et al., SOSP 2023)
and Orca's iteration-level scheduling (Yu et al., OSDI 2022), under the same
"one jitted step, static shapes" discipline as the pretraining hot loop:

- **Paged KV cache** — one static pool of `[num_pages, page_size, KVH, hd]`
  pages per layer (`models.gpt.init_paged_cache`) + per-slot page tables
  (`inference.cache.PagedKVCache`): memory scales with live tokens, pages
  recycle as requests retire.
- **Slot-indexed decode** — ONE compiled step program of fixed batch
  `num_slots` (`models.gpt.serve_step_paged`) serves a churning request set;
  retired slots are refilled without recompiling.
- **Prefix cache** (vLLM copy-on-write page sharing) — prompt pages are
  content-hashed at page granularity as their KV lands; admission maps the
  longest cached page-aligned prefix read-only into the new slot's table
  (refcount++), COW-copies a matched partial page (one jitted page-copy
  executable), and only prefills the uncached tail.  Retired prefixes stay
  matchable until LRU-evicted under pool pressure.
- **Chunked prefill** (Sarathi-Serve, Agrawal et al. OSDI 2024) — prompts
  prefill in fixed-size chunks through ONE compiled chunk executable
  (`models.gpt.prefill_chunk_paged`, any q_offset), and `step()` interleaves
  at most one chunk with each decode iteration: a 4k-token prompt no longer
  stalls every decode slot for a whole bucket-padded pass, and the prefill
  program count collapses from #buckets to 0: the chunk rides the step's
  one batch.  The bucketed one-shot path (`prefill_paged`, power-of-2
  buckets) is the default for uncached prompts (`prefill_chunk=None`); there
  the standalone chunk program serves the tails of prefix hits.
- **Speculative decoding** (Leviathan et al. 2023; prompt-lookup drafting a la
  vLLM) — `spec_len=K` breaks the one-token-per-step decode bound: a pluggable
  `DraftProposer` (default: n-gram self-drafting from the slot's own
  prompt+generated history, `inference.spec.NgramProposer`) guesses up to K
  continuation tokens per slot, the step program scores all K+1 positions
  through the same paged attention, and greedy longest-prefix acceptance
  emits 1..K+1 tokens with output exactly identical to vanilla decode
  whenever the K+1-wide and the one-token scoring agree at argmax —
  guaranteed at matching kernel numerics (asserted token-exact on CPU in
  tests; under TPU bf16 matmuls a near-tie could in principle resolve
  differently, still a valid greedy decode of the model).  Rejected
  candidates roll back as a per-slot length decrement (their KV is stale
  garbage inside the slot's own reserved pages, overwritten on reuse); slots
  with no draft ride at valid=1 (plain decode).  The verify lane is part of
  the ONE step program (decode-side count: 1).
- **Scheduler** — each `step()` admits queued requests into free slots
  (reservation-based page admission with prefix matching), advances at most
  one prefill chunk, runs one decode iteration over all fully-prefilled
  slots, and retires finished sequences (EOS or max_new_tokens), returning
  their pages to the refcounted pool.
- **One-dispatch fused step** (the reference's
  single-graph `AnalysisPredictor::ZeroCopyRun` step + true Sarathi
  piggybacking) — the steady-state step dispatches exactly ONE fixed-shape
  program (`models.gpt.serve_step_paged`): vanilla decode slots ride at
  valid=1, spec-verify slots at valid=1+K, and the interleaved prefill chunk
  rides the SAME batch at valid=chunk_len (instead of its own program), with
  per-slot mode implied by (q_offset, valid, page-table row).  Greedy argmax,
  temperature sampling (the shared `gpt.sample_token` split-key discipline)
  and the spec longest-prefix accept scan all run inside the program, so the
  per-step host fetch is a `[B, K+1] + [B]` int32 token/accept buffer —
  ~3 orders of magnitude smaller than `[B, V]` logits — and the decode-side
  compiled-program count is ONE.
- **Double-buffered scheduling** (`double_buffer=True`) —
  the fused dispatch returns un-synced, and where the next batch is
  predictable the NEXT fused program is launched before the last one's
  tokens are read: its decode rows take their input token from the last
  program's `out`, on the device (a select in the jitted wrapper, the same
  ONE program), so the fetch (`engine.sample.sync`), the emission, the
  retirements and the caller's loop all run while the device computes and
  the host's turnaround is behind the device's work.  Predictable
  (`_plan_ahead`) is decided each step from what the engine observes: a
  program is in flight, no lane carries or could carry a draft, growth
  cannot need a victim, and no admission is due (the queue is empty, or no
  slot is free and the program in flight ends no request by its budget).
  Any other step keeps the older order — harvest, admit, build, launch —
  where the token fetch for step n is at the TOP of step n+1 and the device
  waits for `engine.turnaround`; why it was kept is counted
  (`fused_serial_steps{reason}`, `SERIAL_REASONS`), and so is a launch ahead
  that came too late to hide anything (`fused_ahead_late`: the program in
  flight had already finished).  INVARIANT: at every return from `step()`
  at most ONE program is in flight (`_inflight`); two exist only inside
  `step()`, between the launch of k+1 and the harvest of k.  Host scheduler
  state (lengths, page tables, EOS/finish) is updated at harvest time;
  `abort()` harvests the in-flight batch first so bookkeeping stays exact.
  A request that ends where the host cannot foresee it (EOS, a deadline)
  leaves a lane behind in the program already launched: that lane's write
  lands at `lengths[slot]`, inside the slot's own reservation and past what
  `register_prefix` publishes, its harvest drops the token
  (`fused_ahead_discarded_lanes`), and a recurrent state it moved is zeroed
  at the slot's next admission.  In-flight KV writes of a just-retired
  slot are safe: the page pool threads through every dispatch as a donated
  buffer, so device writes are program-ordered — a page recycled to a new
  request is rewritten by the new owner's prefill before its attention can
  read any position the stale write touched.
- **Multi-chip serving** (vLLM's Megatron-style tensor parallelism) —
  `mp=N` shards the model over N chips: Megatron serving params placed once
  at init (`parallel.hybrid.serving_param_specs`), page pool sharded on its
  KVH axis (each chip holds kv_heads/mp heads of every page), paged
  attention per-chip on the local head slice.  The scheduler and the cache
  manager above are mp-oblivious — page tables/lengths/refcounts stay
  replicated host state — and greedy outputs are token-identical to
  single-chip serving.  Executables are AOT-compiled under mp (`_AotCache`)
  so the per-mesh-config program budget stays exact.

- **Observability** (Orca/vLLM-style serving metrics over the repo's own
  profiler subsystem) — every engine counter lives in a
  `inference.metrics.MetricsRegistry` (`engine.metrics`): Prometheus text
  exposition via `metrics.to_prometheus()`, JSON via `metrics.snapshot()`,
  and the flat `stats()` dict unchanged on top.  Each request is stamped at
  enqueue/admission/first-token/finish, feeding queue-time, TTFT, TPOT and
  e2e-latency histograms plus a per-request `RequestOutput.metrics` record
  (abort and prefix-hit paths included).  `step()` appends one record per
  iteration to a bounded ring (`step_trace()`): decode-batch occupancy,
  chunk interleave, verify dispatches, tokens emitted, page-pool levels —
  the victim-selection signal the ROADMAP's preemption work needs.
  `engine.trace(dir)` wraps a serving window in `profiler.RecordEvent` spans
  around the host phases (admit, prefill dispatch, proposer scan, batch
  build, fused dispatch, sample sync, emit), exports them as a chrome trace
  next to the step timeline and a metrics dump, and starts/stops a
  `jax.profiler` device capture when available.  Instrumentation is host-only: zero new
  compiled programs, spans skipped entirely unless a trace is recording.

- **Health & perf signals** (the router-grade signal plane over the
  telemetry above) — sliding-window rates (`inference.metrics.RateWindow`,
  sampled once per step) derive tokens/s, admits/s, preemptions/s,
  timeouts/s and rejects/s over ~10s/1m/5m from the engine counters,
  exposed as pull gauges, `stats()["rates"]` and the Prometheus exposition;
  multi-window SLO burn rates over the deadline-attainment account fold
  with pool pressure, admission saturation and steady-state recompile
  anomalies into `health()` / the `engine_health` gauge
  (ok/degraded/overloaded against `analysis.registry.SERVE_SLO`, served by
  the obs server's ``/healthz`` with 200/503 semantics, fleet-merged
  worst-of); and the static roofline prediction goes live — `warm_decode()`
  traces `engine_step_cost(...).predicted_ms` once (abstract, zero extra
  dispatches or executables), steady-state step times feed an EWMA
  `measured_step_ms` gauge, and `roofline_drift` (measured/predicted) plus
  a drift-band alert counter and a `steady_state_recompiles` anomaly
  counter surface silent perf regressions while they happen.

- **Oversubscribed admission** (vLLM preempt-then-swap-or-recompute, Kwon et
  al. §4.3, over the Sarathi chunked-prefill machinery) —
  `admission="optimistic"` admits on the PROMPT footprint only and grows a
  slot's pages token-granularly as decode proceeds (`PagedKVCache.grow`), so
  live tokens — not worst-case `prompt + max_new_tokens` reservations —
  bound concurrency.  When a growth allocation fails, the engine preempts:
  victims picked by (priority, pages-held, progress), the in-flight
  double-buffered batch harvested first (the TPL007 discipline holds by
  construction: growth runs after the step-top harvest), then either
  **recompute** — the victim's pages are released and it re-queues at the
  head with prompt+generated replayed as a longer prompt through the prefix
  cache and chunked prefill — or **swap** (`preempt="swap"`): its pages are
  gathered into standalone device buffers (`models.gpt.swap_out_pages`, ONE
  fixed-shape executable and one dispatch, its output in pieces of `_swap_w`
  pages), each wanted piece's device->host copy started at once on a worker
  thread while
  the engine goes on stepping (`_gather_d2h`; it waits for the bytes only
  when the victim is re-admitted before they have landed), content parked
  in a bounded host-side numpy pool (`swap_pool_pages`, the fourth `swapped`
  page partition in `PagedKVCache.check_invariants`), and restored by one
  h2d scatter on re-admission (`swap_in_pages`) — no prefill replay at all.
  Greedy outputs are byte-identical preempted-vs-undisturbed: recompute
  replays land on the same chunk/verify logits parity the prefix cache
  already guarantees, and swap restores bit-exact KV.  Requests whose
  worst-case footprint can never fit the pool are rejected at `add_request`
  (`finish_reason="rejected"`) instead of wedging the queue head; a
  per-request `deadline_s` retires overdue work as
  `finish_reason="timeout"`; and an injectable `inference.faults.FaultPlan`
  forces pool pressure / failing swap copies / clock skew so tests can drive
  every preempt interleaving deterministically.

- **KV tiering** (ROADMAP item 3: the swap pool generalized from a
  preemption escape hatch into a capacity tier; the serving-side analogue of
  the reference's save/load_inference_model persistence path) — with
  `kv_tier=True` (default), prefix-cache pages evicted under pool pressure
  spill device -> host instead of being dropped: `PagedKVCache._evict`
  routes them through the SAME fixed-shape `swap_out_pages` gather the
  preemption swap uses — only the evicted pages, rounded up to a piece;
  the copy starts at the eviction, off the engine thread, and a record in
  `_pending_d2h` lands at the first step boundary after its bytes arrive;
  a restore or an export that needs bytes still in flight waits for them,
  and so does a gather that would put more than two slots' width of pages
  in flight — parking the content in a `HostKVTier` under the UNIFIED
  host-pool budget (`swap_pool_pages`, JXP009) shared with swap parking —
  and admission maps a prefix hit from ANY tier: a later request whose
  prefix lives on host (a returning chat session re-submitting its
  conversation) restores it with ONE `swap_in_pages` scatter, collapsing
  TTFT from O(context) prefill to one h2d + scatter.  Over-budget tier
  content cascades to a disk level (`spill_dir=`) or drops, oldest first;
  failed copies degrade spill -> drop and restore -> re-prefill with zero
  leaked pages.  The prefix index itself is upgraded to a ROLLING-HASH
  partial-page index: a prompt sharing only a partial tail of any cached
  page COW-copies (or tier-scatters) the matched fraction and prefills only
  the true remainder.  Zero new executables: spill/restore reuse the two
  swap programs.

`bench_serve.py` replays a Poisson request stream through this engine and
reports decode tokens/s/chip, TTFT percentiles, prefix-cache hit rate,
accepted tokens per verify step, compiled-program counts and — under
`--oversubscribe F` — preemptions/step, the swap-vs-recompute split and
goodput vs an unpressured replay.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import os
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import wait as _wait_futures
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.registry import SERVE_SLO
from ..models import gpt as gpt_mod
from ..models import hybrid as hybrid_mod
from ..profiler import profiler as _prof
from .cache import PagedKVCache, RecurrentStateTable
from .faults import FaultInjected, FaultPlan
from .health import HEALTH_CODES, evaluate_engine_health
from .metrics import MetricsRegistry
from .spec import DraftProposer, NgramProposer
from .tracing import RequestTrace

# measured-step EWMA smoothing: ~the last 10 busy steps dominate, so the
# drift gauge reacts inside a scrape interval without tracking single-step
# scheduler noise
_EWMA_ALPHA = 0.2


@dataclasses.dataclass(eq=False)
class Request:
    """One generation request: prompt token ids + a decode budget.

    temperature=None inherits the engine's sampling mode; 0.0 forces the
    greedy fast path for this request (argmax, PRNG-key independent) even on
    a sampling engine.  priority orders preemption victims (LOWER priorities
    are preempted first; default 0); deadline is the absolute engine-clock
    instant past which the request is retired as finish_reason="timeout".
    eq=False: identity comparison only — the generated __eq__ would compare
    numpy prompts, whose truth value is ambiguous."""
    prompt: np.ndarray
    max_new_tokens: int = 16
    request_id: int = -1
    t_enqueue: float = 0.0
    temperature: Optional[float] = None
    priority: int = 0
    deadline: Optional[float] = None


@dataclasses.dataclass
class RequestMetrics:
    """Wall-clock lifecycle of one request, stamped with the engine clock
    (injectable, monotonic — absolute fields are engine-clock readings, not
    epoch time).  Answers "why was this request slow" after the fact: a large
    `queue_s` is admission pressure (pages or slots), a large `ttft_s` with a
    small `queue_s` is prefill cost, a large `tpot_s` is decode contention.
    Stage stamps are None for stages the request never reached (an abort
    while queued has only t_enqueue/t_finish)."""
    t_enqueue: float
    t_admit: Optional[float] = None         # popped from the queue into a slot
    t_first_token: Optional[float] = None   # joined the decode set
    t_finish: Optional[float] = None        # retired (stop/length/abort)
    queue_s: Optional[float] = None         # t_admit - t_enqueue
    ttft_s: Optional[float] = None          # t_first_token - t_enqueue
    tpot_s: Optional[float] = None          # decode time per token after first
    e2e_s: Optional[float] = None           # t_finish - t_enqueue
    cached_tokens: int = 0                  # prompt tokens from the prefix cache
    n_generated: int = 0
    preemptions: int = 0                    # times this request was preempted
    # one (engine clock, tokens) pair each time tokens were appended to the
    # request: the first where t_first_token is stamped, then one a harvest
    # (several tokens under speculation) — inter-token latency comes from
    # here; sum(tokens) == n_generated, bounded by max_new_tokens
    emit_times: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list)


@dataclasses.dataclass
class RequestOutput:
    request_id: int
    prompt: np.ndarray
    token_ids: List[int]            # generated tokens (prompt excluded)
    finish_reason: str              # "stop" (EOS) | "length" (budget) |
                                    # "abort" | "timeout" (deadline) |
                                    # "rejected" (footprint can never fit)
    cached_tokens: int = 0          # prompt tokens served from the prefix cache
    ttft_s: Optional[float] = None  # enqueue -> first generated token
    metrics: Optional[RequestMetrics] = None    # full lifecycle record
    trace: Optional[RequestTrace] = None        # structured event timeline
                                                # (None with tracing off)

    @property
    def tokens(self) -> np.ndarray:
        """prompt + generated, the `generate()`-compatible view.  Both inputs
        are host data by construction (add_request normalizes the prompt to
        numpy; token_ids are Python ints synced during step()), so these
        np.asarray calls never touch the device."""
        return np.concatenate(
            [np.asarray(self.prompt, np.int64), np.asarray(self.token_ids,
                                                           np.int64)])


@dataclasses.dataclass
class _Running:
    request: Request
    slot: int
    generated: List[int]
    cached_tokens: int = 0
    ttft_s: Optional[float] = None
    greedy: bool = True             # resolved request temperature == 0.0
    spec_zero_streak: int = 0       # consecutive verify events accepting 0
    spec_off: bool = False          # adaptive back-off: stop drafting


@dataclasses.dataclass
class _Prefilling:
    """A slot whose prompt KV is still landing: `filled` prompt tokens are in
    pages (prefix-cache hits + completed chunks); the slot joins the decode
    set only once filled == len(prompt).  `prompt` is the EFFECTIVE prompt
    being prefilled — for a preempted request resuming via recompute it is
    the original prompt + the tokens in `prior` (generation already banked),
    replayed as one longer prompt; `ttft`/`spec_off`/`streak` carry the
    pre-preemption state back into the decode set."""
    request: Request
    slot: int
    filled: int
    cached_tokens: int
    prompt: np.ndarray = None
    prior: Optional[List[int]] = None
    ttft: Optional[float] = None
    spec_off: bool = False
    streak: int = 0


def _pow2_buckets(lo: int, hi: int) -> List[int]:
    out = []
    b = lo
    while b <= hi:
        out.append(b)
        b *= 2
    return out


class _NullSpan:
    """Stand-in for `profiler.RecordEvent` when nothing is recording: the
    decode loop enters a span per host phase per step, so the off state must
    cost one attribute read and an empty context manager, not a
    perf_counter_ns + TraceAnnotation pair."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def begin(self):
        pass

    def end(self):
        pass


_NULL_SPAN = _NullSpan()

# Host-phase span names `engine.trace()` emits into the chrome trace — one
# tuple so tests and dashboards don't chase string literals through the
# scheduler.  admit covers prefix matching + reservation (+ the one-shot
# bucketed prefill when taken synchronously); dispatch spans end when the
# async call returns, sample/accept spans contain the blocking device sync.
# The step dispatches through engine.fused.dispatch; prefill.dispatch covers
# the bucketed one-shot prefill and the standalone chunk program of a
# prefix-hit tail.  engine.turnaround (see `LLMEngine._turn_begin`) is the
# host stretch the device waits for between two fused programs; emit, admit,
# batch.build and fused.dispatch (with fused.h2d, its five puts, inside) tile
# it.  A step that launches ahead of the last result (`_plan_ahead`) has no
# such stretch: its batch.build, fused.dispatch, sample.sync and emit lie
# under engine.step.ahead, in that order.  engine.step holds at most one of
# step.ahead (the step launched before it read) and step.serial (it read
# first, and had a program to read or work to launch: turnaround and admit
# lie inside it); a step with neither only polled, or only read the last
# program.  prefill.sync, inside sample.sync, is the blocking first-token
# read of a prefill program alone — never the fused program's.  admit.reserve
# is one queue head's prefix match and page reservation with the eviction it
# sets off.  swap.gather is the host side of one gather dispatch (a spill's or
# a swap-out's); swap.d2h is the engine thread taking one piece from the fetch
# worker: .ready its wait for bytes still in flight, .copy the hand-over;
# swap.fetch is the copy itself, on the worker's own thread.
ENGINE_SPANS = (
    "engine.step",
    "engine.step.ahead",
    "engine.step.serial",
    "engine.turnaround",
    "engine.emit",
    "engine.admit",
    "engine.admit.reserve",
    "engine.prefill.dispatch",
    "engine.prefill.sync",
    "engine.spec.propose",
    "engine.batch.build",
    "engine.fused.dispatch",
    "engine.fused.h2d",
    "engine.sample.sync",
    "engine.swap.gather",
    "engine.swap.fetch",
    "engine.swap.d2h",
    "engine.swap.d2h.ready",
    "engine.swap.d2h.copy",
    "engine.swap.h2d",
)

# why a step kept the harvest-first order, in the order `_plan_ahead` tests
# them: the `reason` label of `fused_serial_steps`, the ring's `serial_reason`
SERIAL_REASONS = ("idle_start", "draft", "prefilling", "admission_due",
                  "budget_end", "pages")


# the most one piece of a spill/swap-out gather may hold (`LLMEngine._swap_w`
# pages).  Small, because the link is fastest there and a token read issued
# beside one piece's copy is not held up: on a TPU v5e a 16 MB piece crosses
# at 4.8 GB/s and a 134 MB buffer at 1.0 GB/s, and a small `device_get`
# behind the large copy waits 10-13 ms, behind the piece under 1 ms more
# (PERF.md, PR 31).  Not smaller: 8 MB pieces cross at 3.8 GB/s, and at 16
# rounding a page count up to a piece already costs a quarter or less
_D2H_PIECE_BYTES = 16 << 20


def _d2h_live(rec: Dict[str, object]) -> bool:
    """A pending record the engine still has to land — not one consumed by
    a swap-in, degraded by a failed copy or dropped with its request."""
    return rec["kind"] in ("spill", "swap") and not rec.get("fetched")


def _fetch_piece(data, n: int) -> List[Dict[str, np.ndarray]]:
    """The fetch worker's task: the blocking device->host copy of one
    gathered piece, returned as its `n` wanted pages ({lane: [L, page, ...]}
    each, contiguous — the pads and the piece's buffer die here).  Runs off
    the engine thread and touches nothing of the engine."""
    host = jax.device_get(data)
    return [{name: np.ascontiguousarray(a[:, i]) for name, a in host.items()}
            for i in range(n)]


def _fetch_on_worker(data, n: int) -> List[Dict[str, np.ndarray]]:
    """`_fetch_piece` as the fetch worker runs it: under `engine.swap.fetch`
    while a Profiler records, so the copy shows on the worker's own host
    line (one span a piece: with `_D2H_PIECE_BYTES` it is the link's rate)."""
    with _prof.RecordEvent("engine.swap.fetch") if _prof.is_recording() \
            else _NULL_SPAN:
        return _fetch_piece(data, n)


class _AotCache:
    """`jax.jit` replacement for the tensor-parallel serving path: one
    `lower().compile()` per input signature (shape/dtype of every leaf),
    cached here.

    Why not plain jit: with donated, committed-sharded inputs (the mp pool),
    jit's two dispatch layers (per-function fastpath + eval-path global cache)
    each build the SAME program once — every serving executable showed two
    XLA compilations and two cache entries for one program, which both wastes
    a warmup compile per program and breaks the compiled-program budget that
    `tools/check_program_count.py` enforces.  AOT-compiling keeps the program
    set exact: `_cache_size()` is the number of DISTINCT programs, the number
    the budget is about.  Inputs whose sharding diverges from the compiled
    signature fail loudly instead of recompiling — under mp every input is
    either host data (replicated) or pinned by the engine, so divergence is a
    bug, not traffic.

    skip_args: leading args excluded from the dispatch key — the params
    pytree (placed once at init, its shapes can never change) would otherwise
    be re-flattened into hundreds of (shape, dtype) tuples on every decode
    dispatch."""

    def __init__(self, fn, donate_argnums, skip_args=0):
        self._jit = jax.jit(fn, donate_argnums=donate_argnums)
        self._skip = skip_args
        self._cache: Dict = {}

    def __call__(self, *args):
        key = tuple((x.shape, str(x.dtype))
                    for x in jax.tree_util.tree_leaves(args[self._skip:]))
        exe = self._cache.get(key)
        if exe is None:
            exe = self._jit.lower(*args).compile()
            self._cache[key] = exe
        return exe(*args)

    def _cache_size(self) -> int:
        return len(self._cache)


class LLMEngine:
    """Continuous-batching serving engine over the functional GPT core.

    params/config: the `models.gpt` pytree + GPTConfig.  `num_slots` is the
    fixed decode batch; `num_pages`/`page_size` size the KV pool (default pool
    is half of the dense `num_slots * max_model_len` footprint — the paged
    cache's whole point is that this still serves full-length traffic as long
    as *live* tokens fit).  Greedy by default; temperature/top_k compile the
    sampling variant of the same executables.

    `prefix_cache=True` shares prompt pages across requests copy-on-write;
    `prefill_chunk=N` switches prompt processing from the bucketed one-shot
    ladder to N-token chunks interleaved one-per-step with decode.  Both are
    scheduler-level: the decode executable, page pool and table shapes are
    identical in every mode.  `prefill_chunk="auto"` picks the chunk width
    adaptively: `spec_len + 1` (the fused program is `max(spec_len+1,
    chunk)` tokens wide, so a wider chunk pads every decode row), or one
    page when spec is off.

    `spec_len=K` (> 0) enables speculative decoding: `draft_proposer`
    (default `NgramProposer`) guesses up to K continuation tokens per greedy
    slot each iteration, the step program scores K+1 positions, and greedy
    longest-prefix acceptance emits 1..K+1 tokens per step with exact
    vanilla-decode token parity.  Drafting applies only to greedy slots —
    acceptance needs a deterministic pick — so sampled slots ride the same
    program at valid=1.  `spec_backoff_window=W` (adaptive
    spec_len, 0 disables): a slot whose drafts go W consecutive verify events
    without a single accepted token stops being drafted for — it skips the
    proposer scan and rides verify at valid=1 (`stats()["spec_backoffs"]`).

    The steady-state step is ONE fixed-shape dispatch with on-device
    sampling/acceptance (`gpt.serve_step_paged`): a
    busy step's decode slots, verify slots and the interleaved prefill chunk
    share one `[num_slots, max(spec_len+1, prefill_chunk)]` batch, and the
    host fetches a small int token/accept buffer instead of `[B, V]` logits.
    `double_buffer=True` (default) makes the dispatch return
    un-synced and, on every step whose next batch is predictable, launches
    program *n+1* before it fetches program *n*'s tokens (its rows take
    them on the device), so the device computes while the host fetches,
    emits and schedules; any other step fetches at its top, as before —
    finishes are observed one `step()` later than in synchronous mode,
    which `run()`/`has_work` account for.  `double_buffer=False` is the
    synchronous schedule: launch, then harvest, inside one step.

    Observability: `engine.metrics` is the metrics registry (counters,
    page/queue gauges, latency histograms; `to_prometheus()` for scraping),
    `stats()` the flat dict benches consume, `step_trace()` the per-iteration
    ring timeline (`trace_ring` entries), and `engine.trace(dir)` a capture
    window writing chrome-trace + timeline + metrics dumps.  `clock` injects
    the monotonic clock behind every lifecycle stamp (default
    `time.perf_counter`) so tests drive deterministic latencies.

    Overload behavior: `admission="optimistic"` admits on the prompt
    footprint only and grows pages token-granularly as decode proceeds —
    live-token capacity, not worst-case reservations, bounds concurrency.
    On pool pressure (a failed growth) the engine preempts victims — lowest
    `priority` first, then most pages held, least progress, youngest —
    and either releases + re-queues them for recompute (prompt+generated
    replayed as a longer prompt through the prefix cache; the default) or
    swaps their KV pages to a bounded host-side pool (`preempt="swap"`,
    `swap_pool_pages` cap) restored by one h2d scatter on re-admission.
    Greedy outputs stay byte-identical preempted-vs-undisturbed.
    `admission="reservation"` (default) keeps the PR-1 full-footprint
    reservation discipline — no growth, no preemption.  Per-request
    `deadline_s` retires overdue work as `finish_reason="timeout"`; a
    request whose `prompt + max_new_tokens` footprint exceeds the whole pool
    is rejected at `add_request` (`finish_reason="rejected"`) instead of
    wedging the queue head.  `fault_plan` injects deterministic pool
    pressure / swap-copy failures / clock skew (tests only; see
    `inference.faults.FaultPlan`).

    KV tiering: `kv_tier=True` (default; needs the prefix cache and a
    positive `swap_pool_pages`) spills LRU-evicted prefix pages to a host
    tier under the unified host-pool budget instead of dropping them, and
    admission restores a matched prefix from host (or the optional
    `spill_dir=` disk level) with one `swap_in_pages` scatter — a
    returning session skips its re-prefill entirely.  `kv_tier=False`
    restores the PR-10 drop-on-evict behavior (`bench_serve.py
    --no-kv-tier`).

    Quantized serving: `weight_dtype="int8"` PTQ-quantizes the serving
    matmul weights once at init (symmetric per-channel,
    `quantization.serving.quantize_serving_params`; dequant rides per block
    inside the existing executables — zero program-count change) and
    `kv_dtype="int8"` stores the KV page pool as int8 pages + per-token
    scale lanes, quantized at every in-program write and dequantized per
    page on read inside the paged-attention kernels.  Both default off and
    the fp engine is byte-identical to a quantization-free build; the
    quantized engine keeps every internal parity bar (mp/preempt)
    against itself, while outputs vs the fp engine are a top-1 agreement
    RATE (quantization is lossy) reported by `bench_serve.py
    --weight-dtype/--kv-dtype int8`.

    `mp=N` (or an explicit `mesh` with an 'mp' axis) serves tensor-parallel
    over N chips: params are placed ONCE at init in the Megatron serving
    layout (`parallel.hybrid.serving_param_specs` — qkv/fc1 column-, proj/fc2
    row-sharded, embedding/head VOCAB-sharded with the packed qkv permuted
    into the per-partition column layout), the page pool shards on its KVH
    axis (each chip holds kv_heads/mp heads of every page), and the paged
    attention runs per-chip on the local head slice.  The head never
    materializes replicated [B, V] logits: the embed is a masked local
    take + psum, the head matmul produces [.., V/mp] shards, and
    argmax/top-k/sampling merge per-chip (value, global index) pairs on
    device (`models.gpt.sharded_argmax` / `sample_token`).  All scheduler
    state (page tables, lengths, refcounts, prefix index) stays replicated
    host memory — the paging/prefix/COW logic is mp-oblivious — and greedy
    outputs are token-identical to single-chip serving.  Per-mesh-config the
    compiled decode-side program count is unchanged: the ONE fused step
    program.
    """

    # always True: benchmarks/drivers/serve.py:66 and serve_hybrid.py:115
    # read it in their default-mode check
    fused = True

    def __init__(self, params, config: gpt_mod.GPTConfig, *,
                 num_slots: int = 4, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 max_model_len: Optional[int] = None,
                 prefill_buckets: Optional[List[int]] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = True,
                 eos_token_id: Optional[int] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 spec_len: int = 0,
                 draft_proposer: Optional[DraftProposer] = None,
                 spec_backoff_window: int = 8,
                 double_buffer: Optional[bool] = None,
                 admission: str = "reservation",
                 preempt: str = "recompute",
                 swap_pool_pages: Optional[int] = None,
                 kv_tier: bool = True,
                 spill_dir: Optional[str] = None,
                 spill_disk_pages: Optional[int] = None,
                 page_store=None,
                 role: Optional[str] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 weight_dtype: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 mesh=None, mp: Optional[int] = None,
                 seed: int = 0,
                 clock: Optional[Callable[[], float]] = None,
                 trace_ring: int = 512,
                 request_tracing: bool = True,
                 trace_retention: Optional[int] = 4096):
        import jax.sharding as jsh

        from ..quantization.serving import (kv_page_bytes,
                                            normalize_quant_dtype,
                                            quantize_serving_params)

        # quantized serving (ref QAT/PTQ deployment form + int8 predictor):
        # weight_dtype="int8" PTQ-quantizes the serving matmul weights ONCE
        # at init (symmetric per-channel; dequant rides inside the existing
        # executables, so the program set is unchanged); kv_dtype="int8"
        # stores the KV page pool as int8 + per-token scale lanes (the
        # paged-attention kernels dequantize per page on read).  Both default
        # OFF — the fp engine is byte-identical to a quantization-free build.
        self.weight_dtype = normalize_quant_dtype(weight_dtype, "weight_dtype")
        self.kv_dtype = normalize_quant_dtype(kv_dtype, "kv_dtype")
        # a patterned configuration (`models.hybrid`) has programs of its
        # own, and with an `M` in its pattern it keeps recurrent state per
        # slot beside the page pool.  Every path that moves, shares or rolls
        # back a request's pages would have to move, snapshot or roll back
        # that state too; the ones that cannot yet are refused here, loudly,
        # rather than served from a wrong state.  A pattern WITHOUT `M` keeps
        # everything in pages (K/V or latent rows): it is a paged model, with
        # the prefix index, parked pages and the spill tier
        pattern = getattr(config, "layer_pattern", None)
        self.patterned = pattern is not None
        self.recurrent = self.patterned and "M" in pattern
        if self.patterned:
            self._refuse_for_pattern(
                self.recurrent, spec_len=spec_len, admission=admission,
                preempt=preempt, weight_dtype=self.weight_dtype,
                kv_dtype=self.kv_dtype, mp=mp, mesh=mesh, role=role)
        self._kv_page_bytes = kv_page_bytes(config, page_size, self.kv_dtype)
        if self.weight_dtype == "int8":
            # quantization is host numpy; re-place the tree ONCE here so no
            # dispatch ever pays an implicit h2d for a param leaf (the
            # steady-state loop runs under transfer_guard("disallow"))
            params = jax.tree_util.tree_map(
                jnp.asarray, quantize_serving_params(params, config))

        if mp is not None and mp > 1 and mesh is None:
            from ..parallel.hybrid import serving_mesh
            mesh = serving_mesh(mp)
        self.mesh = mesh
        self.mp = int(dict(mesh.shape).get("mp", 1)) if mesh is not None else 1
        if self.mp > 1:
            if config.num_heads % self.mp or config.kv_heads % self.mp:
                raise ValueError(
                    f"mp={self.mp} must divide num_heads "
                    f"({config.num_heads}) and kv_heads ({config.kv_heads})")
            if config.vocab_size % self.mp:
                raise ValueError(
                    f"mp={self.mp} must divide vocab_size "
                    f"({config.vocab_size}) — the embedding/head shard over "
                    f"the vocab axis")
            # place the serving params ONCE at init: Megatron block layout
            # with the embedding/head VOCAB-SHARDED
            # (parallel.hybrid.serving_param_specs); the packed qkv leaves
            # are permuted into the per-partition column layout first so each
            # chip's shard lands exactly on its own head slices — no
            # replicate→reslice staging at placement or inside the step
            from ..parallel.hybrid import (pack_qkv_partitions,
                                           serving_param_specs)
            params = pack_qkv_partitions(params, config, self.mp)
            specs = serving_param_specs(config, params)
            self._param_shardings = jax.tree_util.tree_map(
                lambda s: jsh.NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, jsh.PartitionSpec))
            params = jax.device_put(params, self._param_shardings)
            # page pool sharded on the KVH axis: every chip holds
            # kv_heads/mp heads of EVERY page, so the host-side page tables /
            # lengths / refcounts (inference.cache) stay replicated and the
            # prefix-cache/COW/eviction logic is mp-oblivious.  NOTE the spec
            # leaves the trailing hd dim implicit: executables re-derive the
            # output sharding in this normalized form, and a trailing-None
            # variant hashes as a DIFFERENT executable-cache key (one silent
            # recompile per jit on the second call)
            self._pool_sharding = jsh.NamedSharding(
                mesh, jsh.PartitionSpec(None, None, None, "mp"))
            self._repl_sharding = jsh.NamedSharding(mesh, jsh.PartitionSpec())
        else:
            self._param_shardings = None
            self._pool_sharding = None
            self._repl_sharding = None
        self.params = params
        self.config = config
        self.eos_token_id = eos_token_id
        max_model_len = max_model_len or config.max_seq_len
        if max_model_len % page_size:
            raise ValueError("max_model_len must be a multiple of page_size")
        if not config.use_rope and max_model_len > config.max_seq_len:
            # learned positions: jnp.take clamps past wpe's last row, which
            # would be silently wrong — generate() raises here too
            raise ValueError(
                f"max_model_len {max_model_len} exceeds max_seq_len "
                f"{config.max_seq_len} (learned positions)")
        self.max_model_len = max_model_len
        max_pages_per_slot = max_model_len // page_size
        if num_pages is None:
            # default: half the dense footprint (+ the null page)
            num_pages = max(2, num_slots * max_pages_per_slot // 2 + 1)
        if prefill_buckets is None:
            prefill_buckets = _pow2_buckets(page_size, max_model_len)
            if not prefill_buckets or prefill_buckets[-1] != max_model_len:
                # non-power-of-2 max_model_len: cover the top tokens too
                prefill_buckets.append(max_model_len)
        self.buckets = sorted(prefill_buckets)
        for b in self.buckets:
            if b % page_size or b > max_model_len:
                raise ValueError(f"bucket {b} incompatible with page_size "
                                 f"{page_size} / max_model_len {max_model_len}")
        if spec_len < 0:
            raise ValueError(f"spec_len must be >= 0, got {spec_len}")
        if prefill_chunk == "auto":
            # adaptive chunk width: the fused program's token width is
            # max(spec_len+1, prefill_chunk), so any chunk wider than the
            # verify lane pads EVERY decode row of EVERY fused dispatch with
            # dead positions.  spec_len+1 makes the chunk ride the fused
            # batch at exactly the width verify already needs (zero decode
            # padding); with spec off there is no verify lane to hide
            # behind, so fall back to one page per chunk — page-granular KV
            # writes, and a bounded 1-page cost on decode rows.
            prefill_chunk = min(spec_len + 1 if spec_len else page_size,
                                max_model_len)
        if prefill_chunk is not None and not 1 <= prefill_chunk <= max_model_len:
            raise ValueError(f"prefill_chunk {prefill_chunk} outside "
                             f"[1, {max_model_len}]")
        self.prefill_chunk = prefill_chunk
        self.chunked = prefill_chunk is not None
        # chunk width also serves prefix-hit tails in bucketed mode, where the
        # largest bucket bounds any tail in one call
        self._chunk = prefill_chunk if self.chunked else self.buckets[-1]
        # a prefix hit hands a request the PAGES of its prefix; the recurrent
        # state at that boundary was never kept, so for a recurrent
        # configuration no hit is usable: the index is off, finished
        # requests' pages go straight back to the free list (nothing to keep
        # or spill), and every admission that would have looked is counted
        # (`prefix_lookups_skipped_no_state`)
        self._prefix_wanted = bool(prefix_cache)
        self.prefix_cache = prefix_cache = bool(prefix_cache) and \
            not self.recurrent
        if spec_len and spec_len + 1 > max_model_len:
            raise ValueError(f"spec_len {spec_len} + 1 exceeds max_model_len")
        self.spec_len = spec_len
        self.proposer = (draft_proposer or NgramProposer()) if spec_len \
            else draft_proposer
        if spec_backoff_window < 0:
            raise ValueError(
                f"spec_backoff_window must be >= 0, got {spec_backoff_window}")
        self.spec_backoff_window = spec_backoff_window
        # fused one-dispatch step (see module docstring): the program's token
        # width covers the widest lane that can ride it — K+1 verify rows
        # and, in chunked mode, the prefill chunk (choose prefill_chunk near
        # spec_len+1 to minimize decode-row padding)
        self.double_buffer = True if double_buffer is None \
            else bool(double_buffer)
        self._fused_T = max(self.spec_len + 1,
                            prefill_chunk if self.chunked else 1)
        if admission not in ("reservation", "optimistic"):
            raise ValueError(f"admission must be 'reservation' or "
                             f"'optimistic', got {admission!r}")
        if preempt not in ("recompute", "swap"):
            raise ValueError(f"preempt must be 'recompute' or 'swap', "
                             f"got {preempt!r}")
        self.admission = admission
        self.optimistic = admission == "optimistic"
        self.preempt = preempt
        self._faults = fault_plan or FaultPlan()
        self.cache = PagedKVCache(num_pages, page_size, num_slots,
                                  max_pages_per_slot)
        if self.recurrent:
            self.cache.attach_state(RecurrentStateTable(
                num_slots, config.state_bytes_per_slot()))
        # UNIFIED host pool bound, in pages: preempt="swap" victim parking
        # AND the kv_tier spilled-prefix store share this one ceiling (the
        # JXP009 budget).  Default mirrors the device pool — the host
        # obligation can never exceed what the device could hold
        self.swap_pool_pages = (num_pages - 1) if swap_pool_pages is None \
            else int(swap_pool_pages)
        if self.swap_pool_pages < 0:
            raise ValueError(
                f"swap_pool_pages must be >= 0, got {swap_pool_pages}")
        # KV tiering (ROADMAP item 3): retired prefix-cache pages spill
        # device -> host (-> optional disk via spill_dir) instead of being
        # LRU-dropped, and admission restores a prefix hit from ANY tier
        # with one swap_in_pages scatter — no prefill replay.  Needs the
        # prefix index (the trie keys the tier) and host-pool room.
        self.kv_tier = bool(kv_tier) and prefix_cache and \
            self.swap_pool_pages > 0
        self.spill_dir = spill_dir if self.kv_tier else None
        # disaggregated serving role (ROADMAP item 2): "prefill" engines run
        # admission + chunked prefill and export finished prompts through the
        # tier store; "decode" engines tier-restore them.  None = colocated
        # (the classic engine).  The role changes ROUTING and HEALTH only —
        # every engine keeps the full executable set, so a degraded handoff
        # can always fall back to local re-prefill.
        if role not in (None, "prefill", "decode"):
            raise ValueError(f"role must be 'prefill', 'decode' or None, "
                             f"got {role!r}")
        self.role = role
        self._store_restored_nodes = 0
        if self.kv_tier:
            from .cache import HostKVTier
            self.cache.attach_tier(
                HostKVTier(spill_dir=self.spill_dir,
                           disk_pages=spill_disk_pages,
                           store=page_store),
                self._spill_prefix_nodes)
            # durable-index re-attach: merge any kvindex_* blobs a previous
            # process (or a prefill peer on the same store) published, so a
            # restarted engine's first returning session tier-restores with
            # one scatter instead of re-prefilling
            self._store_restored_nodes = self.cache.load_tier_index()
        # optimistic-admission watermark: global free-page headroom kept back
        # at admission (vLLM's watermark_blocks), ~1% of the pool
        self._watermark = max(1, (self.cache.num_pages - 1) // 100)
        if self.patterned:
            self._pool = hybrid_mod.init_paged_cache(config, num_pages,
                                                     page_size, num_slots)
        else:
            self._pool = gpt_mod.init_paged_cache(config, num_pages, page_size,
                                                  kv_dtype=self.kv_dtype)
        if self._pool_sharding is not None:
            self._pool = jax.device_put(
                self._pool, {n: self._pool_sharding for n in self._pool})
        self._queue: deque = deque()
        self._running: Dict[int, _Running] = {}
        self._prefilling: Dict[int, _Prefilling] = {}   # slot -> state, FIFO
        self._free_slots = list(range(num_slots - 1, -1, -1))
        self._ids = itertools.count()
        self._key = jax.random.key(seed)
        if self.mp > 1:
            # commit the key to the mesh (replicated) up front: an uncommitted
            # first-call key is a different executable-cache signature than the
            # committed key every later call carries — one silent recompile
            self._key = jax.device_put(
                self._key, jsh.NamedSharding(mesh, jsh.PartitionSpec()))
        self._outputs: Dict[int, RequestOutput] = {}

        # ---- observability state (all host-side: no executable sees any of
        # this, so the compiled-program budget is untouched) ----------------
        if trace_ring < 1:
            raise ValueError(f"trace_ring must be >= 1, got {trace_ring}")
        if trace_retention is not None and trace_retention < 0:
            raise ValueError(f"trace_retention must be >= 0 or None "
                             f"(unbounded), got {trace_retention}")
        m = MetricsRegistry(namespace="llm_engine",
                            clock=clock or time.perf_counter)
        self.metrics = m
        self._now = m.now
        self._decode_iters = m.counter("decode_iterations",
                                       "decode-side engine iterations")
        self._decode_tokens = m.counter("decode_tokens",
                                        "tokens emitted by decode/verify")
        self._prefill_chunks = m.counter("prefill_chunks",
                                         "chunk-prefill dispatches")
        self._prefilled_tokens = m.counter("prefilled_tokens",
                                           "prompt tokens actually computed")
        self._prefix_cached_tokens = m.counter(
            "prefix_cached_tokens", "prompt tokens served from the cache")
        self._prefix_hit_requests = m.counter(
            "prefix_hit_requests", "requests admitted with a prefix hit")
        self._cow_copies = m.counter("cow_page_copies",
                                     "copy-on-write page copies")
        self._verify_steps = m.counter("verify_steps",
                                       "verify-program dispatches")
        self._spec_events = m.counter(
            "spec_events", "per-slot verify events carrying a draft")
        self._spec_drafted = m.counter("spec_drafted_tokens",
                                       "drafted tokens offered to verify")
        self._spec_accepted = m.counter("spec_accepted_tokens",
                                        "drafted tokens accepted")
        self._spec_emitted = m.counter(
            "spec_emitted_tokens", "accepted + bonus tokens emitted")
        self._spec_backoffs = m.counter(
            "spec_backoffs", "slots that stopped drafting (adaptive back-off)")
        self._finished_requests = m.counter(
            "finished_requests", "requests retired by stop/length")
        self._aborted_requests = m.counter("aborted_requests",
                                           "requests retired by abort()")
        self._preemptions = m.counter(
            "preemptions", "running requests evicted under pool pressure")
        self._preempt_swaps = m.counter(
            "preempt_swaps",
            "preemptions whose KV swap-out d2h completed")
        self._preempt_recomputes = m.counter(
            "preempt_recomputes",
            "preemptions resolved by recompute (incl. degraded swaps)")
        self._swapped_pages_c = m.counter(
            "swapped_pages", "KV pages delivered to the host swap pool")
        self._swap_ms_c = m.counter(
            "swap_ms", "milliseconds spent in swap d2h/h2d copies")
        # how far the paged kernel's length-bounded walk engages: the pages
        # it visits against the table entries the programs were handed
        # (walked / entries is the share of a whole-table walk that is left)
        self._pages_walked = m.counter(
            "paged_pages_walked",
            "KV pages the paged attention kernel walks, a layer: sum over "
            "dispatched rows of ceil((q_offset + valid) / page)")
        self._table_entries = m.counter(
            "paged_table_entries",
            "page-table entries handed to those dispatches (rows x "
            "max pages a slot)")
        # what crossed at the two swap boundaries against what was wanted.
        # Out: `_swap_w`-page pieces, so moved/useful says how tight the
        # gather is (under 1 + `_swap_w`/n for n pages); in: still a
        # max_pages_per_slot-wide staging buffer whatever the page count
        self._d2h_fetches = m.counter(
            "swap_d2h_fetches",
            "gathered swap/spill pieces whose bytes the engine took")
        self._d2h_bytes = m.counter(
            "swap_d2h_bytes", "bytes those fetches moved (the pieces' nbytes)")
        self._d2h_useful = m.counter(
            "swap_d2h_useful_bytes",
            "bytes of the pages those fetches were for (pages x page bytes)")
        # the copies run on the fetch worker; these say what they still cost
        # the engine thread and how far ahead of it they run
        self._d2h_blocked_ms = m.counter(
            "swap_d2h_blocked_ms",
            "milliseconds the engine thread waited for spill/swap bytes to "
            "land (inside swap_ms)")
        self._d2h_landed_free = m.counter(
            "swap_d2h_landed_free",
            "fetches whose bytes had landed when the engine came for them")
        self._d2h_bp_waits = m.counter(
            "swap_d2h_backpressure_waits",
            "gathers held until the oldest piece in flight had landed (the "
            "bound on gathered pages was reached)")
        m.gauge("swap_d2h_inflight_pages", lambda: self._d2h_inflight,
                "pages of gathered device buffers whose bytes the engine has "
                "not taken yet (bounded by `_d2h_bound`, two slots' width)")
        self._h2d_bytes = m.counter(
            "swap_h2d_bytes",
            "bytes staged to the device by swap-in/tier-restore scatters")
        self._h2d_useful = m.counter(
            "swap_h2d_useful_bytes",
            "bytes of the pages those scatters restored")
        # recurrent configurations only (zero otherwise): the expert layers'
        # routing account and the state lanes' traffic, counted inside the
        # two hybrid programs and fetched with their tokens (`_note_aux`)
        self._aux_counters = {
            "moe_pairs_here": m.counter(
                "moe_pairs_here",
                "token-expert picks that fell on experts held here (computed)"),
            "moe_pairs_away": m.counter(
                "moe_pairs_away",
                "token-expert picks that fell on absent experts (left out)"),
            "moe_experts_touched": m.counter(
                "moe_experts_touched",
                "distinct held experts with >= 1 token, summed over expert "
                "layers and programs"),
            "ssm_slots_live": m.counter(
                "ssm_slots_live",
                "slots whose recurrent state a program read and wrote, "
                "summed over programs"),
            "ssm_state_resets": m.counter(
                "ssm_state_resets",
                "slots a program started from a zero state (a new request)"),
            "latent_tokens_written": m.counter(
                "latent_tokens_written",
                "latent rows that lay written behind the programs' active "
                "slots (sum of q_offset + valid: what a latent layer's "
                "attention read, not what was reserved)"),
            "mla_absorbed_rows": m.counter(
                "mla_absorbed_rows",
                "query tokens that went through absorbed latent attention "
                "(decode, chunk and prefill rows alike)"),
            # DSA configurations only: one DSA layer's worth (every DSA
            # layer of a program scores and selects the same rows)
            "dsa_rows_scored": m.counter(
                "dsa_rows_scored",
                "rows the sparse-attention indexer scored, summed over query "
                "tokens (one DSA layer's worth)"),
            "dsa_rows_selected": m.counter(
                "dsa_rows_selected",
                "rows the sparse latent attention read, sum of min(context, "
                "index_topk) over query tokens (one DSA layer's worth)"),
            "dsa_rows_searched": m.counter(
                "dsa_rows_searched",
                "query tokens whose selection needed the top-k search: more "
                "than index_topk candidates (one DSA layer's worth)"),
            "dsa_expanded_tokens": m.counter(
                "dsa_expanded_tokens",
                "query tokens whose sparse attention ran in the expanded "
                "form (per-head keys and values: a long pass)"),
            # the expert layers of a long pass (DSA configurations only)
            "moe_pairs_compacted": m.counter(
                "moe_pairs_compacted",
                "held token-expert picks a long pass computed over the held "
                "pairs only (the compact expert layer; 0 in a fused step)"),
            "moe_compact_extra_chunks": m.counter(
                "moe_compact_extra_chunks",
                "chunks the compact expert layer ran past the first (its "
                "static chunk was short of the held pairs)"),
        }
        # the same three, of the fused steps alone (decode rows)
        self._dsa_step_rows = {"dsa_rows_scored": 0, "dsa_rows_selected": 0,
                               "dsa_rows_searched": 0}
        m.gauge("latent_page_bytes", self._latent_page_bytes,
                "bytes of one page of the latent lane over its layers (0 "
                "for a configuration without latent attention)")
        m.gauge("index_page_bytes", self._index_page_bytes,
                "bytes of one page of the sparse-attention index lane over "
                "its layers (0 for a configuration without DSA)")
        self._moe_load_max = 0
        m.gauge("moe_load_max", lambda: self._moe_load_max,
                "busiest held expert's tokens in one layer of the last "
                "program harvested", agg="max")
        self._ssm_state_bytes = m.counter(
            "ssm_state_bytes",
            "bytes of recurrent state read and written (live slots x state "
            "bytes per slot x 2)")
        self._prefix_skipped = m.counter(
            "prefix_lookups_skipped_no_state",
            "admissions that skipped the prefix index because a recurrent "
            "configuration has no state snapshot at a prefix boundary")
        self._turnaround_ms_c = m.counter(
            "turnaround_ms",
            "host milliseconds between a fused program's result in hand and "
            "the next fused launch's return")
        self._launched_ahead = m.counter(
            "fused_launched_ahead",
            "fused launches made before the previous program's result was "
            "read (over decode_iterations: the share of steps whose host "
            "turnaround ran behind the device's work)")
        self._ahead_late = m.counter(
            "fused_ahead_late",
            "launches ahead that found the program in flight already "
            "finished (over fused_launched_ahead: the share of ahead steps "
            "whose host work outlasted the device's; above a few per cent "
            "the host is the bottleneck again)")
        self._serial_steps = {
            why: m.counter(
                "fused_serial_steps",
                "steps that read the last program before launching the next "
                "(harvest, admit, build, launch), by why the order was kept",
                labels={"reason": why})
            for why in SERIAL_REASONS}
        self._step_late = False
        self._ahead_discarded = m.counter(
            "fused_ahead_discarded_lanes",
            "lanes of a program launched ahead whose request had ended by "
            "the time the program before it was read (EOS, deadline): the "
            "lane's token is dropped at its harvest")
        self._recomputed_tokens = m.counter(
            "recomputed_tokens",
            "prompt tokens re-prefilled because of preemption")
        self._timeouts = m.counter(
            "timeouts", "requests retired by deadline expiry")
        self._rejected_requests = m.counter(
            "rejected_requests",
            "requests rejected at intake (footprint can never fit)")
        self._intake_swap_rejects = m.counter(
            "intake_swap_rejects",
            "intake rejections because the worst-case footprint exceeds the "
            "host swap pool (the request could never be parked)")
        # KV-tier surface: spill/restore traffic between the device prefix
        # cache and the host (+disk) tier, plus the rolling-hash partial-
        # page index's hit counter
        self._tier_spills = m.counter(
            "kv_tier_spills",
            "evicted prefix pages delivered to the host KV tier (counted "
            "at d2h success, like swapped_pages)")
        self._tier_restores = m.counter(
            "kv_tier_restores",
            "tier restore scatters (one per admission resuming >= 1 page "
            "from the host/disk tier)")
        self._tier_restored_tokens = m.counter(
            "kv_tier_restored_tokens",
            "prompt tokens restored from the KV tier instead of re-prefilled")
        self._partial_hits = m.counter(
            "partial_page_hits",
            "admissions whose prefix match ended inside a cached page "
            "(rolling-hash partial index: COW copy or tier scatter of the "
            "matched fraction)")
        # disaggregated handoff surface: prompts a prefill-role engine
        # exported through the shared tier store for a decode peer
        self._handoff_exports = m.counter(
            "kv_handoff_exports",
            "finished prompts exported to the shared tier store for a "
            "decode-role peer")
        self._handoff_pages = m.counter(
            "kv_handoff_pages", "KV pages published to the store by exports")
        self._handoff_tokens = m.counter(
            "kv_handoff_tokens",
            "prompt tokens whose KV a decode peer can restore instead of "
            "re-prefilling")
        # SLO accounting (deadline attainment + per-priority-class goodput):
        # attainment's denominator is EVERY retired deadline-bearing request
        # (timeouts and aborts count as misses there), while the latency
        # histograms keep excluding them — two different questions
        self._deadline_requests = m.counter(
            "deadline_requests",
            "retired requests that carried a deadline (attainment "
            "denominator — timeouts/aborts/rejects included)")
        self._deadline_met = m.counter(
            "deadline_met",
            "deadline-bearing requests that finished (stop/length) on time")
        self._goodput_prio: Dict[int, object] = {}
        self._h_queue = m.histogram("queue_time_seconds",
                                    help="enqueue -> admission into a slot")
        self._h_ttft = m.histogram("ttft_seconds",
                                   help="enqueue -> first generated token")
        self._h_tpot = m.histogram(
            "tpot_seconds", help="decode seconds per token after the first")
        self._h_e2e = m.histogram("e2e_latency_seconds",
                                  help="enqueue -> finish (stop/length only)")
        self._h_step = m.histogram("step_seconds",
                                   help="wall time of one engine step()")
        m.gauge("queued", lambda: len(self._queue), "requests waiting")
        m.gauge("prefilling", lambda: len(self._prefilling),
                "slots mid-prefill")
        m.gauge("running", lambda: len(self._running), "slots decoding")
        m.gauge("kv_pool_bytes", self.kv_pool_bytes,
                "at-rest bytes of the device KV page pool (all lanes)")
        self.cache.attach_metrics(m)
        # ---- health & perf signal plane (all host-side) -------------------
        # windowed rates: sliding-window views over the counters above,
        # sampled once per step() — the router's freshness-weighted signal
        # (a counter answers "since reset", a probe needs "lately")
        self._admitted_requests = m.counter(
            "admitted_requests",
            "requests popped into a slot (recompute resumes included)")
        self._rw_tokens = m.rate_window(
            "tokens_per_sec", lambda: self._decode_tokens.value,
            help="decode tokens emitted per second")
        self._rw_admits = m.rate_window(
            "admits_per_sec", lambda: self._admitted_requests.value,
            help="requests admitted per second")
        self._rw_preemptions = m.rate_window(
            "preemptions_per_sec", lambda: self._preemptions.value,
            help="running requests preempted per second")
        self._rw_timeouts = m.rate_window(
            "timeouts_per_sec", lambda: self._timeouts.value,
            help="requests retired by deadline expiry per second")
        self._rw_rejects = m.rate_window(
            "rejects_per_sec", lambda: self._rejected_requests.value,
            help="requests rejected at intake per second")
        # the stats()["rates"] surface, captured once: registry-owned ring
        # state, independent of the per-signal handles health() evaluates
        self._rate_surface = (self._rw_tokens, self._rw_admits,
                              self._rw_preemptions, self._rw_timeouts,
                              self._rw_rejects)
        # burn-rate inputs: windowed deltas of the SLO account (not exposed
        # as per-window gauges themselves — the burn ratios below are the
        # signal; agg="max" because a burn is a fraction-of-budget ratio)
        self._rw_deadline_req = m.rate_window(
            "deadline_requests_window",
            lambda: self._deadline_requests.value, expose=False)
        self._rw_deadline_met = m.rate_window(
            "deadline_met_window",
            lambda: self._deadline_met.value, expose=False)
        for _lbl, _w in self._rw_deadline_req.windows:
            if _lbl in (SERVE_SLO["burn_window_fast"],
                        SERVE_SLO["burn_window_slow"]):
                m.gauge(f"slo_burn_rate_{_lbl}",
                        (lambda w=_w: self._burn_rate(w)),
                        f"deadline-attainment burn over the trailing {_lbl} "
                        f"(1.0 = consuming the error budget exactly as fast "
                        f"as the SLO allows)", agg="max")
        # live roofline drift: predicted_step_ms traced once at warmup
        # (lazy — never from a scrape), measured EWMA fed by busy steps
        self._predicted_ms: Optional[float] = None
        self._measured_ewma_ms: Optional[float] = None
        self._drift_violation = False
        self._exec_baseline: Optional[int] = None
        self._roofline_alerts = m.counter(
            "roofline_drift_alerts",
            "transitions of roofline_drift out of the declared band")
        self._ss_recompiles = m.counter(
            "steady_state_recompiles",
            "decode-side executable-count growth observed after warm")
        m.gauge("measured_step_ms",
                lambda: self._measured_ewma_ms or 0.0,
                "EWMA wall time of busy engine steps (harvest to harvest)",
                agg="max")
        m.gauge("roofline_drift", self._roofline_drift,
                "measured_step_ms / predicted_step_ms (0 until both exist)",
                agg="max")
        m.gauge("engine_health", self._health_code,
                "health state code: 0 ok, 1 degraded, 2 overloaded "
                "(fleet merge folds worst-of, not sum)", agg="max")
        self._lifecycles: Dict[int, RequestMetrics] = {}
        # per-request tracing (always-on observability plane; request_tracing
        # =False strips both the timelines and the exemplar attachment — the
        # bench's overhead A/B axis).  Live traces move to RequestOutput
        # .trace at retirement, so /requests/<rid> keeps resolving after —
        # for the last `trace_retention` retired requests: a long-running
        # server retires millions, and timelines held forever on the
        # RequestOutput ledger would grow host memory without bound, so the
        # oldest retired trace is dropped (its output keeps its tokens) once
        # the cap is passed.  trace_retention=None retains every timeline.
        self._req_tracing = bool(request_tracing)
        self._traces: Dict[int, RequestTrace] = {}
        self._trace_retention = trace_retention
        self._retired_traced: deque = deque()
        self._step_idx = 0
        self._step_trace: deque = deque(maxlen=trace_ring)
        self._tracing = False
        self._turn_t0: Optional[float] = None
        self._turn_span = _NULL_SPAN
        self._step_turnaround_s = 0.0
        self._step_d2h_s = 0.0

        sample = bool(temperature and temperature > 0.0)
        self._sample = sample
        self._temperature = temperature

        cfg = config
        mesh_ = mesh if self.mp > 1 else None
        pool_sh = self._pool_sharding

        if sample:
            def pick(logits, key, greedy):
                # gpt.sample_token is shared with generate() — parity by
                # construction; the greedy mask routes per-request
                # temperature=0.0 slots through argmax (their output is
                # PRNG-independent; the batch-wide split still advances).
                # Under mp the logits arrive vocab-sharded and both picks
                # run as on-device sharded merges.
                ids, key = gpt_mod.sample_token(logits, key, sample=True,
                                                temperature=temperature,
                                                top_k=top_k, mesh=mesh_)
                greedy_ids = gpt_mod.sharded_argmax(logits, mesh_)
                return jnp.where(greedy, greedy_ids, ids), key
        else:
            def pick(logits, key, greedy):
                # fully greedy engine: argmax, the PRNG key is never consumed
                return gpt_mod.sample_token(logits, key, sample=False,
                                            temperature=temperature,
                                            top_k=top_k, mesh=mesh_)

        def pin_pool(pool):
            # pin the output pool to EXACTLY the committed input sharding (the
            # normalized spec): the donated buffer is reused in place and every
            # call after the first carries an identical executable-cache
            # signature — without the pin, GSPMD-inferred output shardings
            # drift and decode/chunk ping-pong recompiles (4 chunk compiles
            # observed for one engine)
            if pool_sh is None:
                return pool
            return {n: jax.lax.with_sharding_constraint(a, pool_sh)
                    for n, a in pool.items()}

        def prefill_impl(params, ids, pool, pages, length, key, greedy):
            logits, pool = gpt_mod.prefill_paged(params, ids, cfg, pool,
                                                 pages, length, mesh=mesh_)
            first, key = pick(logits, key, greedy)
            return first, pin_pool(pool), key

        def chunk_impl(params, ids, pool, table, q_offset, valid, key, greedy):
            logits, pool = gpt_mod.prefill_chunk_paged(params, ids, cfg, pool,
                                                       table, q_offset, valid,
                                                       mesh=mesh_)
            tok, key = pick(logits, key, greedy)
            return tok, pin_pool(pool), key

        temp_, topk_ = temperature, top_k

        repl_sh = self._repl_sharding

        def feed(tokens, prev_out, from_prev):
            # the token source of a program launched before the last one's
            # result was read (`_plan_ahead`): row b's first token is
            # `prev_out[b, from_prev[b]]`, the last program's own output,
            # still on the device; -1 keeps the host's row
            col = jnp.take_along_axis(
                prev_out, jnp.maximum(from_prev, 0)[:, None], axis=1)[:, 0]
            return tokens.at[:, 0].set(jnp.where(
                from_prev >= 0, col.astype(tokens.dtype), tokens[:, 0]))

        def fused_impl(params, tokens, pool, table, q_offset, valid, key,
                       greedy, prev_out, from_prev):
            # THE one-dispatch step: decode/verify/chunk slots in one batch,
            # sampling + accept scan on device, host-visible output O(B*K)
            # ints (never [B, V] logits — guarded by the JXP005 jaxpr audit)
            out, accept, pool, key = gpt_mod.serve_step_paged(
                params, feed(tokens, prev_out, from_prev), pool, table,
                q_offset, valid, cfg, key=key, greedy=greedy, sample=sample,
                temperature=temp_, top_k=topk_, mesh=mesh_)
            if repl_sh is not None:
                # `out` is the next launch's `prev_out`: pinned to the
                # layout the AOT executable was compiled for
                out = jax.lax.with_sharding_constraint(out, repl_sh)
            return out, accept, pin_pool(pool), key

        if self.patterned:
            # the patterned configuration's programs, under the names the
            # dense ones have (a trace finds `jit_fused_impl` /
            # `jit_prefill_impl` whichever family is served): the same
            # contracts, one more small result (`aux`, the program's
            # counters) and, for the prefill, the slot its state is kept in
            def chunk_impl(params, ids, pool, table, q_offset, valid, key,
                           greedy):
                logits, pool, aux = hybrid_mod.prefill_chunk_paged(
                    params, ids, cfg, pool, table, q_offset, valid)
                tok, key = pick(logits, key, greedy)
                return tok, pool, key, aux

            def prefill_impl(params, ids, pool, pages, length, key, greedy,
                             slots):
                logits, pool, aux = hybrid_mod.prefill_paged(
                    params, ids, cfg, pool, pages, length, slots)
                first, key = pick(logits, key, greedy)
                return first, pool, key, aux

            def fused_impl(params, tokens, pool, table, q_offset, valid, key,
                           greedy, prev_out, from_prev):
                return hybrid_mod.serve_step_paged(
                    params, feed(tokens, prev_out, from_prev), pool, table,
                    q_offset, valid, cfg, key=key, greedy=greedy,
                    sample=sample, temperature=temp_, top_k=topk_)

        def copy_impl(pool, src, dst):
            # COW page copy: one [page, KVH, hd] slab per layer, src -> dst
            # (page axis is unsharded, so the copy is collective-free under mp)
            return pin_pool({n: a.at[:, dst].set(a[:, src])
                             for n, a in pool.items()})

        # how bytes leave the device (spill and swap-out alike): the pages
        # are gathered in pieces of `_swap_w` — the widest power of two whose
        # piece stays within `_D2H_PIECE_BYTES`, at most a slot's pages — and
        # each piece's device->host copy runs on ONE worker thread, a piece
        # at a time, while the engine thread goes on (`_gather_d2h`); only
        # the pieces that hold wanted pages are copied.  Gathered buffers
        # hold HBM until their bytes have landed, so at most `_d2h_bound`
        # pages of them are in flight: what TWO slot-wide buffers would hold,
        # one landing while the next is gathered (the engine before PR 31
        # held one such buffer a pending record, 32 of them at its worst)
        P = self.cache.max_pages_per_slot
        w = max(1, min(P, _D2H_PIECE_BYTES // max(1, self._kv_page_bytes)))
        self._swap_w = W = 1 << (w.bit_length() - 1)
        self._d2h_slot_w = -(-P // W) * W       # the gather's fixed width
        self._d2h_bound = 2 * self._d2h_slot_w

        def swap_out_impl(pool, ids):
            # swap-out / spill: gather a slot's width of page ids out of the
            # pool (NOT donated — it stays live) as SEPARATE buffers of
            # `_swap_w` pages, so that each is its own transfer and the ones
            # past the wanted pages are dropped unfetched; ONE fixed-shape
            # executable and ONE dispatch whatever the page count (a dispatch
            # a piece cost 0.7 ms of host time each on the chip).  Each page
            # is one `dynamic_slice` of the pool laid into its piece
            # (`swap_out_pages`), so the program reads and writes a slot's
            # width of pages and nothing else of the pool.  The pin
            # keeps the gathered buffers in the pool's KVH-sharded layout
            # under mp (the gather stays chip-local; the host fetch
            # assembles).
            return [pin_pool(gpt_mod.swap_out_pages(pool, ids[i:i + W]))
                    for i in range(0, ids.shape[0], W)]

        def swap_in_impl(pool, ids, data):
            # preemption swap-in: scatter the parked KV back into freshly
            # allocated pages, in place (`data` is the pool-keyed staging
            # dict — int8 pools restore their scale lanes in the same
            # dispatch).  Only the pool is donated — the staging uploads
            # cannot alias the pool-shaped output, so donating them would
            # just burn a "donation unusable" warning per swap-in
            return pin_pool(gpt_mod.swap_in_pages(pool, ids, data))

        # pool donated, and carried through each paged pass's layer loop
        # (`gpt._scan_paged_layers`): the program scatters the new tokens' KV
        # into the one buffer and the kernels read it there — no copy of the
        # pool at the jit boundary and none per layer (donation alone saves
        # only the first).  The mp path AOT-compiles (see
        # _AotCache) so the program set stays exact under committed-sharded
        # donated inputs; single-chip keeps plain jit.
        jit_ = (lambda fn, donate, skip=0: _AotCache(fn, donate, skip)) \
            if self.mp > 1 \
            else (lambda fn, donate, skip=0:
                  jax.jit(fn, donate_argnums=donate))
        # the fused program IS the decode-side executable (count: exactly
        # 1).  In chunked mode the chunk rides its batch; bucketed mode keeps
        # a standalone chunk program for prefix-hit tails (cold path, like
        # the bucketed one-shot prefill) — which a recurrent configuration
        # never has (no prefix hit)
        self._decode_fn = jit_(fused_impl, (2,), 1)  # skip=1: params static
        self._chunk_fn = None if self.chunked or self.recurrent \
            else jit_(chunk_impl, (2,), 1)
        self._prefill_fn = jit_(prefill_impl, (2,), 1)
        self._copy_fn = jit_(copy_impl, (0,))
        self._swap_out_fn = jit_(swap_out_impl, ())
        self._swap_in_fn = jit_(swap_in_impl, (0,))
        self._seen_buckets = set()
        self._chunk_used = False
        self._copy_used = False
        self._swap_out_used = False
        self._swap_in_used = False
        self._decode_used = False       # any decode-side dispatch happened
        # preemption/overload state: rid -> resume record ("recompute" keeps
        # the banked generation for the longer-prompt replay; "swap" adds the
        # parked KV, first as pieces in flight then host numpy pages);
        # _pending_d2h holds the swap and spill records whose bytes the
        # engine has not taken yet, oldest first; _has_deadlines gates the
        # per-step expiry scan
        self._preempted: Dict[int, Dict[str, object]] = {}
        self._pending_d2h: List[Dict[str, object]] = []
        self._d2h_inflight = 0          # gathered pages not yet taken
        self._d2h_worker: Optional[ThreadPoolExecutor] = None
        self._has_deadlines = False
        self._step_preempted = 0
        # double-buffer state: the un-synced result of the last fused
        # dispatch (device arrays + the host metadata to interpret them) and
        # finishes surfaced outside step() (an abort-time harvest)
        self._inflight: Optional[Dict[str, object]] = None
        self._orphan_finished: List[RequestOutput] = []
        # what a launch in today's order hands the program where a launch
        # ahead hands it the last program's `out` and the rows to take from
        # it: nothing to take, every row the host's.  Device constants, so
        # such a launch keeps its five puts
        self._no_prev = self._h2d(
            np.zeros((num_slots, self._fused_T), np.int32))
        self._host_rows = self._h2d(np.full((num_slots,), -1, np.int32))
        self._step_dispatches = 0
        self._step_sync_s = 0.0
        self._step_slots = {"decode": 0, "verify": 0, "chunk": 0}
        # serving-loop surface (front door / fleet): the engine itself is
        # single-threaded by design, so one RLock serializes the background
        # step() loop against submit/cancel/probe/result callers; the
        # condition (same lock) wakes the loop on intake and waiters on
        # every step's outputs
        self._serve_lock = threading.RLock()
        self._serve_cond = threading.Condition(self._serve_lock)
        self._serve_thread: Optional[threading.Thread] = None
        self._serve_stop = False
        self._serve_error: Optional[BaseException] = None
        self.reset_counters()

    def reset_counters(self) -> None:
        """Zero the throughput/prefix counters and latency histograms
        (stats(), not executables) — benches call this after warmup so
        compile-time traffic is excluded.  Also clears the step-trace ring and
        the proposer's drafting telemetry; the `prefix_evictions` int mirrors
        its registry counter so both zero together.

        Contract with an OPEN capture/trace window (audited; see
        tests/test_observability.py::test_reset_counters_mid_trace_window):

        - the chrome-trace host spans live in the profiler's own event
          buffer, which this method never touches — a reset inside an
          `engine.trace(dir)` window does not corrupt ``host_trace.json``;
        - the step-trace ring and `_step_idx` restart at zero, so the
          window's ``step_timeline.json`` holds only post-reset records
          (by design: the same warmup-exclusion semantics as the counters);
        - histogram resets clear their EXEMPLARS with their bucket counts
          (`Histogram.reset`) — the exposition can never carry a stale
          request handle on a bucket whose count says nothing was observed;
        - live per-request timelines (`RequestOutput.trace` /
          ``/requests/<rid>``) are request state, not counters: in-flight
          traces and already-retired outputs survive, so exemplar handles
          attached AFTER the reset keep resolving;
        - the signal plane restarts with the counters it derives from: rate
          windows clear their sample rings (`MetricsRegistry.reset`), the
          measured-step EWMA and the steady-state recompile baseline
          re-seed on the next busy step (warmup compiles stay excluded the
          same way warmup counter traffic does).  The static
          `predicted_step_ms` survives — it is a property of the engine's
          shapes, not of any run.

        Spill/swap-out copies still in flight land first (the engine waits
        for them): their fetches belong to the traffic before the reset."""
        self._land_d2h(wait=True)
        self.metrics.reset()
        self.cache.prefix_evictions = 0
        self._dsa_step_rows = dict.fromkeys(self._dsa_step_rows, 0)
        if self.cache._tier is not None:
            # the tier's own event mirrors zero with the registry counters
            # (its CONTENT — parked pages — is cache state and survives,
            # like the prefix index itself)
            self.cache._tier.disk_spills = 0
            self.cache._tier.disk_restores = 0
            self.cache._tier.tier_drops = 0
        getattr(self.proposer, "reset_stats", lambda: None)()
        self._step_idx = 0
        self._step_trace.clear()
        self._measured_ewma_ms = None
        self._drift_violation = False
        self._exec_baseline = None
        # seed every rate ring with (t_reset, 0): events between the reset
        # and the first step-end sample stay countable, and a young window
        # reads exactly events-since-reset / elapsed-since-reset
        self.metrics.sample_rates()

    # ---- request intake ---------------------------------------------------
    def add_request(self, prompt, max_new_tokens: int = 16,
                    temperature: Optional[float] = None,
                    priority: int = 0,
                    deadline_s: Optional[float] = None) -> int:
        """Enqueue one request.  temperature=None inherits the engine's
        sampling mode; 0.0 is the per-request greedy fast path (argmax pick,
        output independent of the PRNG stream — what speculative decoding
        verifies against).  A positive value must equal the engine's compiled
        temperature: the sampling variant is baked into the executables.

        `priority` orders preemption under optimistic admission (lower
        priorities are evicted first; default 0).  `deadline_s` bounds the
        request's total wall time: past `enqueue + deadline_s` it is retired
        with finish_reason="timeout" wherever it is (queued, prefilling,
        decoding, or swapped out).  A request whose worst-case footprint
        (prompt + max_new_tokens) exceeds the whole page pool can NEVER be
        served — it is rejected immediately (finish_reason="rejected",
        output available via outputs/run()) instead of wedging the queue
        head forever while it waits for pages that cannot exist."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        if temperature is not None and temperature < 0.0:
            raise ValueError(f"temperature must be >= 0.0, got {temperature}")
        if temperature is not None and temperature > 0.0:
            if not self._sample:
                raise ValueError(
                    "engine compiled greedy (temperature=0.0) cannot serve "
                    "sampled requests; construct it with temperature > 0")
            if temperature != self._temperature:
                raise ValueError(
                    f"per-request temperature {temperature} != engine "
                    f"temperature {self._temperature}; only the greedy fast "
                    f"path (temperature=0.0) overrides per request")
        if not self.chunked and prompt.size > self.buckets[-1]:
            raise ValueError(f"prompt length {prompt.size} exceeds largest "
                             f"prefill bucket {self.buckets[-1]}")
        total = prompt.size + max_new_tokens
        if total > self.max_model_len:
            raise ValueError(f"prompt + max_new_tokens = {total} exceeds "
                             f"max_model_len {self.max_model_len}")
        if deadline_s is not None and deadline_s <= 0.0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        rid = next(self._ids)
        t = self._now()
        deadline = None if deadline_s is None else t + deadline_s
        req = Request(prompt, max_new_tokens, rid, t, temperature,
                      priority, deadline)
        self._lifecycles[rid] = RequestMetrics(t_enqueue=t)
        if self._req_tracing:
            tr = RequestTrace(rid)
            tr.event(t, "enqueue", prompt_len=int(prompt.size),
                     max_new_tokens=int(max_new_tokens),
                     priority=int(priority),
                     deadline_s=deadline_s)
            self._traces[rid] = tr
        need = self.cache.pages_needed(total)
        if need > self.cache.num_pages - 1:
            # fail fast: even alone on an empty pool this footprint cannot
            # fit — queueing it would wedge the queue head forever in
            # _admit's wait-for-pages path
            self._rejected_requests.inc()
            self._finish_output(req, [], "rejected", 0, None)
            # anchor the reject in the rate rings at its true time (intake
            # runs outside step(), whose sampling would otherwise miss it)
            self.metrics.sample_rates(force=True)
            return rid
        if self.optimistic and self.preempt == "swap" and \
                self.swap_pool_pages > 0 and need > self.swap_pool_pages:
            # swap-pool intake admission (PR-10 follow-on): under swap-mode
            # oversubscription every admitted request is a preemption
            # candidate, and its worst-case footprint counts against the
            # HOST swap-pool budget at intake — a request that could never
            # be parked even in an empty pool would degrade EVERY preemption
            # of it to recompute (swap->recompute thrash), so it is rejected
            # here.  A request that merely finds the pool transiently full
            # queues as usual: parked victims re-queue at the head and drain
            # the pool before fresh work reaches it.  swap_pool_pages=0
            # declares parking disabled (pure recompute) — no gate.
            self._intake_swap_rejects.inc()
            self._rejected_requests.inc()
            self._finish_output(req, [], "rejected", 0, None)
            self.metrics.sample_rates(force=True)
            return rid
        if deadline is not None:
            self._has_deadlines = True
        self._queue.append(req)
        return rid

    def _req_greedy(self, req: Request) -> bool:
        t = req.temperature
        return (not self._sample) if t is None else t <= 0.0

    def abort(self, request_id: int) -> bool:
        """Cancel a queued or in-flight request and free/deref its pages
        immediately (a stuck client no longer leaks its reservation until
        max_new_tokens runs out).  Shared prefix pages are only
        deref-counted; the request lands in the outputs map with
        finish_reason="abort" and whatever tokens it had produced.  Returns
        False when the id is unknown or already finished.

        Under double-buffering the in-flight fused batch (at most one
        between steps, also where steps launch ahead) is harvested first,
        so the abort sees exact bookkeeping (a request the pending tokens
        just finished is reported as already done, not aborted); requests
        that finish during this harvest surface from the NEXT step() call."""
        if self._inflight is not None:
            self._harvest(self._orphan_finished)
        for i, req in enumerate(self._queue):
            if req.request_id == request_id:
                # del by index, NOT deque.remove: remove's equality scan would
                # run Request.__eq__ against every earlier entry, and numpy
                # prompt comparison has no scalar truth value (it raised for
                # any aborted request not at the head of the queue)
                del self._queue[i]
                rec = self._drop_preempted(request_id)
                if rec is not None:
                    # a preempted request keeps the tokens it had produced
                    self._finish_output(req, list(rec["generated"]), "abort",
                                        rec["cached_tokens"], rec["ttft"])
                else:
                    self._finish_output(req, [], "abort", 0, None)
                return True
        for slot, st in list(self._prefilling.items()):
            if st.request.request_id == request_id:
                del self._prefilling[slot]
                self.cache.release(slot)
                self._free_slots.append(slot)
                # a recompute-resume mid-replay keeps its banked generation
                # (same contract as the queued and timeout paths)
                self._finish_output(st.request, list(st.prior or []),
                                    "abort", st.cached_tokens, st.ttft)
                return True
        for slot, seq in list(self._running.items()):
            if seq.request.request_id == request_id:
                del self._running[slot]
                self.cache.release(slot)
                self._free_slots.append(slot)
                self._finish_output(seq.request, seq.generated, "abort",
                                    seq.cached_tokens, seq.ttft_s)
                return True
        return False

    def _finish_output(self, req: Request, token_ids: List[int], reason: str,
                       cached: int, ttft: Optional[float]) -> RequestOutput:
        """Close the request's lifecycle record and publish the output.
        Latency histograms only see stop/length retirements — an abort's (or
        timeout's) wall time measures the client/deadline, not the engine —
        but every retirement gets its full RequestMetrics record and its own
        counter.  (The "rejected" counter is incremented at intake, where
        the decision is made.)"""
        rid = req.request_id
        lc = self._lifecycles.pop(rid, None)
        if lc is not None:
            lc.t_finish = self._now()
            lc.e2e_s = lc.t_finish - lc.t_enqueue
            lc.cached_tokens = cached
            lc.n_generated = len(token_ids)
            if lc.t_first_token is not None and len(token_ids) > 1:
                lc.tpot_s = (lc.t_finish - lc.t_first_token) / \
                    (len(token_ids) - 1)
            if reason == "abort":
                self._aborted_requests.inc()
            elif reason == "timeout":
                self._timeouts.inc()
            elif reason == "rejected":
                pass                    # counted at the intake decision
            else:
                self._finished_requests.inc()
                ex = self._exemplar(rid)
                self._h_e2e.observe(lc.e2e_s, exemplar=ex)
                if lc.tpot_s is not None:
                    self._h_tpot.observe(lc.tpot_s, exemplar=ex)
            # SLO accounting: every retired deadline-bearing request lands in
            # the attainment denominator; only an on-time stop/length finish
            # counts as met.  Goodput credits FINAL-output tokens to the
            # request's priority class (replayed prefill work earns nothing,
            # same rule as the bench's goodput_tokens_per_sec).
            if req.deadline is not None:
                self._deadline_requests.inc()
                if reason in ("stop", "length") and \
                        lc.t_finish <= req.deadline:
                    self._deadline_met.inc()
            if reason in ("stop", "length") and token_ids:
                prio = int(req.priority)
                c = self._goodput_prio.get(prio)
                if c is None:
                    c = self.metrics.counter(
                        f"goodput_tokens_priority_{prio}",
                        f"final-output tokens from priority-{prio} requests")
                    self._goodput_prio[prio] = c
                c.inc(len(token_ids))
        self._tev(rid, "finish", reason=reason, n_generated=len(token_ids))
        out = RequestOutput(req.request_id, req.prompt, token_ids, reason,
                            cached, ttft, lc, self._traces.pop(rid, None))
        self._outputs[out.request_id] = out
        if out.trace is not None and self._trace_retention is not None:
            # bounded retirement ledger: drop the OLDEST retired timeline
            # past the cap (the output itself keeps its tokens/metrics)
            self._retired_traced.append(rid)
            while len(self._retired_traced) > self._trace_retention:
                old = self._outputs.get(self._retired_traced.popleft())
                if old is not None:
                    old.trace = None
        return out

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"no bucket for prompt length {n}")

    def _h2d(self, a, dtype=None):
        """Host->device for per-step scheduler inputs (tokens, page tables,
        lengths, flags): numpy-first + EXPLICIT placement, so the
        steady-state decode loop runs clean under
        `jax.transfer_guard("disallow")` — a bare Python list/int through
        `jnp.asarray` is an implicit transfer, and under mp a single-device
        array would be implicitly resharded to the mesh at every AOT
        dispatch.  (The other direction is `jax.device_get`: on the chip
        `np.asarray(device_array)` is an implicit device->host transfer and
        the guard refuses it; the CPU backend has no copy to refuse.)"""
        a = np.asarray(a, dtype)
        if self._repl_sharding is not None:
            return jax.device_put(a, self._repl_sharding)
        return jnp.asarray(a)

    def _span(self, name: str):
        """A profiler span for one host phase — real only while a trace is
        recording (engine.trace() or a user Profiler); the steady-state step
        loop pays a flag check, nothing else."""
        if self._tracing or _prof.is_recording():
            return _prof.RecordEvent(name)
        return _NULL_SPAN

    def _step_marker(self):
        """`StepTraceAnnotation("engine_step")` numbered like the ring record
        this step will append, so a record joins its device operations in a
        profiler trace; behind the same gate as `_span`."""
        if self._tracing or _prof.is_recording():
            return jax.profiler.StepTraceAnnotation(
                "engine_step", step_num=self._step_idx + 1)
        return _NULL_SPAN

    def _turn_begin(self, t: float) -> None:
        """Open `engine.turnaround`, the host stretch the device waits for:
        from `t`, the instant the previous fused program's
        result was in hand — `_harvest`'s device_get has returned — or the
        step's start when nothing was in flight, to the return of this
        step's fused launch (`_turn_end`).  Only a step that keeps the
        harvest-first order opens it: one that launches ahead of the last
        result (`_plan_ahead`) makes the device wait for nothing, opens no
        span and adds 0 to ring and counter.  Measured (one more clock
        read a step) into the ring's `turnaround_ms` and the `turnaround_ms`
        counter; a span only while something records.  With
        `double_buffer=False` the harvest follows the launch inside the same
        step, so the stretch runs from the step's start to the launch and
        leaves out that harvest's `engine.emit`: the device then waits for
        turnaround + emit + whatever the caller does between steps."""
        self._turn_t0 = t
        self._turn_span = self._span("engine.turnaround")
        self._turn_span.begin()

    def _turn_end(self, launched: bool) -> None:
        """Close the stretch.  A step that launched nothing closes the span
        at its end and adds nothing to ring or counter."""
        self._turn_span.end()
        self._turn_span = _NULL_SPAN
        if launched and self._turn_t0 is not None:
            self._step_turnaround_s = self._now() - self._turn_t0
            self._turnaround_ms_c.inc(self._step_turnaround_s * 1e3)
        self._turn_t0 = None

    # ---- per-request tracing ----------------------------------------------
    def _tev(self, rid: int, name: str, **attrs) -> None:
        """Stamp one event on a request's timeline (no-op with tracing off or
        for an unknown/finished rid).  Hot-path cost: one dict lookup, one
        clock read, one dict+list append — plain host data, inside whatever
        ENGINE_SPANS phase the caller already occupies (no new spans, no
        device access, no compiled-program change)."""
        tr = self._traces.get(rid)
        if tr is not None:
            tr.event(self._now(), name, **attrs)

    def _exemplar(self, rid: int) -> Optional[Dict[str, str]]:
        """Exemplar labels binding a histogram observation to its request:
        the id plus the obs-server handle that resolves it
        (``GET /requests/<rid>`` returns the chrome-trace span tree).  None
        with request tracing off — the exposition then carries no exemplars,
        matching the absent timelines."""
        if not self._req_tracing:
            return None
        return {"request_id": str(rid), "trace": f"/requests/{rid}"}

    def _trace_for(self, rid: int):
        """The request's timeline, live (`_traces`) or retired (riding its
        RequestOutput) — the single lookup behind `export_request_trace`
        and the debug bundle's per-request states; None when the id is
        unknown or tracing is off."""
        tr = self._traces.get(rid)
        if tr is None:
            out = self._outputs.get(rid)
            tr = out.trace if out is not None else None
        return tr

    def export_request_trace(self, rid: int) -> Optional[Dict[str, object]]:
        """The chrome-trace span tree of one request's timeline (live or
        retired — retired traces ride their RequestOutput, retained for the
        last `trace_retention` retirements), or None when the id is unknown,
        tracing is off, or the timeline aged out.  Served by the obs server
        as ``GET /requests/<rid>``; the raw event list is
        `RequestOutput.trace.events`."""
        tr = self._trace_for(rid)
        return None if tr is None else tr.to_chrome()

    # ---- scheduler --------------------------------------------------------
    def step(self) -> List[RequestOutput]:
        """One engine iteration, in one of two orders.  Where the next batch
        is predictable (`_plan_ahead`: double-buffered mode, a program in
        flight, nothing to admit, draft or preempt): build and launch the
        next fused program FIRST, its decode rows fed on the device from the
        one in flight, then harvest that one — the fetch, the emission and
        the retirements run behind the device's work.  Otherwise: harvest
        the previous fused dispatch (double-buffered mode), admit queued
        requests into free slots (prefix-cache matching + page reservation),
        stage at most ONE prefill chunk, then dispatch decode work — ONE
        fused program covering every decode/verify/chunk slot.  Either way
        at most one program is in flight when this returns.  Returns the
        requests that finished this iteration (under double-buffering a
        request finishes the step its tokens are harvested, one after its
        last dispatch).

        Each iteration appends one v2 record to the step-trace ring
        (`step_trace()`): what the step dispatched (decode-batch occupancy,
        per-mode slot counts, dispatch count, harvest-sync time, chunk
        interleaved, verify dispatches, tokens emitted) and the page pool it
        left behind — the timeline that answers "what was the engine doing
        when this request was slow"."""
        finished: List[RequestOutput] = self._orphan_finished
        self._orphan_finished = []
        t0 = self._now()
        tok0 = self._decode_tokens.value
        ver0 = self._verify_steps.value
        chunk0 = self._prefill_chunks.value
        self._step_dispatches = 0
        self._step_sync_s = 0.0
        self._step_preempted = 0
        self._step_slots = {"decode": 0, "verify": 0, "chunk": 0}
        self._step_turnaround_s = 0.0
        self._step_d2h_s = 0.0
        self._step_pages_walked = 0
        self._step_aux = dict.fromkeys(("moe_pairs_here", "moe_pairs_away",
                                        "moe_experts_touched",
                                        "latent_tokens_written"), 0)
        self._step_late = False
        with self._step_marker(), self._span("engine.step"):
            prev = self._inflight
            lanes, serial = self._plan_ahead(prev)
            if lanes is not None:
                # a plan with nothing to launch (every lane ended with
                # `prev`) only reads the last program: neither order
                order = "engine.step.ahead" if lanes or (
                    self.chunked and self._prefilling) else None
            elif prev is None and not (self._queue or self._running or
                                       self._prefilling):
                order = serial = None           # an idle poll
            else:
                order = "engine.step.serial"
                self._serial_steps[serial].inc()
            with _NULL_SPAN if order is None else self._span(order):
                if lanes is not None:
                    # the next program goes out BEFORE the last one's tokens
                    # are read: its decode rows take their token from `prev`'s
                    # output on the device, and everything below — the fetch,
                    # the emission, the retirements, the caller's loop — runs
                    # while it computes.  Two programs are in flight only
                    # from here to the harvest; the step returns with one
                    chunk_job = self._stage_chunk() if self.chunked else None
                    self._inflight = None
                    if self.optimistic:
                        # the page under each lane's write: the plan saw that
                        # all of them fit without a victim (`_fits_ahead`)
                        for slot, (_, q, _, _) in lanes.items():
                            self.cache.grow(slot, q + 1)
                    if lanes or chunk_job is not None:
                        self._fused_iter(chunk_job, finished, lanes, prev)
                    self._harvest(finished, prev)
                    if self._has_deadlines:
                        # a slot retired here leaves a lane in the program
                        # just launched: its harvest drops that lane's token
                        self._expire_deadlines(finished)
                else:
                    if prev is None:
                        self._turn_begin(t0)
                    # step n-1's tokens land first
                    self._harvest(finished, turnaround=True)
                    if self._has_deadlines:
                        # right after harvest: bookkeeping is exact, nothing
                        # in flight
                        self._expire_deadlines(finished)
                    with self._span("engine.admit"):
                        self._admit(finished)
                    if self.chunked:
                        chunk_job = self._stage_chunk()
                    else:
                        # bucketed mode: prefix-hit tails keep the standalone
                        # chunk program (cold path, next to the one-shot
                        # prefill)
                        self._prefill_tick(finished)
                        chunk_job = None
                    if self._running or chunk_job is not None:
                        self._fused_iter(chunk_job, finished)
                # decode-batch occupancy of what actually DISPATCHED: on a
                # preemption step the pre-dispatch running count overstates
                # the batch (victims left before the program ran)
                decode_batch = self._step_slots["decode"] + \
                    self._step_slots["verify"]
                self._turn_end(launched=False)      # no-op after a launch
                # spill/swap-out records whose bytes have arrived land here,
                # behind the dispatch; the others stay in flight
                if self._pending_d2h:
                    self._land_d2h()
        dur = self._now() - t0
        self._h_step.observe(dur)
        if self._step_dispatches:
            # busy steps only: an idle/admission-only step measures the
            # scheduler, not the serving step the roofline predicts
            self._note_steady_state(dur)
        # one rate-window sample per step (throttled), FORCED on eventful
        # steps (retirements or preemptions) so the last event before the
        # engine goes idle is anchored at its true time — that is what
        # makes idle rates read exactly 0.0 once the window passes the
        # burst, instead of decaying against a stale reference
        self.metrics.sample_rates(
            force=bool(finished) or self._step_preempted > 0)
        self._step_idx += 1
        mgr = self.cache
        self._step_trace.append({
            # v2 record: the v1 keys plus `v`/`dispatches`/`sync_ms`/`slots`
            "v": 2,
            "step": self._step_idx,
            "t": t0,
            "dur_s": dur,
            "queued": len(self._queue),
            "prefilling": len(self._prefilling),
            "running": len(self._running),
            "decode_batch": decode_batch,
            "chunk": self._prefill_chunks.value > chunk0,
            "verify_dispatches": self._verify_steps.value - ver0,
            "tokens_emitted": self._decode_tokens.value - tok0,
            "finished": len(finished),
            "pages_in_use": mgr.pages_in_use(),
            # pages the paged kernel walked in this step's dispatches, a
            # layer (`_note_walk`)
            "pages_walked": self._step_pages_walked,
            "pages_free": mgr.num_free_pages,
            "pages_evictable": mgr.num_evictable_pages,
            # decode-path dispatches this step (the fused program and the
            # standalone chunk program; the admission-time one-shot prefill
            # is the cold path and is not counted)
            "dispatches": self._step_dispatches,
            # blocking device->host sync time spent inside this step's
            # engine.sample.sync spans (harvest + prefill first-token fetches)
            "sync_ms": self._step_sync_s * 1e3,
            # engine.turnaround of this step (0 when it launched nothing,
            # and when it launched ahead of the last result: `ahead`) and
            # the swap/spill fetches it drained
            "turnaround_ms": self._step_turnaround_s * 1e3,
            "ahead": lanes is not None and self._step_dispatches > 0,
            # an ahead step whose launch found the program in flight already
            # finished (the device had gone idle: `fused_ahead_late`), and
            # why a step kept the harvest-first order (`SERIAL_REASONS`;
            # None for an ahead step, an idle poll or a last read)
            "late": self._step_late,
            "serial_reason": serial,
            "d2h_ms": self._step_d2h_s * 1e3,
            # per-mode slot occupancy of this step's decode-path dispatches
            "slots": dict(self._step_slots),
            # overload lane (v2-additive): victims evicted this step and the
            # live pool-pressure fraction the decision saw
            "preempted": self._step_preempted,
            "pool_pressure": round(mgr.pool_pressure(), 4),
            # expert-routing account of the programs harvested this step
            # (recurrent configurations; 0 otherwise)
            **self._step_aux,
        })
        return finished

    def step_trace(self) -> List[Dict[str, object]]:
        """The per-step timeline ring, oldest first (bounded at `trace_ring`
        records; cleared by `reset_counters()`)."""
        return list(self._step_trace)

    # ---- fused one-dispatch step machinery --------------------------------
    def _stage_chunk(self) -> Optional[Dict[str, object]]:
        """Chunked mode: pick the oldest mid-prefill slot's next chunk
        and describe it for the fused batch (no standalone dispatch).  The
        host bookkeeping that doesn't need the result — filled counter,
        prefix registration — happens here; a chunk that completes its
        prompt leaves `_prefilling` now and is resolved to a decode slot at
        harvest, when its first token is known."""
        if not self._prefilling:
            return None
        slot, st = next(iter(self._prefilling.items()))
        lp = st.prompt.size
        n = min(self._chunk, lp - st.filled)
        job = {"slot": slot, "n": n, "q_offset": st.filled, "st": st,
               "done": st.filled + n == lp}
        self._tev(st.request.request_id, "prefill_chunk",
                  q_offset=int(st.filled), n=int(n))
        st.filled += n
        self._prefill_chunks.inc()
        self._prefilled_tokens.inc(n)
        if self.prefix_cache:
            self.cache.register_prefix(slot, st.prompt, st.filled)
        if job["done"]:
            del self._prefilling[slot]      # resolved at harvest
        return job

    def _plan_ahead(self, prev: Optional[Dict[str, object]]
                    ) -> Tuple[Optional[Dict[int, tuple]], Optional[str]]:
        """Whether the next fused program can be launched before `prev`, the
        one in flight, is read — decided from what the engine observes this
        step — and, if so, its decode lanes: ({slot: (column of `prev`'s
        `out` that holds the lane's input token, q_offset, greedy, request
        id)}, None).  (None, reason) keeps today's order (harvest, admit,
        build, launch), the reason one of `SERIAL_REASONS`, the first that
        holds in the order tested here.

        Predictable means that `prev`'s tokens are the ONLY thing the next
        batch lacks: `prev` carried no draft (a verify lane's accepted count
        moves its slot's length) and no lane could carry one now (the
        proposer reads the token); nothing waits for the standalone chunk
        program; no admission is due — the queue is empty, or no slot is
        free and `prev` ends no request by its budget — so a new request
        never waits behind one more program than it would have; and, under
        optimistic admission, every lane's next page is there without a
        victim (`_fits_ahead`).  A lane whose request ends with `prev`'s
        token by `max_new_tokens` is left out; one that ends where the host
        cannot foresee it (EOS, a deadline) rides, and `_harvest` drops its
        token.  A decision only: no state moves here (`step()` grows the
        lanes' pages once the plan stands)."""
        if prev is None:
            return None, "idle_start"
        if prev["drafts"]:
            return None, "draft"
        if self._prefilling and not self.chunked:
            return None, "prefilling"
        if self._queue and self._free_slots:
            return None, "admission_due"
        lanes: Dict[int, tuple] = {}
        ending = False
        lengths = self.cache.lengths
        for slot, rid in prev["rids"].items():
            seq = self._running.get(slot)
            if seq is None or seq.request.request_id != rid:
                continue                # ended unforeseen: nothing to feed
            if len(seq.generated) + 1 >= seq.request.max_new_tokens:
                ending = True
            elif self.spec_len and seq.greedy and not seq.spec_off:
                return None, "draft"
            else:
                lanes[slot] = (0, int(lengths[slot]) + 1, seq.greedy, rid)
        cj = prev["chunk"]
        if cj is not None and cj["done"]:
            # the prompt's last chunk: the slot starts decoding at `prev`'s
            # harvest, from the token under the chunk's last position
            st = cj["st"]
            greedy = self._req_greedy(st.request)
            if len(st.prior or ()) + 1 >= st.request.max_new_tokens:
                ending = True
            elif self.spec_len and greedy and not st.spec_off:
                return None, "draft"
            else:
                lanes[cj["slot"]] = (cj["n"] - 1, st.prompt.size, greedy,
                                     st.request.request_id)
        if self._queue and ending:
            return None, "budget_end"   # its slot is free at the harvest
        if self.optimistic and not self._fits_ahead(lanes):
            return None, "pages"
        return lanes, None

    def _fits_ahead(self, lanes: Dict[int, tuple]) -> bool:
        """Optimistic admission, launching ahead: whether the pages under
        ALL the lanes' write positions fit with what is free or evictable —
        no victim, so no page state a program in flight depends on moves.
        False (today's order, where `_grow_running` may preempt) also on a
        step with injected pool pressure."""
        mgr = self.cache
        short = sum(max(0, mgr.pages_needed(q + 1) - mgr.pages_held(slot))
                    for slot, (_, q, _, _) in lanes.items())
        return short <= mgr.num_free_pages + mgr.num_evictable_pages and \
            not self._faults.pressure_due(self._step_idx)

    def _fused_iter(self, chunk_job: Optional[Dict[str, object]],
                    finished: List[RequestOutput],
                    lanes: Optional[Dict[int, tuple]] = None,
                    prev: Optional[Dict[str, object]] = None) -> None:
        """Build and dispatch the ONE fused program covering every active
        lane this step: decode slots at valid=1, drafted (greedy) slots at
        valid=1+len(draft), the staged prefill chunk at valid=chunk tokens.
        Inactive/mid-prefill slots get null table rows.  The dispatch
        returns un-synced; `_harvest` interprets the token/accept buffer —
        immediately (double_buffer=False) or in the next step.

        `lanes` / `prev` (`_plan_ahead`): a launch ahead of `prev`'s
        harvest.  The decode lanes are the plan's, each row's token the
        program's own to take from `prev["out"]`; the host state a lane's
        q_offset was read from is one token behind, which the plan added.
        Without them the lanes are the running slots as the host has them:
        the token from the host's row (column -1), q_offset the slot's
        length, drafts where the proposer has any."""
        mgr = self.cache
        B, T = mgr.num_slots, self._fused_T
        ahead = lanes is not None
        drafts: Dict[int, np.ndarray] = {}
        if not ahead and self.spec_len and self._running:
            with self._span("engine.spec.propose"):
                drafts = self._propose_drafts()
        with self._span("engine.batch.build"):
            if not ahead:
                # optimistic admission: every running slot must own pages
                # for the positions this dispatch writes — growth failures
                # preempt victims out of self._running (and out of drafts)
                # before the batch is built
                self._grow_running(drafts)
                if not self._running and chunk_job is None:
                    return              # everything got preempted this step
                lanes = {slot: (-1, int(mgr.lengths[slot]), seq.greedy,
                                seq.request.request_id)
                         for slot, seq in self._running.items()}
            tokens = np.zeros((B, T), np.int32)
            valid = np.ones((B,), np.int32)
            qoff = np.zeros((B,), np.int32)
            greedy = np.zeros((B,), bool)
            live = np.zeros((B,), bool)
            from_prev = np.full((B,), -1, np.int32)
            rids: Dict[int, int] = {}
            nds: Dict[int, int] = {}
            for slot, (col, q, g, rid) in sorted(lanes.items()):
                # harvested in slot order
                from_prev[slot], qoff[slot], greedy[slot] = col, q, g
                rids[slot] = rid
                if col < 0:
                    tokens[slot, 0] = self._running[slot].generated[-1]
                d = drafts.get(slot)
                if d is not None:
                    tokens[slot, 1:1 + d.size] = d
                    valid[slot] = 1 + d.size
                    nds[slot] = d.size
            slots = list(rids)
            live[slots] = True
            if slots:
                self._decode_iters.inc()
            if chunk_job is not None:
                slot, st = chunk_job["slot"], chunk_job["st"]
                n, q0 = chunk_job["n"], chunk_job["q_offset"]
                tokens[slot, :n] = st.prompt[q0:q0 + n]
                valid[slot] = n
                qoff[slot] = q0
                greedy[slot] = self._req_greedy(st.request)
                live[slot] = True
            table = mgr.page_table.copy()
            table[~live] = 0            # inactive: KV to the null page
            self._note_walk(table, qoff, valid)
        with self._span("engine.fused.dispatch"):
            with self._span("engine.fused.h2d"):
                tokens, table, qoff, valid, greedy = (
                    self._h2d(a) for a in (tokens, table, qoff, valid, greedy))
                # every row the host's: the two device constants, no put
                prev_out, from_prev = (prev["out"], self._h2d(from_prev)) \
                    if ahead else (self._no_prev, self._host_rows)
            if ahead and prev_out.is_ready():
                # the program in flight finished before its successor
                # reached the device: this step's launch-ahead hid nothing
                self._step_late = True
                self._ahead_late.inc()
            out, accept, self._pool, self._key, *aux = self._decode_fn(
                self.params, tokens, self._pool, table, qoff, valid,
                self._key, greedy, prev_out, from_prev)
        self._turn_end(launched=True)       # adds nothing to a launch ahead
        self._decode_used = True
        self._step_dispatches += 1
        self._step_slots["verify"] += len(nds)
        self._step_slots["decode"] += len(slots) - len(nds)
        self._step_slots["chunk"] += int(chunk_job is not None)
        self._launched_ahead.inc(int(ahead))
        if nds:
            # the fused dispatch carried >= 1 draft: it IS this step's verify
            # dispatch (the counter keeps its "verify-program dispatches"
            # meaning for timeline/bench consumers)
            self._verify_steps.inc()
        inflight = {"out": out, "accept": accept, "aux": aux, "rids": rids,
                    "drafts": {s: drafts[s] for s in nds},
                    "chunk": chunk_job}
        if self.double_buffer:
            self._inflight = inflight
        else:
            self._harvest(finished, inflight)

    def _harvest(self, finished: List[RequestOutput],
                 inflight: Optional[Dict[str, object]] = None,
                 turnaround: bool = False) -> None:
        """Fetch and apply the result of a fused dispatch: the `[B, T] + [B]`
        int token/accept buffer (the step's ONLY device->host transfer —
        O(B*K) ints, not [B, V] logits).  Emits each running slot's accepted
        prefix + bonus (or its single decode/sampled token), resolves a
        completed chunk into the decode set, and retires finishers.
        `turnaround`: the step-top harvest opens `engine.turnaround` the
        moment the result is in hand (`_turn_begin`)."""
        inf = inflight if inflight is not None else self._inflight
        if inflight is None:
            self._inflight = None
        if inf is None:
            return
        t_sync = self._now()
        with self._span("engine.sample.sync"):
            # blocks on the device result
            out, accept, aux = jax.device_get(
                (inf["out"], inf["accept"], inf["aux"]))
        t_hand = self._now()
        self._step_sync_s += t_hand - t_sync
        if turnaround:
            self._turn_begin(t_hand)
        drafts = inf["drafts"]
        with self._span("engine.emit"):
            if aux:
                self._note_aux(aux[0], fused=True)
            for slot, rid in inf["rids"].items():
                seq = self._running.get(slot)
                if seq is None or seq.request.request_id != rid:
                    # launched ahead of the harvest that ended this request
                    # (EOS, a deadline): its write landed past what the
                    # slot's pages publish, its token is nobody's
                    self._ahead_discarded.inc()
                    continue
                d = drafts.get(slot)
                nd = 0 if d is None else d.size
                a = int(accept[slot])           # on-device prefix match, <= nd
                # accepted drafts equal the predictions they matched, so the
                # emitted run is out[:a] + the bonus token out[a]
                emitted = [int(x) for x in out[slot, :a + 1]]
                if self._emit_slot(seq, slot, emitted, nd, a, finished):
                    del self._running[slot]
            cj = inf["chunk"]
            if cj is not None and cj["done"]:
                st = cj["st"]
                tok = int(out[cj["slot"], cj["n"] - 1])
                self._start_decoding(st.request, cj["slot"], tok,
                                     st.cached_tokens, finished,
                                     prompt_len=st.prompt.size,
                                     prior=st.prior, ttft=st.ttft,
                                     spec_off=st.spec_off, streak=st.streak)

    def _emit_slot(self, seq: _Running, slot: int, emitted: List[int],
                   nd: int, a: int, finished: List[RequestOutput]) -> bool:
        """Apply one slot's decode/verify emission — budget-room truncation,
        EOS cut, length advance (rejected candidate KV above it is stale
        garbage inside the slot's own reservation), token/spec counters, the
        zero-accept back-off streak — and retire the slot if it finished.
        Returns True when the caller must drop the slot from the running
        set."""
        room = seq.request.max_new_tokens - len(seq.generated)
        emitted = emitted[:room]
        if self.eos_token_id is not None and self.eos_token_id in emitted:
            emitted = emitted[:emitted.index(self.eos_token_id) + 1]
        self.cache.lengths[slot] += len(emitted)
        seq.generated.extend(emitted)
        self._stamp_emit(seq.request.request_id, len(emitted))
        self._decode_tokens.inc(len(emitted))
        if nd:
            self._spec_events.inc()
            self._spec_drafted.inc(nd)
            self._spec_accepted.inc(a)
            self._spec_emitted.inc(len(emitted))
            self._tev(seq.request.request_id, "spec_verify", drafted=int(nd),
                      accepted=int(a), emitted=len(emitted))
            # adaptive spec back-off: a slot whose drafts are NEVER accepted
            # (acceptance rate ~0 over the window) stops paying the proposer
            # scan and the wasted candidate positions — it keeps riding the
            # decode-side program at valid=1.  Output parity is untouched:
            # greedy acceptance is lossless either way.
            if a == 0:
                seq.spec_zero_streak += 1
                if self.spec_backoff_window and not seq.spec_off and \
                        seq.spec_zero_streak >= self.spec_backoff_window:
                    seq.spec_off = True
                    self._spec_backoffs.inc()
            else:
                seq.spec_zero_streak = 0
        return self._maybe_finish(seq, finished)

    @staticmethod
    def _refuse_for_pattern(recurrent: bool, *, spec_len, admission, preempt,
                            weight_dtype, kv_dtype, mp, mesh, role) -> None:
        """What a patterned configuration cannot be served with yet, each
        with the reason (ROADMAP queue B has what would lift it); the last
        two only where its pattern holds recurrent state (`M`)."""
        why = "a patterned configuration (layer_pattern) "
        if spec_len:
            raise ValueError(
                why + "cannot be served with speculative decoding: its fused "
                "step has no accept scan, and a rejected draft would already "
                "have moved a recurrent state with no roll-back of it "
                "(spec_len must be 0)")
        if weight_dtype is not None or kv_dtype is not None:
            raise ValueError(
                why + "has no quantized serving path (weight_dtype and "
                "kv_dtype must be None)")
        if (mp is not None and mp > 1) or mesh is not None:
            raise ValueError(
                why + "is served on one chip (no mp / mesh): its state "
                "lanes, latent lane and expert layer have no sharded form "
                "yet")
        if not recurrent:
            return
        why = "a configuration with recurrent state (an M in layer_pattern) "
        if admission == "optimistic" and preempt == "swap":
            raise ValueError(
                why + "cannot be preempted by swap: the swap programs move "
                "pages, not the slot's state (use preempt='recompute')")
        if role is not None:
            raise ValueError(
                why + "cannot hand prompts off through the KV tier store "
                "(role must be None): the store keeps pages, not state")

    def _note_walk(self, table, q_offset, valid) -> None:
        """Account one dispatch of a program that holds the paged prefill
        kernel, from the host arrays it was built with: the kernel walks the
        pages at or below each row's last real query position, and none of
        a null row (the models tell it an inactive slot has no real query)."""
        table = np.asarray(table)
        page = self.cache.page_size
        walked = int(np.sum(
            -(-(np.asarray(q_offset) + np.asarray(valid)) // page),
            where=table[:, 0] != 0))
        self._step_pages_walked += walked
        self._pages_walked.inc(walked)
        self._table_entries.inc(table.size)

    def _note_aux(self, aux: np.ndarray, fused: bool = False) -> None:
        """Fold one hybrid program's counter vector (`hybrid.aux_fields`,
        already on the host: it came with the tokens) into the registry and
        the step's ring fields (`fused`: a fused step's, whose DSA rows are
        also kept apart)."""
        vals = dict(zip(hybrid_mod.aux_fields(self.config),
                        (int(v) for v in aux)))
        self._moe_load_max = vals.pop("moe_load_max")
        for name, v in vals.items():
            self._aux_counters[name].inc(v)
            if name in self._step_aux:
                self._step_aux[name] += v
            if fused and name in self._dsa_step_rows:
                self._dsa_step_rows[name] += v
        if self.cache.state is not None:
            self._ssm_state_bytes.inc(
                2 * vals["ssm_slots_live"] * self.cache.state.bytes_per_slot)

    def _latent_page_bytes(self, name: str = "c") -> int:
        lane = self._pool.get(name)
        return 0 if lane is None else \
            lane.dtype.itemsize * lane.size // lane.shape[1]

    def _index_page_bytes(self) -> int:
        return self._latent_page_bytes("ix")

    def _stamp_emit(self, rid: int, n: int, t: Optional[float] = None) -> None:
        """One `RequestMetrics.emit_times` pair: `n` tokens were appended to
        request `rid` now (or at `t`, a clock reading the caller holds)."""
        lc = self._lifecycles.get(rid)
        if lc is not None and n:
            lc.emit_times.append((self._now() if t is None else t, n))

    # ---- oversubscription: growth, preemption, swap, deadlines ------------
    def _grow_running(self, drafts: Dict[int, np.ndarray]) -> None:
        """Optimistic admission's pre-dispatch capacity pass: every running
        slot must own pages covering the positions this step will write
        (its last token's KV at lengths, plus one slot per drafted
        candidate).  A failed growth is THE preemption trigger: victims are
        evicted until the growth fits, the growing slot itself last of all
        (it re-queues at the head and replays later).  Runs only in a step
        that kept the harvest-first order, strictly after the step-top
        harvest, so nothing is in flight while a victim's page state moves
        (the TPL007 discipline); a step that launches ahead grows its lanes
        itself, and only where no victim is needed (`_fits_ahead`).
        `drafts` is pruned of any slot that got preempted.  Reservation mode
        returns immediately — every slot's full footprint is already
        reserved."""
        if not self.optimistic or not self._running:
            return
        forced = self._faults.pool_pressure(self._step_idx)
        for slot in list(self._running):
            while slot in self._running:
                d = drafts.get(slot)
                need = int(self.cache.lengths[slot]) + 1 + \
                    (d.size if d is not None else 0)
                try:
                    if forced:
                        forced = False
                        raise RuntimeError("fault-injected pool pressure")
                    self.cache.grow(slot, need)
                    break
                except RuntimeError:
                    # the growing slot is a candidate too: if IT ranks worst
                    # (lowest priority), preempting it both respects the
                    # policy and resolves the failure — and alone it always
                    # fits eventually (add_request rejected any footprint
                    # larger than the pool), so its replay cannot loop
                    self._tev(self._running[slot].request.request_id,
                              "grow_fail", need_tokens=int(need))
                    self._preempt_slot(self._pick_victim())
        for slot in list(drafts):
            if slot not in self._running:
                del drafts[slot]

    def _pick_victim(self) -> int:
        """The next preemption victim among ALL running slots: lowest
        priority first, then most pages held (frees the most), least
        progress (least work at stake), youngest last-arrived."""
        return min(
            self._running.items(),
            key=lambda kv: (kv[1].request.priority,
                            -self.cache.pages_held(kv[0]),
                            len(kv[1].generated) /
                            kv[1].request.max_new_tokens,
                            -kv[1].request.request_id))[0]

    def _preempt_slot(self, slot: int) -> None:
        """Evict one running slot: bank its generation, park its KV (swap
        mode, pool room permitting) or mark it for recompute, release its
        pages, and re-queue it at the HEAD (preempted work outranks fresh
        arrivals — starving a half-done request wastes the pages it already
        burned)."""
        seq = self._running.pop(slot)
        req = seq.request
        rid = req.request_id
        mgr = self.cache
        self._preemptions.inc()
        self._step_preempted += 1
        rec: Dict[str, object] = {
            "rid": rid, "kind": "recompute",
            "generated": list(seq.generated),
            "cached_tokens": seq.cached_tokens, "ttft": seq.ttft_s,
            "spec_off": seq.spec_off, "streak": seq.spec_zero_streak,
        }
        L = int(mgr.lengths[slot])
        n = mgr.pages_needed(L)
        # live victims outrank cached prefixes in the unified host pool:
        # reclaim tier room (demote to disk or drop) before giving up
        room = self._host_room_for(n) if self.preempt == "swap" else -1
        if self.preempt == "swap" and n <= room:
            # gather the victim's pages into standalone buffers NOW (the
            # pages are about to be handed to a new owner); their copies to
            # the host start at once, off this thread
            rec.update(kind="swap", L=L, n=n, fetched=False,
                       pieces=self._gather_d2h(mgr.slot_pages(slot)[:n]))
            mgr.note_swap_out(rid, n)
            self._pending_d2h.append(rec)
            # swapped_pages/preempt_swaps count at d2h SUCCESS (in
            # _materialize_swap): a copy that fails and degrades to
            # recompute never delivered KV to the host pool, and the
            # bench's swap-vs-recompute split must not claim it did
        else:
            self._preempt_recomputes.inc()
        self._tev(rid, "preempt", kind=rec["kind"], pages=int(n),
                  progress=len(seq.generated))
        self._preempted[rid] = rec
        lc = self._lifecycles.get(rid)
        if lc is not None:
            lc.preemptions += 1
        mgr.release(slot)
        self._free_slots.append(slot)
        self._queue.appendleft(req)

    def _host_room_for(self, n: int) -> int:
        """Pages of the UNIFIED host pool open to `n` more: what swap
        parking and the tier have not claimed, reclaiming host-tier room
        downward (disk or drop) when short.  A pending gather cannot move,
        so what has arrived lands first, and only if room is still short
        does the engine wait for the records in flight — no page is turned
        away that a landed tier would have had room for."""
        mgr = self.cache
        room = mgr.host_pool_room(self.swap_pool_pages)
        for wait in (False, True):
            if room >= n or (wait and not self._pending_d2h):
                break
            self._land_d2h(wait=wait)
            room += mgr.tier_make_room(n - room)
        return room

    def _gather_d2h(self, pages: Sequence[int]) -> List[tuple]:
        """How bytes leave the device, for a spill and a swap-out alike:
        ONE dispatch gathers `pages` out of the pool as pieces of `_swap_w`
        pages (one fixed-shape executable, a slot's width of ids padded with
        the null page 0; a page is copied where it lies, so the program's
        device time is a slot's width of page copies whatever the pool's
        size) and each piece that holds wanted pages starts its
        device->host copy on the fetch worker right away — the engine
        thread does not wait for it, and the pieces past the wanted pages
        are dropped where they lie.  The worker copies one piece at a time,
        in order, so a token read issued beside spill bytes is held up by
        one piece at most.  Returns the pieces as (future of the pages' host
        content, pages wanted, the gathered buffers); a gathered buffer
        stays in HBM until the engine has taken its piece, so before a
        dispatch that would put more than `_d2h_bound` pages in flight the
        oldest records land first."""
        W, slot_w = self._swap_w, self._d2h_slot_w
        if self._d2h_worker is None:
            self._d2h_worker = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="kv-d2h")
        pieces = []
        for i in range(0, len(pages), slot_w):
            chunk = pages[i:i + slot_w]
            width = -(-len(chunk) // W) * W
            # one span a dispatch of the gather program; the records it has
            # to land first (`engine.swap.d2h`) are its children
            with self._span("engine.swap.gather"):
                while self._d2h_inflight + width > self._d2h_bound and \
                        self._pending_d2h:
                    if not all(p[0].done()
                               for p in self._pending_d2h[0]["pieces"]):
                        self._d2h_bp_waits.inc()
                    self._land_record(self._pending_d2h.pop(0))
                ids = np.zeros((slot_w,), np.int32)
                ids[:len(chunk)] = chunk
                gathered = self._swap_out_fn(self._pool, self._h2d(ids))
                for j in range(0, len(chunk), W):
                    data, n = gathered[j // W], min(W, len(chunk) - j)
                    pieces.append((self._d2h_worker.submit(
                        _fetch_on_worker, data, n), n, data))
            self._d2h_inflight += width
        self._swap_out_used = True
        return pieces

    def _take_piece(self, piece: tuple) -> List[Dict[str, np.ndarray]]:
        """The engine thread takes one piece's pages — the one place it may
        be held by spill/swap bytes.  `.ready` is its wait for a copy the
        worker is in the middle of (nothing when the bytes have landed),
        `.copy` the hand-over, or the copy itself where the worker had not
        reached the piece; the same calls run traced or not.  Counts what
        crossed against what was wanted, and whether the engine was held."""
        fut, n, data = piece
        t0 = self._now()
        with self._span("engine.swap.d2h"):
            with self._span("engine.swap.d2h.ready"):
                if fut.done():
                    self._d2h_landed_free.inc()
                elif not fut.cancel():      # the worker is at it: wait
                    _wait_futures([fut])
            t1 = self._now()
            with self._span("engine.swap.d2h.copy"):
                # a piece the worker had not reached yet is copied here and
                # now, rather than waited for behind the ones before it
                pages = _fetch_piece(data, n) if fut.cancelled() \
                    else fut.result()
        dt = self._now() - t0
        self._d2h_inflight -= self._swap_w
        self._d2h_blocked_ms.inc((t1 - t0) * 1e3)
        self._swap_ms_c.inc(dt * 1e3)
        self._step_d2h_s += dt
        self._d2h_fetches.inc()
        self._d2h_bytes.inc(self._swap_w * self._kv_page_bytes)
        self._d2h_useful.inc(n * self._kv_page_bytes)
        return pages

    def _release_pieces(self, rec: Dict[str, object]) -> None:
        """Let go of a record's pieces the engine will never take (a failed
        copy, a dropped victim): one the worker has not started is
        cancelled, the others finish and are forgotten."""
        for piece in rec.pop("pieces", ()):
            piece[0].cancel()
            self._d2h_inflight -= self._swap_w

    def _materialize_swap(self, rec: Dict[str, object]) -> None:
        """Take a swap record's pages into host numpy, waiting for those
        still in flight (idempotent).  Raises FaultInjected under an
        injected d2h failure — the caller degrades the record to
        recompute."""
        if rec.get("fetched"):
            return
        self._faults.d2h()
        data: List[Dict[str, np.ndarray]] = []
        while rec["pieces"]:
            data += self._take_piece(rec["pieces"].pop(0))
        rec["data"] = data
        rec["fetched"] = True
        self._swapped_pages_c.inc(rec["n"])
        self._preempt_swaps.inc()
        self._tev(rec["rid"], "swap_out", pages=int(rec["n"]))

    def _degrade_to_recompute(self, rec: Dict[str, object]) -> None:
        """A swap whose d2h/h2d copy failed falls back to recompute: drop
        the parked KV, clear the host-pool obligation, keep the banked
        generation — nothing leaks, the replay just costs prefill again."""
        rec["kind"] = "recompute"
        rec.pop("data", None)
        self._release_pieces(rec)
        self.cache.note_swap_in(rec["rid"])
        self._preempt_recomputes.inc()
        self._tev(rec["rid"], "swap_degrade")

    def _land_record(self, rec: Dict[str, object]) -> None:
        """Land one pending record on the engine thread (the tier and the
        cache stay single-threaded): its pages go into the host tier or the
        swap record, waiting for bytes still in flight; a failed copy
        degrades spill -> drop and swap -> recompute.  A record consumed,
        degraded or dropped in the meantime only gives up its pieces."""
        if not _d2h_live(rec):
            self._release_pieces(rec)
            return
        spill = rec["kind"] == "spill"
        try:
            (self._materialize_spill if spill else self._materialize_swap)(rec)
        except FaultInjected:
            (self._degrade_spill_to_drop if spill
             else self._degrade_to_recompute)(rec)

    def _land_d2h(self, wait: bool = False, spills_only: bool = False) -> None:
        """Land the pending records whose bytes have arrived and leave the
        others in flight — what `step()` does at its boundary, after the
        dispatch.  `wait=True` lands every record, waiting for its bytes:
        where the engine needs them (a tier restore or an export:
        `spills_only`, swap records keep their own seam in `_swap_in`) or
        is about to rest (`run`, `drain`, `stop_loop`, `reset_counters`)."""
        rest: List[Dict[str, object]] = []
        for rec in self._pending_d2h:
            if _d2h_live(rec) and (
                    (spills_only and rec["kind"] != "spill") or not (
                        wait or all(p[0].done() for p in rec["pieces"]))):
                rest.append(rec)
            else:
                self._land_record(rec)
        self._pending_d2h = rest

    # ---- KV tiering: prefix spill (device -> host -> disk) and restore ----
    def _spill_prefix_nodes(self, nodes) -> set:
        """`PagedKVCache._evict`'s spill callback: gather the evicted
        prefix pages and start their copies to the host (`_gather_d2h`, the
        path a preemption swap-out takes), one pending record a piece, each
        landing in the host tier at the first step boundary after its bytes
        arrive.  Room comes from the UNIFIED host pool (`_host_room_for`).
        Returns the node ids accepted — the cache drops the rest."""
        room = self._host_room_for(len(nodes))
        if room <= 0:
            return set()
        accept = nodes[-room:] if room < len(nodes) else nodes
        pieces = self._gather_d2h([nd.page for nd in accept])
        # pending only now: the nodes become tier entries when this returns,
        # and a record landed before that (the bound) would find none
        W = self._swap_w
        self._pending_d2h += [
            {"kind": "spill", "nodes": list(accept[i * W:(i + 1) * W]),
             "pieces": [piece], "fetched": False}
            for i, piece in enumerate(pieces)]
        return {nd.node_id for nd in accept}

    def _materialize_spill(self, rec: Dict[str, object]) -> None:
        """Take a spill record's pages into the host tier, waiting for them
        if they are still in flight (idempotent).  Raises FaultInjected
        under an injected d2h failure — the caller degrades spill -> drop."""
        if rec.get("fetched"):
            return
        self._faults.d2h()
        pages = self._take_piece(rec["pieces"].pop())
        rec["fetched"] = True
        tier = self.cache._tier
        landed = 0
        for node, data in zip(rec["nodes"], pages):
            if tier is not None and tier.is_pending(node.node_id):
                tier.fill(node.node_id, data)
                landed += 1
        self._tier_spills.inc(landed)

    def _degrade_spill_to_drop(self, rec: Dict[str, object]) -> None:
        """A spill whose d2h copy failed drops its nodes from the index —
        the pages were already reclaimed, so the only cost is that a later
        match re-prefills instead of restoring.  Nothing leaks."""
        rec["fetched"] = True           # never retried
        self._release_pieces(rec)
        tier = self.cache._tier
        pend = [nd for nd in rec["nodes"]
                if tier is not None and tier.is_pending(nd.node_id)]
        self.cache.drop_tier_nodes(pend)

    def _tier_restore(self, slot: int, plan, rid: int) -> bool:
        """Scatter a matched prefix's parked KV from the host/disk tier into
        `slot`'s freshly allocated pages — ONE `swap_in_pages` dispatch for
        the whole plan, after which the restored full pages are device
        prefix pages again (`commit_restore`).  Returns False when the
        restore degraded (failed h2d copy, vanished tier data): the plan's
        nodes are dropped and the caller re-matches — the request
        re-prefills those positions instead, nothing leaks."""
        mgr = self.cache
        tier = mgr._tier
        if any(tier.is_pending(node.node_id) for _, node, _ in plan):
            # the restore needs bytes still in flight: wait for them
            self._land_d2h(wait=True, spills_only=True)
        nodes = [node for _, node, _ in plan]
        try:
            datas = [mgr.tier_data(node) for node in nodes]
            self._faults.h2d()
        except (KeyError, RuntimeError):
            # FaultInjected is a RuntimeError; real vanished-data errors
            # degrade identically — drop the nodes, let the caller re-match
            mgr.drop_tier_nodes(nodes)
            return False
        k = len(plan)
        ids = np.zeros((mgr.max_pages_per_slot,), np.int32)
        staging: Dict[str, np.ndarray] = {}
        for name, a in datas[0].items():
            staging[name] = np.zeros(
                (a.shape[0], mgr.max_pages_per_slot) + a.shape[1:], a.dtype)
        for i, ((dst, node, ntok), d) in enumerate(zip(plan, datas)):
            ids[i] = dst
            for name, a in d.items():
                staging[name][:, i] = a
        t0 = self._now()
        with self._span("engine.swap.h2d"):
            up = {name: self._h2d(a) for name, a in staging.items()}
            self._pool = self._swap_in_fn(self._pool, self._h2d(ids), up)
        self._swap_in_used = True
        self._swap_ms_c.inc((self._now() - t0) * 1e3)
        self._note_h2d(up, k)
        mgr.commit_restore(slot, plan)
        tokens = sum(ntok for _, _, ntok in plan)
        self._tier_restores.inc()
        self._tier_restored_tokens.inc(tokens)
        self._tev(rid, "tier_restore", slot=slot, pages=int(k),
                  tokens=int(tokens))
        return True

    def _note_h2d(self, staged, n: int) -> None:
        """Count one swap-in/tier-restore scatter: the staged buffers' bytes
        against the bytes of the `n` pages it was for."""
        self._h2d_bytes.inc(sum(a.nbytes for a in staged.values()))
        self._h2d_useful.inc(n * self._kv_page_bytes)

    def export_prefix(self, tokens: np.ndarray,
                      rid: Optional[int] = None) -> Dict[str, int]:
        """Disaggregated handoff (send side): publish the cached KV chain of
        `tokens` to the shared tier store so a DECODE-role peer can restore
        it with one scatter.  Device-resident chain nodes that are parked in
        the LRU (refcount 0 — the finished prompt just released them) spill
        through the ordinary `_spill_prefix_nodes` gather, the pending d2h
        is flushed, host entries are pushed to the store level, and the
        durable index is re-published.  Zero new programs: the export rides
        the same two swap executables the tier already warmed.  Returns
        {"pages", "tokens", "index_nodes"} — all 0 when no store is
        attached or nothing was exportable (the peer then degrades to local
        re-prefill, parity-lossless)."""
        from .cache import HOST_PAGE
        out = {"pages": 0, "tokens": 0, "index_nodes": 0}
        with self._serve_lock:
            mgr = self.cache
            tier = mgr._tier
            if not self.kv_tier or tier is None or tier.store is None:
                return out
            full, partial = mgr._match(np.asarray(tokens, np.int32))
            chain = list(full) + ([partial[0]] if partial else [])
            if not chain:
                return out
            todo = [nd for nd in chain
                    if nd.page >= 0 and nd.node_id in mgr._lru]
            accepted = self._spill_prefix_nodes(todo) if todo else set()
            for nd in todo:
                if nd.node_id not in accepted:
                    continue
                # mirror _evict's accept bookkeeping: the page goes back to
                # the free pool, the node becomes an off-device tier entry
                mgr._lru.pop(nd.node_id)
                mgr._free.append(nd.page)
                del mgr._page_node[nd.page]
                nd.page = HOST_PAGE
                mgr._tier_nodes[nd.node_id] = nd
                tier.add_pending(nd.node_id)
            self._land_d2h(wait=True, spills_only=True)
            pages = tokens_out = 0
            for nd in chain:
                if nd.page >= 0 or tier.is_pending(nd.node_id):
                    continue            # still on device / spill degraded
                if nd.node_id in tier._host:
                    tier.to_disk(nd.node_id)
                if nd.node_id in tier._disk:
                    pages += 1
                    tokens_out += nd.n_tokens
            out["pages"] = pages
            out["tokens"] = tokens_out
            out["index_nodes"] = mgr.save_tier_index(tag=tier.tag)
            if pages:
                self._handoff_exports.inc()
                self._handoff_pages.inc(pages)
                self._handoff_tokens.inc(tokens_out)
                if rid is not None:
                    # the prefill request has already retired (export runs
                    # after result()), so its trace rides the RequestOutput
                    # — _tev only sees live traces and would drop the event
                    tr = self._trace_for(rid)
                    if tr is not None:
                        tr.event(self._now(), "handoff", pages=int(pages),
                                 tokens=int(tokens_out))
        return out

    def refresh_store_index(self) -> int:
        """Disaggregated handoff (receive side): re-merge the shared store's
        published index so the NEXT admission can tier-restore prefixes a
        prefill peer just exported.  Idempotent and cheap (already-known
        nodes are skipped).  Returns nodes newly imported."""
        if not self.kv_tier:
            return 0
        with self._serve_lock:
            n = self.cache.load_tier_index()
        self._store_restored_nodes += n
        return n

    def _drop_preempted(self, rid: int) -> Optional[Dict[str, object]]:
        """Remove a resume record on abort/timeout, clearing any host swap
        obligation; returns the record (its banked generation feeds the
        output) or None."""
        rec = self._preempted.pop(rid, None)
        if rec is None:
            return None
        if rec["kind"] == "swap":
            self.cache.note_swap_in(rid)
            rec["kind"] = "dropped"     # _land_record lets its pieces go
        return rec

    def _swap_in(self, req: Request, rec: Dict[str, object],
                 slot: int) -> bool:
        """Restore a swapped victim into `slot`: allocate fresh pages for
        its parked footprint and scatter the KV back in one h2d dispatch —
        the request rejoins the decode set with NO prefill replay.  Returns
        True when running again; False when it must keep waiting for pages
        or was degraded to recompute (the caller re-examines the record)."""
        rid = req.request_id
        mgr = self.cache
        try:
            self._materialize_swap(rec)
        except FaultInjected:
            self._degrade_to_recompute(rec)
            return False
        try:
            mgr.allocate(slot, rec["L"])
        except RuntimeError:            # no pages yet — stay queued
            return False
        try:
            self._faults.h2d()
        except FaultInjected:
            mgr.release(slot)
            self._degrade_to_recompute(rec)
            return False
        n = rec["n"]
        ids = np.zeros((mgr.max_pages_per_slot,), np.int32)
        ids[:n] = mgr.slot_pages(slot)[:n]
        data = {}
        t0 = self._now()
        with self._span("engine.swap.h2d"):
            # staging uploads count as h2d cost: swap_ms and the span cover
            # the host->device copies AND the scatter dispatch, as in the
            # single-lane (k, v) form this generalizes
            for name, a in rec["data"][0].items():
                pad = np.zeros(
                    (a.shape[0], mgr.max_pages_per_slot) + a.shape[1:],
                    a.dtype)
                for i, page in enumerate(rec["data"]):
                    pad[:, i] = page[name]
                data[name] = self._h2d(pad)
            self._pool = self._swap_in_fn(self._pool, self._h2d(ids), data)
        self._swap_in_used = True
        self._swap_ms_c.inc((self._now() - t0) * 1e3)
        self._note_h2d(data, n)
        mgr.note_swap_in(rid)
        self._preempted.pop(rid)
        self._tev(rid, "swap_in", slot=slot, pages=int(n))
        mgr.lengths[slot] = rec["L"]
        seq = _Running(req, slot, list(rec["generated"]),
                       rec["cached_tokens"], rec["ttft"],
                       self._req_greedy(req))
        seq.spec_off = rec["spec_off"]
        seq.spec_zero_streak = rec["streak"]
        self._running[slot] = seq
        return True

    def _expire_deadlines(self, finished: List[RequestOutput]) -> None:
        """Retire every request past its deadline, wherever it lives
        (queued/swapped, prefilling, decoding), as finish_reason="timeout".
        Runs right after the step's harvest so page bookkeeping is exact (in
        a step that launched ahead the next program is in flight by then: a
        slot retired here leaves a lane in it, dropped at its harvest);
        injected clock skew (FaultPlan.skew) shifts only this evaluation.
        Also re-derives `_has_deadlines` so an engine that served one
        deadlined request long ago stops paying this scan once no
        deadline-bearing request remains."""
        now = self._now() + self._faults.skew()
        live = False
        for i in range(len(self._queue) - 1, -1, -1):
            req = self._queue[i]
            if req.deadline is not None and now >= req.deadline:
                del self._queue[i]
                rec = self._drop_preempted(req.request_id)
                gen = list(rec["generated"]) if rec is not None else []
                finished.append(self._finish_output(
                    req, gen, "timeout",
                    rec["cached_tokens"] if rec is not None else 0,
                    rec["ttft"] if rec is not None else None))
            elif req.deadline is not None:
                live = True
        for slot, st in list(self._prefilling.items()):
            req = st.request
            if req.deadline is not None and now >= req.deadline:
                del self._prefilling[slot]
                self.cache.release(slot)
                self._free_slots.append(slot)
                finished.append(self._finish_output(
                    req, list(st.prior or []), "timeout",
                    st.cached_tokens, st.ttft))
            elif req.deadline is not None:
                live = True
        for slot, seq in list(self._running.items()):
            req = seq.request
            if req.deadline is not None and now >= req.deadline:
                del self._running[slot]
                self.cache.release(slot)
                self._free_slots.append(slot)
                finished.append(self._finish_output(
                    req, seq.generated, "timeout", seq.cached_tokens,
                    seq.ttft_s))
            elif req.deadline is not None:
                live = True
        self._has_deadlines = live

    def _admit(self, finished: List[RequestOutput]) -> None:
        mgr = self.cache
        while self._queue and self._free_slots:
            req = self._queue[0]
            rid = req.request_id
            slot = self._free_slots[-1]
            rec = self._preempted.get(rid)
            if rec is not None and rec["kind"] == "swap":
                # swap resume: one h2d scatter, no prefill replay
                if self._swap_in(req, rec, slot):
                    self._queue.popleft()
                    self._free_slots.pop()
                    continue
                if rec["kind"] == "swap":
                    break               # no pages yet — wait at the head
                continue                # degraded to recompute: retry now
            prior = list(rec["generated"]) if rec is not None else None
            if prior:
                # recompute resume: the banked generation is just a longer
                # prompt — replayed through the prefix cache (its own pages
                # are usually still indexed) and chunked prefill
                prompt = np.concatenate(
                    [req.prompt, np.asarray(prior, np.int32)])
            else:
                prompt = req.prompt
            lp = prompt.size
            remaining = req.max_new_tokens - len(prior or ())
            # optimistic admission: reserve the PROMPT footprint only —
            # decode growth allocates the rest token-granularly
            total = lp if self.optimistic else lp + remaining
            if self.optimistic and rec is None and \
                    (self._running or self._prefilling) and \
                    mgr.pages_needed(lp) + self._watermark > \
                    mgr.num_free_pages + mgr.num_evictable_pages:
                # vLLM-style admission watermark: a small GLOBAL headroom
                # (~1% of the pool, >= 1 page) so a fresh admission cannot
                # consume the very last page a running slot needs this step;
                # beyond that, preemption — not admission control — is the
                # pressure valve (a per-slot headroom would just re-create
                # reservation admission with extra steps).  Only enforced
                # while something is actually active: on an idle engine
                # there is no slot to protect, and holding back a prompt
                # whose footprint sits within the watermark of the whole
                # pool would wedge the queue head forever
                break
            tokens = prompt if self.prefix_cache else None
            alloc = None
            restored = ()
            # one span a queue head, pages or none: the match, the
            # reservation and the eviction it sets off (host-tier room, the
            # spill's gather) — `engine.admit`'s share that is not prefill
            with self._span("engine.admit.reserve"):
                while True:
                    try:
                        # one shot: the prefix match and the reservation
                        # happen in the same call (a failed attempt rolls its
                        # sharing back), instead of re-hashing the prompt in
                        # a can_allocate probe every step
                        alloc = mgr.allocate_prefixed(slot, total, tokens)
                    except RuntimeError:        # out of KV pages
                        alloc = None
                        break
                    plan = mgr.take_restore(slot)
                    if not plan:
                        break
                    # the match reached into the KV tier: ONE swap_in scatter
                    # restores the parked prefix into the slot's fresh pages
                    # — no prefill replay.  A degraded restore (failed copy,
                    # vanished data) dropped the offending nodes; roll the
                    # slot back and re-match without them.
                    if self._tier_restore(slot, plan, rid):
                        restored = plan
                        break
                    mgr.release(slot)
            if alloc is None:
                if not self._running and not self._prefilling and \
                        mgr.pages_in_use() == 0:
                    # backstop (near-unreachable since add_request rejects
                    # impossible footprints): nothing will ever free
                    raise ValueError(
                        f"request {rid} needs "
                        f"{mgr.pages_needed(total)} pages but the pool only "
                        f"has {mgr.num_pages - 1}; raise num_pages")
                break                       # wait for pages to free up
            row, matched, cow = alloc
            self._queue.popleft()
            self._free_slots.pop()
            lc = self._lifecycles.get(rid)
            if lc is not None and lc.t_admit is None:
                lc.t_admit = self._now()
                lc.queue_s = lc.t_admit - lc.t_enqueue
                self._h_queue.observe(lc.queue_s, exemplar=self._exemplar(rid))
                lc.cached_tokens = matched
            self._admitted_requests.inc()
            if self.recurrent and self._prefix_wanted:
                self._prefix_skipped.inc()
            self._tev(rid, "admit", slot=slot, prefix_hit_tokens=int(matched),
                      cow=cow is not None, resume=rec is not None)
            if rec is not None:
                self._preempted.pop(rid)
                self._recomputed_tokens.inc(lp - matched)
            # resume-state fan-out, computed ONCE for both branches below
            cached_out = rec["cached_tokens"] if rec is not None else matched
            r_ttft = rec["ttft"] if rec is not None else None
            r_spec_off = rec["spec_off"] if rec is not None else False
            r_streak = rec["streak"] if rec is not None else 0
            if cow is not None:
                # the matched partial page is shared: copy it into the slot's
                # own page before anything is appended into it
                src, dst = cow
                self._pool = self._copy_fn(self._pool,
                                           self._h2d(src, np.int32),
                                           self._h2d(dst, np.int32))
                self._cow_copies.inc()
                self._copy_used = True
            if cow is not None or any(ntok < mgr.page_size
                                      for _, _, ntok in restored):
                # rolling-hash partial-page hit: the match ended INSIDE a
                # cached page (device COW copy or tier scatter of the
                # matched fraction)
                self._partial_hits.inc()
            if matched:
                self._prefix_cached_tokens.inc(matched)
                self._prefix_hit_requests.inc()
            if not self.chunked and matched == 0:
                # one-shot bucketed prefill, synchronous at admission
                bucket = self._bucket_for(lp)
                self._tev(rid, "prefill", n=int(lp), bucket=int(bucket))
                ids = np.zeros((1, bucket), np.int32)
                ids[0, :lp] = prompt
                pages = row[:bucket // mgr.page_size][None, :]
                with self._span("engine.prefill.dispatch"):
                    # a recurrent configuration's prefill is also told the
                    # slot (where the prompt's state is kept) and also
                    # returns its counters
                    first, self._pool, self._key, *aux = self._prefill_fn(
                        self.params, self._h2d(ids), self._pool,
                        self._h2d(pages), self._h2d([lp], np.int32),
                        self._key, self._h2d([self._req_greedy(req)]),
                        *([self._h2d([slot], np.int32)]
                          if self.patterned else []))
                self._seen_buckets.add(bucket)
                self._prefilled_tokens.inc(lp)
                if self.prefix_cache:
                    mgr.register_prefix(slot, prompt, lp)
                t_sync = self._now()
                with self._span("engine.sample.sync"), \
                        self._span("engine.prefill.sync"):
                    first, aux = jax.device_get((first, aux))   # blocks
                    first = int(first[0])
                self._step_sync_s += self._now() - t_sync
                if aux:
                    self._note_aux(aux[0])
                self._start_decoding(
                    req, slot, first, cached_out, finished, prompt_len=lp,
                    prior=prior, ttft=r_ttft, spec_off=r_spec_off,
                    streak=r_streak)
            else:
                self._prefilling[slot] = _Prefilling(
                    req, slot, matched, cached_out, prompt=prompt,
                    prior=prior, ttft=r_ttft, spec_off=r_spec_off,
                    streak=r_streak)

    def _prefill_tick(self, finished: List[RequestOutput]) -> None:
        """Advance the oldest admitted prompt by ONE chunk through the
        standalone chunk program (the Sarathi interleave cap: long prompts
        share each iteration with decode instead of stalling it).  Serves
        prefix-hit tails in bucketed mode; in chunked mode the chunk rides
        the fused batch instead (`_stage_chunk`)."""
        if not self._prefilling:
            return
        slot, st = next(iter(self._prefilling.items()))
        mgr = self.cache
        lp = st.prompt.size
        C = self._chunk
        n = min(C, lp - st.filled)
        self._tev(st.request.request_id, "prefill_chunk",
                  q_offset=int(st.filled), n=int(n))
        ids = np.zeros((1, C), np.int32)
        ids[0, :n] = st.prompt[st.filled:st.filled + n]
        self._note_walk(mgr.page_table[slot][None, :], [st.filled], [n])
        with self._span("engine.prefill.dispatch"):
            tok, self._pool, self._key, *aux = self._chunk_fn(
                self.params, self._h2d(ids), self._pool,
                self._h2d(mgr.page_table[slot][None, :]),
                self._h2d([st.filled], np.int32),
                self._h2d([n], np.int32),
                self._key, self._h2d([self._req_greedy(st.request)]))
        self._chunk_used = True
        self._step_dispatches += 1
        self._step_slots["chunk"] += 1
        self._prefill_chunks.inc()
        self._prefilled_tokens.inc(n)
        st.filled += n
        if self.prefix_cache:
            mgr.register_prefix(slot, st.prompt, st.filled)
        if st.filled == lp:
            del self._prefilling[slot]
            t_sync = self._now()
            with self._span("engine.sample.sync"), \
                    self._span("engine.prefill.sync"):
                tok, aux = jax.device_get((tok, aux))   # blocks on the result
                tok = int(tok[0])
            self._step_sync_s += self._now() - t_sync
            if aux:
                self._note_aux(aux[0])
            self._start_decoding(st.request, slot, tok, st.cached_tokens,
                                 finished, prompt_len=lp, prior=st.prior,
                                 ttft=st.ttft, spec_off=st.spec_off,
                                 streak=st.streak)

    def _start_decoding(self, req: Request, slot: int, first: int,
                        cached: int, finished: List[RequestOutput],
                        prompt_len: Optional[int] = None,
                        prior: Optional[List[int]] = None,
                        ttft: Optional[float] = None,
                        spec_off: bool = False, streak: int = 0) -> None:
        """Prompt fully in pages + first token picked: join the decode set.
        A recompute resume passes the EFFECTIVE prompt length (original +
        banked generation in pages) and its `prior` tokens — the new `first`
        token continues that stream, and TTFT/back-off state carry over from
        before the preemption instead of being re-stamped."""
        self.cache.lengths[slot] = \
            req.prompt.size if prompt_len is None else prompt_len
        now = self._now()
        lc = self._lifecycles.get(req.request_id)
        if prior:
            generated = list(prior) + [first]
        else:
            generated = [first]
            ttft = now - req.t_enqueue
            if lc is not None:
                lc.t_first_token = now
                lc.ttft_s = ttft
            self._h_ttft.observe(ttft,
                                 exemplar=self._exemplar(req.request_id))
            self._tev(req.request_id, "first_token")
        self._stamp_emit(req.request_id, 1, now)
        seq = _Running(req, slot, generated, cached, ttft,
                       self._req_greedy(req))
        seq.spec_off = spec_off
        seq.spec_zero_streak = streak
        if not self._maybe_finish(seq, finished):
            self._running[slot] = seq

    def _propose_drafts(self) -> Dict[int, np.ndarray]:
        """Ask the proposer for up to spec_len continuation tokens per greedy
        slot, capped at the slot's remaining decode budget so speculative KV
        writes stay inside the reservation (prompt + max_new_tokens).  When
        the proposer declares a bounded lookback, only that history tail is
        materialized — this runs on the host every decode iteration, so the
        work per slot must not grow with context length."""
        drafts: Dict[int, np.ndarray] = {}
        win = getattr(self.proposer, "max_lookback", 0)
        for slot, seq in self._running.items():
            if not seq.greedy:
                continue            # acceptance needs a deterministic pick
            if seq.spec_off:
                continue            # adaptive back-off: drafting never landed
                                    # for this slot, skip the proposer scan
                                    # (the slot rides verify at valid=1)
            cap = min(self.spec_len,
                      seq.request.max_new_tokens - len(seq.generated))
            if cap < 1:
                continue
            if win:
                gen = np.asarray(seq.generated[-win:], np.int32)
                head = seq.request.prompt[-(win - gen.size):] \
                    if gen.size < win else seq.request.prompt[:0]
                ctx = np.concatenate([head, gen])
            else:
                ctx = np.concatenate([seq.request.prompt,
                                      np.asarray(seq.generated, np.int32)])
            d = self.proposer.propose(ctx, cap)
            if d is not None and len(d):
                drafts[slot] = np.asarray(d, np.int32).reshape(-1)[:cap]
        return drafts

    def warm_spec(self) -> None:
        """Nothing to compile: speculation's verify lane rides the one fused
        program `warm_decode` warms.  Kept because
        `benchmarks/drivers/serve.py` calls it."""

    def warm_decode(self) -> None:
        """Compile the decode-side executable against inert inputs (all
        slots masked to the null page) — a 1-token warmup request picks its
        only token at prefill and retires without ever decoding, so benches
        warm the decode program explicitly.  This compiles THE one fused
        program (decode/verify/chunk share its fixed shape).  On a
        sampling engine this advances the PRNG stream by one split, like any
        real decode dispatch would."""
        B = self.cache.num_slots
        tbl = np.zeros((B, self.cache.max_pages_per_slot), np.int32)
        _, _, self._pool, self._key, *_ = self._decode_fn(
            self.params, self._h2d(np.zeros((B, self._fused_T), np.int32)),
            self._pool, self._h2d(tbl),
            self._h2d(np.zeros((B,), np.int32)),
            self._h2d(np.ones((B,), np.int32)), self._key,
            self._h2d(np.zeros((B,), bool)), self._no_prev, self._host_rows)
        self._decode_used = True
        # warmup is also where the live roofline arms: one abstract trace of
        # the decode-side program (cached; zero dispatches, zero programs)
        # so the drift gauge reads real from the first steady-state step
        _ = self.predicted_step_ms

    def warm_swap(self) -> None:
        """Compile the swap gather/scatter against null-page ids (all
        content lands on the never-read page 0) — benches call this in
        warmup so the first preemption swap-out OR KV-tier spill/restore
        (both ride the SAME two executables) doesn't pay a compile inside
        the timed section.  The gather has ONE shape (a slot's width of
        ids, `_swap_w` pages a piece, one page copy an id) whatever the page
        count, so this is every shape the path can reach.  No-op unless the
        engine can reach them (optimistic admission + preempt="swap", or
        kv_tier on)."""
        if not ((self.optimistic and self.preempt == "swap") or self.kv_tier):
            return
        P = self.cache.max_pages_per_slot
        self._swap_out_fn(self._pool,
                          self._h2d(np.zeros((self._d2h_slot_w,), np.int32)))
        self._swap_out_used = True
        # the scatter is still a slot wide; staged from host numpy so the
        # swap-in signature matches the real resume path (replicated staging
        # uploads, not device outputs)
        staged = {n: self._h2d(np.zeros((a.shape[0], P) + a.shape[2:],
                                        a.dtype))
                  for n, a in self._pool.items()}
        self._pool = self._swap_in_fn(
            self._pool, self._h2d(np.zeros((P,), np.int32)), staged)
        self._swap_in_used = True

    def _maybe_finish(self, seq: _Running,
                      finished: List[RequestOutput]) -> bool:
        reason = None
        if self.eos_token_id is not None and \
                seq.generated[-1] == self.eos_token_id:
            reason = "stop"
        elif len(seq.generated) >= seq.request.max_new_tokens:
            reason = "length"
        if reason is None:
            return False
        if self.prefix_cache:
            # finish-time registration (tier follow-on): publish the
            # GENERATED pages next to the prompt pages before the slot
            # releases, so a returning session's last reply is a prefix hit
            # (device trie or tier restore) instead of a full re-prefill.
            # KV completeness bound: `cache.lengths[slot]` counts positions
            # whose KV actually landed — (prompt ++ generated) minus the
            # final sampled token, whose KV is never computed — so the
            # registered content is exactly that written prefix, tail
            # partial page included (filled == tokens.size).
            kvlen = int(self.cache.lengths[seq.slot])
            conv = np.concatenate([
                np.asarray(seq.request.prompt, np.int32),
                np.asarray(seq.generated, np.int32)])[:kvlen]
            self.cache.register_prefix(seq.slot, conv, kvlen, upgrade=True)
        self.cache.release(seq.slot)
        self._free_slots.append(seq.slot)
        out = self._finish_output(seq.request, seq.generated, reason,
                                  seq.cached_tokens, seq.ttft_s)
        finished.append(out)
        return True

    def host_pool_bytes(self) -> int:
        """Worst-case HOST memory the unified host pool may hold — the
        declared bound `swap_pool_pages` (shared by preemption swap parking
        AND the kv_tier spilled-prefix store; disk pages are off-budget)
        times the bytes one page occupies across all layers and pool lanes
        (k + v, plus the per-token scale lanes of an int8 pool,
        `quantization.serving.kv_page_bytes`) — the number
        `tools/tpu_cost.py` audits against
        `SERVE_RESOURCE_BUDGET["host_pool_bytes"]` (JXP009; int8 pools park
        int8 pages, so their bound shrinks with the pool).  Occupancy is
        `kv_pages_swapped` + `kv_tier_pages_host`; this is the ceiling."""
        return self.swap_pool_pages * self._kv_page_bytes

    def swap_pool_bytes(self) -> int:
        """Legacy alias for `host_pool_bytes` (the PR-10 name, kept for
        external consumers — the budget it maps to is now the unified
        host-pool ceiling)."""
        return self.host_pool_bytes()

    def kv_pool_bytes(self) -> int:
        """At-rest bytes of the device KV page pool (all lanes — the number
        the quantized-serving capacity math is about: int8 pools hold the
        same token geometry in ~2-4x fewer bytes)."""
        return int(sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                       for a in self._pool.values()))

    def at_rest_bytes(self) -> Dict[str, int]:
        """Cached at-rest memory account for this engine's params, classified
        by the serving layout (`analysis.cost_model.params_at_rest` over
        `serving_param_specs` — the SAME account `tools/tpu_cost.py` audits
        under JXP006): `{replicated_bytes_per_device, sharded_bytes_per_device,
        wte_bytes}`.  Host-side arithmetic over leaf shapes — no trace, no
        dispatch, no new executable — so bench rows report the sharded-head
        memory win for free.  `wte_bytes` is the FP embedding-table size (the
        pre-shard replicated ceiling this layout retired): at mp>1 the
        per-device replicated remainder must sit strictly below it."""
        if getattr(self, "_at_rest_bytes", None) is None:
            from ..analysis.cost_model import AtRestAccount, params_at_rest
            a = AtRestAccount(max(self.mp, 1),
                              params_at_rest(self.params, self.config,
                                             self.mp))
            c = self.config
            wte_bytes = int(c.vocab_size * c.hidden_size
                            * np.dtype(c.dtype).itemsize)
            self._at_rest_bytes = {
                "replicated_bytes_per_device": int(a.param_bytes_replicated),
                "sharded_bytes_per_device": int(a.param_bytes_sharded_per_device),
                "wte_bytes": wte_bytes,
            }
        return dict(self._at_rest_bytes)

    # ---- health & perf signal plane ---------------------------------------
    @property
    def predicted_step_ms(self) -> float:
        """Static roofline prediction for the decode-side program at this
        engine's shapes (`analysis.cost_model.engine_step_cost` over the
        nameplate `device_spec()`), traced abstractly ONCE and cached — no
        dispatch, no new executable, program counts untouched.
        `warm_decode()` takes the trace during warmup so the drift gauge is
        live from the first steady-state step; reading the property earlier
        pays the one-off trace right here."""
        if self._predicted_ms is None:
            from ..analysis.cost_model import device_spec, engine_step_cost
            self._predicted_ms = engine_step_cost(self).predicted_ms(
                device_spec(), mp=self.mp)
        return self._predicted_ms

    def _roofline_drift(self) -> float:
        """measured_step_ms EWMA / predicted roofline ms — the live drift
        gauge.  0.0 until BOTH exist (never triggers the trace itself: a
        metrics scrape must stay a pure read)."""
        if not self._predicted_ms or not self._measured_ewma_ms:
            return 0.0
        return self._measured_ewma_ms / self._predicted_ms

    def _note_steady_state(self, dur_s: float) -> None:
        """Per-busy-step bookkeeping of the live perf signals: the
        measured-step EWMA, the drift-band alert counter (TRANSITIONS into
        violation, not steps spent there) and the steady-state recompile
        anomaly (decode-side executable count growing after the first busy
        step fixed the baseline — a fixed-shape engine must never do that)."""
        ms = dur_s * 1e3
        self._measured_ewma_ms = ms if self._measured_ewma_ms is None else \
            _EWMA_ALPHA * ms + (1.0 - _EWMA_ALPHA) * self._measured_ewma_ms
        try:
            n = self._decode_fn._cache_size()
        except AttributeError:
            n = 1 if self._decode_used else 0
        if self._exec_baseline is None:
            self._exec_baseline = n
        elif n > self._exec_baseline:
            self._ss_recompiles.inc(n - self._exec_baseline)
            self._exec_baseline = n
        drift = self._roofline_drift()
        lo, hi = SERVE_SLO["roofline_drift_band"]
        bad = bool(drift) and not (lo <= drift <= hi)
        if bad and not self._drift_violation:
            self._roofline_alerts.inc()
        self._drift_violation = bad

    def _burn_rate(self, window_s: float) -> float:
        """Deadline-attainment burn over one window (`health.burn_rate`
        semantics): in-window miss fraction over the declared error budget."""
        from .health import burn_rate
        return burn_rate(self._rw_deadline_req, self._rw_deadline_met,
                         window_s, SERVE_SLO["deadline_attainment_target"])

    def health(self) -> Dict[str, object]:
        """The engine's health report — state (ok/degraded/overloaded),
        numeric code, per-signal detail and reasons — evaluated against
        `analysis.registry.SERVE_SLO` from host state only (see
        `inference.health`).  The obs server's ``/healthz`` serves it with
        200/503 semantics; `stats()["health"]` carries the compact pair."""
        return evaluate_engine_health(self)

    def _health_code(self) -> float:
        """The `engine_health` gauge read: 0 ok / 1 degraded / 2 overloaded.
        A health evaluation that cannot run at all reads as the worst state
        — a wedged engine must never scrape as healthy — and the exception
        is preserved for ``/healthz``, which re-evaluates and reports it."""
        try:
            return float(self.health()["code"])
        except Exception:
            return float(max(HEALTH_CODES.values()))

    def run(self) -> Dict[int, RequestOutput]:
        """Drain the queue: step until every request completes.  Returns
        {request_id: RequestOutput} for everything finished so far."""
        while self.has_work:
            self.step()
        self._land_d2h(wait=True)       # nothing stays in flight at rest
        return dict(self._outputs)

    @property
    def has_work(self) -> bool:
        return bool(self._queue or self._running or self._prefilling or
                    self._inflight is not None or self._orphan_finished)

    # ---- serving-loop surface (front door / fleet) ------------------------
    # One replica = one LLMEngine + one background step() thread.  Every
    # entry point below takes `_serve_lock`, so a fleet router (or the HTTP
    # front door's event loop) can submit/stream/abort from any thread while
    # the loop steps; the lock is re-entrant, so single-threaded callers
    # (benches, tests) can keep driving step()/run() directly.

    def start_loop(self, idle_wait_s: float = 0.002) -> None:
        """Start the background step() loop (idempotent).  The loop parks on
        the serve condition when idle — submit()/cancel() wake it — and
        re-checks `has_work` every `idle_wait_s` as a fallback heartbeat."""
        with self._serve_lock:
            if self._serve_thread is not None and \
                    self._serve_thread.is_alive():
                return
            self._serve_stop = False
            self._serve_error = None
            self._serve_thread = threading.Thread(
                target=self._serve_loop, args=(float(idle_wait_s),),
                name="llm-serve-loop", daemon=True)
            self._serve_thread.start()

    def stop_loop(self, timeout: float = 30.0) -> None:
        """Stop the loop thread (idempotent; queued work stays queued —
        call drain() first for a clean flush).  Spill/swap-out copies in
        flight land before this returns, and the fetch worker ends."""
        with self._serve_cond:
            self._serve_stop = True
            self._serve_cond.notify_all()
        t = self._serve_thread
        if t is not None and t.is_alive():
            t.join(timeout)
        self._serve_thread = None
        with self._serve_lock:
            self._land_d2h(wait=True)
            if self._d2h_worker is not None:
                self._d2h_worker.shutdown()
                self._d2h_worker = None

    @property
    def loop_running(self) -> bool:
        t = self._serve_thread
        return t is not None and t.is_alive()

    def _serve_loop(self, idle_wait_s: float) -> None:
        while True:
            with self._serve_cond:
                if self._serve_stop:
                    return
                if not self.has_work:
                    self._land_d2h()    # what arrived since the last step
                    self._serve_cond.wait(idle_wait_s)
                    continue
                try:
                    self.step()
                except BaseException as exc:    # noqa: BLE001 — surfaced to
                    self._serve_error = exc     # result()/drain() waiters
                    self._serve_cond.notify_all()
                    return
                self._serve_cond.notify_all()

    def _check_loop(self) -> None:
        if self._serve_error is not None:
            raise RuntimeError("serve loop died") from self._serve_error

    def submit(self, prompt, **kwargs) -> int:
        """Thread-safe add_request(): enqueue under the serve lock and wake
        the loop.  Same signature/validation/rejection semantics."""
        with self._serve_cond:
            self._check_loop()
            rid = self.add_request(prompt, **kwargs)
            self._serve_cond.notify_all()
            return rid

    def cancel(self, request_id: int) -> bool:
        """Thread-safe abort() (client disconnect propagation: frees the
        request's pages immediately)."""
        with self._serve_cond:
            ok = self.abort(request_id)
            if ok:
                self._serve_cond.notify_all()
            return ok

    def progress(self, request_id: int) -> Dict[str, object]:
        """Streaming snapshot: the tokens a request has produced so far and
        whether it finished (`output` carries the final RequestOutput then).
        Under double-buffering the snapshot may lag the device by one
        in-flight step — exact at finish, which is what streaming needs."""
        with self._serve_lock:
            out = self._outputs.get(request_id)
            if out is not None:
                return {"known": True, "finished": True,
                        "token_ids": list(out.token_ids), "output": out}
            for seq in self._running.values():
                if seq.request.request_id == request_id:
                    return {"known": True, "finished": False,
                            "token_ids": list(seq.generated), "output": None}
            for st in self._prefilling.values():
                if st.request.request_id == request_id:
                    return {"known": True, "finished": False,
                            "token_ids": list(st.prior or []), "output": None}
            rec = self._preempted.get(request_id)
            if rec is not None:
                return {"known": True, "finished": False,
                        "token_ids": list(rec.get("generated") or []),
                        "output": None}
            for req in self._queue:
                if req.request_id == request_id:
                    return {"known": True, "finished": False,
                            "token_ids": [], "output": None}
            return {"known": False, "finished": False,
                    "token_ids": [], "output": None}

    def result(self, request_id: int,
               timeout: Optional[float] = None) -> Optional[RequestOutput]:
        """Block until `request_id` finishes (or `timeout` elapses — then
        None).  With the loop running this waits on its step notifications;
        without it, the caller's own thread drives step() inline, so the
        surface also works single-threaded."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._serve_cond:
            while True:
                out = self._outputs.get(request_id)
                if out is not None:
                    return out
                self._check_loop()
                if not self.loop_running:
                    if not self.has_work:
                        return None
                    self.step()
                    continue
                rem = 0.5 if deadline is None \
                    else deadline - time.monotonic()
                if rem <= 0.0:
                    return None
                self._serve_cond.wait(rem)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait until the engine is fully idle (False on timeout)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._serve_cond:
            while self.has_work:
                self._check_loop()
                if not self.loop_running:
                    self.step()
                    continue
                rem = 0.5 if deadline is None \
                    else deadline - time.monotonic()
                if rem <= 0.0:
                    return False
                self._serve_cond.wait(rem)
            self._land_d2h(wait=True)   # idle means nothing in flight either
            return True

    def queue_depth(self) -> int:
        """Live request count (queued + prefilling + decoding) — the
        router's load signal, cheap enough to read per routing decision."""
        with self._serve_lock:
            return (len(self._queue) + len(self._prefilling) +
                    len(self._running))

    def probe_affinity(self, tokens) -> Dict[str, int]:
        """Router probe: longest cached prefix of `tokens` this replica
        holds, split into total matched tokens and the portion that is
        tier-resident (host/disk — a hit there restores via one scatter
        instead of re-prefilling).  Pure read — no LRU touch, no COW, no
        refcount; the admission-time `_match` in step() remains the only
        mutating matcher."""
        if not self.prefix_cache:
            return {"cached_tokens": 0, "tier_tokens": 0}
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        with self._serve_lock:
            full, partial = self.cache._match(tokens)
        page = self.cache.page_size
        matched = len(full) * page
        tier = sum(page for n in full if n.page < 0)
        if partial is not None:
            node, j = partial
            matched += j
            if node.page < 0:
                tier += j
        return {"cached_tokens": int(matched), "tier_tokens": int(tier)}

    # ---- observability ----------------------------------------------------
    @contextlib.contextmanager
    def trace(self, dir_name: str, device: bool = True):
        """Capture a serving trace window into `dir_name`:

        - ``host_trace.json`` — chrome-tracing export of the engine's host
          phase spans (`ENGINE_SPANS`: admit, prefill and fused dispatch,
          proposer scan, sample sync, emit) recorded through
          `paddle_tpu.profiler.RecordEvent`, so it opens in the same
          ``chrome://tracing`` / Perfetto flow as the trainer's traces;
        - ``step_timeline.json`` — the step-trace ring as captured at exit;
        - ``metrics.json`` — a full `metrics.snapshot()` (plus the proposer's
          drafting telemetry when available);
        - ``device/`` — a `jax.profiler` trace (TensorBoard XPlane) when
          `device=True` and the runtime supports capture; spans also forward
          as TraceAnnotations so engine phases land in the device timeline.

        Tracing is additive-only: no executable recompiles (the spans wrap
        host code), and the spans themselves exist only inside this window.
        When a user `Profiler` is ALREADY recording, this window rides it
        instead of starting its own (a nested start would wipe the outer
        profiler's event buffer and a nested stop would end its recording):
        the outer recording continues untouched and ``host_trace.json``
        snapshots everything collected so far, engine spans included.
        """
        os.makedirs(dir_name, exist_ok=True)
        prof = None
        if not _prof.is_recording():
            prof = _prof.Profiler(timer_only=not device,
                                  log_dir=os.path.join(dir_name, "device"))
            prof.start()
        self._tracing = True
        try:
            yield prof
        finally:
            self._tracing = False
            if prof is not None:
                prof.stop()     # the event buffer survives stop()
            _prof.dump_chrome_trace(os.path.join(dir_name,
                                                 "host_trace.json"))
            with open(os.path.join(dir_name, "step_timeline.json"), "w") as f:
                json.dump(self.step_trace(), f)
            snap = self.metrics.snapshot()
            snap["proposer"] = getattr(self.proposer, "stats", dict)()
            with open(os.path.join(dir_name, "metrics.json"), "w") as f:
                json.dump(snap, f)

    def stats(self) -> Dict[str, object]:
        def execs(fn, fallback):
            # only the expected miss — a plain-jit wrapper without
            # _cache_size — falls back to the tracked approximation; a real
            # bug INSIDE _cache_size must raise, not be silently counted
            try:
                return fn._cache_size()
            except AttributeError:
                return fallback
        cached = self._prefix_cached_tokens.value
        computed = self._prefilled_tokens.value
        spec_events = self._spec_events.value
        try:
            health = self.health()
        except Exception as e:
            # stats() feeds the crash postmortem (debug_bundle) and /stats:
            # a signal plane wrecked by the very crash being postmortemed
            # must degrade to an "error" health entry, not take the whole
            # surface down (same contract as /healthz and the gauge)
            health = {"state": "error", "code": max(HEALTH_CODES.values()),
                      "reasons": [f"health evaluation failed: "
                                  f"{type(e).__name__}: {e}"],
                      "burn_rates": {}}
        # _decode_fn IS the one fused program (decode-side count 1); there
        # is no verify program (its key stays for the benchmark's sum), and
        # the standalone chunk program exists in bucketed mode only
        return {
            "decode_executables": execs(self._decode_fn,
                                        1 if self._decode_used else 0),
            "verify_executables": 0,
            "prefill_executables": execs(self._prefill_fn,
                                         len(self._seen_buckets)) +
                                   (0 if self._chunk_fn is None else
                                    execs(self._chunk_fn,
                                          1 if self._chunk_used else 0)),
            "copy_executables": execs(self._copy_fn,
                                      1 if self._copy_used else 0),
            "swap_executables": execs(self._swap_out_fn,
                                      1 if self._swap_out_used else 0) +
                                execs(self._swap_in_fn,
                                      1 if self._swap_in_used else 0),
            "buckets": list(self.buckets),
            "prefill_chunk": self.prefill_chunk,
            "spec_len": self.spec_len,
            "mp": self.mp,
            "role": self.role,
            "engine_steps": self._step_idx,
            "decode_iterations": self._decode_iters.value,
            "decode_tokens": self._decode_tokens.value,
            "verify_steps": self._verify_steps.value,
            # per-slot verify events that carried a draft — the denominator
            # of accepted_per_step, reported so benches can recompute it
            "spec_events": spec_events,
            "spec_drafted_tokens": self._spec_drafted.value,
            "spec_accepted_tokens": self._spec_accepted.value,
            "spec_emitted_tokens": self._spec_emitted.value,
            "spec_backoffs": self._spec_backoffs.value,
            # mean tokens emitted per drafted verify event (>= 1.0; 1.0 means
            # drafts never helped, spec_len+1 means every draft fully accepted)
            "accepted_per_step": self._spec_emitted.value / spec_events
                                 if spec_events else 0.0,
            "prefill_chunks": self._prefill_chunks.value,
            "prefilled_tokens": computed,
            "prefix_cached_tokens": cached,
            "prefix_hit_requests": self._prefix_hit_requests.value,
            "prefix_hit_rate": cached / (cached + computed)
                               if cached + computed else 0.0,
            "cow_page_copies": self._cow_copies.value,
            "pages_in_use": self.cache.pages_in_use(),
            "pages_free": self.cache.num_free_pages,
            "pages_evictable": self.cache.num_evictable_pages,
            "prefix_evictions": self.cache.prefix_evictions,
            "kv_token_capacity": self.cache.token_capacity(),
            "dense_token_footprint": self.cache.num_slots * self.max_model_len,
            "queued": len(self._queue),
            "prefilling": len(self._prefilling),
            "running": len(self._running),
            "finished_requests": self._finished_requests.value,
            "aborted_requests": self._aborted_requests.value,
            # overload surface: admission/preempt modes + the counters the
            # oversubscription bench and dashboards consume
            "admission": self.admission,
            "preempt": self.preempt,
            "preemptions": self._preemptions.value,
            "preempt_swaps": self._preempt_swaps.value,
            "preempt_recomputes": self._preempt_recomputes.value,
            "swapped_pages": self._swapped_pages_c.value,
            "paged_pages_walked": self._pages_walked.value,
            "paged_table_entries": self._table_entries.value,
            "swap_ms": self._swap_ms_c.value,
            # the two swap boundaries: buffers fetched, bytes that crossed
            # and bytes of the pages they were for; and the host's turnaround
            # between fused programs, summed (turnaround_ms / engine_steps)
            "swap_d2h_fetches": self._d2h_fetches.value,
            "swap_d2h_bytes": self._d2h_bytes.value,
            "swap_d2h_useful_bytes": self._d2h_useful.value,
            # the copies run beside the engine thread: what it still waited
            # for them, how many had landed when it came, what is in flight
            "swap_d2h_blocked_ms": self._d2h_blocked_ms.value,
            "swap_d2h_landed_free": self._d2h_landed_free.value,
            "swap_d2h_backpressure_waits": self._d2h_bp_waits.value,
            "swap_d2h_inflight_pages": self._d2h_inflight,
            "swap_h2d_bytes": self._h2d_bytes.value,
            "swap_h2d_useful_bytes": self._h2d_useful.value,
            "turnaround_ms": self._turnaround_ms_c.value,
            # launches made before the last result was read, and the lanes
            # of such launches whose request had ended meanwhile
            "fused_launched_ahead": self._launched_ahead.value,
            "fused_ahead_late": self._ahead_late.value,
            "fused_serial_steps": {why: c.value for why, c
                                   in self._serial_steps.items()},
            "fused_ahead_discarded_lanes": self._ahead_discarded.value,
            # recurrent configurations: the expert layers' routing account
            # and the state lanes (all 0 for a dense configuration)
            **{n: c.value for n, c in self._aux_counters.items()},
            "moe_load_max": self._moe_load_max,
            "latent_page_bytes": self._latent_page_bytes(),
            "index_page_bytes": self._index_page_bytes(),
            # the DSA rows of the fused steps alone (the totals, with the
            # prefills', are dsa_rows_scored / _selected / _searched above)
            "dsa_step_rows_scored": self._dsa_step_rows["dsa_rows_scored"],
            "dsa_step_rows_selected":
                self._dsa_step_rows["dsa_rows_selected"],
            "dsa_step_rows_searched":
                self._dsa_step_rows["dsa_rows_searched"],
            "ssm_state_bytes": self._ssm_state_bytes.value,
            "ssm_state_pool_bytes": 0 if self.cache.state is None else
                                    self.cache.state.pool_bytes,
            "prefix_lookups_skipped_no_state": self._prefix_skipped.value,
            "recomputed_tokens": self._recomputed_tokens.value,
            "timeouts": self._timeouts.value,
            "rejected_requests": self._rejected_requests.value,
            "intake_swap_rejects": self._intake_swap_rejects.value,
            "swapped": self.cache.swapped_requests,
            "kv_pages_swapped": self.cache.swapped_page_count,
            "kv_pool_pressure": round(self.cache.pool_pressure(), 4),
            # KV-tier surface (ROADMAP item 3): spilled-prefix occupancy per
            # tier level + the spill/restore traffic and rolling-hash
            # partial-index hits the multi-turn bench keys on
            "kv_tier": {
                "enabled": self.kv_tier,
                "spill_dir": self.spill_dir,
                "pages_host": self.cache.tier_pages_host,
                "pages_disk": self.cache.tier_pages_disk,
                "spills": self._tier_spills.value,
                "restores": self._tier_restores.value,
                "restored_tokens": self._tier_restored_tokens.value,
                "partial_page_hits": self._partial_hits.value,
                "disk_spills": 0 if self.cache._tier is None
                               else self.cache._tier.disk_spills,
                "disk_restores": 0 if self.cache._tier is None
                                 else self.cache._tier.disk_restores,
                "tier_drops": 0 if self.cache._tier is None
                              else self.cache._tier.tier_drops,
                # disaggregated handoff surface (ROADMAP item 2)
                "store": self.cache._tier is not None and
                         self.cache._tier.store is not None,
                "handoff_exports": self._handoff_exports.value,
                "handoff_pages": self._handoff_pages.value,
                "handoff_tokens": self._handoff_tokens.value,
                "store_nodes_restored": self._store_restored_nodes,
            },
            # quantized serving surface: the knobs and the at-rest pool bytes
            # the capacity math is about (None = full-precision default)
            "weight_dtype": self.weight_dtype,
            "kv_dtype": self.kv_dtype,
            "kv_pool_bytes": self.kv_pool_bytes(),
            # SLO surface (PR-10 deadlines made end-to-end): attainment over
            # every retired deadline-bearing request (timeouts/aborts are
            # misses in the denominator, still excluded from the latency
            # histograms) + final-output tokens per priority class
            "slo": {
                "deadline_requests": self._deadline_requests.value,
                "deadline_met": self._deadline_met.value,
                "deadline_attainment":
                    self._deadline_met.value / self._deadline_requests.value
                    if self._deadline_requests.value else None,
                "goodput_tokens_by_priority":
                    {p: c.value
                     for p, c in sorted(self._goodput_prio.items())},
            },
            # windowed rates (health & signals PR): sliding-window views of
            # the counters above — tokens/s etc. over ~10s/1m/5m, the
            # router's freshness-weighted signal (also pull gauges, e.g.
            # `tokens_per_sec_10s`, in the exposition)
            "rates": {rw.name: rw.rates() for rw in self._rate_surface},
            # compact health pair (full per-signal report via health());
            # state folds SLO burn + pressure + admission saturation +
            # recompile anomalies against analysis.registry.SERVE_SLO
            "health": {
                "state": health["state"],
                "code": health["code"],
                "reasons": health["reasons"],
                "burn_rates": health["burn_rates"],
            },
            # live roofline: the PR-8 static prediction next to the
            # steady-state EWMA it is now compared against every step
            "roofline": {
                "predicted_step_ms": self._predicted_ms,    # None until armed
                "measured_step_ms": self._measured_ewma_ms,
                "drift": self._roofline_drift() or None,
                "drift_alerts": self._roofline_alerts.value,
                "steady_state_recompiles": self._ss_recompiles.value,
            },
            # latency distributions (engine-side histograms; seconds) — the
            # serving SLO surface: benches report p50/p99 straight from here
            "latency": {
                "queue_s": self._h_queue.summary(),
                "ttft_s": self._h_ttft.summary(),
                "tpot_s": self._h_tpot.summary(),
                "e2e_s": self._h_e2e.summary(),
                "step_s": self._h_step.summary(),
            },
        }

    # ---- postmortem debug bundle ------------------------------------------
    def _request_states(self, finished_limit: int = 64) \
            -> Dict[str, Dict[str, object]]:
        """Per-request state map for the debug bundle: every live request
        (queued — including preempted/swapped resumes waiting at the head —
        prefilling, running) plus the last `finished_limit` retired ones,
        each with its scheduler coordinates, its trace timeline (empty
        with tracing off) and, once decoding, its `emit_times` stamps.  Keys
        are request-id strings (JSON object keys)."""
        def base(req, state, **extra):
            tr = self._trace_for(req.request_id)
            d = {"state": state, "prompt_len": int(req.prompt.size),
                 "max_new_tokens": int(req.max_new_tokens),
                 "priority": int(req.priority),
                 "deadline": req.deadline,
                 "events": list(tr.events) if tr is not None else []}
            d.update(extra)
            return d

        def emits(lc):
            return [] if lc is None else [list(p) for p in lc.emit_times]

        out: Dict[str, Dict[str, object]] = {}
        # snapshot the live containers: an obs-server handler thread walks
        # them concurrently with step()'s mutations, and iterating the deque/
        # dicts directly would raise mid-scrape ("mutated during iteration")
        for req in list(self._queue):
            rec = self._preempted.get(req.request_id)
            out[str(req.request_id)] = base(
                req, "queued",
                preempted_kind=None if rec is None else rec["kind"],
                banked_tokens=0 if rec is None else len(rec["generated"]))
        for slot, st in list(self._prefilling.items()):
            out[str(st.request.request_id)] = base(
                st.request, "prefilling", slot=slot, filled=int(st.filled),
                effective_prompt_len=int(st.prompt.size))
        for slot, seq in list(self._running.items()):
            out[str(seq.request.request_id)] = base(
                seq.request, "running", slot=slot,
                n_generated=len(seq.generated),
                kv_len=int(self.cache.lengths[slot]),
                spec_off=seq.spec_off, emit_times=emits(
                    self._lifecycles.get(seq.request.request_id)))
        # last-N retired requests WITHOUT materializing the all-time output
        # ledger (unbounded on a long-running server): walk the insertion
        # order backwards, then flip to oldest-first
        recent = list(itertools.islice(reversed(self._outputs), finished_limit))
        for rid in reversed(recent):
            o = self._outputs[rid]
            out[str(rid)] = {
                "state": "finished", "finish_reason": o.finish_reason,
                "prompt_len": int(np.asarray(o.prompt).size),
                "n_generated": len(o.token_ids),
                "cached_tokens": int(o.cached_tokens),
                "emit_times": emits(o.metrics),
                "events": list(o.trace.events) if o.trace is not None else [],
            }
        return out

    def debug_bundle(self, finished_limit: int = 64) -> Dict[str, object]:
        """The postmortem snapshot the obs server serves as ``GET /debug``
        and `dump_debug_bundle` writes to disk: engine/pool configuration,
        page-partition levels, per-request states with their trace
        timelines, the last-N step-trace ring, `stats()` and a full metrics
        snapshot — everything "what was the engine doing when it died" needs,
        all plain JSON (prompt/KV CONTENT deliberately excluded).  Safe to
        call mid-flight: it reads host scheduler state only, no device sync,
        no executable dispatch."""
        mgr = self.cache
        return {
            "version": 1,
            "t": self._now(),
            "engine": {
                "num_slots": mgr.num_slots, "page_size": mgr.page_size,
                "num_pages": mgr.num_pages,
                "max_model_len": self.max_model_len,
                "prefill_chunk": self.prefill_chunk,
                "spec_len": self.spec_len,
                "double_buffer": self.double_buffer,
                "admission": self.admission, "preempt": self.preempt,
                "kv_tier": self.kv_tier, "spill_dir": self.spill_dir,
                "mp": self.mp, "weight_dtype": self.weight_dtype,
                "kv_dtype": self.kv_dtype,
                "request_tracing": self._req_tracing,
                "inflight": self._inflight is not None,
            },
            "pool": {
                "pages_in_use": mgr.pages_in_use(),
                "pages_free": mgr.num_free_pages,
                "pages_evictable": mgr.num_evictable_pages,
                "kv_pages_swapped": mgr.swapped_page_count,
                "swapped_requests": mgr.swapped_requests,
                "pool_pressure": round(mgr.pool_pressure(), 4),
                "kv_pool_bytes": self.kv_pool_bytes(),
                "swap_pool_pages": self.swap_pool_pages,
                "kv_tier_pages_host": mgr.tier_pages_host,
                "kv_tier_pages_disk": mgr.tier_pages_disk,
            },
            "requests": self._request_states(finished_limit),
            "step_trace": self.step_trace(),
            "stats": self.stats(),
            "metrics": self.metrics.snapshot(),
        }

    def dump_debug_bundle(self, dir_name: str,
                          finished_limit: int = 64) -> str:
        """Write `debug_bundle()` to ``<dir_name>/debug_bundle.json`` and
        return the path — `bench_serve.py` calls this automatically on a
        crash or a drain-invariant failure, and operators call it on demand
        (or hit the obs server's ``/debug``) for a live snapshot."""
        os.makedirs(dir_name, exist_ok=True)
        path = os.path.join(dir_name, "debug_bundle.json")
        with open(path, "w") as f:
            json.dump(self.debug_bundle(finished_limit), f)
        return path
