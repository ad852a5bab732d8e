"""tpu_lint rule engine: rule catalog, findings, suppression comments.

Reference lineage: the reference repo ships a `tools/` CI layer of custom
static checks (op-registry audits, API-signature guards, lint passes over
generated kernels — SURVEY §tools) because framework invariants rot silently.
Ours guard the serving/training hot-path discipline instead of op registries:
one fixed program set, no stray host<->device syncs, donated hot buffers,
no shape-dependent Python branches inside traced code.

Rules are small classes over a prebuilt per-file index (`visitor.FileContext`)
— the expensive work (scope table, call graph, device-value taint) happens
once per file in `visitor.py`; each rule is a thin query over it.

Suppression syntax (same line or the line directly above the finding):

    # tpu-lint: disable=TPL001 -- reason why this sync is intentional
    # tpu-lint: disable=TPL001,TPL005 -- shared reason
    # tpu-lint: disable-file=TPL004 -- file-wide, e.g. generated code

A reason (the `-- ...` tail) is mandatory: a disable comment without one is
itself reported as LINT000 — an unexplained suppression is exactly the silent
rot this tool exists to stop.
"""
from __future__ import annotations

import dataclasses
import io
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Tuple

# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Finding:
    """One diagnostic: rule code + location + message.  `suppressed` findings
    are kept (they appear in --json output and suppression-audit tooling) but
    do not fail the run."""
    rule: str
    path: str
    line: int
    col: int
    message: str
    suppressed: bool = False
    reason: str = ""

    def format(self) -> str:
        tag = f" [suppressed: {self.reason}]" if self.suppressed else ""
        return f"{self.path}:{self.line}:{self.col}: {self.rule} " \
               f"{self.message}{tag}"

    def to_json(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

_DISABLE_RE = re.compile(
    r"#\s*tpu-lint:\s*disable(?P<filewide>-file)?\s*=\s*"
    r"(?P<codes>[A-Za-z0-9_]+(?:\s*,\s*[A-Za-z0-9_]+)*)"
    r"(?:\s*--\s*(?P<reason>.*\S))?")


class Suppressions:
    """Per-file suppression table parsed from `# tpu-lint: disable=...`
    comments.  A line-scoped disable covers findings on its own line and the
    line directly below (comment-above style); `disable-file=` covers the
    whole file."""

    def __init__(self, source: str):
        self.by_line: Dict[int, Tuple[List[str], str]] = {}
        self.file_wide: Dict[str, str] = {}
        self.malformed: List[int] = []      # disable comments missing a reason
        # tokenize so only REAL comments count: a docstring or string literal
        # that merely quotes the disable syntax (this module's own docs, a
        # test fixture) must not become a live suppression
        try:
            comments = [(t.start[0], t.string) for t in
                        tokenize.generate_tokens(io.StringIO(source).readline)
                        if t.type == tokenize.COMMENT]
        except (tokenize.TokenError, IndentationError, SyntaxError):
            comments = list(enumerate(source.splitlines(), start=1))
        for i, text in comments:
            m = _DISABLE_RE.search(text)
            if not m:
                continue
            codes = [c.strip().upper() for c in m.group("codes").split(",")]
            reason = (m.group("reason") or "").strip()
            if not reason:
                self.malformed.append(i)
                continue                    # an unexplained disable disables nothing
            if m.group("filewide"):
                for c in codes:
                    self.file_wide[c] = reason
            else:
                self.by_line[i] = (codes, reason)

    def lookup(self, rule: str, line: int) -> Optional[str]:
        """The reason string when `rule` is suppressed at `line`, else None."""
        if rule in self.file_wide:
            return self.file_wide[rule]
        for ln in (line, line - 1):
            entry = self.by_line.get(ln)
            if entry and (rule in entry[0] or "ALL" in entry[0]):
                return entry[1]
        return None

    def apply(self, findings: Iterable[Finding]) -> List[Finding]:
        out = []
        for f in findings:
            reason = self.lookup(f.rule, f.line)
            if reason is not None:
                f.suppressed = True
                f.reason = reason
            out.append(f)
        return out


# ---------------------------------------------------------------------------
# rule base + catalog
# ---------------------------------------------------------------------------


class Rule:
    """One static check.  Subclasses set `code`/`title`/`rationale` and
    implement `check(ctx)` over a `visitor.FileContext`."""
    code = "TPL000"
    title = ""
    rationale = ""

    def check(self, ctx) -> Iterable[Finding]:  # pragma: no cover - interface
        raise NotImplementedError

    def finding(self, ctx, node, message: str) -> Finding:
        return Finding(self.code, ctx.relpath, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), message)


class HostSyncRule(Rule):
    """TPL001: scalarization sync on a device value in step()-reachable code.

    `.item()`, `float()`, `int()`, or an implicit `bool()` (an `if`/`while`
    test) on a value produced by a device dispatch blocks the host per call
    AND round-trips one scalar at a time — the pattern that turns a
    one-dispatch engine step into a sync-per-slot crawl.  Bulk fetches
    (`np.asarray`) are TPL005's business."""
    code = "TPL001"
    title = "host-sync-in-hot-path"
    rationale = "scalar device->host syncs serialize the engine step loop"

    def check(self, ctx):
        for ev in ctx.hot_sync_events:
            if ev.kind == "scalarize":
                yield self.finding(
                    ctx, ev.node,
                    f"host sync `{ev.what}` on device value in "
                    f"step()-reachable `{ev.func}` — batch the fetch "
                    f"(np.asarray inside a RecordEvent span) or keep the "
                    f"value on device")
            elif ev.kind == "implicit_bool":
                yield self.finding(
                    ctx, ev.node,
                    f"implicit bool() of device value in step()-reachable "
                    f"`{ev.func}` — a hidden blocking sync; fetch explicitly "
                    f"first")


class UnregisteredJitRule(Rule):
    """TPL002: `jax.jit`/`pjit`/`shard_map` call site not declared in
    `analysis/registry.py`.

    The serving program budget (`tools/check_program_count.py`) is only
    enforceable if every place that can mint a compiled program is known.  A
    new jit site must be declared — with which budget bucket it compiles into
    — or it is invisible to the budget until it blows it in production.
    Also flags stale registry entries (declared site no longer in the code),
    so the registry cannot drift from reality in either direction."""
    code = "TPL002"
    title = "unregistered-program-source"
    rationale = "every compiled-program source must be budgeted centrally"

    def check(self, ctx):
        seen = set()
        for site in ctx.jit_sites:
            entry = ctx.registry.lookup(ctx.relpath, site.qualname)
            if entry is not None:
                seen.add((ctx.relpath, entry.qualname))
            else:
                yield self.finding(
                    ctx, site.node,
                    f"{site.kind} call site `{ctx.relpath}::"
                    f"{site.qualname or '<module>'}` not declared in "
                    f"analysis/registry.py — declare it (with its program "
                    f"budget bucket) so check_program_count stays exhaustive")
        for entry in ctx.registry.for_path(ctx.relpath):
            if (ctx.relpath, entry.qualname) not in seen:
                yield Finding(
                    self.code, ctx.relpath, 1, 0,
                    f"stale registry entry: `{entry.qualname or '<module>'}` "
                    f"is declared as a program source but no jit/shard_map "
                    f"call site remains there — remove it from "
                    f"analysis/registry.py")


class MissingDonateRule(Rule):
    """TPL003: jitted function taking a large persistent buffer
    (pool/params/opt_state-style parameter) without `donate_argnums`.

    Without donation XLA must materialize input and output copies of the
    buffer every dispatch — for a KV page pool that doubles serving memory
    and adds a copy to every engine step.  (Deliberately non-donated buffers
    — e.g. params reused across calls — get a suppression with the reason.)"""
    code = "TPL003"
    title = "undonated-hot-buffer"
    rationale = "non-donated large buffers double memory and copy per step"

    BIG_PARAMS = frozenset({"params", "pool", "state", "opt_state", "kv",
                            "kv_cache", "cache", "buffers", "weights"})

    def check(self, ctx):
        for site in ctx.jit_sites:
            if site.kind != "jit" or site.fn_params is None:
                continue
            big = sorted(self.BIG_PARAMS & set(site.fn_params))
            if big and site.donate is False:
                yield self.finding(
                    ctx, site.node,
                    f"jit of `{site.fn_name}({', '.join(site.fn_params)})` "
                    f"has large-buffer param(s) {big} but no donate_argnums "
                    f"— the buffer is copied every dispatch")


class TracedBranchRule(Rule):
    """TPL004: Python `if`/`while` on a traced value inside a jitted function.

    Tracing specializes the branch on the concrete value, silently compiling
    one program per value seen — the exact per-shape/per-value recompile the
    fixed-program-set engine design forbids.  Branch on static config, use
    `jnp.where`/`lax.cond`, or hoist the decision to the host."""
    code = "TPL004"
    title = "python-branch-on-traced-value"
    rationale = "value-dependent Python branches multiply compiled programs"

    def check(self, ctx):
        for br in ctx.traced_branches:
            yield self.finding(
                ctx, br.node,
                f"Python `{br.stmt}` on traced parameter `{br.param}` of "
                f"jitted `{br.func}` — use jnp.where/lax.cond or make the "
                f"argument static")


class UntimedFetchRule(Rule):
    """TPL005: blocking device->host fetch outside a RecordEvent span.

    `engine.trace()` (PR 5) reconstructs where a serving step spends its
    time from the host-phase spans; a bulk fetch (`np.asarray` /
    `jax.device_get` on a device value) that blocks outside any span is
    invisible to that timeline — the trace shows an idle host while the
    device sync eats the step budget."""
    code = "TPL005"
    title = "untimed-blocking-fetch"
    rationale = "unspanned device syncs are invisible to the step trace"

    def check(self, ctx):
        for ev in ctx.hot_sync_events:
            if ev.kind == "fetch":
                yield self.finding(
                    ctx, ev.node,
                    f"blocking device fetch `{ev.what}` outside a "
                    f"RecordEvent span in step()-reachable `{ev.func}` — "
                    f"wrap it in the engine's sample-sync span so the step "
                    f"trace can see the stall")


class BareExceptDeviceRule(Rule):
    """TPL006: `except Exception`/bare `except` around device code.

    The PR-5 `execs()` bug class: a broad handler around a jax call converts
    a real defect (bad sharding, Mosaic compile failure, donated-buffer
    reuse) into a silently-wrong fallback.  Catch the specific exceptions the
    guarded degradation is FOR, or suppress with the reason."""
    code = "TPL006"
    title = "bare-except-around-device-code"
    rationale = "broad handlers around device calls hide real defects"

    def check(self, ctx):
        for h in ctx.broad_device_handlers:
            yield self.finding(
                ctx, h.node,
                f"`except {h.caught}` around device call(s) "
                f"({', '.join(sorted(h.device_calls)[:3])}) — narrow to the "
                f"exceptions the fallback is for")


class DoubleBufferHazardRule(Rule):
    """TPL007: page-state mutation before harvesting the in-flight batch.

    Under double-buffered scheduling (`double_buffer=True`, the default)
    the fused dispatch of step *n* is still writing KV when the host runs
    between steps — its result is parked in `self._inflight` until the next
    harvest.  At every return from `step()` that is at most ONE program: a
    step may launch program *n+1* before it harvests *n*, but the two are
    in flight together only inside `step()`, which harvests before it
    returns.  A public entry point that frees or reassigns page-table/
    refcount state (release/allocate, `lengths[...]`/`page_table[...]`
    stores) while that batch is in flight hands pages to a new owner whose
    bookkeeping the in-flight result will then corrupt — the invariant
    `LLMEngine.abort()` protects by harvesting FIRST.  The rule keys on the
    class publishing `_inflight` and on a `_harvest` call (directly or via a
    callee) preceding the first mutation."""
    code = "TPL007"
    title = "double-buffer-hazard"
    rationale = "page mutation with a dispatch in flight corrupts harvests"

    def check(self, ctx):
        for hz in ctx.db_hazards:
            yield self.finding(
                ctx, hz.node,
                f"public `{hz.method}` mutates page state ({hz.what}) "
                f"without first harvesting the in-flight batch — call "
                f"self._harvest() (or gate on self._inflight) before "
                f"touching page tables/refcounts")


class SuppressionReasonRule(Rule):
    """LINT000: a `# tpu-lint: disable=` comment without a `-- reason`."""
    code = "LINT000"
    title = "suppression-without-reason"
    rationale = "unexplained suppressions defeat the audit trail"

    def check(self, ctx):
        for line in ctx.suppressions.malformed:
            yield Finding(
                self.code, ctx.relpath, line, 0,
                "tpu-lint disable comment without a `-- reason`; the "
                "suppression is ignored until a reason is given")


AST_RULES: Tuple[Rule, ...] = (
    HostSyncRule(), UnregisteredJitRule(), MissingDonateRule(),
    TracedBranchRule(), UntimedFetchRule(), BareExceptDeviceRule(),
    DoubleBufferHazardRule(), SuppressionReasonRule(),
)

# jaxpr-level checks (implemented in jaxpr_checks.py) share the catalog so
# --list-rules documents both levels in one table
JAXPR_RULE_TABLE: Tuple[Tuple[str, str, str], ...] = (
    ("JXP001", "transfer-inside-program",
     "device_put/callback primitives inside a serving executable"),
    ("JXP002", "donation-mismatch",
     "declared-donated buffer not donated, or large undeclared buffer "
     "copied per dispatch"),
    ("JXP003", "dtype-upcast",
     "float64 avals or f32->f64 / bf16->f64 upcasts inside the program"),
    ("JXP004", "missing-sharding-constraint",
     "mp-mode executable without a sharding_constraint pinning its output "
     "layout"),
    ("JXP005", "oversized-host-output",
     "serving-step output exceeds the O(B*K)-int budget or is logits-shaped "
     "— reintroduces the per-step [B, V] host fetch the fused step removed"),
    # resource budgets (implemented in cost_model.py, enforced by tpu_cost)
    ("JXP006", "oversized-replicated-buffer",
     "an mp at-rest buffer replicated on every chip exceeds the declared "
     "ceiling — the embedding/head replication that blocks 70B configs"),
    ("JXP007", "undeclared-collective",
     "collective traffic (psum/all-gather/reduce-scatter) undeclared in "
     "SERVE_RESOURCE_BUDGET or above its per-step byte budget"),
    ("JXP008", "peak-hbm-over-budget",
     "a serving program's modeled peak HBM (donation-aware jaxpr liveness) "
     "exceeds its declared per-executable budget"),
    ("JXP009", "swap-pool-over-budget",
     "the engine's host-side KV swap pool bound exceeds the declared "
     "swap_pool_bytes budget — preemption parking must stay host-memory "
     "accountable"),
)


def rule_table() -> List[Tuple[str, str, str]]:
    """(code, title, rationale) for every shipped rule, both levels."""
    rows = [(r.code, r.title, r.rationale) for r in AST_RULES]
    rows += list(JAXPR_RULE_TABLE)
    return rows
