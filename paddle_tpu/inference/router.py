"""dp-replicated engine fleet with affinity-aware request routing.

Reference lineage: the reference repo's serving story ends at ONE
`AnalysisPredictor` per process — scale-out is "run more processes behind a
load balancer" and the balancer knows nothing about what each process has
cached.  For an LLM serving fleet that is the wrong default: PRs 2 and 15
made each engine's KV state *valuable* (prefix trie + host/disk tier — a
returning session restores its conversation in one scatter instead of
re-prefilling it), and PRs 12–13 made each engine *self-describing*
(`stats()["rates"]`, `health()`, pool-pressure/preemption churn).  This
module closes the loop: `EngineFleet` holds N data-parallel `LLMEngine`
replicas, each driven by its own background `step()` loop (the engine's
serving-loop surface), and routes every request to the replica where it is
cheapest to serve:

- **prefix/tier affinity** (`router="affinity"`, default): probe every
  healthy replica's prefix index (`LLMEngine.probe_affinity` — a pure read
  of the trie + rolling-hash partial index, tier-resident pages included)
  for the longest cached prefix of the prompt.  Sessions are sticky by
  default (ties break toward the replica that served the session last),
  but a replica whose cache/tier holds strictly MORE of the conversation
  wins — after an eviction-and-respill shuffle the pages, not the history,
  decide.
- **load**: among equal-affinity candidates, lowest live request count
  (`queue_depth`) wins, then highest windowed `tokens_per_sec` (a replica
  that is draining faster absorbs the next request sooner).  Replicas whose
  `health()` reads `overloaded` (SLO burn / pressure, PR-13 semantics) or
  that fail to evaluate are excluded from routing entirely.
- **victim-awareness**: low-priority requests (`priority < 0`) are the
  first preemption victims under optimistic admission, so routing them onto
  a replica already running hot (pool pressure over `victim_pressure`, or
  visible preemption churn in the 1m window) just schedules them to be
  evicted.  When a calmer replica exists, they go there instead.
- **load shedding**: when EVERY replica is overloaded/unreachable the fleet
  refuses the request with `FleetOverloaded` (carrying `retry_after_s`) —
  the front door maps it to 503 + `Retry-After` so clients back off instead
  of deepening queues that already burn their SLO budget.

`router="round_robin"` and `router="least_loaded"` are the A/B baselines
(`bench_serve.py --replicas N --router ...`): round-robin is what a
cache-blind balancer does, and the fleet bench measures exactly what that
blindness costs in prefix-hit rate and returning-turn TTFT.

Replication must not multiply compiled programs: replicas 0..N-1 run the
SAME model at the SAME shapes on the SAME mesh, so replica 0 compiles and
every other replica ADOPTS its executables (`_adopt_executables` — the
engine's jitted step functions are per-instance attributes precisely so a
fleet can share them).  `tools/check_program_count.py` runs a 2-replica
pass asserting per-replica program counts stay inside the single-engine
budget and that the executable objects are literally shared.

**Disaggregated prefill/decode** (`roles="P:D"`, ROADMAP item 2,
DistServe/Splitwise-style): the fleet partitions its replicas into a
PREFILL pool and a DECODE pool sharing one durable tier store
(`spill_dir`).  A new prompt routes least-loaded onto a prefill replica,
which runs admission + chunked prefill, generates one throwaway token, and
`export_prefix`-publishes the prompt's KV pages + durable index to the
store; the decode replica (chosen by affinity, sticky per session)
`refresh_store_index`-merges the published index and its ordinary
admission tier-restores the whole prompt with ONE scatter — long prefills
never steal fused-step slots from decode batches.  A returning turn whose
prefix the decode replica already holds skips the prefill hop entirely;
a shed prefill pool or a failed export degrades to a direct decode-side
submit (local re-prefill) — parity-lossless by construction, since the
decode engine re-computes exactly what the store could not provide.
Role-aware health: prefill replicas burn on TTFT only, decode replicas on
TPOT only (`health.py`), so shedding matches each pool's actual SLO.
"""
from __future__ import annotations

import dataclasses
import re
import tempfile
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from .engine import LLMEngine, RequestOutput
from .metrics import FleetMetrics

ROUTER_POLICIES = ("affinity", "round_robin", "least_loaded")

# the jitted step executables an engine builds in __init__ — per-instance
# attributes so a dp fleet can share ONE compiled set across replicas
_EXEC_ATTRS = ("_decode_fn", "_chunk_fn", "_prefill_fn", "_copy_fn",
               "_swap_out_fn", "_swap_in_fn")

# health states a request must never be routed to
_UNROUTABLE = ("overloaded", "error")


def _parse_roles(roles: str) -> Tuple[int, int]:
    """Parse a ``"P:D"`` / ``"2P:3D"`` role spec into (prefill, decode)
    replica counts (an omitted count means 1)."""
    m = re.fullmatch(r"(\d*)\s*P\s*:\s*(\d*)\s*D", str(roles).strip(), re.I)
    if not m:
        raise ValueError(f"roles must look like 'P:D' or '2P:3D', "
                         f"got {roles!r}")
    n_p = int(m.group(1)) if m.group(1) else 1
    n_d = int(m.group(2)) if m.group(2) else 1
    if n_p < 1 or n_d < 1:
        raise ValueError(f"roles needs >= 1 replica per pool, got {roles!r}")
    return n_p, n_d


class FleetOverloaded(RuntimeError):
    """Every replica is overloaded/unreachable — shed instead of queueing.
    `retry_after_s` is the client back-off hint (HTTP `Retry-After`)."""

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


@dataclasses.dataclass(frozen=True)
class FleetHandle:
    """A routed request: which replica took it and its engine-local rid.
    `str(handle)` (``engine0/3``) is the wire id the front door exposes;
    `parse` round-trips it."""
    label: str
    rid: int
    session: Optional[str] = None

    def __str__(self) -> str:
        return f"{self.label}/{self.rid}"

    @classmethod
    def parse(cls, s: str) -> "FleetHandle":
        label, _, rid = str(s).rpartition("/")
        return cls(label=label, rid=int(rid))


@dataclasses.dataclass
class ReplicaView:
    """One replica's routing signals, snapshotted per decision — the pure
    input to `rank_replicas`, so scoring is unit-testable without engines."""
    label: str
    state: str = "ok"               # health(): ok | degraded | overloaded
    matched_tokens: int = 0         # longest cached prefix of the prompt
    tier_tokens: int = 0            # ... of which host/disk tier-resident
    depth: int = 0                  # live requests (queued+prefill+decode)
    tokens_per_sec: float = 0.0     # windowed decode throughput (10s)
    pool_pressure: float = 0.0      # fraction of KV pool in live use
    preemptions_per_sec: float = 0.0  # victim churn (1m window)
    sticky: bool = False            # served this session last


def rank_replicas(views: List[ReplicaView], *, policy: str = "affinity",
                  priority: int = 0, victim_pressure: float = 0.85,
                  victim_churn: float = 0.5) -> Optional[ReplicaView]:
    """Pick the replica a request should land on, or None when nothing is
    routable.  Pure function of the snapshots (see module docstring for the
    scoring story); `round_robin` is stateful and lives on the fleet."""
    if policy not in ROUTER_POLICIES:
        raise ValueError(f"unknown router policy {policy!r}; "
                         f"expected one of {ROUTER_POLICIES}")
    usable = [v for v in views if v.state not in _UNROUTABLE]
    if not usable:
        return None
    if policy == "least_loaded":
        return min(usable, key=lambda v: (v.depth, -v.tokens_per_sec,
                                          v.label))
    if policy == "round_robin":
        raise ValueError("round_robin needs fleet state; route via "
                         "EngineFleet.select")
    # affinity: victim-aware pre-filter, then cache-weight ordering
    if priority < 0:
        calm = [v for v in usable if v.pool_pressure < victim_pressure and
                v.preemptions_per_sec <= victim_churn]
        if calm:
            usable = calm
    return max(usable, key=lambda v: (v.matched_tokens, v.sticky,
                                      -v.depth, v.tokens_per_sec,
                                      # stable last resort: lowest label
                                      tuple(-ord(c) for c in v.label)))


def _adopt_executables(replica: LLMEngine, leader: LLMEngine) -> None:
    """Point `replica`'s jitted step functions at `leader`'s compiled set.
    Sound exactly when both engines were built with identical construction
    arguments on the SAME mesh (the closures capture only config/sampling
    constants and the shared-mesh shardings) — which `EngineFleet` enforces
    by constructing every replica from one kwargs dict."""
    if replica.mesh is not leader.mesh:
        raise ValueError("executable adoption requires replicas on the "
                         "same mesh object (distinct meshes hash as "
                         "distinct jit cache keys -> one recompile per "
                         "replica)")
    for name in _EXEC_ATTRS:
        setattr(replica, name, getattr(leader, name))


class EngineFleet:
    """N dp-replicated `LLMEngine`s behind one routed submit/stream/abort
    surface.  Construct from `(params, config)` plus `engine_kwargs`
    (forwarded verbatim to every replica), or adopt pre-built `engines`.

    Lifecycle: `start()` spins one step()-loop thread per replica,
    `drain()` waits for quiescence, `stop()` joins the loops; the fleet is
    also a context manager.  `fleet_metrics` carries every replica for the
    PR-12 exposition (`per-{engine=...}` series + `llm_fleet_*` merges) and
    plugs straight into `ObservabilityServer(fleet=...)`.
    """

    def __init__(self, params=None, config=None, *, replicas: int = 2,
                 engines: Optional[List[LLMEngine]] = None,
                 router: str = "affinity",
                 roles: Optional[str] = None,
                 shed_retry_after_s: float = 1.0,
                 victim_pressure: float = 0.85,
                 victim_churn: float = 0.5,
                 handoff_timeout_s: float = 120.0,
                 engine_kwargs: Optional[Dict[str, object]] = None):
        if router not in ROUTER_POLICIES:
            raise ValueError(f"unknown router policy {router!r}; "
                             f"expected one of {ROUTER_POLICIES}")
        self.router = router
        self.roles = roles
        self.shed_retry_after_s = float(shed_retry_after_s)
        self.victim_pressure = float(victim_pressure)
        self.victim_churn = float(victim_churn)
        self.handoff_timeout_s = float(handoff_timeout_s)
        role_list: Optional[List[Optional[str]]] = None
        if roles is not None:
            n_p, n_d = _parse_roles(roles)
            role_list = ["prefill"] * n_p + ["decode"] * n_d
        if engines is None:
            if params is None or config is None:
                raise ValueError("EngineFleet needs (params, config) or "
                                 "pre-built engines=[...]")
            kw = dict(engine_kwargs or {})
            if role_list is not None:
                replicas = len(role_list)
                # disaggregation moves KV through the durable tier store:
                # force the tier on and give every pool member the SAME
                # store root so any decode replica can restore any prompt
                kw.setdefault("kv_tier", True)
                kw.setdefault("spill_dir",
                              tempfile.mkdtemp(prefix="kvstore_"))
                kw["role"] = role_list[0]
            if replicas < 1:
                raise ValueError(f"replicas must be >= 1, got {replicas}")
            leader = LLMEngine(params, config, **kw)
            engines = [leader]
            if replicas > 1:
                # replicas share the leader's mesh (mp>1: a fresh mesh per
                # replica would hash as a fresh jit cache key) and adopt
                # its compiled executables — dp replication adds ZERO
                # programs per mesh config
                kw.setdefault("mesh", leader.mesh)
                for i in range(1, replicas):
                    if role_list is not None:
                        kw["role"] = role_list[i]
                    e = LLMEngine(params, config, **kw)
                    _adopt_executables(e, leader)
                    engines.append(e)
        self.engines: "OrderedDict[str, LLMEngine]" = OrderedDict(
            (f"engine{i}", e) for i, e in enumerate(engines))
        # role pools (pre-built engines partition by their declared role)
        self.prefill_pool = [l for l, e in self.engines.items()
                             if e.role == "prefill"]
        self.decode_pool = [l for l, e in self.engines.items()
                            if e.role == "decode"]
        if roles is not None and not (self.prefill_pool and self.decode_pool):
            raise ValueError(
                f"roles={roles!r} needs >= 1 prefill and >= 1 decode "
                f"replica; got pools {self.prefill_pool} / "
                f"{self.decode_pool}")
        self.fleet_metrics = FleetMetrics()
        for label, eng in self.engines.items():
            self.fleet_metrics.add(label, eng)
        self._sessions: Dict[str, str] = {}
        self._rr = 0
        self.shed_count = 0
        self._submitted: Dict[str, int] = {l: 0 for l in self.engines}
        # handoff telemetry (disaggregated mode): per-handoff wall latency
        # (prefill submit -> store published + decode index refreshed),
        # plus skip (warm continuation) / degrade (fell back to decode-side
        # re-prefill) counts
        self.handoff_ms: List[float] = []
        self.handoff_skips = 0
        self.handoff_degrades = 0

    # ---- lifecycle --------------------------------------------------------
    def start(self, idle_wait_s: float = 0.002) -> "EngineFleet":
        for eng in self.engines.values():
            eng.start_loop(idle_wait_s)
        return self

    def stop(self, timeout: float = 30.0) -> None:
        for eng in self.engines.values():
            eng.stop_loop(timeout)

    def drain(self, timeout: Optional[float] = None) -> bool:
        deadline = None if timeout is None else time.monotonic() + timeout
        for eng in self.engines.values():
            rem = None if deadline is None \
                else max(0.0, deadline - time.monotonic())
            if not eng.drain(rem):
                return False
        return True

    def warm(self) -> None:
        """Warm every replica's executables outside any timed section.
        With adopted executables the leader's compiles are shared, so
        replica warmups re-dispatch cached programs (cheap) rather than
        compiling N times."""
        for eng in self.engines.values():
            eng.warm_decode()
            eng.warm_swap()

    def __enter__(self) -> "EngineFleet":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---- routing ----------------------------------------------------------
    def _view(self, label: str, eng: LLMEngine, prompt,
              sticky_label: Optional[str]) -> ReplicaView:
        try:
            state = str(eng.health().get("state", "error"))
        except Exception:
            state = "error"
        v = ReplicaView(label=label, state=state,
                        sticky=(label == sticky_label))
        if state in _UNROUTABLE:
            return v
        try:
            probe = eng.probe_affinity(prompt) if prompt is not None \
                else {"cached_tokens": 0, "tier_tokens": 0}
            v.matched_tokens = probe["cached_tokens"]
            v.tier_tokens = probe["tier_tokens"]
            v.depth = eng.queue_depth()
            v.pool_pressure = float(eng.cache.pool_pressure())
            rates = {rw.name: rw for rw in eng._rate_surface}
            v.tokens_per_sec = float(
                rates["tokens_per_sec"].rate(10.0))
            v.preemptions_per_sec = float(
                rates["preemptions_per_sec"].rate(60.0))
        except Exception:
            v.state = "error"
        return v

    def views(self, prompt=None, session: Optional[str] = None,
              labels: Optional[List[str]] = None) -> List[ReplicaView]:
        sticky = self._sessions.get(session) if session is not None else None
        return [self._view(label, eng, prompt, sticky)
                for label, eng in self.engines.items()
                if labels is None or label in labels]

    def select(self, prompt=None, *, session: Optional[str] = None,
               priority: int = 0, policy: Optional[str] = None,
               labels: Optional[List[str]] = None) -> str:
        """Route: the chosen replica's label, or raise `FleetOverloaded`.
        `labels` restricts the candidate set (disagg role pools)."""
        policy = policy or self.router
        views = self.views(
            prompt if policy == "affinity" else None, session, labels)
        if policy == "round_robin":
            usable = [v for v in views if v.state not in _UNROUTABLE]
            if usable:
                pick = usable[self._rr % len(usable)]
                self._rr += 1
                return pick.label
            chosen = None
        else:
            chosen = rank_replicas(views, policy=policy, priority=priority,
                                   victim_pressure=self.victim_pressure,
                                   victim_churn=self.victim_churn)
        if chosen is None:
            self.shed_count += 1
            raise FleetOverloaded(
                f"all {len(views)} replicas overloaded/unreachable "
                f"(states: {[v.state for v in views]})",
                retry_after_s=self.shed_retry_after_s)
        return chosen.label

    # ---- request surface --------------------------------------------------
    def submit(self, prompt, *, session: Optional[str] = None,
               policy: Optional[str] = None, max_new_tokens: int = 16,
               temperature: Optional[float] = None, priority: int = 0,
               deadline_s: Optional[float] = None) -> FleetHandle:
        """Route + enqueue.  Raises `FleetOverloaded` when shedding; the
        per-engine validation/rejection semantics are `add_request`'s.
        With `roles` set the request takes the disaggregated path instead
        (prefill-pool hop + store handoff + decode-pool submit)."""
        if self.roles is not None:
            return self._submit_disagg(
                prompt, session=session, max_new_tokens=max_new_tokens,
                temperature=temperature, priority=priority,
                deadline_s=deadline_s)
        label = self.select(prompt, session=session, priority=priority,
                            policy=policy)
        rid = self.engines[label].submit(
            prompt, max_new_tokens=max_new_tokens, temperature=temperature,
            priority=priority, deadline_s=deadline_s)
        if session is not None:
            self._sessions[session] = label
        self._submitted[label] += 1
        return FleetHandle(label=label, rid=rid, session=session)

    def _submit_disagg(self, prompt, *, session: Optional[str],
                       max_new_tokens: int, temperature: Optional[float],
                       priority: int,
                       deadline_s: Optional[float]) -> FleetHandle:
        """Disaggregated routing: decode replica by affinity (sticky per
        session), prefill hop only when the decode replica is cold on this
        prompt.  Every degrade point (prefill pool shed, prefill timeout,
        empty export) falls through to the plain decode-side submit — the
        decode engine re-prefills locally, so outputs never depend on the
        handoff succeeding."""
        dlabel = self.select(prompt, session=session, priority=priority,
                             policy="affinity", labels=self.decode_pool)
        deng = self.engines[dlabel]
        prompt = np.asarray(prompt, np.int32)
        probe = deng.probe_affinity(prompt)
        if probe["cached_tokens"] * 2 >= prompt.size:
            # warm continuation: the decode replica already holds most of
            # the conversation — a prefill hop would only add latency
            self.handoff_skips += 1
        else:
            try:
                plabel = self.select(None, priority=priority,
                                     policy="least_loaded",
                                     labels=self.prefill_pool)
            except FleetOverloaded:
                plabel = None           # prefill pool shed: degrade
            if plabel is None:
                self.handoff_degrades += 1
            else:
                peng = self.engines[plabel]
                t0 = time.monotonic()
                prid = peng.submit(prompt, max_new_tokens=1,
                                   temperature=temperature)
                self._submitted[plabel] += 1
                out = peng.result(prid, timeout=self.handoff_timeout_s)
                exp = {"pages": 0}
                if out is not None and out.finish_reason in ("stop",
                                                             "length"):
                    exp = peng.export_prefix(prompt, rid=prid)
                if exp["pages"] > 0:
                    deng.refresh_store_index()
                    self.handoff_ms.append((time.monotonic() - t0) * 1e3)
                else:
                    self.handoff_degrades += 1
        rid = deng.submit(prompt, max_new_tokens=max_new_tokens,
                          temperature=temperature, priority=priority,
                          deadline_s=deadline_s)
        if session is not None:
            self._sessions[session] = dlabel
        self._submitted[dlabel] += 1
        return FleetHandle(label=dlabel, rid=rid, session=session)

    def _engine_of(self, handle: FleetHandle) -> LLMEngine:
        try:
            return self.engines[handle.label]
        except KeyError:
            raise KeyError(f"unknown replica {handle.label!r}") from None

    def abort(self, handle: FleetHandle) -> bool:
        return self._engine_of(handle).cancel(handle.rid)

    def progress(self, handle: FleetHandle) -> Dict[str, object]:
        return self._engine_of(handle).progress(handle.rid)

    def result(self, handle: FleetHandle,
               timeout: Optional[float] = None) -> Optional[RequestOutput]:
        return self._engine_of(handle).result(handle.rid, timeout)

    # ---- fleet state ------------------------------------------------------
    def health(self) -> Dict[str, object]:
        """Worst-of fleet health (the `/healthz` aggregation the obs plane
        already applies per engine)."""
        worst = {"state": "ok", "code": 0}
        per = {}
        for label, eng in self.engines.items():
            try:
                h = eng.health()
            except Exception as exc:
                h = {"state": "error", "code": 99, "error": repr(exc)}
            per[label] = h
            if float(h.get("code", 99)) > float(worst.get("code", 0)):
                worst = dict(h)
        worst["per_engine"] = {l: {"state": h.get("state"),
                                   "code": h.get("code"),
                                   "role": self.engines[l].role}
                               for l, h in per.items()}
        return worst

    def stats(self) -> Dict[str, object]:
        """Routing-plane summary (the full per-engine firehose stays on
        `engines[label].stats()` / the obs exposition)."""
        per = {}
        for label, eng in self.engines.items():
            with eng._serve_lock:
                st = eng.stats()
            per[label] = {
                "role": st["role"],
                "queue_depth": (st["queued"] + st["prefilling"] +
                                st["running"]),
                "decode_tokens": st["decode_tokens"],
                "tokens_per_sec_10s": st["rates"]["tokens_per_sec"]["10s"],
                "kv_pool_pressure": st["kv_pool_pressure"],
                "health": st["health"],
                "submitted": self._submitted[label],
            }
        out = {"router": self.router,
               "replicas": len(self.engines),
               "sessions": len(self._sessions),
               "shed": self.shed_count,
               "per_engine": per}
        if self.roles is not None:
            ms = sorted(self.handoff_ms)

            def _pct(q: float) -> float:
                return ms[min(len(ms) - 1, int(q * len(ms)))] if ms else 0.0

            out["disagg"] = {
                "roles": self.roles,
                "prefill_pool": list(self.prefill_pool),
                "decode_pool": list(self.decode_pool),
                "handoffs": len(ms),
                "handoff_skips": self.handoff_skips,
                "handoff_degrades": self.handoff_degrades,
                "handoff_p50_ms": round(_pct(0.50), 3),
                "handoff_p99_ms": round(_pct(0.99), 3),
            }
        return out

    def check_invariants(self) -> None:
        for eng in self.engines.values():
            with eng._serve_lock:
                eng.cache.check_invariants()

    def shared_executables(self) -> bool:
        """True when every replica runs the leader's compiled set (what
        check_program_count's fleet pass asserts)."""
        engines = list(self.engines.values())
        return all(getattr(e, n) is getattr(engines[0], n)
                   for e in engines[1:] for n in _EXEC_ATTRS)
