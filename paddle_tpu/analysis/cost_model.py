"""tpu_cost: static HBM / collective / roofline accounting over the serving
jaxprs (reference counterpart: the memory-optimize and inference-analysis
passes that run over the graph before execution — SURVEY "Inference API" +
the `tools/` CI rows).

The quantized-KV and 70B-head roadmap arcs are *memory claims* — "halving KV
bytes doubles live-token capacity", "the two replicated-memory ceilings" —
and until this module nothing in the repo could state, let alone guard, how
many bytes a serving executable actually holds or moves.  Four accounts, all
static (no profiler, no device counters):

- **At-rest HBM** (`engine_at_rest`): every param leaf classified
  sharded-vs-replicated through the SAME `serving_param_specs` layout the mp
  engine places with, plus the page-pool bytes (KVH-sharded under mp).
  Per-device bytes divide the sharded set by mp and keep the replicated set
  whole — which names the embedding/head replication that blocks 70B-class
  configs: any single replicated buffer above the declared ceiling is a
  **JXP006** finding.
- **Peak transient HBM** (`program_cost`): per-eqn liveness over the traced
  jaxpr — a value is live from the eqn that defines it to its last use;
  the peak is the max live-byte watermark.  Donation-aware: an output whose
  (shape, dtype) matches a donated input (the page pool) aliases the input
  buffer and allocates nothing.  This is an XLA-independent *model* (no
  fusion, no buffer reuse beyond liveness), deterministic across backends —
  the budget yardstick; the CLI prints XLA's own `memory_analysis()` numbers
  next to it where available.
- **Collective accounting** (`collective_costs`): the mp programs' psum /
  all-gather / reduce-scatter / collective-permute traffic read from the
  OPTIMIZED HLO (GSPMD inserts Megatron's per-layer all-reduces at compile
  time — they never appear in the jaxpr), with payload bytes from the
  instruction shapes and per-step totals multiplied through while-loop trip
  counts (the layer scan).  A program with collective traffic that the
  registry does not declare, or above its declared per-step byte budget, is
  a **JXP007** finding — single-chip executables must be collective-free.
- **Bytes/flops roofline** (`ProgramCost.predicted_ms`): analytic flops
  (dot_general exact, elementwise = output elems, scan bodies multiplied by
  trip count) over nameplate device specs, against compulsory HBM traffic
  (every input read once + every non-aliased output written once — the
  perfect-fusion lower bound, which for decode is the classic weights-bound
  roofline).  `tools/tpu_cost.py` prints it per executable.

Budgets (per-executable peak-HBM, the replicated-bytes ceiling, per-
executable collective bytes/step) are declared ONCE in
`analysis/registry.py::SERVE_RESOURCE_BUDGET` alongside the program-count
budget, enforced by `tools/tpu_cost.py --ci`, and are the yardstick the
quantization PR must move (quantized KV pages shrink `pool_bytes`; a
vocab-sharded head moves `wte` out of the replicated set).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .rules import Finding

# ---------------------------------------------------------------------------
# device specs (nameplate numbers for the roofline)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Peak rates the roofline divides by.  Nameplate numbers — the model
    predicts the *hardware floor* of a dispatch, not a fitted runtime."""
    name: str
    flops_per_s: float          # dense matmul peak (bf16 on TPU)
    hbm_bytes_per_s: float      # HBM bandwidth
    ici_bytes_per_s: float      # per-chip interconnect bandwidth


DEVICE_SPECS: Dict[str, DeviceSpec] = {
    # TPU generations (per chip, bf16 peak / HBM BW / ICI per link-direction)
    "v4": DeviceSpec("tpu-v4", 275e12, 1228e9, 50e9),
    "v5e": DeviceSpec("tpu-v5e", 197e12, 819e9, 45e9),
    "v5p": DeviceSpec("tpu-v5p", 459e12, 2765e9, 90e9),
    "v6e": DeviceSpec("tpu-v6e", 918e12, 1640e9, 90e9),
    # host CPU fallback: order-of-magnitude numbers so the CPU audit
    # (`tools/tpu_cost.py`, the tests) has a column to print, not a fit
    "cpu": DeviceSpec("cpu", 1e11, 2e10, 1e10),
}


# device_kind substrings -> spec row, most specific first (real kind strings
# spell the lite chips out: "TPU v5 lite" / "TPU v6 lite", not "v5e"/"v6e")
_KIND_MATCH = (("v6", "v6e"), ("v5p", "v5p"), ("v5e", "v5e"), ("v5", "v5e"),
               ("v4", "v4"))


def device_spec(device=None) -> DeviceSpec:
    """Spec for `device` (default: jax.devices()[0]) by device_kind
    substring; CPU hosts get the cpu row.  An accelerator that is not in the
    table is an error: a roofline against another chip's peaks is a wrong
    number under the right name."""
    import jax

    if device is None:
        device = jax.devices()[0]
    kind = (getattr(device, "device_kind", "") or "").lower()
    platform = (getattr(device, "platform", "") or "").lower()
    for sub, tag in _KIND_MATCH:
        if sub in kind:
            return DEVICE_SPECS[tag]
    if platform == "cpu":
        return DEVICE_SPECS["cpu"]
    raise ValueError(
        f"no DEVICE_SPECS row for platform {platform!r}, device_kind "
        f"{kind!r}: add its peaks (with their source) to the table")


# ---------------------------------------------------------------------------
# aval sizes + per-eqn flops
# ---------------------------------------------------------------------------

_EXTENDED_DTYPE_BYTES = 8       # PRNG key leaves: fry keys are 2x uint32


def aval_bytes(aval) -> int:
    """Bytes one materialized value of `aval` occupies (padding ignored)."""
    import numpy as np

    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    try:
        item = np.dtype(aval.dtype).itemsize
    except TypeError:           # extended dtype (jax PRNG key)
        item = _EXTENDED_DTYPE_BYTES
    return n * item


def _prod(xs: Iterable[int]) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


# Primitives XLA runs in place on operand 0 when nothing else reads it
# afterwards, and the position of the update they write into it.
_UPDATE_OPERAND = {"scatter": 2, "scatter-add": 2, "dynamic_update_slice": 1}
# ... and the ones whose output is the operand's bytes under another shape
_VIEWS = ("reshape",)


def _carry_slice(eqn) -> slice:
    """Where a scan eqn's carries sit among its invars (and its body's)."""
    nc = eqn.params["num_consts"]
    return slice(nc, nc + eqn.params["num_carry"])


def eqn_flops(eqn) -> int:
    """Analytic flop count of one (leaf) eqn: dot_general exact from its
    dimension numbers, everything else one op per output element — the
    standard matmul-dominated model (conv-free codebase)."""
    if eqn.primitive.name == "dot_general":
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        rhs = eqn.invars[1].aval.shape
        batch = _prod(lhs[i] for i in lb)
        contract = _prod(lhs[i] for i in lc)
        m = _prod(d for i, d in enumerate(lhs) if i not in lc and i not in lb)
        n = _prod(d for i, d in enumerate(rhs) if i not in rc and i not in rb)
        return 2 * batch * m * n * contract
    if eqn.primitive.name in _VIEWS:
        return 0
    # an in-place update's work is the update, not the buffer it lands in
    # (the serving passes scatter a few tokens into the whole page pool)
    upd = _UPDATE_OPERAND.get(eqn.primitive.name)
    vs = [eqn.invars[upd]] if upd is not None else eqn.outvars
    return sum(aval_bytes(v.aval) // max(_itemsize(v.aval), 1)
               for v in vs if hasattr(v, "aval"))


def _itemsize(aval) -> int:
    import numpy as np
    try:
        return np.dtype(aval.dtype).itemsize
    except TypeError:
        return _EXTENDED_DTYPE_BYTES


def _sub_jaxprs(eqn) -> List[Tuple[object, int]]:
    """(sub-jaxpr, trip multiplier) pairs for a higher-order eqn.  scan
    bodies multiply by `length`; while bodies have unknown trips (counted
    once — the serving programs' only loop is the layer scan).  `cond`
    eqns execute exactly ONE branch, so the walk takes the max over this
    list instead of the sum for them."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    prim = eqn.primitive.name
    mult = int(eqn.params.get("length", 1)) if prim == "scan" else 1
    subs: List[Tuple[object, int]] = []
    for v in eqn.params.values():
        stack = [v]
        while stack:
            x = stack.pop()
            if isinstance(x, ClosedJaxpr):
                subs.append((x.jaxpr, mult))
            elif isinstance(x, Jaxpr):
                subs.append((x, mult))
            elif isinstance(x, (list, tuple)):
                stack.extend(x)
    return subs


# ---------------------------------------------------------------------------
# per-eqn liveness over a jaxpr
# ---------------------------------------------------------------------------


def _jaxpr_walk(jaxpr, aliased_outs, writable=frozenset()
                ) -> Tuple[int, int, str]:
    """(flops, live-byte peak of body-DEFINED values, label of the peak eqn)
    for one jaxpr.  Invars are excluded (the caller accounts them as
    argument bytes); outvars are included from their defining eqn to the end
    — except `aliased_outs`, which write into a donated input buffer and
    allocate nothing.  Higher-order eqns recurse: their body's peak rides on
    top of the outer live set at that program point.

    In-place updates allocate nothing: a scatter / dynamic_update_slice or a
    reshape whose operand 0 is read for the last time by that eqn hands the
    operand's buffer to its output, and so does a scan for each carry.  The
    operand has to be the program's to overwrite: defined in this jaxpr, or
    one of `writable` (its donated arguments; in a scan's body, the
    carries).  That is how the paged passes hold the page pool — one donated
    buffer, reshaped, carried through the layer scan and scattered into —
    and a pool that is copied instead (not donated, read again after the
    update) is priced at its full size, as before."""
    from jax.extend.core import Literal

    eqns = list(jaxpr.eqns)
    last_use: Dict[object, int] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                last_use[v] = i
    for v in jaxpr.outvars:
        if not isinstance(v, Literal):
            last_use[v] = len(eqns)

    flops = 0
    live = 0
    peak = 0
    peak_at = ""
    sizes: Dict[object, int] = {}

    def takes_over(eqn, i):
        """{outvar: operand} for the outputs of eqn that reuse an operand's
        buffer."""
        prim = eqn.primitive.name
        if prim in _UPDATE_OPERAND or prim in _VIEWS:
            pairs = [(eqn.outvars[0], eqn.invars[0])]
        elif prim == "scan":
            pairs = zip(eqn.outvars, eqn.invars[_carry_slice(eqn)])
        else:
            return {}
        return {o: v for o, v in pairs
                if not isinstance(v, Literal) and last_use.get(v) == i
                and (v in sizes or v in writable)}

    for i, eqn in enumerate(eqns):
        subs = _sub_jaxprs(eqn)
        inner_peak = 0
        if subs:
            # cond executes ONE branch: take the worst branch, not the sum
            take_max = eqn.primitive.name == "cond"
            branch_flops = []
            for sub, mult in subs:
                carries = frozenset(sub.invars[_carry_slice(eqn)]) \
                    if eqn.primitive.name == "scan" else frozenset()
                f, p, _ = _jaxpr_walk(sub, frozenset(), carries)
                branch_flops.append(f * mult)
                inner_peak = max(inner_peak, p)
            flops += max(branch_flops) if take_max else sum(branch_flops)
        else:
            flops += eqn_flops(eqn)
        alloc = 0
        reused = takes_over(eqn, i)
        for v in eqn.outvars:
            if v in reused:
                # the operand's bytes live on under the output's name
                sizes[v] = sizes.pop(reused[v], 0)
                continue
            sz = 0 if v in aliased_outs else aval_bytes(getattr(v, "aval",
                                                                None))
            sizes[v] = sz
            alloc += sz
        here = live + alloc + inner_peak
        if here > peak:
            peak = here
            peak_at = f"eqn {i}: {eqn.primitive.name}"
        live += alloc
        # free every defined value whose last use is this eqn (or that is
        # never used at all — a dropped output exists only transiently)
        for v in list(eqn.outvars) + [x for x in eqn.invars
                                      if not isinstance(x, Literal)]:
            if v in sizes and last_use.get(v, i) <= i:
                live -= sizes.pop(v)
    return flops, peak, peak_at


# ---------------------------------------------------------------------------
# collective accounting from optimized HLO
# ---------------------------------------------------------------------------

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
# TPU-optimized modules rewrite collectives into async start/done pairs:
# count the `-start` half only (it carries the payload; matching `-done` too
# would double every transfer), plus the plain synchronous forms CPU emits.
_COLLECTIVE_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|\S+)\s+"
    r"(?P<kind>(?:" + "|".join(COLLECTIVE_OPS) + r")(?:-start)?)\(")
_WHILE_RE = re.compile(
    r"\bwhile\(.*condition=%([\w.\-]+), body=%([\w.\-]+)")
_COMPARE_LT_RE = re.compile(
    r"compare\(([^)]*)\)\s*,\s*direction=LT")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_TRIP_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")


def _shape_bytes(shape_text: str, largest_only: bool = False) -> int:
    """Bytes of an HLO result shape ('f32[2,8,64]{2,1,0}' or a tuple).
    `largest_only` takes the biggest component instead of the sum — the
    async `-start` forms return an (operand-alias, result, ...) tuple, and
    summing it would double-count the one transfer."""
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        item = _HLO_DTYPE_BYTES.get(dtype)
        if item is None:
            continue            # token/opaque element — no payload
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * item)
    if not sizes:
        return 0
    return max(sizes) if largest_only else sum(sizes)


@dataclasses.dataclass
class CollectiveOp:
    """One collective instruction in the optimized module: `payload_bytes`
    is the per-device operand footprint of ONE execution; `multiplier` is
    the enclosing loop trip product (the layer scan), so
    `payload_bytes * multiplier` is this instruction's per-step traffic."""
    kind: str
    shape: str
    payload_bytes: int
    multiplier: int

    @property
    def bytes_per_step(self) -> int:
        return self.payload_bytes * self.multiplier


def _split_computations(hlo_text: str) -> Dict[str, List[str]]:
    """computation name -> its instruction lines.  HLO text opens each
    computation at column 0 (`%name (...) {` / `ENTRY %name (...) {`) and
    closes with a column-0 `}`."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            s = line.strip()
            if s.endswith("{"):
                head = s[:-1].strip()
                if head.startswith("ENTRY"):
                    cur = "ENTRY"
                else:
                    cur = head.split()[0].lstrip("%") if head else None
                if cur:
                    comps[cur] = []
            elif s == "}":
                cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps


def collective_costs(hlo_text: str) -> List[CollectiveOp]:
    """Every collective instruction in an optimized HLO module, with payload
    bytes and the while-loop trip multiplier of its enclosing computation.

    Trip counts come from the paired condition computation's
    `compare(iv, constant(N)), direction=LT` bound; a condition that does
    not parse contributes multiplier 1 (an under-count, never a phantom)."""
    comps = _split_computations(hlo_text)

    # condition computation -> trip count, read from the constant OPERAND of
    # the LT compare (not just any constant in the computation — folded
    # constants would otherwise yield a wrong or zero multiplier); clamped
    # to >= 1 so a misparse can only under-count, never erase traffic
    trips: Dict[str, int] = {}
    for name, lines in comps.items():
        body = "\n".join(lines)
        m = _COMPARE_LT_RE.search(body)
        if not m:
            continue
        bound = None
        for op in _OPERAND_NAME_RE.findall(m.group(1)):
            dm = re.search(r"%" + re.escape(op) +
                           r"\s*=\s*s32\[\]\s+constant\((\d+)\)", body)
            if dm:
                bound = int(dm.group(1))
        if bound is None:
            dm = _TRIP_RE.search(body)      # legacy fallback
            bound = int(dm.group(1)) if dm else None
        if bound is not None:
            trips[name] = max(bound, 1)

    # propagate multipliers along while edges from ENTRY
    mult: Dict[str, int] = {name: 1 for name in comps}
    edges: List[Tuple[str, str, int]] = []      # (enclosing, body, trip)
    for name, lines in comps.items():
        for line in lines:
            m = _WHILE_RE.search(line)
            if m:
                cond, body = m.group(1), m.group(2)
                edges.append((name, body, trips.get(cond, 1)))
    for _ in range(len(edges) + 1):             # fixed point (loops nest)
        changed = False
        for enclosing, body, trip in edges:
            want = mult.get(enclosing, 1) * trip
            if mult.get(body, 1) != want:
                mult[body] = want
                changed = True
        if not changed:
            break

    out: List[CollectiveOp] = []
    for name, lines in comps.items():
        for line in lines:
            m = _COLLECTIVE_RE.search(line)
            if m:
                is_start = m.group("kind").endswith("-start")
                out.append(CollectiveOp(
                    m.group("kind").removesuffix("-start"),
                    m.group("shape").strip(),
                    _shape_bytes(m.group("shape"), largest_only=is_start),
                    mult.get(name, 1)))
    return out


# ---------------------------------------------------------------------------
# per-program cost
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ProgramCost:
    """Static cost account of one serving executable.  All byte fields are
    model units (traced aval bytes, no XLA padding): `peak_bytes` =
    argument bytes + the liveness watermark of program-defined values
    (donation-aliased outputs allocate nothing); `hbm_min_bytes` is the
    compulsory-traffic floor the roofline divides by."""
    name: str
    flops: int
    arg_bytes: int
    out_bytes: int
    alias_bytes: int            # outputs aliasing donated inputs
    temp_peak_bytes: int        # liveness watermark of defined values
    peak_bytes: int             # arg_bytes + temp_peak_bytes
    peak_at: str
    collectives: Optional[List[CollectiveOp]] = None    # None = not compiled
    xla_temp_bytes: Optional[int] = None    # XLA memory_analysis, if compiled

    @property
    def hbm_min_bytes(self) -> int:
        return self.arg_bytes + self.out_bytes - self.alias_bytes

    @property
    def collective_bytes(self) -> int:
        return sum(c.bytes_per_step for c in self.collectives or ())

    def predicted_ms(self, spec: DeviceSpec, mp: int = 1) -> float:
        """Roofline step time: max(compute, HBM) + collective transfer.
        Under mp the flop/byte work divides across chips (the traced shapes
        are global); collective payloads are already per-device."""
        compute_s = self.flops / mp / spec.flops_per_s
        memory_s = self.hbm_min_bytes / mp / spec.hbm_bytes_per_s
        ici_s = self.collective_bytes / spec.ici_bytes_per_s
        return (max(compute_s, memory_s) + ici_s) * 1e3

    def to_json(self) -> Dict[str, object]:
        d = {
            "name": self.name, "flops": self.flops,
            "arg_bytes": self.arg_bytes, "out_bytes": self.out_bytes,
            "alias_bytes": self.alias_bytes,
            "temp_peak_bytes": self.temp_peak_bytes,
            "peak_bytes": self.peak_bytes, "peak_at": self.peak_at,
            "hbm_min_bytes": self.hbm_min_bytes,
        }
        if self.collectives is not None:
            d["collective_bytes_per_step"] = self.collective_bytes
            d["collectives"] = [dataclasses.asdict(c)
                                for c in self.collectives]
        if self.xla_temp_bytes is not None:
            d["xla_temp_bytes"] = self.xla_temp_bytes
        return d


def program_cost(name: str, fn, args, *, compile_collectives: bool = False
                 ) -> ProgramCost:
    """Trace `fn(*args)` (a jitted callable; ShapeDtypeStructs are fine) and
    account it.  Donation is read from the traced pjit eqn itself — the same
    source of truth JXP002 audits — so the cost and the donation audit
    cannot disagree.  `compile_collectives=True` additionally runs the XLA
    compile and reads collective traffic + XLA's own temp-byte number from
    the optimized module (skipped on the bench path, where an extra compile
    would perturb the program-count stats)."""
    import jax
    from jax.extend.core import Literal

    closed = jax.make_jaxpr(fn)(*args)
    body = closed.jaxpr
    consts = closed.consts
    donated = ()
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "jit":
            sub = eqn.params["jaxpr"]
            body, consts = sub.jaxpr, sub.consts
            donated = eqn.params.get("donated_invars", ())
            break

    arg_bytes = sum(aval_bytes(v.aval) for v in body.invars)
    arg_bytes += sum(aval_bytes(c) for c in consts)   # consts carry shape/dtype
    out_bytes = sum(aval_bytes(getattr(v, "aval", None))
                    for v in body.outvars if not isinstance(v, Literal))

    # donation aliasing: each donated invar signature absorbs ONE matching
    # output — that output writes in place and allocates nothing
    donated_sigs: List[Tuple[tuple, str]] = []
    for d, v in zip(donated, body.invars):
        if d:
            donated_sigs.append((tuple(v.aval.shape), str(v.aval.dtype)))
    aliased = set()
    alias_bytes = 0
    invars = set(body.invars)
    for v in body.outvars:
        if isinstance(v, Literal) or v in invars or v in aliased:
            continue
        sig = (tuple(v.aval.shape), str(v.aval.dtype))
        if sig in donated_sigs:
            donated_sigs.remove(sig)
            aliased.add(v)
            alias_bytes += aval_bytes(v.aval)

    writable = frozenset(v for d, v in zip(donated, body.invars) if d)
    flops, temp_peak, peak_at = _jaxpr_walk(body, frozenset(aliased),
                                            writable)

    collectives = None
    xla_temp = None
    if compile_collectives:
        compiled = fn.lower(*args).compile()
        collectives = collective_costs(compiled.as_text())
        try:
            xla_temp = int(compiled.memory_analysis().temp_size_in_bytes)
        except (AttributeError, NotImplementedError):
            xla_temp = None     # backend without memory_analysis support
    return ProgramCost(name, flops, arg_bytes, out_bytes, alias_bytes,
                       temp_peak, arg_bytes + temp_peak, peak_at,
                       collectives, xla_temp)


# ---------------------------------------------------------------------------
# at-rest HBM accounting
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BufferAccount:
    name: str                   # pytree path ("blocks.qkv_w", "wte", "pool.k")
    bytes: int                  # global (unsharded) footprint
    sharded: bool               # divides by mp per device

    def per_device(self, mp: int) -> int:
        return self.bytes // mp if self.sharded else self.bytes


@dataclasses.dataclass
class AtRestAccount:
    """The serving executable set's resident HBM, per device: params split
    by the mp layout they are PLACED with (`serving_param_specs` — the same
    spec tree the engine device_puts at init) plus the KVH-sharded page
    pool.  At mp=1 the classification still runs (sharded = "what tensor
    parallelism would divide"), so mp1-vs-mp2 comparisons read off the same
    account."""
    mp: int
    buffers: List[BufferAccount]

    def _sum(self, sharded: bool, per_device: bool) -> int:
        return sum(b.per_device(self.mp) if per_device else b.bytes
                   for b in self.buffers
                   if b.sharded == sharded and not b.name.startswith("pool."))

    @property
    def param_bytes_sharded(self) -> int:        # global
        return self._sum(True, False)

    @property
    def param_bytes_sharded_per_device(self) -> int:
        return self._sum(True, True)

    @property
    def param_bytes_replicated(self) -> int:     # per device == global
        return self._sum(False, False)

    @property
    def pool_bytes(self) -> int:                 # global
        return sum(b.bytes for b in self.buffers
                   if b.name.startswith("pool."))

    @property
    def pool_bytes_per_device(self) -> int:
        return sum(b.per_device(self.mp) for b in self.buffers
                   if b.name.startswith("pool."))

    @property
    def per_device_bytes(self) -> int:
        return sum(b.per_device(self.mp) for b in self.buffers)

    def replicated_over(self, ceiling: int) -> List[BufferAccount]:
        return [b for b in self.buffers
                if not b.sharded and b.bytes > ceiling]

    def to_json(self) -> Dict[str, object]:
        return {
            "mp": self.mp,
            "param_bytes_sharded": self.param_bytes_sharded,
            "param_bytes_sharded_per_device":
                self.param_bytes_sharded_per_device,
            "param_bytes_replicated": self.param_bytes_replicated,
            "pool_bytes": self.pool_bytes,
            "pool_bytes_per_device": self.pool_bytes_per_device,
            "per_device_bytes": self.per_device_bytes,
            "top_replicated": [dataclasses.asdict(b) for b in sorted(
                (b for b in self.buffers if not b.sharded),
                key=lambda b: -b.bytes)[:4]],
        }


def _spec_is_sharded(spec) -> bool:
    return any(e is not None for e in (spec or ()))


def params_at_rest(params, config, mp: int = 1) -> List[BufferAccount]:
    """One BufferAccount per param leaf, classified through
    `serving_param_specs` — the layout `LLMEngine(mp=N)` actually places."""
    import jax
    from jax.sharding import PartitionSpec

    from ..parallel.hybrid import serving_param_specs

    specs = serving_param_specs(config, params)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    spec_leaves = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))[0]
    by_path = {jax.tree_util.keystr(p): s for p, s in spec_leaves}
    out = []
    for path, leaf in leaves:
        key = jax.tree_util.keystr(path)
        name = key.replace("['", ".").replace("']", "").lstrip(".")
        out.append(BufferAccount(name, aval_bytes(leaf),
                                 _spec_is_sharded(by_path.get(key))))
    return out


def engine_at_rest(engine) -> AtRestAccount:
    """At-rest account of a live LLMEngine: its params (classified by the
    serving layout) + its page pool (KVH-sharded under mp)."""
    buffers = params_at_rest(engine.params, engine.config, engine.mp)
    for k, v in engine._pool.items():
        buffers.append(BufferAccount(f"pool.{k}", aval_bytes(v), True))
    return AtRestAccount(max(engine.mp, 1), buffers)


# ---------------------------------------------------------------------------
# engine-level costing (the bench hook)
# ---------------------------------------------------------------------------


def engine_step_target(engine):
    """(jitted fn, abstract args) of the engine's decode-side program (the
    fused `serve_step_paged`) at the ENGINE's own shapes, the inputs carrying
    the engine's REAL shardings — what `fn.lower(*args)` needs, with no dispatch and no transfer, and
    outside the `_AotCache` dispatch cache (program-count stats untouched)."""
    import jax
    import numpy as np

    def sds(a, sh=None):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

    B = engine.cache.num_slots
    P = engine.cache.max_pages_per_slot
    repl = engine._repl_sharding
    if engine._param_shardings is not None:
        params = jax.tree_util.tree_map(sds, engine.params,
                                        engine._param_shardings)
    else:
        params = jax.tree_util.tree_map(sds, engine.params)
    pool = {k: sds(v, engine._pool_sharding)
            for k, v in engine._pool.items()}

    def host(shape, dtype=np.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    fn = getattr(engine._decode_fn, "_jit", engine._decode_fn)
    args = (params, host((B, engine._fused_T)), pool, host((B, P)),
            host((B,)), host((B,)), sds(engine._key, repl),
            host((B,), np.bool_), host((B, engine._fused_T)), host((B,)))
    return fn, args


def engine_step_cost(engine, *, compile_collectives: Optional[bool] = None
                     ) -> ProgramCost:
    """Cost of the engine's decode-side program (`engine_step_target`),
    traced abstractly — no dispatch, no transfer.

    `compile_collectives` defaults to `engine.mp > 1`: the mp program's
    per-layer all-reduces only exist in the compiled module, and the
    roofline's ICI term needs them — the same account `tools/tpu_cost.py`
    prints, so the bench JSON and the CLI cannot disagree.  Single-chip
    engines skip the compile (nothing to collect)."""
    fn, args = engine_step_target(engine)
    if compile_collectives is None:
        compile_collectives = engine.mp > 1
    return program_cost("serve.step", fn, args,
                        compile_collectives=compile_collectives)


# ---------------------------------------------------------------------------
# budget enforcement (tools/tpu_cost.py --ci + tests)
# ---------------------------------------------------------------------------


def _basename(target_name: str) -> str:
    """'serve.mp2.fused_step' -> 'fused_step' (budget keys are per
    executable; the mp tag picks the budget table)."""
    return target_name.split(".")[-1]


def audit_resources(targets, at_rest: AtRestAccount, budget,
                    *, compile_collectives: bool = True
                    ) -> Tuple[List[ProgramCost], List[Finding]]:
    """Run the full account over `targets` ((name, fn, args, kw) rows, the
    `jaxpr_checks.serving_targets` shape) against `budget`
    (`registry.SERVE_RESOURCE_BUDGET`-shaped dict).  Returns the per-program
    costs and the findings:

    - JXP006: a replicated at-rest buffer above the declared ceiling
      (only meaningful at mp > 1 — replication is free on one chip);
    - JXP007: collective traffic in a program with no declared budget, or
      above its declared per-step bytes;
    - JXP008: a program's modeled peak HBM above its declared budget.
    """
    findings: List[Finding] = []
    costs: List[ProgramCost] = []

    ceiling = budget.get("replicated_bytes_ceiling")
    if ceiling is not None and at_rest.mp > 1:
        for b in at_rest.replicated_over(ceiling):
            findings.append(Finding(
                "JXP006", "<at-rest>", 0, 0,
                f"replicated buffer `{b.name}` is {b.bytes} bytes on EVERY "
                f"chip (ceiling {ceiling}) — this is the replicated-memory "
                f"ceiling that blocks 70B-class configs; shard it (e.g. "
                f"vocab-shard the embedding/head) or raise the declared "
                f"ceiling with the math that justifies it"))

    coll_budget: Dict[str, int] = budget.get("collective_bytes_per_step", {})
    peak_budget: Dict[str, int] = budget.get("peak_hbm_bytes", {})
    for name, fn, args, _kw in targets:
        cost = program_cost(name, fn, args,
                            compile_collectives=compile_collectives)
        costs.append(cost)
        path = f"<cost:{name}>"
        if cost.collectives:
            allowed = coll_budget.get(name)
            total = cost.collective_bytes
            if allowed is None:
                kinds = sorted({c.kind for c in cost.collectives})
                findings.append(Finding(
                    "JXP007", path, 0, 0,
                    f"undeclared collective traffic: {total} bytes/step "
                    f"({', '.join(kinds)}) in a program with no "
                    f"collective_bytes_per_step entry in "
                    f"analysis/registry.py — declare it or remove the "
                    f"collective"))
            elif total > allowed:
                findings.append(Finding(
                    "JXP007", path, 0, 0,
                    f"collective traffic {total} bytes/step exceeds the "
                    f"declared budget {allowed} — a reshard/allgather crept "
                    f"into the step program"))
        cap = peak_budget.get(_basename(name), {}).get(f"mp{at_rest.mp}") \
            if isinstance(peak_budget.get(_basename(name)), dict) \
            else peak_budget.get(_basename(name))
        if cap is not None and cost.peak_bytes > cap:
            findings.append(Finding(
                "JXP008", path, 0, 0,
                f"modeled peak HBM {cost.peak_bytes} bytes exceeds the "
                f"declared budget {cap} — the step program holds more "
                f"live bytes than the serving memory plan allows"))
    return costs, findings


def run_cost_checks(include_mp: bool = True, mp=(2, 4),
                    budget=None) -> Tuple[Dict[int, Dict[str, object]],
                                          List[Finding]]:
    """The CI entry: audit the registry-declared serving executables (same
    tiny engines as the jaxpr checks) at mp1 (+ each requested mp degree with
    enough devices — the default covers mp2 AND mp4, the mesh size where the
    vocab-shard win compounds) against `registry.SERVE_RESOURCE_BUDGET`.
    `mp` accepts an int or a sequence of degrees.  Returns ({mp: report},
    all findings)."""
    import jax

    from .jaxpr_checks import (_build_engine, quantized_targets,
                               serving_targets)
    from . import registry

    if budget is None:
        budget = registry.SERVE_RESOURCE_BUDGET
    findings: List[Finding] = []
    reports: Dict[int, Dict[str, object]] = {}
    passes = [1]
    if include_mp:
        for m in ((mp,) if isinstance(mp, int) else tuple(mp)):
            if len(jax.devices()) >= m and m not in passes:
                passes.append(m)
    spec = device_spec()
    for m in passes:
        # ONE engine serves both the at-rest account and the audit
        # targets (plus the bucketed one serving_targets takes the chunk
        # program from) — same instance, so the two accounts cannot diverge
        eng, _ = _build_engine(m)
        bkt, _ = _build_engine(m, prefill_chunk=None)
        at_rest = engine_at_rest(eng)
        costs, fs = audit_resources(serving_targets(m, engines=(eng, bkt)),
                                    at_rest, budget)
        findings.extend(fs)
        # JXP009: the UNIFIED host pool (preempt="swap" victim parking +
        # the kv_tier spilled-prefix store, one swap_pool_pages ceiling) is
        # sized, not traced — its declared bound is audited exactly, once
        # per mesh pass (host memory does not shard: the bound is per host)
        host_cap = budget.get("host_pool_bytes")
        host_bytes = eng.host_pool_bytes()
        if host_cap is not None and host_bytes > host_cap:
            findings.append(Finding(
                "JXP009", "<at-rest>", 0, 0,
                f"unified host pool bound {host_bytes} bytes exceeds the "
                f"declared host_pool_bytes budget {host_cap} — size "
                f"swap_pool_pages down (it caps swap parking AND spilled "
                f"prefix pages) or raise the budget with the host memory "
                f"math that justifies it"))
        # ---- quantized serving pass (ISSUE-11): the int8 engine at the
        # SAME pool geometry, audited against its own declared yardstick —
        # the quantization win must show up here before any TPU run -------
        qeng, _ = _build_engine(m, weight_dtype="int8", kv_dtype="int8")
        q_at_rest = engine_at_rest(qeng)
        q_budget = dict(budget)
        q_ceiling = budget.get("replicated_bytes_ceiling_int8")
        if q_ceiling is not None:
            # tightened JXP006 ceiling for the quantized engine: a fp-width
            # embedding re-materializing in the quantized at-rest account
            # is a regression the fp ceiling would never see
            q_budget["replicated_bytes_ceiling"] = q_ceiling
        q_costs, q_fs = audit_resources(
            quantized_targets(m, engine=qeng), q_at_rest, q_budget)
        findings.extend(q_fs)
        costs.extend(q_costs)
        pool_ratio = at_rest.pool_bytes / max(q_at_rest.pool_bytes, 1)
        min_ratio = budget.get("quantized_pool_min_ratio")
        if min_ratio is not None and pool_ratio < min_ratio:
            findings.append(Finding(
                "JXP010", "<at-rest>", 0, 0,
                f"int8 KV pool at-rest bytes shrink only {pool_ratio:.2f}x "
                f"vs the fp pool at the same geometry (declared floor "
                f"{min_ratio}x) — the quantized pool stopped paying for "
                f"itself (a scale lane widened, or pages re-materialized at "
                f"fp width)"))
        q_pool_cap = budget.get("quantized_pool_bytes")
        if q_pool_cap is not None and q_at_rest.pool_bytes > q_pool_cap:
            findings.append(Finding(
                "JXP010", "<at-rest>", 0, 0,
                f"int8 KV pool at-rest bytes {q_at_rest.pool_bytes} exceed "
                f"the declared quantized_pool_bytes budget {q_pool_cap}"))
        # the quantization win is measured on the WHOLE param account: with
        # the embedding/head vocab-sharded, the replicated remainder is just
        # the norm/bias vectors (identical either way, plus tiny fp32 scale
        # leaves on the int8 side), so replicated-only comparison would
        # false-positive on a correct build
        q_total = q_at_rest.param_bytes_sharded \
            + q_at_rest.param_bytes_replicated
        fp_total = at_rest.param_bytes_sharded + at_rest.param_bytes_replicated
        if q_total >= fp_total:
            findings.append(Finding(
                "JXP010", "<at-rest>", 0, 0,
                f"int8 weights do not reduce the at-rest param account "
                f"({q_total} vs fp {fp_total} bytes) — the quantized "
                f"weights are not actually stored int8"))
        q_host_cap = budget.get("host_pool_bytes_int8")
        q_host_bytes = qeng.host_pool_bytes()
        if q_host_cap is not None and q_host_bytes > q_host_cap:
            findings.append(Finding(
                "JXP009", "<at-rest>", 0, 0,
                f"int8 unified host pool bound {q_host_bytes} bytes exceeds "
                f"the declared host_pool_bytes_int8 budget {q_host_cap} — "
                f"int8 pages must park as int8, not re-widened fp"))
        reports[m] = {
            "at_rest": at_rest.to_json(),
            "at_rest_quantized": q_at_rest.to_json(),
            "quantized_pool_ratio": round(pool_ratio, 3),
            "host_pool_bytes": host_bytes,
            "host_pool_bytes_int8": q_host_bytes,
            # predicted_ms computed HERE through ProgramCost.predicted_ms:
            # one roofline formula for every reader of the report
            "programs": [dict(c.to_json(),
                              predicted_ms=round(c.predicted_ms(spec, mp=m),
                                                 4))
                         for c in costs],
        }
    return reports, findings
