"""tpu_lint level 2: jaxpr audits of the serving executables.

Level 1 reads source; this level reads what jax will actually compile.  Each
registry-declared serving executable is traced with abstract inputs
(`jax.make_jaxpr` — tracing only, no XLA compile) and the closed jaxpr is
audited:

- **JXP001** transfer primitives inside the program (`device_put`, host
  callbacks): a serving step must be pure device compute — an embedded
  transfer is a hidden per-dispatch host round-trip that no AST pattern can
  see once it hides behind a helper.
- **JXP002** donation mismatch, both directions: every declared-donated
  buffer (the KV page pool) must actually arrive donated in the pjit params
  (else XLA double-buffers the pool every step), and declared-persistent
  buffers (params, reused across calls) must NOT be donated (else the second
  dispatch reads freed memory).  Any other large undeclared input that is
  not donated is flagged too.
- **JXP003** dtype upcasts: float64 anywhere in the program (a leaked Python
  float / np.float64 under x64) or an upcast `convert_element_type` to f64.
- **JXP004** (mp mode) missing sharding constraint: the tensor-parallel
  executables must pin their output pool layout (`pin_pool`'s
  `with_sharding_constraint`) — without the pin, GSPMD-inferred output
  shardings drift between calls and the fixed program set silently forks.
- **JXP005** oversized host-visible output: the fused one-dispatch step
  moved sampling and spec acceptance on device precisely so the per-step
  host fetch is O(B*K) ints — this audit bounds the program's non-donated
  output elements (`host_output_budget`) and flags any float matrix output
  (logits-shaped), so a refactor cannot quietly reintroduce the `[B, V]`
  logits fetch.  Outputs whose (shape, dtype) matches a donated input (the
  in-place page pool) are exempt: they never cross to the host.

`audit_jaxpr` is the reusable core (tests feed it toy jits for
positive/negative pairs); `run_jaxpr_checks` builds tiny CPU engines (a
chunked one, and a bucketed one for the standalone chunk program that
serves prefix-hit tails there) and checks the real serving set — fused step,
chunk prefill, bucketed prefill, COW copy, and the two
preemption KV-swap copies (swap-out gather / swap-in scatter) — plus an
mp=2 pass when enough devices exist.  The quantized serving engine's fused
step (`quantized_targets`, weight/kv int8) rides the same audit so dequant
cannot smuggle a transfer/upcast/logits-fetch into the one-dispatch step.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

from .rules import Finding

TRANSFER_PRIMITIVES = frozenset({
    "device_put", "pure_callback", "io_callback", "debug_callback",
    "infeed", "outfeed"})

LARGE_LEAF_ELEMS = 1 << 16      # "large" for the undeclared-buffer check


def _iter_eqns(jaxpr):
    """Every eqn in `jaxpr` and its nested sub-jaxprs (pjit bodies, scan/cond
    branches, custom_vjp calls...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _as_jaxprs(v):
                yield from _iter_eqns(sub)


def _as_jaxprs(value):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _as_jaxprs(v)


def _arg_paths(args) -> List[str]:
    """Human-readable path per flattened leaf of `args`, aligned with the
    pjit eqn's invar order: 'arg2[k][0]' style."""
    import jax
    leaves_with_path = jax.tree_util.tree_flatten_with_path(tuple(args))[0]
    out = []
    for path, _ in leaves_with_path:
        s = ""
        for i, key in enumerate(path):
            if i == 0:
                s = f"arg{getattr(key, 'idx', key)}"
            else:
                s += jax.tree_util.keystr((key,))
        out.append(s)
    return out


def _under(path: str, prefixes: Sequence[str]) -> bool:
    return any(path == p or path.startswith(p + "[") or
               path.startswith(p + ".") for p in prefixes)


def audit_jaxpr(name: str, fn, args, *, donate_paths: Sequence[str] = (),
                keep_paths: Sequence[str] = (),
                require_sharding_constraint: bool = False,
                host_output_budget: Optional[int] = None,
                large_leaf_elems: int = LARGE_LEAF_ELEMS) -> List[Finding]:
    """Trace `fn(*args)` (a jitted callable) and run every jaxpr check.
    Findings carry the pseudo-path `<jaxpr:name>` — they live in the traced
    program, not on a source line."""
    import jax
    import numpy as np

    path = f"<jaxpr:{name}>"
    findings: List[Finding] = []
    closed = jax.make_jaxpr(fn)(*args)

    # the jitted callable traces to a single pjit eqn carrying the program
    pjit_eqn = None
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "jit":
            pjit_eqn = eqn
            break

    # ---- JXP001: transfers inside the program -----------------------------
    for eqn in _iter_eqns(closed.jaxpr):
        if eqn.primitive.name in TRANSFER_PRIMITIVES and eqn is not pjit_eqn:
            findings.append(Finding(
                "JXP001", path, 0, 0,
                f"`{eqn.primitive.name}` primitive inside the program — a "
                f"hidden per-dispatch transfer/host round-trip"))

    # ---- JXP002: donation, both directions --------------------------------
    if pjit_eqn is not None:
        donated = pjit_eqn.params.get("donated_invars", ())
        paths = _arg_paths(args)
        if len(paths) == len(donated):
            for p, d, var in zip(paths, donated, pjit_eqn.invars):
                aval = getattr(var, "aval", None)
                size = int(np.prod(aval.shape)) if aval is not None and \
                    aval.shape else 1
                if _under(p, donate_paths) and not d:
                    findings.append(Finding(
                        "JXP002", path, 0, 0,
                        f"declared-donated buffer `{p}` "
                        f"({aval.str_short() if aval else '?'}) is NOT "
                        f"donated — XLA double-buffers it every dispatch"))
                elif _under(p, keep_paths) and d:
                    findings.append(Finding(
                        "JXP002", path, 0, 0,
                        f"persistent buffer `{p}` IS donated — the next "
                        f"dispatch would read freed memory"))
                elif not d and size >= large_leaf_elems and \
                        not _under(p, keep_paths) and \
                        not _under(p, donate_paths):
                    findings.append(Finding(
                        "JXP002", path, 0, 0,
                        f"large input `{p}` ({aval.str_short()}) neither "
                        f"donated nor declared persistent — copied every "
                        f"dispatch; donate it or register it as kept"))
        elif donate_paths or keep_paths:
            findings.append(Finding(
                "JXP002", path, 0, 0,
                f"cannot align {len(donated)} pjit inputs with "
                f"{len(paths)} argument leaves — donation audit skipped; "
                f"does the traced function close over arrays?"))
    elif donate_paths or keep_paths:
        # the audit must fail CLOSED: if the callable was not actually jitted
        # (make_jaxpr inlined it, no pjit eqn), a declared donation contract
        # cannot be verified and silence would mean CI green while unguarded
        findings.append(Finding(
            "JXP002", path, 0, 0,
            "no pjit eqn in the traced program (callable not jitted?) — "
            "declared donation contract cannot be audited"))

    # ---- JXP003: dtype upcasts --------------------------------------------
    seen_f64 = False
    for eqn in _iter_eqns(closed.jaxpr):
        for v in list(eqn.outvars) + [x for x in eqn.invars
                                      if hasattr(x, "aval")]:
            aval = getattr(v, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt == "float64" and not seen_f64:
                seen_f64 = True
                findings.append(Finding(
                    "JXP003", path, 0, 0,
                    "float64 value inside the program — a Python float / "
                    "np.float64 leaked into the trace (4x the bf16 compute "
                    "budget per element)"))
        if eqn.primitive.name == "convert_element_type":
            new = str(eqn.params.get("new_dtype", ""))
            old = str(getattr(eqn.invars[0].aval, "dtype", "")) \
                if hasattr(eqn.invars[0], "aval") else ""
            if new == "float64" and old in ("float32", "bfloat16"):
                findings.append(Finding(
                    "JXP003", path, 0, 0,
                    f"upcast convert_element_type {old} -> float64 inside "
                    f"the program"))

    # ---- JXP005: oversized host-visible output ----------------------------
    if host_output_budget is not None:
        donated_sigs: List[Tuple[tuple, str]] = []
        if pjit_eqn is not None:
            for d, var in zip(pjit_eqn.params.get("donated_invars", ()),
                              pjit_eqn.invars):
                aval = getattr(var, "aval", None)
                if d and aval is not None:
                    donated_sigs.append((tuple(aval.shape), str(aval.dtype)))
        small_elems = 0
        for aval in closed.out_avals:
            sig = (tuple(aval.shape), str(aval.dtype))
            if sig in donated_sigs:
                # an output shaped exactly like a donated input is the
                # in-place buffer (page pool) riding through — never fetched
                donated_sigs.remove(sig)
                continue
            # extended-dtype-aware floating check: bfloat16 (the TPU serving
            # dtype) must be caught too, and PRNG key dtypes must not crash
            if jax.dtypes.issubdtype(aval.dtype, np.floating) and \
                    len(aval.shape) >= 2:
                findings.append(Finding(
                    "JXP005", path, 0, 0,
                    f"host-visible float output {aval.str_short()} — "
                    f"logits-shaped; the fused step must return O(B*K) int "
                    f"tokens/accept counts, never [B, V] logits"))
            small_elems += int(np.prod(aval.shape)) if aval.shape else 1
        if small_elems > host_output_budget:
            findings.append(Finding(
                "JXP005", path, 0, 0,
                f"host-visible output totals {small_elems} elements (budget "
                f"{host_output_budget}) — the per-step fetch must stay "
                f"O(B*K) ints or the fused step's sync win is gone"))

    # ---- JXP004: sharding constraint under mp -----------------------------
    if require_sharding_constraint:
        n = sum(1 for eqn in _iter_eqns(closed.jaxpr)
                if eqn.primitive.name == "sharding_constraint")
        if n == 0:
            findings.append(Finding(
                "JXP004", path, 0, 0,
                "mp-mode executable has NO sharding_constraint — the output "
                "pool layout is GSPMD-inferred and can drift between calls "
                "(pin it with with_sharding_constraint, see engine.pin_pool)"))
    return findings


# ---------------------------------------------------------------------------
# the real serving targets
# ---------------------------------------------------------------------------


def _build_engine(mp: int, prefill_chunk=8, weight_dtype=None,
                  kv_dtype=None):
    import jax

    from ..inference.engine import LLMEngine
    from ..models import gpt as gpt_mod

    cfg = gpt_mod.gpt_tiny(64)
    params = gpt_mod.init_params(cfg, jax.random.key(0))
    return LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                     prefill_chunk=prefill_chunk, spec_len=2,
                     weight_dtype=weight_dtype, kv_dtype=kv_dtype,
                     mp=mp if mp > 1 else None), cfg


def serving_targets(mp: int = 1, engines=None
                    ) -> List[Tuple[str, object, tuple, dict]]:
    """(name, jitted fn, example args, audit kwargs) for every serving
    executable, mirroring the engine's own dispatch shapes.  Two engines:
    a chunked one supplies the one-dispatch step (audited under JXP001-005 —
    the host-output budget proves the O(B*K)-int fetch), the bucketed cold
    prefill and the COW copy; a bucketed one (the mode the benchmark's cells
    run) supplies the standalone chunk program, which exists there only (it
    serves prefix-hit tails).  `engines` injects a prebuilt (chunked,
    bucketed) pair so callers that also need the engine for other accounts
    (tpu_cost's at-rest pass) build it once."""
    import jax.numpy as jnp

    if engines is not None:
        eng, bkt = engines
    else:
        eng, _cfg = _build_engine(mp)
        bkt, _ = _build_engine(mp, prefill_chunk=None)
    B = eng.cache.num_slots
    P = eng.cache.max_pages_per_slot
    i32 = jnp.int32
    tag = f"mp{mp}." if mp > 1 else ""
    mp_kw = dict(require_sharding_constraint=mp > 1)

    def unwrap(fn):
        return getattr(fn, "_jit", fn)     # _AotCache under mp, jit else

    C = bkt._chunk
    bucket = eng.buckets[0]
    Tf = eng._fused_T
    cfgL = eng._pool["k"].shape[0]      # layers: swap staging leading dim
    return [
        (f"serve.{tag}fused_step", unwrap(eng._decode_fn),
         (eng.params, jnp.zeros((B, Tf), i32), eng._pool,
          jnp.zeros((B, P), i32), jnp.zeros((B,), i32),
          jnp.ones((B,), i32), eng._key, jnp.zeros((B,), bool),
          jnp.zeros((B, Tf), i32), jnp.full((B,), -1, i32)),
         dict(donate_paths=("arg2",), keep_paths=("arg0",),
              host_output_budget=B * (Tf + 2) + 2, **mp_kw)),
        (f"serve.{tag}chunk_prefill", unwrap(bkt._chunk_fn),
         (bkt.params, jnp.zeros((1, C), i32), bkt._pool,
          jnp.zeros((1, P), i32), jnp.zeros((1,), i32),
          jnp.ones((1,), i32), bkt._key, jnp.zeros((1,), bool)),
         dict(donate_paths=("arg2",), keep_paths=("arg0",), **mp_kw)),
        (f"serve.{tag}bucketed_prefill", unwrap(eng._prefill_fn),
         (eng.params, jnp.zeros((1, bucket), i32), eng._pool,
          jnp.zeros((1, bucket // eng.cache.page_size), i32),
          jnp.ones((1,), i32), eng._key, jnp.zeros((1,), bool)),
         dict(donate_paths=("arg2",), keep_paths=("arg0",), **mp_kw)),
        (f"serve.{tag}cow_copy", unwrap(eng._copy_fn),
         (eng._pool, jnp.zeros((), i32), jnp.ones((), i32)),
         dict(donate_paths=("arg0",), **mp_kw)),
        # preemption KV swap copies: the swap-out gather reads a slot's
        # width of pages out of the pool into standalone buffers, one a
        # piece (pool NOT donated — it stays live; its outputs ARE host-bound
        # bulk fetches, so no host_output_budget applies); the swap-in
        # scatter restores a slot's width in place (pool donated).
        (f"serve.{tag}swap_out", unwrap(eng._swap_out_fn),
         (eng._pool, jnp.zeros((eng._d2h_slot_w,), i32)),
         dict(keep_paths=("arg0",), **mp_kw)),
        (f"serve.{tag}swap_in", unwrap(eng._swap_in_fn),
         (eng._pool, jnp.zeros((P,), i32),
          {n: jnp.zeros((cfgL, P) + a.shape[2:], a.dtype)
           for n, a in eng._pool.items()}),
         dict(donate_paths=("arg0",), **mp_kw)),
    ]


def quantized_targets(mp: int = 1, engine=None
                      ) -> List[Tuple[str, object, tuple, dict]]:
    """The int8 serving engine's fused step as an audit target: same JXP001-
    005 discipline as the fp fused step (pool donated, params kept, O(B*K)
    int host output) over a weight_dtype=kv_dtype="int8" engine — dequant
    must not smuggle a transfer, an f64 upcast, a logits-shaped output or an
    undonated pool copy into the program.  `engine` injects a prebuilt
    quantized engine (tpu_cost builds one for the at-rest account anyway)."""
    import jax.numpy as jnp

    qeng = engine
    if qeng is None:
        qeng, _ = _build_engine(mp, weight_dtype="int8", kv_dtype="int8")
    B = qeng.cache.num_slots
    P = qeng.cache.max_pages_per_slot
    i32 = jnp.int32
    tag = f"mp{mp}." if mp > 1 else ""
    Tf = qeng._fused_T
    return [
        (f"serve.{tag}fused_step_int8", getattr(qeng._decode_fn, "_jit",
                                                qeng._decode_fn),
         (qeng.params, jnp.zeros((B, Tf), i32), qeng._pool,
          jnp.zeros((B, P), i32), jnp.zeros((B,), i32),
          jnp.ones((B,), i32), qeng._key, jnp.zeros((B,), bool),
          jnp.zeros((B, Tf), i32), jnp.full((B,), -1, i32)),
         dict(donate_paths=("arg2",), keep_paths=("arg0",),
              host_output_budget=B * (Tf + 2) + 2,
              require_sharding_constraint=mp > 1)),
    ]


def run_jaxpr_checks(include_mp: bool = True,
                     mp: int = 2) -> List[Finding]:
    """Audit every serving executable's jaxpr; adds the mp pass when the
    host exposes enough devices (CI forces 8 virtual CPU chips)."""
    import jax

    findings: List[Finding] = []
    passes: List[int] = [1]
    if include_mp and len(jax.devices()) >= mp:
        passes.append(mp)
    for m in passes:
        for name, fn, args, kw in serving_targets(m) + quantized_targets(m):
            findings.extend(audit_jaxpr(name, fn, args, **kw))
    return findings
