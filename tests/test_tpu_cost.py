"""tpu_cost static resource accounting: golden hand-computed byte/flop
counts on toy programs, mp sharded-vs-replicated at-rest math, donation-
aware liveness, collective accounting cross-checked against the jaxpr,
JXP006/JXP007/JXP008 budget enforcement and CLI exit codes (ref: the reference's memory-optimize / inference-analysis
passes over the graph)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.analysis.cost_model import (
    AtRestAccount, BufferAccount, audit_resources, collective_costs,
    device_spec, engine_at_rest, engine_step_cost, program_cost,
    run_cost_checks)
from paddle_tpu.analysis.jaxpr_checks import _build_engine, serving_targets
from paddle_tpu.analysis.registry import SERVE_RESOURCE_BUDGET

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# golden counts on toy programs (every number hand-computed)
# ---------------------------------------------------------------------------

def test_matmul_golden_flops_and_bytes():
    """[4,8] @ [8,16] f32: flops = 2*M*K*N = 1024; args = 128 + 512 bytes;
    out = 256; the product is the only defined value, live to the end, so
    the watermark is exactly the output and peak = args + out."""
    fn = jax.jit(lambda a, b: a @ b)
    c = program_cost("mm", fn, (jnp.ones((4, 8), jnp.float32),
                                jnp.ones((8, 16), jnp.float32)))
    assert c.flops == 2 * 4 * 8 * 16 == 1024
    assert c.arg_bytes == 4 * 8 * 4 + 8 * 16 * 4 == 640
    assert c.out_bytes == 4 * 16 * 4 == 256
    assert c.temp_peak_bytes == 256
    assert c.peak_bytes == 896
    assert "dot_general" in c.peak_at
    assert c.hbm_min_bytes == 896
    assert c.collectives is None        # not compiled


def test_elementwise_chain_liveness_peak():
    """((a*2)+1)*3 over [1024] f32: three elementwise eqns, 4096 B each.
    The watermark is two simultaneously-live temporaries (t1 while t2 is
    produced) = 8192 B — NOT the 12288 B sum of all three, because t1 dies
    at its last use."""
    fn = jax.jit(lambda a: ((a * 2) + 1) * 3)
    c = program_cost("chain", fn, (jnp.ones((1024,), jnp.float32),))
    assert c.flops == 3 * 1024
    assert c.arg_bytes == 4096 and c.out_bytes == 4096
    assert c.temp_peak_bytes == 8192
    assert c.peak_bytes == 4096 + 8192


def test_donation_excluded_from_peak():
    """The donated pool aliases its output: the output allocates nothing, so
    donating removes exactly pool-bytes from the modeled peak."""
    pool = jnp.zeros((16384,), jnp.float32)     # 65536 B
    x = jnp.ones((), jnp.float32)

    def body(pool, x):
        return pool.at[0].set(x), x + 1

    donated = program_cost("d", jax.jit(body, donate_argnums=(0,)), (pool, x))
    plain = program_cost("p", jax.jit(body), (pool, x))
    assert donated.alias_bytes == 65536
    assert plain.alias_bytes == 0
    assert plain.peak_bytes - donated.peak_bytes == 65536
    # donation also removes the output copy from the compulsory-traffic floor
    assert plain.hbm_min_bytes - donated.hbm_min_bytes == 65536


def _carried_pool(pool, x, reread=False):
    """A layer loop of 3 that scatters one row into a [4*3, 256] view of the
    pool a layer — the paged passes' shape in small."""
    flat = pool.reshape(12, 256)

    def body(carry, l):
        buf, acc = carry
        new = buf.at[l * 4].set(x)
        # `reread` keeps the pre-update buffer alive past the scatter
        acc = acc + (buf[0, 0] if reread else new[0, 0])
        return (new, acc), None

    (flat, acc), _ = jax.lax.scan(body, (flat, jnp.float32(0)),
                                  jnp.arange(3))
    return flat.reshape(3, 4, 256), acc


@pytest.mark.parametrize("case", ["donated", "not_donated", "reread"])
def test_in_place_update_through_a_scan_carry(case):
    """A donated buffer that is reshaped, carried through a scan and
    scattered into allocates nothing — the paged passes' page pool; the same
    program without the donation, or one that reads the buffer again after
    the update, pays for a copy of it (12288 B).  A scatter's flops are its
    update's elements (256 a layer), not the buffer's."""
    pool = jnp.zeros((3, 4, 256), jnp.float32)          # 12288 B
    x = jnp.ones((256,), jnp.float32)
    fn = jax.jit(lambda p, x: _carried_pool(p, x, reread=case == "reread"),
                 donate_argnums=() if case == "not_donated" else (0,))
    c = program_cost(case, fn, (pool, x))
    assert c.flops < 3 * 256 + 64
    if case == "donated":
        assert c.alias_bytes == 12288
        assert c.temp_peak_bytes < 64           # scalars only
    else:
        assert 12288 <= c.temp_peak_bytes < 12288 + 64


def test_cond_takes_max_branch_not_sum():
    """`lax.cond` executes one branch: flops are the worst branch, not the
    sum of both."""
    w = jnp.ones((32, 32), jnp.float32)

    def heavy(x):
        return x @ w                        # 2*32*32 flops

    def light(x):
        return x * 2.0                      # 32 flops

    fn = jax.jit(lambda p, x: jax.lax.cond(p, heavy, light, x))
    c = program_cost("cond", fn, (jnp.array(True), jnp.ones((32,),
                                                            jnp.float32)))
    # the heavy branch + the predicate's 1-element convert — NOT both
    # branches (2048 + 32 would mean the light branch was summed in)
    assert 2 * 32 * 32 <= c.flops < 2 * 32 * 32 + 32


def test_scan_multiplies_body_flops():
    """A scanned body's flops count once per trip: 8 iterations of a
    [16]x[16,16] matvec = 8 * 2*16*16 flops."""
    w = jnp.ones((16, 16), jnp.float32)

    def step(x, _):
        return x @ w, None

    fn = jax.jit(lambda x: jax.lax.scan(step, x, None, length=8)[0])
    c = program_cost("scan", fn, (jnp.ones((16,), jnp.float32),))
    assert c.flops == 8 * 2 * 16 * 16


# ---------------------------------------------------------------------------
# at-rest HBM: sharded vs replicated under mp
# ---------------------------------------------------------------------------

def test_at_rest_mp2_halves_sharded_keeps_replicated():
    """The mp=2 engine holds half the sharded param bytes and half the page
    pool per device, with the replicated set (norms, row biases — the
    embedding/head now lives in the SHARDED column) byte-identical to mp=1 —
    the memory math behind 'per-chip block memory drops by mp x' and the
    JXP006 ceiling's denominator."""
    e1, _ = _build_engine(1)
    e2, _ = _build_engine(2)
    a1, a2 = engine_at_rest(e1), engine_at_rest(e2)
    assert a1.mp == 1 and a2.mp == 2
    assert a1.param_bytes_sharded == a2.param_bytes_sharded        # global
    assert a2.param_bytes_sharded_per_device * 2 == \
        a1.param_bytes_sharded_per_device
    assert a1.param_bytes_replicated == a2.param_bytes_replicated
    assert a2.pool_bytes_per_device * 2 == a1.pool_bytes_per_device
    # the tied embedding/head is vocab-sharded (its per-device share halves
    # with mp); what remains replicated is the small norm/bias tail, all of
    # it under the declared JXP006 ceiling
    wte = next(b for b in a2.buffers if b.name == "wte")
    assert wte.sharded
    assert wte.bytes == e1.config.vocab_size * e1.config.hidden_size * 4
    assert a2.param_bytes_replicated < wte.bytes


def test_jxp006_replicated_ceiling():
    """A replicated buffer above the ceiling is flagged at mp>1 and named —
    but never the vocab-sharded `wte`, which left the replicated column; on
    one chip replication is free and the ceiling does not apply."""
    e2, _ = _build_engine(2)
    a2 = engine_at_rest(e2)
    # squeeze below the largest surviving replicated leaf: JXP006 fires and
    # names a norm/bias buffer, not the (sharded) embedding/head
    top = max((b for b in a2.buffers
               if not b.sharded and not b.name.startswith("pool.")),
              key=lambda b: b.bytes)
    _, fs = audit_resources([], a2,
                            {"replicated_bytes_ceiling": top.bytes - 1})
    assert any(f.rule == "JXP006" and f"`{top.name}`" in f.message
               for f in fs)
    assert not any("wte" in f.message for f in fs)
    _, fs = audit_resources([], a2, {"replicated_bytes_ceiling": 1 << 30})
    assert fs == []
    e1, _ = _build_engine(1)
    _, fs = audit_resources([], engine_at_rest(e1),
                            {"replicated_bytes_ceiling": 1000})
    assert fs == []


# ---------------------------------------------------------------------------
# collective accounting
# ---------------------------------------------------------------------------

def _toy_psum_target():
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
    fn = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "mp"),
                               mesh=mesh, axis_names={"mp"},
                               in_specs=(P("mp"),), out_specs=P()))
    return fn, (jnp.ones((8, 16), jnp.float32),)


def test_collective_total_matches_jaxpr():
    """The HLO-derived collective total equals the jaxpr's own psum payload:
    in_specs=P('mp') shards [8,16] to a per-device [4,16] f32 operand =
    256 bytes, one all-reduce, no loop multiplier."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    fn, args = _toy_psum_target()
    c = program_cost("toy.mp2.x", fn, args, compile_collectives=True)
    # ground truth straight from the traced program
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def psums(j):
        out = []
        for e in j.eqns:
            # under shard_map's vma tracking a psum of a varying operand
            # traces as `psum_invariant`
            if e.primitive.name == "psum_invariant":
                out.append(e)
            for v in e.params.values():
                stack = [v]
                while stack:
                    s = stack.pop()
                    if isinstance(s, ClosedJaxpr):
                        out.extend(psums(s.jaxpr))
                    elif isinstance(s, Jaxpr):
                        out.extend(psums(s))
                    elif isinstance(s, (list, tuple)):
                        stack.extend(s)
        return out

    eqns = psums(jax.make_jaxpr(fn)(*args).jaxpr)
    assert len(eqns) == 1
    aval = eqns[0].invars[0].aval
    expect = int(np.prod(aval.shape)) * 4
    assert expect == 256
    assert c.collective_bytes == expect
    assert [o.kind for o in c.collectives] == ["all-reduce"]


def test_collective_loop_multiplier_parses_while_trips():
    """Collectives inside a while body multiply by the parsed trip count —
    the layer scan is where the serving programs' all-reduces live."""
    hlo = """\
HloModule toy

%cond (p: (s32[])) -> pred[] {
  %zero = s32[] constant(0)
  %c = s32[] constant(24)
  %p = (s32[]) parameter(0)
  %iv = s32[] get-tuple-element((s32[]) %p), index=0
  ROOT %lt = pred[] compare(s32[] %iv, s32[] %c), direction=LT
}

%body (p: (s32[])) -> (s32[]) {
  %p = (s32[]) parameter(0)
  %ar = f32[2,64]{1,0} all-reduce(f32[2,64]{1,0} %x), to_apply=%add
  ROOT %t = (s32[]) tuple(%iv)
}

ENTRY %main () -> s32[] {
  %w = (s32[]) while((s32[]) %init), condition=%cond, body=%body
  %top = bf16[8]{0} all-gather(bf16[8]{0} %y), dimensions={0}
  %ars = (f32[16]{0}, f32[16]{0}) all-reduce-start(f32[16]{0} %z), to_apply=%add
  %ard = f32[16]{0} all-reduce-done((f32[16]{0}, f32[16]{0}) %ars)
  ROOT %r = s32[] get-tuple-element((s32[]) %w), index=0
}
"""
    ops = collective_costs(hlo)
    by_kind = {o.kind: [x for x in ops if x.kind == o.kind] for o in ops}
    # trip count resolved from the LT compare's constant OPERAND — the
    # folded constant(0) above it must not become a zero multiplier
    (ar_loop,) = [o for o in by_kind["all-reduce"] if o.multiplier > 1]
    assert ar_loop.multiplier == 24
    assert ar_loop.payload_bytes == 2 * 64 * 4
    assert ar_loop.bytes_per_step == 24 * 512
    (ag,) = by_kind["all-gather"]
    assert ag.multiplier == 1 and ag.payload_bytes == 8 * 2
    # async TPU form: the -start instruction counts ONCE at its largest
    # tuple component; the paired -done is not a second transfer
    starts = [o for o in by_kind["all-reduce"] if o.multiplier == 1]
    assert len(starts) == 1 and starts[0].payload_bytes == 16 * 4


def test_jxp007_undeclared_and_oversized_collective():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    fn, args = _toy_psum_target()
    e1, _ = _build_engine(1)
    at_rest = engine_at_rest(e1)
    target = [("toy.mp2.x", fn, args, {})]
    # undeclared: any collective traffic without a registry entry fails
    _, fs = audit_resources(target, at_rest, {})
    assert any(f.rule == "JXP007" and "undeclared" in f.message for f in fs)
    # declared but over budget fails with the measured total in the message
    _, fs = audit_resources(
        target, at_rest, {"collective_bytes_per_step": {"toy.mp2.x": 100}})
    assert any(f.rule == "JXP007" and "exceeds" in f.message for f in fs)
    # declared with headroom passes
    _, fs = audit_resources(
        target, at_rest, {"collective_bytes_per_step": {"toy.mp2.x": 1024}})
    assert [f for f in fs if f.rule == "JXP007"] == []


def test_jxp008_peak_budget_enforced():
    fn = jax.jit(lambda a, b: a @ b)
    args = (jnp.ones((4, 8), jnp.float32), jnp.ones((8, 16), jnp.float32))
    e1, _ = _build_engine(1)
    at_rest = engine_at_rest(e1)
    _, fs = audit_resources([("toy.mm", fn, args, {})], at_rest,
                            {"peak_hbm_bytes": {"mm": 10}},
                            compile_collectives=False)
    assert any(f.rule == "JXP008" for f in fs)
    _, fs = audit_resources([("toy.mm", fn, args, {})], at_rest,
                            {"peak_hbm_bytes": {"mm": 1 << 20}},
                            compile_collectives=False)
    assert fs == []


# ---------------------------------------------------------------------------
# the real serving set against the declared budget
# ---------------------------------------------------------------------------

def test_serving_resource_budget_clean():
    """The registry-declared SERVE_RESOURCE_BUDGET holds over the live
    serving executables at mp1 (and mp2 when the host has the chips):
    no oversized replicated buffer, no undeclared/oversized collective, no
    peak over budget — the CI gate `tools/tpu_cost.py --ci` enforces."""
    reports, findings = run_cost_checks(include_mp=True)
    assert findings == [], [f.format() for f in findings]
    rep1 = reports[1]
    # mp1 programs must be collective-free (single chip, nothing to talk to)
    for p in rep1["programs"]:
        assert p.get("collective_bytes_per_step", 0) == 0, p["name"]
    # the fused step's host-visible output stays O(B*K) ints: everything
    # except the donated pool alias is tiny
    fused = next(p for p in rep1["programs"] if "fused" in p["name"])
    assert fused["out_bytes"] - fused["alias_bytes"] < 1024
    if 2 in reports:
        # every declared communicating program exists in the mp pass whose
        # namespace it carries (serve.mp2.* under mp=2, serve.mp4.* under
        # mp=4) — a stale registry key fails here, an undeclared collective
        # fails JXP007 above
        names = {p["name"] for m, rep in reports.items() if m > 1
                 for p in rep["programs"]}
        declared = {k for k in SERVE_RESOURCE_BUDGET[
            "collective_bytes_per_step"]
            if int(k.split(".")[1][2:]) in reports}
        assert declared <= names


def test_engine_step_cost_traces_without_dispatch():
    """The bench hook costs the engine's own decode-side program abstractly:
    no compile, no dispatch at mp1 — program-count stats untouched."""
    eng, _ = _build_engine(1)
    before = eng.stats()["decode_executables"]
    c = engine_step_cost(eng)
    assert eng.stats()["decode_executables"] == before
    assert c.flops > 0 and c.peak_bytes > c.arg_bytes
    assert c.alias_bytes > 0            # the donated pool aliases out
    assert c.collectives is None        # single chip: compile skipped
    ms = c.predicted_ms(device_spec())
    assert 0 < ms < 1e3


def test_engine_step_cost_mp2_carries_collectives():
    """At mp>1 engine_step_cost compiles (with the engine's real
    shardings) so its roofline carries the same ICI term tpu_cost reports.
    The compile
    goes through lower(), outside the AOT dispatch cache, so the measured
    program counts stay exact."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    eng, _ = _build_engine(2)
    before = eng.stats()["decode_executables"]
    c = engine_step_cost(eng)
    assert eng.stats()["decode_executables"] == before
    assert c.collectives is not None and c.collective_bytes > 0
    # the ICI term must actually move the prediction
    spec = device_spec()
    no_coll = dataclasses_replace_collectives(c)
    assert c.predicted_ms(spec, mp=2) > no_coll.predicted_ms(spec, mp=2)


def dataclasses_replace_collectives(c):
    import dataclasses
    return dataclasses.replace(c, collectives=[])


# ---------------------------------------------------------------------------
# prefill_chunk="auto" + CLI exit codes
# ---------------------------------------------------------------------------

def test_auto_prefill_chunk_resolution_and_parity():
    """prefill_chunk='auto' picks spec_len+1 (one page when spec is off), so
    the fused program's width never exceeds what verify already needs — and
    greedy tokens are byte-identical to an explicit chunk and to bucketed
    mode."""
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models import gpt as gpt_mod

    cfg = gpt_mod.gpt_tiny(64)
    params = gpt_mod.init_params(cfg, jax.random.key(0))
    kw = dict(num_slots=2, page_size=8, max_model_len=64)
    auto = LLMEngine(params, cfg, prefill_chunk="auto", spec_len=4, **kw)
    assert auto.prefill_chunk == 5 and auto._fused_T == 5
    off = LLMEngine(params, cfg, prefill_chunk="auto", spec_len=0, **kw)
    assert off.prefill_chunk == 8       # one page

    def run(eng):
        rng = np.random.RandomState(7)
        for i in range(4):
            eng.add_request(rng.randint(0, cfg.vocab_size, (9 + 4 * i,))
                            .astype(np.int32), max_new_tokens=5)
        return {k: list(v.token_ids) for k, v in eng.run().items()}

    a = run(LLMEngine(params, cfg, prefill_chunk="auto", spec_len=2, **kw))
    b = run(LLMEngine(params, cfg, prefill_chunk=3, spec_len=2, **kw))
    c = run(LLMEngine(params, cfg, spec_len=2, **kw))
    assert a == b == c


def test_cli_ci_exit_codes(tmp_path):
    """--ci exits 0 against the declared budget and nonzero when an injected
    budget makes every program oversized (the subprocess proof that a budget
    regression cannot slide through CI)."""
    tool = os.path.join(REPO, "tools", "tpu_cost.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ok = subprocess.run(
        [sys.executable, tool, "--ci", "--no-mp", "--json"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=420)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    payload = json.loads(ok.stdout)
    assert payload["ok"] and payload["reports"]["mp1"]["programs"]
    bad = subprocess.run(
        [sys.executable, tool, "--ci", "--no-mp", "--peak-budget", "1"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=420)
    assert bad.returncode == 1
    assert "JXP008" in bad.stdout
