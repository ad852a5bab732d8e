"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the repo's two main paths once, through the entry points a
user calls, at the full width of GPT-3 1.3B (hidden 2048, 16 heads x 128,
vocab 50304, bf16) with random weights from a seed:

  device   JAX must report a TPU (anything else: exit != 0, no result line)
  kernels  every Pallas kernel on the path vs its XLA oracle, at the shapes
           the next phases use
  server   `LLMEngine` in its default mode, warmed, then requests on a
           schedule under `jax.transfer_guard("disallow")`; the same engine
           behind `EngineFleet(replicas=1)` + `ServingFrontend` over HTTP;
           `prefill_paged` logits vs `forward()`
  trainer  `HybridParallelTrainer` steps on one fixed batch, loss falling
  4 chips  (only where JAX reports >= 4) the trainer under dp2 x mp2 + SP and
           pp2 x mp2, `LLMEngine(mp=4)`, placement over four devices

It never sets a platform, never runs a kernel in interpret mode, catches
nothing (the first failing phase ends the run with a traceback and exit != 0)
and starts no other process.  The numbers it prints are set-up facts — it
ran, how long compiling took, how much HBM was touched — not performance
records.  Last stdout line on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The server runs before the trainer so that the cumulative `peak_bytes_in_use`
JAX reports still says something about each (kernels < server < trainer).

The phases are functions of the module constants below, so a scratch script
can drive them on the CPU at a tiny size while debugging (set the constants,
stub `program` and `hbm`, skip `kernels_phase`); the script itself has no CPU
mode.
"""
from __future__ import annotations

import http.client
import importlib.metadata
import json
import sys
import time

import numpy as np

SEED = 0
# trainer: bench.py's cell (batch 4 x 2048, bf16 params + moments, remat)
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 2048, 3
# server: the TPU branch of bench_serve.py
SLOTS, PAGE, MAX_LEN, SPEC_LEN = 32, 16, 1024, 4
# normalised max error (max|got - ref| / max|ref|) allowed against an XLA
# oracle in bf16 — the scale of tests/test_flash_attention.py's on-TPU test
FWD_TOL, GRAD_TOL = 3e-2, 6e-2

_cache_events = {"hits": 0, "misses": 0}


def say(phase: str, **facts) -> None:
    print(f"[{phase}] " + json.dumps(facts, sort_keys=True), flush=True)


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.isfinite(got).all(), "non-finite values out of the kernel"
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6))


def _gib(n_bytes) -> float:
    return round(n_bytes / 2**30, 3)


def hbm(dev) -> dict:
    """The allocator's view: live arrays now, and their high-water mark
    since the process started (cumulative over phases)."""
    st = dev.memory_stats()
    return {"hbm_in_use_gib": _gib(st["bytes_in_use"]),
            "hbm_peak_gib": _gib(st["peak_bytes_in_use"]),
            "hbm_limit_gib": _gib(st["bytes_limit"])}


def release(*trees) -> None:
    """Free device buffers now.  `del` is not enough for a trainer: its
    jitted step closes over it and JAX's function caches keep the step."""
    import jax
    for leaf in jax.tree_util.tree_leaves(trees):
        if isinstance(leaf, jax.Array):
            leaf.delete()


def program(compiled, what: str) -> dict:
    """The compiler's view of one executable, after checking that it holds a
    Mosaic kernel: argument / temporary bytes (outputs that alias a donated
    argument are inside `args`)."""
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{what}: no Mosaic custom call in the compiled program — the " \
        f"Pallas kernel was not used"
    ma = compiled.memory_analysis()
    return {"program_args_gib": _gib(ma.argument_size_in_bytes),
            "program_temp_gib": _gib(ma.temp_size_in_bytes),
            "program_out_not_aliased_gib": _gib(
                ma.output_size_in_bytes - ma.alias_size_in_bytes)}


def gpt3_1p3b_bf16():
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import gpt3_1p3b
    cfg = gpt3_1p3b()
    cfg.dtype = jnp.bfloat16
    return cfg


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_phase(cache_dir: str):
    import jax
    import jax.monitoring
    import jaxlib

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no accelerator (platform "
                 f"{dev.platform!r}, devices {jax.devices()}); this script "
                 f"only passes on a TPU")
    jax.monitoring.register_event_listener(_count_cache_event)
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()), jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=importlib.metadata.version("libtpu"),
        compile_cache_dir=cache_dir)
    return dev


def _count_cache_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _cache_events["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _cache_events["misses"] += 1


# ---------------------------------------------------------------------------
# kernels vs their XLA oracles
# ---------------------------------------------------------------------------

def kernels_phase(cfg, dev) -> None:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate.kernels import flash_attention as FA
    from paddle_tpu.incubate.kernels import paged_attention as PA

    H, hd = cfg.num_heads, cfg.head_dim
    bf16 = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.key(SEED), 32))
    scale = 1.0 / np.sqrt(hd)

    def rnd(*shape):
        return jax.random.normal(next(keys), shape, bf16)

    # flash forward + backward at the trainer's shape
    q, k, v, g = (rnd(TRAIN_BATCH, TRAIN_SEQ, H, hd) for _ in range(4))
    out_p, vjp_p = jax.vjp(
        lambda a, b, c: FA._flash_attention_core(a, b, c, True, scale),
        q, k, v)
    out_x, vjp_x = jax.vjp(
        lambda a, b, c: FA.attention_xla(a, b, c, None, True, scale), q, k, v)
    errs = {"out": rel_err(out_p, out_x)}
    for name, a, b in zip(("dq", "dk", "dv"), vjp_p(g), vjp_x(g)):
        errs[name] = rel_err(a, b)
    assert errs["out"] < FWD_TOL and \
        max(errs["dq"], errs["dk"], errs["dv"]) < GRAD_TOL, errs
    say("kernels", kernel="flash fwd+bwd",
        shape=[TRAIN_BATCH, TRAIN_SEQ, H, hd], rel_err=errs)
    del q, k, v, g, out_p, out_x, vjp_p, vjp_x

    # flash forward at the bucketed prefill's kernel-eligible widths
    for S in (128, MAX_LEN):
        q, k, v = (rnd(1, S, H, hd) for _ in range(3))
        e = rel_err(FA._flash_attention_core(q, k, v, True, scale),
                    FA.attention_xla(q, k, v, None, True, scale))
        assert e < FWD_TOL, (S, e)
        say("kernels", kernel="flash fwd (prefill bucket)",
            shape=[1, S, H, hd], rel_err=e)

    # the engine's pool and page tables: every slot owns distinct pages
    max_pages = MAX_LEN // PAGE
    n_pages = SLOTS * max_pages // 2 + 1
    k_pages, v_pages = rnd(n_pages, PAGE, H, hd), rnd(n_pages, PAGE, H, hd)
    rng = np.random.RandomState(SEED)

    def tables(B, lengths):
        tbl = np.zeros((B, max_pages), np.int32)
        free = rng.permutation(np.arange(1, n_pages))
        at = 0
        for b, n in enumerate(lengths):
            need = -(-int(n) // PAGE)
            tbl[b, :need] = free[at:at + need]
            at += need
        return jnp.asarray(tbl)

    # the fused step's attention at T = spec_len + 1, every slot in its own
    # mode (decode valid=1, verify valid=T, chunk valid in between), and the
    # bucketed engine's prefix-hit tail program at T = max_model_len, B=1
    for B, T in ((SLOTS, SPEC_LEN + 1), (1, MAX_LEN)):
        if B == 1:
            qoff = np.asarray([40], np.int32)       # a prefix hit of 40
            valid = np.asarray([MAX_LEN - 40 - 7], np.int32)
        else:
            valid = rng.randint(1, T + 1, size=B).astype(np.int32)
            valid[0], valid[1] = 1, T
            qoff = rng.randint(0, n_pages * PAGE // SLOTS - T,
                               size=B).astype(np.int32)
            qoff[2] = 0
        tbl = tables(B, qoff + valid)
        q = rnd(B, T, H, hd)
        args = (q, k_pages, v_pages, tbl, jnp.asarray(qoff),
                jnp.asarray(valid))
        got = np.asarray(PA.paged_prefill_attention(*args), np.float32)
        ref = np.asarray(PA.paged_prefill_attention_xla(*args), np.float32)
        # rows t >= valid are padding the scheduler never reads
        real = np.arange(T)[None, :] < valid[:, None]
        e = rel_err(got[real], ref[real])
        assert e < FWD_TOL, (B, T, e)
        say("kernels", kernel="paged serve/prefill", shape=[B, T, H, hd],
            rel_err=e)

    # the fused step's attention as the three serving cells run it (T = 1):
    # GPT-3, Mistral-7B and hybrid widths, ragged lengths in one batch - one
    # token, one page, a block's edge and one past it, a slot that fills its
    # whole table - and an inactive slot (null row, nothing valid), which
    # must come back as zeros
    for B, heads, kvh, entries in ((SLOTS, H, H, max_pages), (32, 32, 8, 128),
                                  (64, 32, 2, 128)):
        pool = B * entries // 2 + 1
        kp, vp = rnd(pool, PAGE, kvh, hd), rnd(pool, PAGE, kvh, hd)
        cap = entries * PAGE
        ln = rng.randint(1, cap // 3, size=B)
        ln[:6] = 1, PAGE, 256, 257, cap, 0
        need = -(-ln // PAGE)
        free, at = rng.permutation(np.arange(1, pool)), 0
        tbl = np.zeros((B, entries), np.int32)
        for b in range(B):
            tbl[b, :need[b]] = free[at:at + need[b]]
            at += need[b]
        valid = (ln > 0).astype(np.int32)
        args = (rnd(B, 1, heads, hd), kp, vp, jnp.asarray(tbl),
                jnp.asarray(ln - valid, jnp.int32), jnp.asarray(valid))
        got = np.asarray(PA.paged_prefill_attention(*args), np.float32)
        ref = np.asarray(PA.paged_prefill_attention_xla(*args), np.float32)
        e = rel_err(got[valid > 0], ref[valid > 0])
        assert e < FWD_TOL and not got[valid == 0].any(), (heads, kvh, e)
        say("kernels", kernel="paged serve/prefill", shape=[B, 1, heads, hd],
            kv_heads=kvh, table_entries=entries, rel_err=e)
    say("kernels", **hbm(dev))


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def _scaled(n: int) -> int:
    """A token count written for max_model_len 1024, at this MAX_LEN."""
    return max(1, n * MAX_LEN // 1024)


def _requests(vocab: int):
    """(arrival seconds, prompt, max_new_tokens) — mixed lengths over the
    bucket ladder; the last two share a prefix of two and a half pages, so
    the second admission takes the full-page share, the copy-on-write page
    copy and the prefix-hit tail program."""
    rng = np.random.RandomState(SEED + 7)

    def prompt(n):
        return rng.randint(0, vocab, (n,)).astype(np.int32)

    shared = prompt(2 * PAGE + PAGE // 2)
    mixed = [(0.00, 5, 24), (0.00, 100, 32), (0.05, 17, 16), (0.10, 333, 24),
             (0.15, 64, 40), (0.20, 900, 32), (0.25, 200, 16), (0.30, 31, 24)]
    reqs = [(t, prompt(_scaled(n)), _scaled(m)) for t, n, m in mixed]
    reqs.append((0.35, shared, _scaled(16)))
    reqs.append((1.50, np.concatenate([shared, prompt(PAGE // 2 + 2)]),
                 _scaled(16)))
    return reqs


def _warm(fleet, vocab: int) -> None:
    """Compile every executable the guarded loop can reach (the warm-up of
    bench_serve.py): one prompt per bucket, a prefix pair for the COW copy
    and the tail program, then the decode/swap programs."""
    eng = fleet.engines["engine0"]
    rng = np.random.RandomState(SEED + 1)
    max_prompt = MAX_LEN - 1
    for n in sorted({min(b, max_prompt) for b in eng.buckets}):
        eng.add_request(rng.randint(0, vocab, (n,)).astype(np.int32),
                        max_new_tokens=1)
    eng.run()
    lp = PAGE + PAGE // 2 + 1
    pair = rng.randint(0, vocab, (lp + 2,)).astype(np.int32)
    eng.add_request(pair[:lp], max_new_tokens=1)
    eng.run()
    eng.add_request(pair, max_new_tokens=1)
    eng.run()
    fleet.warm()
    eng.reset_counters()


def _fused_step_compiled(eng):
    """The engine's fused step compiled at its own shapes."""
    from paddle_tpu.analysis.cost_model import engine_step_target
    fn, args = engine_step_target(eng)
    return fn.lower(*args).compile()


def _executables(eng) -> int:
    st = eng.stats()
    return sum(st[k] for k in st if k.endswith("_executables"))


def server_phase(cfg, params, dev, mp=None):
    """The default engine, direct requests under the transfer guard.
    Returns (fleet, {rid: token ids})."""
    import jax

    from paddle_tpu.inference.router import EngineFleet

    tag = "server" if mp is None else f"server mp={mp}"
    t0 = time.perf_counter()
    fleet = EngineFleet(params, cfg, replicas=1, engine_kwargs=dict(
        num_slots=SLOTS, page_size=PAGE, max_model_len=MAX_LEN,
        spec_len=SPEC_LEN, mp=mp))
    eng = fleet.engines["engine0"]
    assert eng.double_buffer and eng.prefix_cache and not eng.chunked, \
        "not the default engine mode"
    _warm(fleet, cfg.vocab_size)
    say(tag, warmup_s=round(time.perf_counter() - t0, 1),
        buckets=eng.buckets, fused_T=eng._fused_T,
        tail_program_T=eng._chunk, executables=_executables(eng),
        pool_gib=round(eng.kv_pool_bytes() / 2**30, 3), **hbm(dev))

    pending = _requests(cfg.vocab_size)
    n_req = len(pending)
    n_exec = _executables(eng)
    done = []
    with jax.transfer_guard("disallow"):
        t0 = time.perf_counter()
        while pending or eng.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                _, prompt, max_new = pending.pop(0)
                eng.add_request(prompt, max_new_tokens=max_new)
            if eng.has_work:
                done.extend(eng.step())
            else:
                time.sleep(min(pending[0][0] - now, 0.01))
        elapsed = time.perf_counter() - t0
    assert len(done) == n_req, (len(done), n_req)
    for o in done:
        assert o.finish_reason in ("length", "stop"), \
            (o.request_id, o.finish_reason)
    eng.cache.check_invariants()
    st = eng.stats()
    assert _executables(eng) == n_exec, "a compile inside the warmed loop"
    assert st["cow_page_copies"] >= 1 and st["prefix_hit_requests"] >= 1 \
        and st["prefill_chunks"] >= 1, \
        "the shared-prefix request did not take the COW + tail route"
    say(tag, fused_step=program(_fused_step_compiled(eng),
                                "fused serve step"))
    say(tag, requests=n_req, elapsed_s=round(elapsed, 2),
        engine_steps=st["engine_steps"], decode_tokens=st["decode_tokens"],
        finish_reasons=sorted({o.finish_reason for o in done}),
        cow_page_copies=st["cow_page_copies"],
        prefix_hit_requests=st["prefix_hit_requests"],
        tail_chunks=st["prefill_chunks"],
        spec_accepted_tokens=st["spec_accepted_tokens"], **hbm(dev))
    return fleet, {o.request_id: list(o.token_ids) for o in done}


def http_phase(fleet) -> None:
    """Three POST /v1/completions through the front door, one streamed."""
    from paddle_tpu.inference.frontend import ServingFrontend

    rng = np.random.RandomState(SEED + 11)
    vocab = fleet.engines["engine0"].config.vocab_size
    asks = [(_scaled(12), _scaled(8), False),
            (_scaled(150), _scaled(12), True),
            (_scaled(60), _scaled(5), False)]
    fleet.start()
    door = ServingFrontend(fleet).start()
    try:
        for n_prompt, max_tokens, stream in asks:
            body = json.dumps({
                "prompt": rng.randint(0, vocab, (n_prompt,)).tolist(),
                "max_tokens": max_tokens, "stream": stream})
            conn = http.client.HTTPConnection("127.0.0.1", door.port,
                                              timeout=300)
            conn.request("POST", "/v1/completions", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read().decode("utf-8")
            conn.close()
            assert resp.status == 200, (resp.status, raw[:300])
            if stream:
                frames = [json.loads(l[len("data: "):])
                          for l in raw.splitlines()
                          if l.startswith("data: ") and l != "data: [DONE]"]
                assert raw.rstrip().endswith("data: [DONE]"), raw[-200:]
                streamed = sum(len(f["choices"][0]["token_ids"])
                               for f in frames[:-1])
                final = frames[-1]
                assert streamed == max_tokens, (streamed, max_tokens)
            else:
                final = json.loads(raw)
            usage = final["usage"]
            assert usage["prompt_tokens"] == n_prompt and \
                usage["completion_tokens"] == max_tokens, usage
            assert final["choices"][0]["finish_reason"] == "length", final
            say("http", status=resp.status, stream=stream, usage=usage)
        assert fleet.drain(timeout=60)
    finally:
        door.close()
        fleet.stop()
    fleet.check_invariants()


def logits_phase(cfg, params) -> None:
    """Numbers, not sampled tokens (bf16 argmax ties are common at random
    weights): one `prefill_paged` call against `forward()` at the same
    position.  The 100-token prompt pads to the 128 bucket, where the
    engine's flash kernel runs; `forward()` at 100 tokens takes the XLA
    attention — an independent reference."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import gpt as G

    n, bucket = 100, 128
    rng = np.random.RandomState(SEED + 3)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :n] = rng.randint(0, cfg.vocab_size, (n,))
    pool = G.init_paged_cache(cfg, bucket // PAGE + 1, PAGE)
    pages = jnp.arange(1, bucket // PAGE + 1, dtype=jnp.int32)[None, :]
    got, _ = jax.jit(lambda p, i, c, pg, ln: G.prefill_paged(
        p, i, cfg, c, pg, ln))(params, jnp.asarray(ids), pool, pages,
                               jnp.asarray([n], jnp.int32))
    ref = jax.jit(lambda p, i: G.forward(p, i, cfg))(
        params, jnp.asarray(ids[:, :n]))[:, n - 1]
    e = rel_err(got, ref)
    assert e < FWD_TOL, e
    say("server", check="prefill_paged logits vs forward()",
        shape=list(got.shape), rel_err=e)


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def trainer_phase(cfg, dev, mesh_cfg, tag: str, steps: int):
    """`steps` train steps on one fixed batch.  Returns (losses, trainer)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import HybridParallelTrainer

    t0 = time.perf_counter()
    trainer = HybridParallelTrainer(cfg, mesh_cfg, moment_dtype=jnp.bfloat16,
                                    seed=SEED)
    init_s = time.perf_counter() - t0
    rng = np.random.RandomState(SEED)
    tok = rng.randint(0, cfg.vocab_size,
                      (TRAIN_BATCH, TRAIN_SEQ)).astype(np.int32)
    lab = np.roll(tok, -1, axis=1).astype(np.int32)

    # the step's compiled text, from the abstract signature (the arrays are
    # donated by every step); this compile also fills the persistent cache
    # the first train_step then hits
    def like(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    t0 = time.perf_counter()
    compiled = trainer._step_fn.lower(
        jax.tree_util.tree_map(like, trainer.params),
        jax.tree_util.tree_map(like, trainer.opt_state),
        *map(like, trainer.shard_batch(tok, lab))).compile()
    compile_s = time.perf_counter() - t0
    step_program = program(compiled, f"{tag} train step")

    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = trainer.train_step(tok, lab)
        loss.block_until_ready()
        step_s.append(round(time.perf_counter() - t0, 3))
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    say(tag, batch=TRAIN_BATCH, seq=TRAIN_SEQ, layers=cfg.num_layers,
        init_s=round(init_s, 1), compile_s=round(compile_s, 1),
        step_s=step_s, losses=[round(l, 4) for l in losses],
        **step_program, **hbm(dev))
    return losses, trainer


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def placement(tree, tag: str, expect_fraction: float) -> None:
    """Where the state's bytes sit: four distinct devices, balanced, and no
    device holding more than its share (nothing piled on device 0)."""
    import jax

    per_dev, total = {}, 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + \
                sh.data.nbytes
    fractions = {d: round(b / total, 3) for d, b in sorted(per_dev.items())}
    say(tag, per_device_fraction_of_state=fractions,
        state_gib=round(total / 2**30, 2))
    assert len(per_dev) == 4, fractions
    assert max(per_dev.values()) <= 1.02 * min(per_dev.values()), fractions
    assert max(fractions.values()) <= expect_fraction + 0.05, \
        (fractions, expect_fraction)


def multichip_phase(cfg, dev, loss0_one_chip: float, tokens_one_chip) -> None:
    import jax

    from paddle_tpu.models import gpt as G
    from paddle_tpu.parallel import MeshConfig

    for tag, mesh_cfg, frac in (
            ("4chip dp2xmp2+sp", MeshConfig(dp=2, mp=2, remat=True,
                                            sequence_parallel=True), 0.5),
            # wte/lnf are not split over pp: a little above a quarter
            ("4chip pp2xmp2", MeshConfig(pp=2, mp=2, micro_batches=2,
                                         remat=True), 0.25)):
        losses, trainer = trainer_phase(cfg, dev, mesh_cfg, tag, steps=2)
        assert abs(losses[0] - loss0_one_chip) < 1e-2 * loss0_one_chip, \
            (tag, losses[0], loss0_one_chip)
        placement(trainer.params, tag + " params", frac)
        placement(trainer.opt_state["m"], tag + " moments", 0.25)
        release(trainer.params, trainer.opt_state)

    params = G.init_params(cfg, jax.random.key(SEED))
    fleet, tokens = server_phase(cfg, params, dev, mp=4)
    eng = fleet.engines["engine0"]
    placement(eng.params, "4chip engine mp=4 params", 0.25)
    placement(eng._pool, "4chip engine mp=4 pool", 0.25)
    same = sum(tokens[r] == tokens_one_chip[r] for r in tokens)
    say("4chip engine mp=4", requests_with_identical_tokens_to_one_chip=same,
        of=len(tokens))


# ---------------------------------------------------------------------------

def main() -> None:
    import jax

    from paddle_tpu.models import gpt as G
    from paddle_tpu.parallel import MeshConfig
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    t_start = time.perf_counter()
    dev = device_phase(enable_compile_cache())
    cfg = gpt3_1p3b_bf16()
    say("config", model="gpt3_1p3b", hidden=cfg.hidden_size,
        heads=cfg.num_heads, head_dim=cfg.head_dim, layers=cfg.num_layers,
        vocab=cfg.vocab_size, dtype="bfloat16", cut="nothing")

    kernels_phase(cfg, dev)

    params = G.init_params(cfg, jax.random.key(SEED))
    logits_phase(cfg, params)
    fleet, tokens = server_phase(cfg, params, dev)
    http_phase(fleet)
    release(params, fleet.engines["engine0"]._pool)
    del fleet

    losses, trainer = trainer_phase(cfg, dev, MeshConfig(remat=True),
                                    "trainer", TRAIN_STEPS)
    release(trainer.params, trainer.opt_state)

    n_dev = jax.device_count()
    if n_dev >= 4:
        multichip_phase(cfg, dev, losses[0], tokens)
        say("multichip", ran=True, devices=n_dev)
    else:
        say("multichip", multichip=f"not run, {n_dev} device(s)")

    say("compile_cache", persistent_cache_hits=_cache_events["hits"],
        persistent_cache_misses=_cache_events["misses"])
    say("done", total_s=round(time.perf_counter() - t_start, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
