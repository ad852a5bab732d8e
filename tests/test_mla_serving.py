"""A latent-attention (DeepSeek-V3 / Xing4.0 shaped) configuration through
the serving engine: latent pages, the absorbed form against the expanded
reference (`benchmarks/reference/xing4.py`, which imports nothing of the
program), YaRN tables, the four-stream residual and its Sinkhorn, gated
experts and their shares, the prefix index and the spill tier on latent
pages, and what stays refused.  Tiny widths, seeded float32 weights, CPU."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import xing4 as ref
from paddle_tpu.incubate.distributed.models.moe.dropless import moe_dropless, route
from paddle_tpu.incubate.kernels import paged_attention as PA
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models import gpt, hybrid
from paddle_tpu.quantization.serving import kv_page_bytes

F32 = jnp.float32


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def model_dict(c):
    """The reference's `model` dict of a program configuration."""
    return dict(
        hidden_size=c.hidden_size, vocab_size=c.vocab_size,
        mixer_pattern=c.layer_pattern, num_attention_heads=c.num_heads,
        q_lora_rank=c.q_lora_rank, kv_lora_rank=c.kv_lora_rank,
        qk_nope_head_dim=c.qk_nope_head_dim,
        qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
        rope_theta=c.rope_theta, rope_scaling=dict(c.rope_scaling),
        rms_norm_eps=c.rms_norm_eps, hc_mult=c.hc_mult,
        hc_sinkhorn_iters=c.hc_sinkhorn_iters, hc_eps=c.hc_eps,
        mhc_h_res_clamp_min=c.hc_res_clamp[0],
        mhc_h_res_clamp_max=c.hc_res_clamp[1],
        n_routed_experts=c.experts_here, router_experts=c.n_routed_experts,
        expert_offset=c.expert_offset,
        num_experts_per_tok=c.num_experts_per_tok,
        routed_scaling_factor=c.routed_scaling_factor,
        norm_topk_prob=c.norm_topk_prob)


def tiny(pattern="LFLELE", **kw):
    kw = dict(dict(n_routed_experts=8, experts_here=4, num_experts_per_tok=3,
                   routed_scaling_factor=2.0), **kw)
    return hybrid.latent_tiny(seq_len=256, pattern=pattern, **kw)


def setup(pattern="LFLELE", seed=1, **kw):
    return _setup(pattern, seed, tuple(sorted(kw.items())))


@functools.lru_cache(maxsize=None)
def _setup(pattern, seed, kw):
    cfg = tiny(pattern, **dict(kw))
    return cfg, hybrid.init_params(cfg, jax.random.key(seed)), model_dict(cfg)


def served_gap(params, model, out):
    """Widest gap by which a served token's reference logit lies below the
    reference's best, prompt plus served tokens teacher-forced."""
    n = len(out.token_ids)
    seq = np.concatenate([out.prompt, np.asarray(out.token_ids[:-1], np.int32)])
    toks = np.zeros((1, 128), np.int32)
    toks[0, :seq.size] = seq
    cols = np.arange(out.prompt.size - 1, out.prompt.size - 1 + n)
    lg = np.asarray(ref.logits_at(params, toks, np.zeros(n, int), cols,
                                  model))
    return float((lg.max(-1) - lg[np.arange(n), out.token_ids]).max())


def run_engine(cfg, params, prompts, new=10, **kw):
    kw = dict(dict(num_slots=2, page_size=8, max_model_len=256), **kw)
    eng = LLMEngine(params, cfg, **kw)
    for p in prompts:
        eng.add_request(p, max_new_tokens=new)
    outs = eng.run()
    eng.cache.check_invariants()
    return eng, outs


def prompts_of(lengths):
    r = np.random.default_rng(sum(lengths))
    return [r.integers(0, 256, n, dtype=np.int32) for n in lengths]


# ---- the pool ----------------------------------------------------------------

def test_the_pool_is_one_latent_lane_and_page_bytes_say_so():
    """A token's row holds kv_lora_rank + qk_rope_head_dim numbers (40 here,
    576 at the published widths) in one lane of whole 128-lane tiles; no key
    or value lane exists, and a page's bytes are the lane's."""
    cfg, _, _ = setup()
    assert (cfg.latent_row, cfg.latent_lane) == (32 + 8, 128)
    pool = hybrid.init_paged_cache(cfg, num_pages=9, page_size=8, num_slots=2)
    assert {n: a.shape for n, a in pool.items()} == {"c": (3, 9, 8, 128)}
    assert kv_page_bytes(cfg, 8) == 3 * 8 * 128 * 4 == cfg.page_bytes(8)
    real = hybrid.HybridConfig(
        layer_pattern="LE", num_layers=2, kv_lora_rank=512,
        qk_rope_head_dim=64, dtype=jnp.bfloat16)
    assert (real.latent_row, real.latent_lane) == (576, 640)
    assert kv_page_bytes(real, 64) == 64 * 640 * 2
    eng = LLMEngine(setup()[1], cfg, num_slots=2, page_size=8,
                    max_model_len=64)
    st = eng.stats()
    assert st["latent_page_bytes"] == eng._kv_page_bytes == 3 * 8 * 128 * 4
    assert eng.kv_pool_bytes() == eng.cache.num_pages * st["latent_page_bytes"]
    # a pattern with both kinds of attention keeps both kinds of lane
    both = tiny("L*")
    assert set(both.paged_lanes()) == {"k", "v", "c"}
    assert kv_page_bytes(both, 8) == 8 * 4 * (128 + 2 * 4 * both.head_dim)


def test_a_quantized_page_pool_is_refused_for_a_pattern():
    with pytest.raises(ValueError, match="no quantized page pool"):
        kv_page_bytes(tiny(), 8, "int8")


# ---- the layer kinds against the reference -----------------------------------

def test_yarn_tables_are_the_references():
    cfg, _, model = setup()
    R = cfg.qk_rope_head_dim
    inv = gpt.yarn_inv_freq(R, cfg.rope_theta, cfg.rope_scaling)
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(
        R, model["rope_theta"], model["rope_scaling"]), rtol=1e-6)
    # the ramp is inside the table: neither plain rotary nor all divided
    plain = gpt.yarn_inv_freq(R, cfg.rope_theta, None)
    assert np.any(np.asarray(inv) < np.asarray(plain) * 0.999)
    assert np.any(np.asarray(inv) > np.asarray(plain) / 4.0 * 1.001)
    assert gpt.yarn_softmax_scale(24, cfg.rope_scaling) == pytest.approx(
        ref.softmax_scale(model))
    # published widths: 64 rope columns, factor 64 over 4096
    sc = dict(factor=64, original_max_position_embeddings=4096, beta_fast=32,
              beta_slow=1, mscale=1, mscale_all_dim=1)
    np.testing.assert_allclose(gpt.yarn_inv_freq(64, 10000.0, sc),
                               ref.yarn_inv_freq(64, 10000.0, sc), rtol=1e-6)
    pos = jnp.asarray([[0, 5, 300]], jnp.int32)
    sin, cos = gpt.yarn_rope_tables_at(R, cfg.rope_theta, cfg.rope_scaling,
                                       pos)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 301, R)), F32)
    want = ref.rotate(x, model)[:, np.asarray(pos[0])]
    from paddle_tpu.incubate.kernels.rope import apply_rope
    got = apply_rope(x[:, np.asarray(pos[0])][:, :, None], sin, cos)[:, :, 0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("S", [5, 24])
def test_absorbed_attention_is_the_expanded_reference(S):
    """The program's latent layer (rows written to a page pool, the absorbed
    product through the page table) against the reference's expanded
    attention on the same weights."""
    cfg, params, model = setup()
    lp = params["layers"][0]
    h = jnp.asarray(np.random.default_rng(S).normal(size=(1, S, 64)), F32)
    want = ref.latent_attention({k: v.astype(F32) for k, v in lp.items()},
                                h, model)
    pos = jnp.arange(S)[None]
    q_nope, q_rope, row = hybrid.latent_qkv(lp, h, pos, cfg)
    assert row.shape == (1, S, 128)
    assert not np.asarray(row[..., cfg.latent_row:]).any()
    pool = jnp.zeros((5, 8, 128), F32).at[
        1 + np.arange(S) // 8, np.arange(S) % 8].set(row[0])
    got = hybrid.latent_attention(
        lp, q_nope, q_rope, pool, jnp.asarray([[1, 2, 3, 0]], jnp.int32),
        jnp.asarray([0]), jnp.asarray([S]), cfg)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-5)


@pytest.mark.parametrize("case,B,T,offsets,valid", [
    ("decode", 3, 1, [5, 17, 0], [1, 1, 0]),
    ("chunk_and_verify", 2, 5, [8, 3], [5, 2]),
    ("prefill_tiles", 1, 40, [0], [33]),
])
def test_the_latent_kernel_is_its_oracle(case, B, T, offsets, valid):
    """The Pallas kernel, interpreted, against the gather oracle, with NaN
    in every page a slot does not own."""
    r = np.random.default_rng(len(case))
    H, W, latent, page, n_pages = 8, 256, 128, 16, 4
    q = jnp.asarray(r.normal(size=(B, T, H, W)), F32)
    q = q.at[..., 160:].set(0.0)
    pool = np.full((1 + B * n_pages, page, W), np.nan, np.float32)
    table = np.zeros((B, n_pages), np.int32)
    for b in range(B):
        if valid[b]:
            table[b] = 1 + b * n_pages + np.arange(n_pages)
            pool[table[b]] = r.normal(size=(n_pages, page, W))
            pool[table[b], :, 160:] = 0.0
    args = (q, jnp.asarray(pool), jnp.asarray(table),
            jnp.asarray(offsets, jnp.int32), jnp.asarray(valid, jnp.int32))
    got = PA.paged_latent_attention_pallas(*args, latent=latent, scale=0.07,
                                           interpret=True)
    clean = jnp.nan_to_num(jnp.asarray(pool))
    want = PA.paged_latent_attention_xla(q, clean, *args[2:], latent=latent,
                                         scale=0.07)
    for b in range(B):
        np.testing.assert_allclose(got[b, :valid[b]], want[b, :valid[b]],
                                   rtol=2e-3, atol=2e-5)
        assert np.isfinite(np.asarray(got[b])).all()


@pytest.mark.parametrize("held,offset", [(8, 0), (4, 0), (4, 4), (2, 3)])
def test_gated_expert_layer_is_a_per_token_loop(held, offset):
    cfg, params, model = setup("E", experts_here=held, expert_offset=offset)
    lp = params["layers"][0]
    assert "gate_w" in lp and "shared_gate_w" in lp
    h = np.random.default_rng(held).normal(size=(24, 64)).astype(np.float32)
    y, ctr = moe_dropless(lp, jnp.asarray(h), cfg, jnp.ones((24,), bool))
    idx, w = (np.asarray(a) for a in route(jnp.asarray(h), lp, cfg))
    p = {k: np.asarray(v, np.float64) for k, v in lp.items()}

    def silu(x):
        return x / (1.0 + np.exp(-x))

    want = np.zeros((24, 64))
    for t in range(24):
        x = h[t].astype(np.float64)
        want[t] = (silu(x @ p["shared_gate_w"]) * (x @ p["shared_up_w"])) \
            @ p["shared_down_w"]
        for e, w_e in zip(idx[t], w[t]):
            if offset <= e < offset + held:
                j = e - offset
                a = silu(p["gate_w"][j] @ x) * (p["up_w"][j] @ x)
                want[t] += w_e * (a @ p["down_w"][j])
    np.testing.assert_allclose(y, want, rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(
        y, ref.gated_experts(lp, jnp.asarray(h)[None], model)[0],
        rtol=1e-3, atol=1e-5)
    here = (idx >= offset) & (idx < offset + held)
    assert int(ctr["moe_pairs_here"]) == here.sum()
    assert int(ctr["moe_pairs_away"]) == 24 * 3 - here.sum()


def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Experts 0-1, 2-3, 4-5, 6-7 on four chips: what the four compute, the
    shared expert counted once, is the uncut reference layer."""
    cfg, params, model = setup("E", experts_here=8)
    lp = params["layers"][0]
    h = jnp.asarray(np.random.default_rng(9).normal(size=(40, 64)), F32)
    real = jnp.ones((40,), bool)
    parts = []
    for offset in (0, 2, 4, 6):
        share = tiny("E", experts_here=2, expert_offset=offset)
        lp_share = dict(lp, **{n: lp[n][offset:offset + 2]
                               for n in ("gate_w", "up_w", "down_w")})
        parts.append(moe_dropless(lp_share, h, share, real)[0])
    nobody = dict(lp, **{n: lp[n][:0] for n in ("gate_w", "up_w", "down_w")})
    shared = ref.gated_experts(nobody, h[None], model)[0]
    uncut = ref.gated_experts(lp, h[None], dict(model, n_routed_experts=8))[0]
    np.testing.assert_allclose(sum(parts) - 3 * shared, uncut,
                               rtol=1e-3, atol=1e-5)
    assert float(jnp.abs(parts[0] - uncut).max()) > 1e-3


def test_the_write_back_matrix_is_doubly_stochastic_after_20_rounds():
    cfg, params, model = setup()
    X = jnp.asarray(np.random.default_rng(3).normal(size=(2, 7, 4, 64)), F32)
    for lp in params["layers"]:
        pre, post, res = hybrid.mhc_mixes(lp, X, cfg)
        assert float(jnp.abs(res.sum(-1) - 1).max()) < 1e-4
        assert float(jnp.abs(res.sum(-2) - 1).max()) < 1e-4
        assert 0 < float(pre.min()) and float(pre.max()) < 1
        assert 0 < float(post.min()) and float(post.max()) < 2
        # neither near the identity nor near uniform: the comparison below
        # can tell the Sinkhorn from its absence
        diag = np.asarray(jnp.diagonal(res, axis1=-2, axis2=-1))
        assert 0.3 < diag.mean() < 0.95
        want = ref.stream_mixes({k: v.astype(F32) for k, v in lp.items()}, X,
                                model)
        for got, w in zip((pre, post, res), want):
            np.testing.assert_allclose(got, w, rtol=1e-4, atol=1e-6)
    one_round = hybrid.mhc_mixes(
        params["layers"][0], X, tiny(hc_sinkhorn_iters=1))[2]
    assert float(jnp.abs(one_round.sum(-1) - 1).max()) > 1e-3


# ---- through the engine ------------------------------------------------------

@pytest.mark.parametrize("case,lengths,kw", [
    ("bucket_padding", [5], {}),
    ("longer_than_a_page_block", [70], {}),
    ("two_lengths_one_batch", [9, 70], {}),
    ("slot_reused_by_a_second_request", [21, 6], dict(num_slots=1)),
    ("queue_behind_the_slots", [5, 37, 70, 3], {}),
    ("chunked_prefill", [5, 37, 70, 3], dict(prefill_chunk=8)),
])
def test_prefill_then_decode_through_latent_pages_is_the_reference(
        case, lengths, kw):
    cfg, params, model = setup()
    eng, outs = run_engine(cfg, params, prompts_of(lengths), **kw)
    assert not eng.recurrent and eng.patterned and eng.cache.state is None
    assert eng.prefix_cache and eng.kv_tier and eng.fused and eng.double_buffer
    assert len(outs) == len(lengths)
    for out in outs.values():
        assert out.finish_reason == "length" and len(out.token_ids) == 10
        assert served_gap(params, model, out) < 1e-4
    st = eng.stats()
    assert st["ssm_slots_live"] == st["ssm_state_resets"] == 0
    assert st["mla_absorbed_rows"] == sum(lengths) + 9 * len(lengths)
    assert st["prefix_lookups_skipped_no_state"] == 0


def program_logits(cfg, params, toks, n_prompt, page=8):
    """Logits [len(toks) - n_prompt + 1, V] of the two passes called
    directly: the bucketed prefill of toks[:n_prompt], then one fused-shape
    step a token (teacher-forced) through the latent pages."""
    n_pages = -(-toks.size // page)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
    pool = hybrid.init_paged_cache(cfg, n_pages + 1, page, 1)
    bucket = -(-n_prompt // page) * page
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :n_prompt] = toks[:n_prompt]
    lg, pool, _ = jax.jit(lambda ids, pool: hybrid.prefill_paged(
        params, ids, cfg, pool, table[:, :bucket // page],
        jnp.asarray([n_prompt]), jnp.asarray([0])))(jnp.asarray(ids), pool)
    step = jax.jit(lambda tok, pool, off: hybrid.prefill_chunk_paged(
        params, tok, cfg, pool, table, off, jnp.asarray([1])))
    out = [lg[0]]
    for t in range(n_prompt, toks.size):
        lg, pool, _ = step(jnp.asarray(toks[None, t:t + 1]), pool,
                           jnp.asarray([t], jnp.int32))
        out.append(lg[0])
    return np.stack(out)


@pytest.mark.parametrize("iters,sound", [(20, True), (1, False)])
def test_logits_through_latent_pages_and_the_sinkhorn_in_them(iters, sound):
    """Prefill then decode through latent pages gives the reference's full
    forward pass on LOGITS; the same weights with one Sinkhorn round instead
    of twenty miss it by orders more than the tolerance."""
    _, params, model = setup()
    cfg = tiny(hc_sinkhorn_iters=iters)
    toks = prompts_of([30])[0]
    got = program_logits(cfg, params, toks, n_prompt=21)
    padded = np.zeros((1, 128), np.int32)
    padded[0, :30] = toks
    cols = np.arange(20, 30)
    want = np.asarray(ref.logits_at(params, padded, np.zeros(10, int), cols,
                                    model))
    # widest difference over the logits' own spread: 1.5e-6 with twenty
    # rounds, 6e-3 with one
    gap = np.abs(got - want).max() / want.std()
    assert (gap < 1e-4) == sound, gap
    assert sound or gap > 1e-3


def test_a_repeated_prompt_hits_the_prefix_index_on_latent_pages():
    """The second request maps the first one's latent pages (full pages of
    its prompt) and prefills only the tail through the chunk program; it is
    served the same tokens, and both are the reference's."""
    cfg, params, model = setup()
    prompt = prompts_of([43])[0]
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=256)
    a = eng.add_request(prompt, max_new_tokens=8)
    outs = eng.run()
    before = eng.stats()
    b = eng.add_request(prompt, max_new_tokens=8)
    outs.update(eng.run())
    st = eng.stats()
    assert st["prefix_hit_requests"] == before["prefix_hit_requests"] + 1
    assert st["prefix_cached_tokens"] - before["prefix_cached_tokens"] >= 40
    assert st["prefilled_tokens"] - before["prefilled_tokens"] <= 3
    assert outs[a].token_ids == outs[b].token_ids
    assert served_gap(params, model, outs[b]) < 1e-4
    eng.cache.check_invariants()


def test_spilled_latent_pages_restore_bit_exact():
    """A session's pages are evicted by churn, spill to the host tier and
    come back with one scatter: the tier holds the pool's bytes, the pool
    holds them again after the restore, and the tokens are those of an
    engine that never tiers."""
    cfg, params, model = setup()
    rng = np.random.RandomState(7)
    shared = rng.randint(0, 256, (20,)).astype(np.int32)
    churn = [rng.randint(0, 256, (30,)).astype(np.int32) for _ in range(6)]
    tail = rng.randint(0, 256, (4,)).astype(np.int32)

    def session(eng):
        r1 = eng.add_request(shared, max_new_tokens=5)
        outs = eng.run()
        pool = jax.device_get(eng._pool)
        held = {nid: {lane: a[:, node.page].copy()
                      for lane, a in pool.items()}
                for nid, node in eng.cache._lru.items()}
        for p in churn:
            eng.add_request(p, max_new_tokens=4)
        outs.update(eng.run())
        eng.drain()
        turn2 = np.concatenate([shared, np.asarray(outs[r1].token_ids,
                                                   np.int32), tail])
        parked = dict(eng.cache._tier._host) if eng.kv_tier else {}
        r2 = eng.add_request(turn2, max_new_tokens=5)
        outs.update(eng.run())
        return outs, outs[r2], held, parked

    kw = dict(num_slots=2, page_size=8, num_pages=9, max_model_len=64,
              prefill_chunk=16, swap_pool_pages=64)
    eng = LLMEngine(params, cfg, **kw)
    outs, ret, held, parked = session(eng)
    st = eng.stats()
    assert st["kv_tier"]["spills"] > 0 and st["kv_tier"]["restores"] >= 1
    assert st["kv_tier"]["restored_tokens"] >= 16
    mine = [nid for nid in parked if nid in held]
    assert mine
    for nid in mine:
        assert set(parked[nid]) == {"c"}
        np.testing.assert_array_equal(parked[nid]["c"], held[nid]["c"])
    base = LLMEngine(params, cfg, **dict(kw, kv_tier=False))
    base_outs, base_ret, _, _ = session(base)
    assert ret.token_ids == base_ret.token_ids
    assert [outs[r].token_ids for r in sorted(outs)] == \
        [base_outs[r].token_ids for r in sorted(base_outs)]
    assert st["prefilled_tokens"] < base.stats()["prefilled_tokens"]
    assert served_gap(params, model, ret) < 1e-4
    eng.cache.check_invariants()


def test_swap_preemption_moves_latent_pages():
    """Optimistic admission on a pool too small for both: the victim's
    latent pages are swapped out and back, and both finish as the reference
    says."""
    cfg, params, model = setup()
    eng, outs = run_engine(cfg, params, prompts_of([30, 28]), new=40,
                           num_pages=10, admission="optimistic",
                           preempt="swap")
    st = eng.stats()
    assert st["preemptions"] >= 1 and st["preempt_swaps"] >= 1
    for out in outs.values():
        assert len(out.token_ids) == 40
        assert served_gap(params, model, out) < 1e-4


def test_counters_reach_stats_metrics_and_the_ring():
    cfg, params, _ = setup()
    eng, _ = run_engine(cfg, params, prompts_of([12, 20]), new=6)
    st = eng.stats()
    # 12 + 20 prompt rows, then 5 steps of two slots at lengths 12+i, 20+i
    assert st["mla_absorbed_rows"] == 32 + 10
    assert st["latent_tokens_written"] == 32 + sum(
        12 + i + 1 + 20 + i + 1 for i in range(5))
    assert st["moe_pairs_here"] + st["moe_pairs_away"] == \
        2 * 3 * st["mla_absorbed_rows"]
    text = eng.metrics.to_prometheus()
    for name in ("latent_tokens_written", "mla_absorbed_rows",
                 "latent_page_bytes"):
        assert f"llm_engine_{name}" in text, name
    ring = eng.step_trace()
    assert sum(r["latent_tokens_written"] for r in ring) == \
        st["latent_tokens_written"]


@pytest.mark.parametrize("kw,says", [
    (dict(spec_len=2), "speculative decoding"),
    (dict(weight_dtype="int8"), "no quantized serving path"),
    (dict(kv_dtype="int8"), "no quantized serving path"),
    (dict(mp=2), "one chip"),
])
def test_what_a_latent_configuration_cannot_be_served_with_is_refused(
        kw, says):
    cfg, params, _ = setup()
    with pytest.raises(ValueError, match=says):
        LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                  **kw)


def test_only_a_pattern_with_recurrent_state_is_recurrent():
    cfg, params, _ = setup()
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64)
    assert eng.patterned and not eng.recurrent and eng.prefix_cache
    mixed = hybrid.hybrid_tiny(pattern="M*")
    eng = LLMEngine(hybrid.init_params(mixed, jax.random.key(0)), mixed,
                    num_slots=2, page_size=8, max_model_len=64)
    assert eng.patterned and eng.recurrent and not eng.prefix_cache
    dense = gpt.gpt_tiny(64)
    eng = LLMEngine(gpt.init_params(dense, jax.random.key(0)), dense,
                    num_slots=2, page_size=8, max_model_len=64)
    assert not eng.patterned and not eng.recurrent


# ---- launch-ahead scheduling over latent pages -------------------------------

from conftest import AHEAD_CASES, ahead_parity_case     # noqa: E402


@pytest.mark.parametrize("case", sorted(
    set(AHEAD_CASES) - {"spec3"}))          # refused for a pattern
def test_launch_ahead_serves_the_synchronous_schedules_tokens(case):
    """The xing4-tiny pattern under `double_buffer=True` against the
    synchronous schedule: a stray lane's latent row lands past what the
    slot's pages publish to the prefix index."""
    cfg, params, _ = setup()

    eng, outs = ahead_parity_case(cfg, params, case, 256)
    st = eng.stats()
    assert st["fused_launched_ahead"] > 0
    if case == "steady_full_batch":
        assert st["fused_launched_ahead"] >= 0.9 * st["decode_iterations"]
    if case in ("eos_mid_batch", "chunked_eos", "deadline_in_flight"):
        assert st["fused_ahead_discarded_lanes"] >= 1
