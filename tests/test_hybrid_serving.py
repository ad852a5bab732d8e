"""A patterned (Nemotron-H style) configuration through the serving engine:
each layer kind against the plain reference (`benchmarks/reference/
nemotron_h.py`, which imports nothing of the program), the chunked scan
against the recurrence, prefill then decode through `LLMEngine` against the
reference's full forward pass, the expert layer's shares adding up to the
uncut layer, and every refusal.  Tiny widths, seeded float32 weights, CPU."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h as ref
from paddle_tpu.incubate.distributed.models.moe.dropless import moe_dropless, route
from paddle_tpu.incubate.kernels.grouped_matmul import grouped_matmul
from paddle_tpu.incubate.kernels.ssm import ssm_chunk_scan, ssm_update
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models import gpt, hybrid

F32 = jnp.float32


@pytest.fixture(autouse=True)
def highest_precision():
    """Program and reference compared at the same matmul precision (a
    context, not a global setting: every worker imports this file)."""
    with jax.default_matmul_precision("highest"):
        yield


def model_dict(c):
    """The reference's `model` dict of a program configuration."""
    return dict(
        hidden_size=c.hidden_size, vocab_size=c.vocab_size,
        hybrid_override_pattern=c.layer_pattern,
        num_attention_heads=c.num_heads, num_key_value_heads=c.kv_heads,
        head_dim=c.head_dim, mamba_num_heads=c.mamba_num_heads,
        mamba_head_dim=c.mamba_head_dim, ssm_state_size=c.ssm_state_size,
        n_groups=c.mamba_n_groups, conv_kernel=c.conv_kernel,
        n_routed_experts=c.experts_here, router_experts=c.n_routed_experts,
        expert_offset=c.expert_offset,
        num_experts_per_tok=c.num_experts_per_tok,
        routed_scaling_factor=c.routed_scaling_factor,
        norm_topk_prob=c.norm_topk_prob, norm_eps=c.rms_norm_eps)


def tiny(pattern="MEM*EM*", **kw):
    kw = dict(dict(n_routed_experts=8, experts_here=4, num_experts_per_tok=3,
                   routed_scaling_factor=2.5, chunk_size=16), **kw)
    return hybrid.hybrid_tiny(seq_len=256, pattern=pattern, **kw)


def setup(pattern="MEM*EM*", seed=1, **kw):
    return _setup(pattern, seed, tuple(sorted(kw.items())))


@functools.lru_cache(maxsize=None)
def _setup(pattern, seed, kw):
    cfg = tiny(pattern, **dict(kw))
    return cfg, hybrid.init_params(cfg, jax.random.key(seed)), model_dict(cfg)


@functools.lru_cache(maxsize=None)
def mixer(cfg_key):
    """`hybrid.mamba_mixer` jitted once per configuration (eagerly it is a
    hundred small programs)."""
    cfg = _CFGS[cfg_key]
    return jax.jit(lambda lp, h, conv, ssm, valid:
                   hybrid.mamba_mixer(lp, h, conv, ssm, valid, cfg))


_CFGS = {}


def mamba(cfg, lp, h, conv, ssm, valid):
    _CFGS[id(cfg)] = cfg
    return mixer(id(cfg))(lp, h, conv, ssm, jnp.asarray(valid, jnp.int32))


def served_gap(params, model, out):
    """Widest gap by which a served token's reference logit lies below the
    reference's best, prompt plus served tokens teacher-forced."""
    n = len(out.token_ids)
    seq = np.concatenate([out.prompt, np.asarray(out.token_ids[:-1], np.int32)])
    # right-padded to one width (every mixer is causal), so that the
    # reference's layers compile once and not once per length
    toks = np.zeros((1, 128), np.int32)
    toks[0, :seq.size] = seq
    cols = np.arange(out.prompt.size - 1, out.prompt.size - 1 + n)
    lg = np.asarray(ref.logits_at(params, toks, np.zeros(n, int), cols,
                                  model))
    return float((lg.max(-1) - lg[np.arange(n), out.token_ids]).max())


# ---- the scan and the layer kinds ------------------------------------------

def scan_inputs(B, T, H=4, P=8, G=2, N=16, seed=0):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(B, T, H, P)), F32)
    dt = jnp.asarray(r.uniform(0.001, 0.3, size=(B, T, H)), F32)
    A = -jnp.asarray(r.uniform(1, 16, size=(H,)), F32)
    Bm = jnp.asarray(r.normal(size=(B, T, G, N)), F32)
    Cm = jnp.asarray(r.normal(size=(B, T, G, N)), F32)
    s0 = jnp.asarray(r.normal(size=(B, H, P, N)), F32)
    return x, dt, A, Bm, Cm, s0


def recurrence(x, dt, A, Bm, Cm, s):
    ys = []
    for t in range(x.shape[1]):
        y, s = ssm_update(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], s)
        ys.append(y)
    return jnp.stack(ys, axis=1), s


@pytest.mark.parametrize("T,chunk", [(1, 16), (7, 16), (16, 16), (17, 16),
                                     (45, 16), (128, 128), (131, 128),
                                     (300, 128)])
def test_chunked_scan_equals_the_recurrence(T, chunk):
    args = scan_inputs(2, T)
    y, s = ssm_chunk_scan(*args, chunk=chunk)
    y_ref, s_ref = recurrence(*args)
    np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, s_ref, rtol=2e-4, atol=2e-4)


def test_zero_dt_positions_do_not_move_the_state():
    x, dt, A, Bm, Cm, s0 = scan_inputs(2, 20)
    dt = dt.at[:, 12:].set(0.0)
    _, s = ssm_chunk_scan(x, dt, A, Bm, Cm, s0, chunk=16)
    _, s_ref = recurrence(x[:, :12], dt[:, :12], A, Bm[:, :12], Cm[:, :12], s0)
    np.testing.assert_allclose(s, s_ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("T", [5, 16, 37])
def test_mamba_layer_matches_the_reference(T):
    cfg, params, model = setup("M")
    lp = params["layers"][0]
    h = jnp.asarray(np.random.default_rng(T).normal(size=(2, T, 64)), F32)
    conv0 = jnp.zeros((2, 3, cfg.conv_dim), F32)
    ssm0 = jnp.zeros((2, cfg.mamba_num_heads, cfg.mamba_head_dim,
                      cfg.ssm_state_size), F32)
    y, _, _ = mamba(cfg, lp, h, conv0, ssm0, [T, T])
    want = ref.mamba_mixer({k: v.astype(F32) for k, v in lp.items()}, h, model)
    np.testing.assert_allclose(y, want, rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("valid", [1, 2, 9, 20])
def test_mamba_padding_touches_neither_state(valid):
    """Bucket padding: the state handed on is that after the last REAL
    position, and the window holds the last three REAL inputs."""
    cfg, params, _ = setup("M")
    lp = params["layers"][0]
    h = jnp.asarray(np.random.default_rng(3).normal(size=(1, 20, 64)), F32)
    conv0 = jnp.zeros((1, 3, cfg.conv_dim), F32)
    ssm0 = jnp.zeros((1, cfg.mamba_num_heads, cfg.mamba_head_dim,
                      cfg.ssm_state_size), F32)
    y, conv, ssm = mamba(cfg, lp, h, conv0, ssm0, [valid])
    y2, conv2, ssm2 = mamba(cfg, lp, h[:, :valid], conv0, ssm0,
                           [valid])
    np.testing.assert_allclose(ssm, ssm2, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(conv, conv2, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(y[:, :valid], y2, rtol=1e-3, atol=1e-5)


def test_mamba_decode_continues_the_prefill():
    cfg, params, model = setup("M")
    lp = params["layers"][0]
    h = jnp.asarray(np.random.default_rng(5).normal(size=(2, 12, 64)), F32)
    conv = jnp.zeros((2, 3, cfg.conv_dim), F32)
    ssm = jnp.zeros((2, cfg.mamba_num_heads, cfg.mamba_head_dim,
                     cfg.ssm_state_size), F32)
    y, conv, ssm = mamba(cfg, lp, h[:, :9], conv, ssm, [9, 9])
    ys = [y]
    for t in range(9, 12):
        y, conv, ssm = mamba(cfg, lp, h[:, t:t + 1], conv, ssm, [1, 1])
        ys.append(y)
    want = ref.mamba_mixer({k: v.astype(F32) for k, v in lp.items()}, h, model)
    np.testing.assert_allclose(jnp.concatenate(ys, axis=1), want, rtol=1e-3,
                               atol=1e-5)


def test_grouped_matmul_computes_the_held_groups_only():
    r = np.random.default_rng(0)
    sizes = jnp.asarray([2, 0, 3, 1, 4, 2], jnp.int32)      # 6 groups, 12 rows
    lhs = jnp.asarray(r.normal(size=(14, 5)), F32)          # 2 rows past them
    rhs = jnp.asarray(r.normal(size=(3, 5, 7)), F32)        # groups 2, 3, 4
    out = grouped_matmul(lhs, rhs, sizes, first=2)
    np.testing.assert_allclose(
        grouped_matmul(lhs, jnp.swapaxes(rhs, 1, 2), sizes, 2, True), out,
        rtol=1e-6)
    np.testing.assert_allclose(out[2:5], lhs[2:5] @ rhs[0], rtol=1e-5)
    np.testing.assert_allclose(out[5:6], lhs[5:6] @ rhs[1], rtol=1e-5)
    np.testing.assert_allclose(out[6:10], lhs[6:10] @ rhs[2], rtol=1e-5)


@pytest.mark.parametrize("held,offset", [(8, 0), (4, 0), (4, 4), (2, 3)])
def test_expert_layer_matches_the_reference(held, offset):
    cfg, params, model = setup("E", experts_here=held, expert_offset=offset)
    lp = params["layers"][0]
    h = jnp.asarray(np.random.default_rng(held).normal(size=(24, 64)), F32)
    real = jnp.ones((24,), bool)
    y, ctr = moe_dropless(lp, h, cfg, real)
    want = ref.expert_mixer(lp, h[None], model)[0]
    np.testing.assert_allclose(y, want, rtol=1e-3, atol=1e-5)
    assert int(ctr["moe_pairs_here"]) + int(ctr["moe_pairs_away"]) == 24 * 3
    idx, _ = route(h, lp, cfg)
    here = (np.asarray(idx) >= offset) & (np.asarray(idx) < offset + held)
    assert int(ctr["moe_pairs_here"]) == here.sum()
    assert int(ctr["moe_experts_touched"]) == \
        len(np.unique(np.asarray(idx)[here]))
    assert int(ctr["moe_load_max"]) == \
        (np.bincount(np.asarray(idx)[here], minlength=1).max()
         if here.any() else 0)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 on one chip, 4-7 on the other: what the two compute, the
    shared expert counted once, is the uncut reference layer."""
    cfg, params, model = setup("E", experts_here=8)
    lp = params["layers"][0]
    h = jnp.asarray(np.random.default_rng(9).normal(size=(40, 64)), F32)
    real = jnp.ones((40,), bool)
    parts = []
    for offset in (0, 4):
        half = tiny("E", experts_here=4, expert_offset=offset)
        lp_half = dict(lp, up_w=lp["up_w"][offset:offset + 4],
                       down_w=lp["down_w"][offset:offset + 4])
        parts.append(moe_dropless(lp_half, h, half, real)[0])
    shared = ref.shared_expert(lp, h)
    uncut = ref.expert_mixer(lp, h[None], dict(model, n_routed_experts=8))[0]
    np.testing.assert_allclose(parts[0] + parts[1] - shared, uncut,
                               rtol=1e-3, atol=1e-5)
    # and neither half alone is the layer
    assert float(jnp.abs(parts[0] - uncut).max()) > 1e-3


def test_padded_rows_are_not_routed():
    cfg, params, _ = setup("E")
    lp = params["layers"][0]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(10, 64)), F32)
    real = jnp.arange(10) < 6
    _, ctr = moe_dropless(lp, h, cfg, real)
    assert int(ctr["moe_pairs_here"]) + int(ctr["moe_pairs_away"]) == 6 * 3


# ---- through the engine ------------------------------------------------------

def run_engine(cfg, params, lengths, new=10, **kw):
    kw = dict(dict(num_slots=2, page_size=8, max_model_len=256), **kw)
    eng = LLMEngine(params, cfg, **kw)
    r = np.random.default_rng(sum(lengths))
    for n in lengths:
        eng.add_request(r.integers(0, 256, n, dtype=np.int32),
                        max_new_tokens=new)
    outs = eng.run()
    eng.cache.check_invariants()
    return eng, outs


@pytest.mark.parametrize("case,lengths,kw", [
    ("bucket_padding", [5], {}),
    ("bucket_exact", [16], {}),
    ("longer_than_a_chunk", [37], {}),
    ("two_lengths_one_batch", [9, 70], {}),
    ("slot_reused_by_a_second_request", [21, 6], dict(num_slots=1)),
    ("queue_behind_the_slots", [5, 37, 70, 3], {}),
    ("chunked_prefill", [5, 37, 70, 3], dict(prefill_chunk=8)),
    ("attention_only", [19, 40], {}),
    ("no_attention", [19, 40], {}),
])
def test_prefill_then_decode_is_the_reference_forward(case, lengths, kw):
    pattern = {"attention_only": "**", "no_attention": "MEME"}.get(
        case, "MEM*EM*")
    cfg, params, model = setup(pattern)
    eng, outs = run_engine(cfg, params, lengths, **kw)
    assert len(outs) == len(lengths)
    for out in outs.values():
        assert out.finish_reason == "length" and len(out.token_ids) == 10
        assert served_gap(params, model, out) < 1e-4
    st = eng.stats()
    assert st["ssm_state_resets"] == (len(lengths) if "M" in pattern else
                                      st["ssm_state_resets"])
    assert not eng.recurrent or eng.cache.state.live == set()


def test_recompute_preemption_restarts_the_state():
    """Optimistic admission on a pool too small for both requests: one is
    preempted, re-queued and prefilled again from a zero state; both finish
    as the reference says."""
    cfg, params, model = setup()
    eng, outs = run_engine(cfg, params, [30, 28], new=40, num_pages=10,
                           admission="optimistic")
    st = eng.stats()
    assert st["preemptions"] >= 1 and st["preempt_recomputes"] >= 1
    assert st["ssm_state_resets"] == 2 + st["preemptions"]
    for out in outs.values():
        assert len(out.token_ids) == 40
        assert served_gap(params, model, out) < 1e-4


def test_abort_frees_the_state_and_the_slot_serves_again():
    cfg, params, model = setup()
    eng = LLMEngine(params, cfg, num_slots=1, page_size=8, max_model_len=256)
    r = np.random.default_rng(0)
    rid = eng.add_request(r.integers(0, 256, 12, dtype=np.int32), 50)
    for _ in range(4):
        eng.step()
    assert eng.cache.state.live == {0}
    assert eng.abort(rid)
    assert eng.cache.state.live == set()
    eng.add_request(r.integers(0, 256, 7, dtype=np.int32), 8)
    outs = eng.run()
    eng.cache.check_invariants()
    done = [o for o in outs.values() if o.finish_reason == "length"]
    assert len(done) == 1 and served_gap(params, model, done[0]) < 1e-4


def test_counters_reach_stats_metrics_and_the_ring():
    cfg, params, _ = setup()
    eng, _ = run_engine(cfg, params, [9, 30], new=6)
    st = eng.stats()
    tokens = st["prefilled_tokens"] + st["decode_tokens"] - 2
    # every computed token routes top-3 in each of the two expert layers
    assert st["moe_pairs_here"] + st["moe_pairs_away"] >= 2 * 3 * tokens
    assert 0 < st["moe_pairs_here"] and 0 < st["moe_pairs_away"]
    assert st["moe_experts_touched"] > 0 and st["moe_load_max"] >= 1
    assert st["ssm_state_bytes"] == \
        2 * st["ssm_slots_live"] * cfg.state_bytes_per_slot()
    assert st["ssm_state_pool_bytes"] == 2 * cfg.state_bytes_per_slot()
    assert st["prefix_lookups_skipped_no_state"] == 2
    assert st["pages_evictable"] == 0 and st["pages_in_use"] == 0
    assert not eng.prefix_cache and not eng.kv_tier
    text = eng.metrics.to_prometheus()
    for name in ("moe_pairs_here", "moe_pairs_away", "moe_experts_touched",
                 "moe_load_max", "ssm_slots_live", "ssm_state_resets",
                 "ssm_state_bytes", "prefix_lookups_skipped_no_state"):
        assert f"llm_engine_{name}" in text, name
    ring = eng.step_trace()
    assert sum(r["moe_pairs_here"] for r in ring) == st["moe_pairs_here"]
    assert sum(r["moe_experts_touched"] for r in ring) == \
        st["moe_experts_touched"]


def test_dense_engine_counts_nothing_new():
    cfg = gpt.gpt_tiny()
    eng = LLMEngine(gpt.init_params(cfg, jax.random.key(0)), cfg, num_slots=2,
                    page_size=8, max_model_len=64)
    eng.add_request(np.arange(5, dtype=np.int32), 4)
    eng.run()
    st = eng.stats()
    assert eng.prefix_cache and not eng.recurrent and eng.cache.state is None
    for name in ("moe_pairs_here", "moe_pairs_away", "ssm_slots_live",
                 "ssm_state_bytes", "prefix_lookups_skipped_no_state"):
        assert st[name] == 0


# ---- refusals ----------------------------------------------------------------

@pytest.mark.parametrize("kw,says", [
    (dict(spec_len=2), "speculative decoding"),
    (dict(admission="optimistic", preempt="swap"), "preempted by swap"),
    (dict(weight_dtype="int8"), "no quantized serving path"),
    (dict(kv_dtype="int8"), "no quantized serving path"),
    (dict(mp=2), "one chip"),
    (dict(role="prefill"), "hand prompts off"),
])
def test_what_recurrent_state_cannot_be_served_with_is_refused(kw, says):
    cfg, params, _ = setup("M*")
    with pytest.raises(ValueError, match=says):
        LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64,
                  **kw)


@pytest.mark.parametrize("kw", [dict(layer_pattern="MX", num_layers=2),
                                dict(layer_pattern="ME", num_layers=3),
                                dict(layer_pattern="E", num_layers=1,
                                     n_routed_experts=4, experts_here=3,
                                     expert_offset=2)])
def test_a_malformed_pattern_or_share_is_refused(kw):
    with pytest.raises(ValueError):
        hybrid.HybridConfig(**kw)


# ---- the two fields on GPTConfig --------------------------------------------

def test_gptconfig_defaults_leave_every_program_as_it_was():
    c = gpt.gpt_tiny()
    assert c.rms_norm_eps == 1e-6 and c.head_dim == 64 // 4
    assert c.qkv_dim == 64 + 2 * c.kv_heads * c.head_dim
    assert gpt.llama_tiny().head_dim == 16 and c.kv_layers == c.num_layers
    assert tiny().kv_layers == 2 and tiny().rms_norm_eps == 1e-5


def test_rms_norm_eps_is_what_the_norm_computes():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 64)) * 1e-3, F32)
    w = jnp.ones((64,), F32)
    for eps in (1e-6, 1e-5):
        c = gpt.GPTConfig(hidden_size=64, num_heads=4, use_rms_norm=True,
                          rms_norm_eps=eps)
        want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        np.testing.assert_allclose(gpt._norm(x, w, None, c), want, rtol=1e-5)


def test_dense_block_with_an_explicit_head_dim():
    """heads x head_dim != hidden through the dense block: the engine's
    prefill and decode agree with the full forward pass."""
    cfg = gpt.GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=4, num_kv_heads=2, head_dim=32,
                        max_seq_len=128, use_rms_norm=True, use_bias=False,
                        tie_word_embeddings=False)
    params = gpt.init_params(cfg, jax.random.key(0))
    assert params["blocks"]["proj_w"].shape == (2, 128, 64)
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64)
    prompt = np.random.default_rng(0).integers(0, 256, 11, dtype=np.int32)
    eng.add_request(prompt, 6)
    out = next(iter(eng.run().values()))
    seq = np.concatenate([prompt, np.asarray(out.token_ids, np.int32)])
    logits = np.asarray(gpt.forward(params, jnp.asarray(seq[None]), cfg))[0]
    picks = logits[prompt.size - 1:-1].argmax(-1)
    assert picks.tolist() == out.token_ids


# ---- launch-ahead scheduling over the state lanes ----------------------------

from conftest import AHEAD_CASES, ahead_parity_case     # noqa: E402


@pytest.mark.parametrize("case", sorted(
    set(AHEAD_CASES) - {"spec3"}))          # refused for a pattern
def test_launch_ahead_serves_the_synchronous_schedules_tokens(case):
    """The Nemotron-tiny pattern under `double_buffer=True` against the
    synchronous schedule: a lane launched for a request that had ended moves
    a state nobody reads again (zeroed at the slot's next admission)."""
    cfg, params, _ = setup()

    eng, outs = ahead_parity_case(cfg, params, case, 256)
    st = eng.stats()
    assert st["fused_launched_ahead"] > 0
    assert eng.cache.state.live == set()
    if case == "steady_full_batch":
        assert st["fused_launched_ahead"] >= 0.9 * st["decode_iterations"]
    if case in ("eos_mid_batch", "chunked_eos", "deadline_in_flight"):
        assert st["fused_ahead_discarded_lanes"] >= 1
    # a request admitted into a slot whose last owner left a stray lane
    # behind starts from a zero state: one reset an admission
    assert st["ssm_state_resets"] == len(outs) + st["preemptions"]
