"""A DeepSeek-sparse-attention (GLM-5 shaped) configuration through the
serving engine: the indexer and its paged lane, the exact top-k, latent
attention over the chosen rows in both forms, against the plain reference
(`benchmarks/reference/glm5.py`, which imports nothing of the program); the
expert shares; the index lane through the spill tier; the counters; and
what `index_topk` 0 leaves alone.  Tiny widths (`hybrid.dsa_tiny`: top-16,
so selection binds past 16 positions), seeded float32 weights, CPU."""
import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import glm5 as ref
from paddle_tpu.incubate.distributed.models.moe.dropless import moe_dropless
from paddle_tpu.incubate.kernels import paged_attention as PA
from paddle_tpu.incubate.kernels import sparse_attention as SA
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models import gpt, hybrid
from paddle_tpu.quantization.serving import kv_page_bytes

F32 = jnp.float32
PAGE = 8


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
        rope_theta=c.rope_theta, rms_norm_eps=c.rms_norm_eps,
        index_n_heads=c.index_n_heads, index_head_dim=c.index_head_dim,
        index_topk=c.index_topk, index_norm_eps=c.index_norm_eps,
        n_routed_experts=c.experts_here, router_experts=c.n_routed_experts,
        expert_offset=c.expert_offset,
        num_experts_per_tok=c.num_experts_per_tok,
        routed_scaling_factor=c.routed_scaling_factor,
        norm_topk_prob=c.norm_topk_prob)


def setup(seed=3, **kw):
    return _setup(seed, tuple(sorted(kw.items())))


@functools.lru_cache(maxsize=None)
def _setup(seed, kw):
    cfg = hybrid.dsa_tiny(**dict(dict(dtype=F32), **dict(kw)))
    return cfg, hybrid.init_params(cfg, jax.random.key(seed)), model_dict(cfg)


def program_logits(cfg, params, toks, n_prompt, bucket=128):
    """Logits of the bucketed prefill at position n_prompt - 1, then of one
    decode step a token (T = 1: the sparse form) through the cache."""
    pool = hybrid.init_paged_cache(cfg, 40, PAGE, 1)
    pages = np.arange(1, bucket // PAGE + 1, dtype=np.int32)[None]
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :n_prompt] = toks[:n_prompt]
    lg, pool, _ = hybrid.prefill_paged(params, jnp.asarray(ids), cfg, pool,
                                       jnp.asarray(pages),
                                       jnp.asarray([n_prompt]),
                                       jnp.asarray([0]))
    got = [np.asarray(lg[0])]
    table = np.zeros((1, 32), np.int32)
    table[0, :pages.shape[1]] = pages[0]
    for j in range(n_prompt, toks.size - 1):
        lg, pool, _ = hybrid.prefill_chunk_paged(
            params, jnp.asarray(toks[None, j:j + 1]), cfg, pool,
            jnp.asarray(table), jnp.asarray([j]), jnp.asarray([1]))
        got.append(np.asarray(lg[0]))
    return np.stack(got), pool


def reference_logits(params, model, toks, n_prompt, fault=""):
    T = np.zeros((1, 128), np.int32)
    T[0, :toks.size] = toks
    cols = np.arange(n_prompt - 1, toks.size - 1)
    return np.asarray(ref.logits_at(params, T, np.zeros(cols.size, int),
                                    cols, model, fault=fault))


# ---- the lanes -------------------------------------------------------------

def test_the_index_lane_sits_beside_the_latent_lane_and_is_counted():
    cfg, params, _ = setup()
    assert set(cfg.paged_lanes()) == {"c", "ix"}
    pool = hybrid.init_paged_cache(cfg, num_pages=9, page_size=8, num_slots=2)
    assert {n: a.shape for n, a in pool.items()} == {
        "c": (3, 9, 8, 128), "ix": (3, 9, 8, 32)}
    assert kv_page_bytes(cfg, 8) == cfg.page_bytes(8) == 3 * 8 * (128 + 32) * 4
    real = hybrid.HybridConfig(
        layer_pattern="LE", num_layers=2, kv_lora_rank=512,
        qk_rope_head_dim=64, index_topk=2048, index_n_heads=32,
        index_head_dim=128, dtype=jnp.bfloat16)
    assert kv_page_bytes(real, 64) == 64 * (640 + 128) * 2
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64)
    st = eng.stats()
    assert st["index_page_bytes"] == 3 * 8 * 32 * 4
    assert st["latent_page_bytes"] + st["index_page_bytes"] == \
        eng._kv_page_bytes
    assert eng.kv_pool_bytes() == eng.cache.num_pages * eng._kv_page_bytes


# ---- against the reference --------------------------------------------------

@pytest.mark.parametrize("block,route", [(2048, "sort"), (32, "sort"),
                                         (2048, "counted")],
                         ids=["2048", "32", "2048-counted"])
def test_prefill_then_decode_through_both_lanes_is_the_reference(
        block, route, monkeypatch):
    """A 90-token prompt (selection binds past 16) in the masked form, whole
    or in query blocks of 32 under the scan, then five decode steps in the
    sparse form: logits against the reference's full forward.  "counted":
    the decode steps' top-k through the chip's route (the threshold search
    and the compaction, in interpret mode), against the sort's logits too."""
    monkeypatch.setattr(hybrid, "DSA_QUERY_BLOCK", block)
    cfg, params, model = setup()
    toks = np.random.default_rng(0).integers(0, 256, 96, dtype=np.int32)
    if route == "counted":
        sorted_route, _ = program_logits(cfg, params, toks, 90)
        monkeypatch.setattr(hybrid.dsa_k, "topk_rows_decode", functools.partial(
            SA.topk_rows_pallas, interpret=True))
    got, _ = program_logits(cfg, params, toks, 90)
    want = reference_logits(params, model, toks, 90)
    assert float(np.abs(got - want).max()) < 1e-4
    if route == "counted":
        np.testing.assert_allclose(got, sorted_route, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fault", ["selection_off", "index_k_unrotated",
                                   "w_ignored"])
def test_a_wrong_indexer_fails_the_comparison(fault):
    """Attention over every earlier position, the indexer's key unrotated,
    its head weights ignored: each moves the logits by orders of magnitude
    more than the program's distance from the reference."""
    cfg, params, model = setup()
    toks = np.random.default_rng(0).integers(0, 256, 96, dtype=np.int32)
    got, _ = program_logits(cfg, params, toks, 90)
    bad = reference_logits(params, model, toks, 90, fault)
    assert float(np.abs(got - bad).max()) > 1e-2


def test_the_chosen_sets_are_the_references_position_by_position():
    """The indexer's scores over the cache (the paged walk) and the exact
    top-16 against the reference's selection, every query of a prompt."""
    cfg, params, model = setup()
    n = 120
    toks = np.random.default_rng(1).integers(0, 256, n, dtype=np.int32)
    lp = params["layers"][0]
    x = jnp.take(params["wte"], jnp.asarray(toks), axis=0)
    h = hybrid._norm(x[None], lp["norm_w"], cfg)
    pos = jnp.arange(n)[None]
    sin, cos = hybrid._rope_tables(pos, cfg)
    ki = hybrid.index_key(lp, h, sin, cos, cfg)
    _, _, cq = hybrid._latent_q(lp, h, sin, cos, cfg)
    qi, w = hybrid.index_query(lp, h, cq, sin, cos, cfg)
    lane = jnp.zeros((17, PAGE, cfg.index_head_dim), F32)
    table = jnp.arange(1, 17, dtype=jnp.int32)[None]
    lane = lane.at[1:1 + n // PAGE].set(ki[0].reshape(-1, PAGE,
                                                      cfg.index_head_dim))
    scores = SA.paged_index_scores(qi, w, lane, table, jnp.asarray([0]),
                                   jnp.asarray([n]))
    got = np.asarray(SA.unblocked(SA.mask_from(
        scores, *SA.topk_threshold(scores, cfg.index_topk))))
    m32 = {k: (v.astype(F32) if hasattr(v, "astype") else v)
           for k, v in lp.items()}
    hr = ref.rms_norm(x.astype(F32), m32["norm_w"], cfg.rms_norm_eps)
    hr = jnp.pad(hr, ((0, 128 - n), (0, 0)))
    cq_r, _, _, ki_r = ref._projections(m32, hr, model, "f32", "")
    want = np.asarray(ref._selection(m32, cq_r, hr, ki_r, model, "f32", ""))
    assert (got[0, :, :n] == want[:n, :n]).all()
    assert got[0, :, n:].sum() == 0
    counts = got[0].sum(-1)
    assert (counts == np.minimum(np.arange(1, n + 1), 16)).all()
    idx, n_sel = SA.topk_rows(scores, cfg.index_topk)
    for t in range(n):
        assert set(np.asarray(idx[0, t, :n_sel[0, t]]).tolist()) == \
            set(np.nonzero(want[t, :n])[0].tolist())


def test_the_masked_and_sparse_kernels_are_their_oracles():
    """Each DSA kernel in interpret mode against its XLA oracle: the index
    walk over live pages, the threshold search, the masked latent walk and
    the sparse attention over gathered rows."""
    rng = np.random.default_rng(0)
    B, T, Hi, Di, P = 2, 24, 8, 128, 20
    q = jnp.asarray(rng.standard_normal((B, T, Hi, Di)), F32)
    w = jnp.asarray(rng.standard_normal((B, T, Hi)), F32)
    ix = jnp.asarray(rng.standard_normal((P, 16, Di)), F32)
    tbl = jnp.asarray(rng.permutation(np.arange(1, P))[:12].reshape(B, 6),
                      jnp.int32)
    qo, vl = jnp.asarray([5, 40], jnp.int32), jnp.asarray([24, 17], jnp.int32)
    a = SA.paged_index_scores_xla(q, w, ix, tbl, qo, vl)
    b = SA.paged_index_scores_pallas(q, w, ix, tbl, qo, vl, interpret=True)
    live = np.asarray(a) > -1e29
    assert (live == (np.asarray(b) > -1e29)).all()
    np.testing.assert_allclose(np.asarray(b)[live], np.asarray(a)[live],
                               rtol=1e-5, atol=1e-3)
    thr = SA.topk_threshold_xla(a, 7)
    for k in (7, 30):
        np.testing.assert_array_equal(
            np.asarray(SA.mask_from(a, *SA.topk_threshold_xla(a, k))),
            np.asarray(SA.mask_from(a, *SA.topk_threshold_pallas(
                a, k, qo, interpret=True))))
    H, W, lat = 8, 256, 128
    qq = jnp.asarray(rng.standard_normal((B, T, H, W)) * 0.1, F32)
    c = jnp.asarray(rng.standard_normal((P, 16, W)), F32)
    sel = (a,) + thr
    x = PA.paged_latent_attention_xla(qq, c, tbl, qo, vl, lat, 0.1,
                                      select=sel)
    y = PA.paged_latent_attention_pallas(qq, c, tbl, qo, vl, lat, 0.1,
                                         interpret=True, select=sel)
    ok = np.arange(T)[None, :] < np.asarray(vl)[:, None]
    np.testing.assert_allclose(np.asarray(y)[ok], np.asarray(x)[ok],
                               rtol=1e-5, atol=1e-5)
    q3 = jnp.asarray(rng.standard_normal((5, H, W)) * 0.1, F32)
    rows = jnp.asarray(rng.standard_normal((5, 128, W)), F32)
    ns = jnp.asarray([1, 5, 128, 60, 0], jnp.int32)
    s1 = SA.sparse_latent_attention_xla(q3, rows, ns, lat, 0.1)
    s2 = SA.sparse_latent_attention_pallas(q3, rows, ns, lat, 0.1,
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(s2)[:4], np.asarray(s1)[:4],
                               rtol=1e-5, atol=1e-5)


def decode_rows(B, S, k, rng):
    """Decode scores of B slots over a table of S positions (not whole
    blocks), with (q_offset, valid): ties and -0 / 0 in slot 0, a slot with
    fewer than k candidates, one with exactly k, one with every position
    live, a padding slot (valid 0), the others at random lengths."""
    x = np.round(rng.standard_normal((B, 1, S)) * 2).astype(np.float32) / 2
    x[0, 0, :50] = 0.0
    x[0, 0, 50:60] = -0.0
    end = rng.integers(1, S + 1, B)
    end[:4] = (S, k - 5, S, k)[:B]
    valid = np.ones(B, np.int32)
    if B > 2:
        valid[2] = 0
    for b in range(B):
        x[b, 0, end[b] if valid[b] else 0:] = -1e30
    return x, end - 1, valid


@pytest.mark.parametrize("case", ["ties", "few", "wide", "decode-1",
                                  "decode-3", "decode-12", "decode-16"])
def test_the_top_k_is_exact_with_ties_to_the_lower_position(case):
    """The threshold search and the sort (`topk_rows`) against the rule
    itself; for decode rows (T = 1) also the chip's route in interpret mode
    (`topk_rows_pallas`): the same sets, in ascending position order, the
    rest position 0."""
    rng = np.random.default_rng(len(case))
    if case.startswith("decode"):
        B = int(case.split("-")[1])
        S, k = 1100, 300
        x, qo, vl = decode_rows(B, S, k, rng)
        scores = SA.blocked(jnp.asarray(x), 16)
        idx, n_sel = map(np.asarray, SA.topk_rows_pallas(
            scores, k, jnp.asarray(qo, jnp.int32), jnp.asarray(vl),
            interpret=True))
        oidx, on_sel = map(np.asarray, SA.topk_rows(scores, k))
        assert idx.shape == oidx.shape == (B, 1, k)
        for b in range(B):
            row = x[b, 0]
            want = sorted(s for s in range(S) if row[s] > -1e29)
            want = sorted(sorted(want, key=lambda s: (-row[s], s))[:k])
            n = int(n_sel[b, 0])
            assert n == on_sel[b, 0] == len(want)
            assert idx[b, 0, :n].tolist() == want
            assert sorted(oidx[b, 0, :n].tolist()) == want
            assert not idx[b, 0, n:].any()
        assert n_sel[0, 0] == k and (B < 4 or n_sel[1:4, 0].tolist() ==
                                     [k - 5, 0, k])
        return
    S = {"ties": 700, "few": 40, "wide": 1500}[case]
    k = {"ties": 100, "few": 64, "wide": 333}[case]
    x = rng.standard_normal((2, 3, S)).astype(np.float32)
    if case == "ties":
        x = np.round(x * 2) / 2
        x[0, 0, :50] = 0.0
        x[0, 0, 50:60] = -0.0
    x[:, :, rng.integers(0, S, S // 3)] = -1e30
    scores = SA.blocked(jnp.asarray(x), 16)
    got = np.asarray(SA.unblocked(SA.mask_from(
        scores, *SA.topk_threshold(scores, k))))[:, :, :S]
    idx, n_sel = SA.topk_rows(scores, k)
    for b in range(2):
        for t in range(3):
            row = x[b, t]
            cand = [s for s in range(S) if row[s] > -1e29]
            want = sorted(cand, key=lambda s: (-row[s], s))[:k]
            assert sorted(np.nonzero(got[b, t])[0].tolist()) == sorted(want)
            assert sorted(np.asarray(idx[b, t, :n_sel[b, t]]).tolist()) == \
                sorted(want)


# ---- the long pass expanded -------------------------------------------------

def _selection(rng, B, T, S, q_offset, valid, k, ties):
    """Blocked scores of B slots' T query tokens at positions q_offset + t
    over S positions (page 8), causal, -inf on padding rows (t >= valid),
    and their exact top-k (thr, last)."""
    x = rng.standard_normal((B, T, S)).astype(np.float32)
    if ties:
        x = np.round(x)                 # a few values only: ties at thr
        x[:, :, :40] = 0.0
    pos = np.asarray(q_offset)[:, None, None] + np.arange(T)[None, :, None]
    pad = np.arange(T)[None, :, None] >= np.asarray(valid)[:, None, None]
    x[(np.arange(S)[None, None] > pos) | pad] = -1e30
    scores = SA.blocked(jnp.asarray(x), PAGE)
    return (scores,) + SA.topk_threshold_xla(scores, k)


@pytest.mark.parametrize("case", ["offset0", "later-block", "ties", "few",
                                  "padding", "slots"])
def test_the_expanded_form_is_the_absorbed_oracle(case):
    """The expanded route (per-head keys from the lane's rows, values formed
    from the latent rows; the XLA form and the kernel in interpret mode)
    against the absorbed oracle `paged_latent_attention_xla(select=...)`
    through W^V: the first query block (q_offset 0), a later block (q_offset
    > 0), ties at the threshold (order key == thr, kept up to `last`),
    tokens with fewer than index_topk candidates, padding past `valid`,
    several slots."""
    cfg, params, _ = setup()
    lp = params["layers"][0]
    rng = np.random.default_rng(len(case))
    T, n_pages = 32, 16
    B, qo, vl, k = {
        "offset0": (1, [0], [32], 16), "later-block": (1, [64], [32], 16),
        "ties": (2, [0, 40], [32, 32], 24), "few": (1, [0], [32], 100),
        "padding": (2, [10, 0], [20, 7], 16),
        "slots": (3, [0, 30, 90], [32, 32, 25], 16)}[case]
    S = n_pages * PAGE
    sel = _selection(rng, B, T, S, qo, vl, k, case == "ties")
    W = cfg.latent_lane
    lane = np.zeros((B * n_pages + 1, PAGE, W), np.float32)
    lane[:, :, :cfg.latent_row] = rng.standard_normal(
        (B * n_pages + 1, PAGE, cfg.latent_row))
    lane = jnp.asarray(lane)
    table = jnp.asarray(1 + rng.permutation(B * n_pages).reshape(B, n_pages),
                        jnp.int32)
    H = cfg.num_heads
    q_nope = jnp.asarray(rng.standard_normal(
        (B, T, H, cfg.qk_nope_head_dim)), F32)
    q_rope = jnp.asarray(rng.standard_normal(
        (B, T, H, cfg.qk_rope_head_dim)), F32)
    qo, vl = jnp.asarray(qo, jnp.int32), jnp.asarray(vl, jnp.int32)
    scale = cfg.mla_softmax_scale
    o_lat = PA.paged_latent_attention_xla(
        hybrid._absorbed_q(lp, q_nope, q_rope, cfg), lane, table, qo, vl,
        cfg.kv_lora_rank, scale, select=sel)
    want = np.asarray(jnp.einsum("bthc,hcv->bthv", o_lat, lp["kv_b_v_w"]))
    rows = lane[table].reshape(B, S, W)
    keys = hybrid._expanded_keys(lp, rows, cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    real = np.arange(T)[None, :] < np.asarray(vl)[:, None]
    for got in (SA.masked_expanded_attention_xla(
                    q, keys, rows, lp["kv_b_v_w"], qo, sel, scale),
                SA.masked_expanded_attention_pallas(
                    q, keys, rows, lp["kv_b_v_w"], qo, sel, scale,
                    interpret=True)):
        np.testing.assert_allclose(np.asarray(got)[real], want[real],
                                   rtol=1e-5, atol=1e-5)
    if case == "few":       # every candidate kept: attention over all
        assert (np.asarray(SA.unblocked(SA.mask_from(*sel))).sum(-1) ==
                np.arange(1, T + 1)[None]).all()


def _pass_logits(cfg, params, toks, n_prompt, form, monkeypatch):
    """(prefill logits at n_prompt - 1, then a chunk of 32 tokens through the
    cache: its logits at every position, both passes' aux) with every pass
    of more than one token in `form`."""
    monkeypatch.setattr(hybrid, "DSA_EXPANDED_MIN_TOKENS",
                        2 if form == "expanded" else 1 << 30)
    pool = hybrid.init_paged_cache(cfg, 40, PAGE, 1)
    pages = np.arange(1, 17, dtype=np.int32)[None]
    ids = np.zeros((1, 128), np.int32)
    ids[0, :n_prompt] = toks[:n_prompt]
    lg, pool, aux = hybrid.prefill_paged(
        params, jnp.asarray(ids), cfg, pool, jnp.asarray(pages),
        jnp.asarray([n_prompt]), jnp.asarray([0]))
    table = np.zeros((1, 32), np.int32)
    table[0, :24] = np.arange(1, 25)
    x, _, aux2 = hybrid._step_hidden(
        params, jnp.asarray(toks[None, n_prompt:n_prompt + 32]), pool,
        jnp.asarray(table), jnp.asarray([n_prompt]), jnp.asarray([32]), cfg)
    chunk = gpt.head_logits(hybrid._norm(x, params["lnf_w"], cfg), params,
                            cfg)[0]
    return np.asarray(lg[0], np.float32), np.asarray(chunk, np.float32), \
        dict(zip(hybrid.aux_fields(cfg), np.asarray(aux))), \
        dict(zip(hybrid.aux_fields(cfg), np.asarray(aux2)))


@pytest.mark.parametrize("block", [2048, 32], ids=["whole", "blocks"])
def test_prefill_and_chunk_through_the_expanded_form_in_bfloat16(
        block, monkeypatch):
    """`tiny_glm5` widths in bfloat16: the bucketed prefill (its own rows,
    q_offset 0; whole, or 2,048-style query blocks of 32 under the scan with
    q_offset > 0) and a 32-token chunk behind it (keys from the lane through
    the page table) in the expanded form lie as close to the float32
    program as bfloat16 lets the absorbed route lie (within twice its
    distance), and `dsa_expanded_tokens` counts the passes' tokens."""
    monkeypatch.setattr(hybrid, "DSA_QUERY_BLOCK", block)
    cfg, params, _ = setup(dtype=jnp.bfloat16)
    cfg32 = setup()[0]
    params32 = jax.tree_util.tree_map(
        lambda x: x.astype(F32) if x.dtype == jnp.bfloat16 else x, params)
    toks = np.random.default_rng(3).integers(0, 256, 128, dtype=np.int32)
    want = _pass_logits(cfg32, params32, toks, 90, "absorbed", monkeypatch)
    with jax.default_matmul_precision(None):
        a = _pass_logits(cfg, params, toks, 90, "absorbed", monkeypatch)
        b = _pass_logits(cfg, params, toks, 90, "expanded", monkeypatch)
    for x, y, w in zip(a[:2], b[:2], want[:2]):
        assert 0 < float(np.abs(y - w).max()) <= \
            2 * float(np.abs(x - w).max())
    assert a[2]["dsa_expanded_tokens"] == a[3]["dsa_expanded_tokens"] == 0
    assert b[2]["dsa_expanded_tokens"] == 90
    assert b[3]["dsa_expanded_tokens"] == 32
    for name in hybrid.DSA_AUX[:3]:
        assert a[2][name] == b[2][name] and a[3][name] == b[3][name]


@pytest.mark.parametrize("T,route", [(1, "sparse"), (1023, "absorbed"),
                                     (1024, "expanded")])
def test_the_route_is_chosen_by_the_pass_length(T, route, monkeypatch):
    """Decode (T = 1) takes the sparse form, a pass below
    `DSA_EXPANDED_MIN_TOKENS` the masked absorbed walk, one at it the
    expanded form; the counter says so."""
    cfg, params, _ = setup(seq_len=2048)
    taken = []
    for name, mod in (("sparse_latent_attention", hybrid.dsa_k),
                      ("masked_expanded_attention", hybrid.dsa_k),
                      ("paged_latent_attention", hybrid)):
        fn = getattr(mod, name)
        monkeypatch.setattr(mod, name, functools.partial(
            lambda fn, name, *a, **kw: taken.append(name) or fn(*a, **kw),
            fn, name))
    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    pool = jax.eval_shape(functools.partial(hybrid.init_paged_cache, cfg,
                                            300, PAGE, 1))
    _, _, aux = jax.eval_shape(
        lambda p, ids, pl, tb, qo, vl: hybrid.prefill_chunk_paged(
            p, ids, cfg, pl, tb, qo, vl),
        params, S((1, T), i32), pool, S((1, 256), i32), S((1,), i32),
        S((1,), i32))
    want = {"sparse": "sparse_latent_attention", "absorbed":
            "paged_latent_attention", "expanded": "masked_expanded_attention"}
    assert set(taken) == {want[route]}
    assert aux.shape == (len(hybrid.AUX_FIELDS),)
    assert hybrid._expanded_pass(T) == (route == "expanded")


# ---- the reference's blocked forms -------------------------------------------

@pytest.mark.parametrize("cap", [2, 5, 64])
def test_the_references_held_experts_are_the_dense_sum(cap, monkeypatch):
    """`held_experts` takes at most `CAP` rows an expert (every row where
    more are routed to it): the dense `gated_experts` sum, whichever form
    each expert takes (cap 2: all every row; 5: some of each; 64: all
    taken)."""
    cfg, params, model = setup()
    lp = params["layers"][3]
    h = jnp.asarray(np.random.default_rng(5).normal(size=(40, 64)), F32)
    monkeypatch.setattr(ref, "CAP", cap)
    np.testing.assert_allclose(ref.held_experts(lp, h, model),
                               ref.gated_experts(lp, h[None], model)[0],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("tk", [32, 64])
def test_the_references_causal_key_ranges_change_nothing(tk, monkeypatch):
    """Rows `TK` at a time, queries against the keys before their block's
    end: the same chosen sets and the same latent layer as one block over
    every key."""
    cfg, params, model = setup()
    lp = {k: v.astype(F32) for k, v in params["layers"][0].items()}
    x = jnp.asarray(np.random.default_rng(6).normal(size=(128, 64)), F32)
    monkeypatch.setattr(ref, "TQ", 16)
    got, want = [], []
    for blocks, out in ((tk, got), (128, want)):
        monkeypatch.setattr(ref, "TK", blocks)
        ref._jits.cache_clear()
        h = ref.rms_norm(x, lp["norm_w"], cfg.rms_norm_eps)
        cq, c, k_r, ki = ref._projections(lp, h, model, "f32", "")
        out += [ref._selection(lp, cq, h, ki, model, "f32", ""),
                ref.layer("L", params["layers"][0], x, model)]
    ref._jits.cache_clear()
    assert (np.asarray(got[0]) == np.asarray(want[0])).all()
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)


# ---- the expert share --------------------------------------------------------

def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """A 16-wide router (top-3) over experts held one a chip on 16 chips:
    what the 16 compute, the shared expert counted once, is the uncut
    reference layer."""
    cfg, params, model = setup(experts_here=16)
    lp = params["layers"][3]
    h = jnp.asarray(np.random.default_rng(9).normal(size=(40, 64)), F32)
    real = jnp.ones((40,), bool)
    parts = []
    for offset in range(16):
        share = hybrid.dsa_tiny(dtype=F32, experts_here=1,
                                expert_offset=offset)
        lp_share = dict(lp, **{n: lp[n][offset:offset + 1]
                               for n in ("gate_w", "up_w", "down_w")})
        parts.append(moe_dropless(lp_share, h, share, real)[0])
    nobody = dict(lp, **{n: lp[n][:0] for n in ("gate_w", "up_w", "down_w")})
    gated = ref.gated_experts
    shared = gated(nobody, h[None], model)[0]
    uncut = gated(lp, h[None], dict(model, n_routed_experts=16))[0]
    np.testing.assert_allclose(sum(parts) - 15 * shared, uncut,
                               rtol=1e-3, atol=1e-5)
    assert float(jnp.abs(parts[0] - uncut).max()) > 1e-3


# ---- the engine --------------------------------------------------------------

def test_spilled_index_pages_restore_bit_exact():
    """A session's pages spill to the host tier and come back: the tier
    holds both lanes' bytes, bit for bit, and the tokens are those of an
    engine that never tiers (the prefix hit's tail through the chunk pass,
    the masked form)."""
    cfg, params, model = setup()
    rng = np.random.RandomState(7)
    shared = rng.randint(0, 256, (40,)).astype(np.int32)
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

    kw = dict(num_slots=2, page_size=8, num_pages=12, max_model_len=64,
              prefill_chunk=16, swap_pool_pages=64)
    eng = LLMEngine(params, cfg, **kw)
    outs, ret, held, parked = session(eng)
    st = eng.stats()
    assert st["kv_tier"]["spills"] > 0 and st["kv_tier"]["restores"] >= 1
    mine = [nid for nid in parked if nid in held]
    assert mine
    for nid in mine:
        assert set(parked[nid]) == {"c", "ix"}
        for lane in ("c", "ix"):
            np.testing.assert_array_equal(parked[nid][lane], held[nid][lane])
    base = LLMEngine(params, cfg, **dict(kw, kv_tier=False))
    base_outs, base_ret, _, _ = session(base)
    assert ret.token_ids == base_ret.token_ids
    assert [outs[r].token_ids for r in sorted(outs)] == \
        [base_outs[r].token_ids for r in sorted(base_outs)]
    # the returning turn's tail went through the chunk pass (the masked form
    # behind a prefix hit): its tokens are the reference's
    seq = np.concatenate([ret.prompt, np.asarray(ret.token_ids[:-1],
                                                 np.int32)])
    n = len(ret.token_ids)
    toks = np.asarray(ret.token_ids)
    want = np.asarray(ref.logits_at(
        params, np.pad(seq, (0, 128 - seq.size))[None],
        np.zeros(n, int), np.arange(ret.prompt.size - 1, seq.size), model))
    gap = want.max(-1) - want[np.arange(n), toks]
    assert float(gap.max()) < 1e-4
    eng.cache.check_invariants()


def test_the_counters_are_the_rows_scored_and_chosen():
    """dsa_rows_scored = sum of (p + 1), dsa_rows_selected = sum of min(p +
    1, 16) over every query token's position p, one DSA layer's worth; the
    fused steps' share apart."""
    cfg, params, _ = setup()
    rng = np.random.default_rng(5)
    lengths = (100, 70, 20)
    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=256)
    for n in lengths:
        eng.add_request(rng.integers(0, 256, n, dtype=np.int32),
                        max_new_tokens=10)
    outs = eng.run()
    st = eng.stats()

    def rows(ps):
        return sum(p + 1 for p in ps), sum(min(p + 1, 16) for p in ps)
    pre = [rows(range(n)) for n in lengths]
    gen = [len(o.token_ids) for o in outs.values()]
    dec = [rows(range(n, n + g - 1)) for n, g in zip(lengths, gen)]
    assert st["dsa_step_rows_scored"] == sum(d[0] for d in dec)
    assert st["dsa_step_rows_selected"] == sum(d[1] for d in dec)
    assert st["dsa_rows_scored"] == sum(r[0] for r in pre + dec)
    assert st["dsa_rows_selected"] == sum(r[1] for r in pre + dec)
    # the tokens with more than 16 candidates: the selection searched
    searched = [sum(p >= 16 for p in range(n, n + g - 1))
                for n, g in zip(lengths, gen)]
    assert st["dsa_step_rows_searched"] == sum(searched) > 0
    assert st["dsa_rows_searched"] == sum(searched) + \
        sum(max(n - 16, 0) for n in lengths)
    snap = eng.metrics.snapshot()["counters"]
    assert snap["dsa_rows_selected"] == st["dsa_rows_selected"]
    assert snap["dsa_rows_searched"] == st["dsa_rows_searched"]
    # no pass here reaches DSA_EXPANDED_MIN_TOKENS
    assert st["dsa_expanded_tokens"] == snap["dsa_expanded_tokens"] == 0


def test_the_engine_counts_the_tokens_of_expanded_passes(monkeypatch):
    """Every pass of more than one token expanded (the threshold lowered to
    2): the engine serves the tokens it serves with the absorbed passes,
    and `dsa_expanded_tokens` is the prefilled tokens (none of a fused
    step's: decode stays sparse)."""
    cfg, params, _ = setup()
    prompts = [np.random.default_rng(s).integers(0, 256, n, dtype=np.int32)
               for s, n in ((1, 100), (2, 70), (3, 20))]

    def run():
        eng = LLMEngine(params, cfg, num_slots=2, page_size=8,
                        max_model_len=256)
        for x in prompts:
            eng.add_request(x, max_new_tokens=8)
        outs = eng.run()
        return [outs[r].token_ids for r in sorted(outs)], eng.stats()
    plain, st0 = run()
    monkeypatch.setattr(hybrid, "DSA_EXPANDED_MIN_TOKENS", 2)
    expanded, st = run()
    assert expanded == plain
    assert st0["dsa_expanded_tokens"] == 0
    assert st["dsa_expanded_tokens"] == st["prefilled_tokens"] == 190


# ---- what index_topk 0 leaves alone ------------------------------------------

# sha256 (first 16 hex) of the jaxpr text of the two serving programs of
# three toy patterns, and of a 4,096-token prefill (past the DSA pass's
# 2,048-row blocks) of two, computed at the parent of the DSA change (PR 39):
# index_topk 0 must trace to the very same programs
PARENT_JAXPRS = {
    "latent.prefill": "355a020ae4f2f748", "latent.step": "1d4bcc0395e9d8ea",
    "joyai.prefill": "48c05578e6409391", "joyai.step": "a5a9e00622992bbd",
    "hybrid.prefill": "46128cad0b9a7ce2", "hybrid.step": "0444fdf909c7780c",
    "latent.prefill4096": "3267c3dfeb63ae65",
    "hybrid.prefill4096": "179ff4fb51ba2585",
}
# and, computed before a long DSA pass took the expanded form: the serving
# programs of the two dense families (LayerNorm, GELU and biases; GQA,
# RMSNorm and SwiGLU), a 1,024-token chunk pass of a latent configuration
# (a pass as long as the expanded form's threshold), and the training
# programs the flash kernels run in (the forward and the one backward at
# one width and at 192 / 128, and the patterned trainer's loss gradient)
PARENT_JAXPRS_MORE = {
    "gpt.prefill": "9e50ae67127b0af5", "gpt.step": "57e53e5df70cc8ca",
    "llama.prefill": "b0b557e6a810dbe2", "llama.step": "4c7c4418a1146ee3",
    "latent.chunk1024": "34b22df822d46bad",
}
PARENT_TRAINING_JAXPRS = {
    "flash128x128.train": "cbe4c13574f38fe7",
    "flash192x128.train": "e424d0cc07e80039",
    "joyai.train": "89324ba9ded74a05",
}


def _jaxpr_hash(j) -> str:
    """A jaxpr's text without object addresses (a checkpoint policy prints
    as a function `at 0x..`), hashed."""
    import re
    return hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", str(j)).encode()
                          ).hexdigest()[:16]


def test_index_topk_0_traces_the_programs_it_always_traced():
    got = {}
    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    with jax.default_matmul_precision(None):
        for name, cfg in (("latent", hybrid.latent_tiny()),
                          ("joyai", hybrid.joyai_tiny(
                              num_nextn_predict_layers=0)),
                          ("hybrid", hybrid.hybrid_tiny())):
            assert cfg.index_topk == 0 and "ix" not in cfg.paged_lanes()
            assert hybrid.aux_fields(cfg) == hybrid.AUX_FIELDS[:8]
            params = jax.eval_shape(functools.partial(hybrid.init_params, cfg),
                                    jax.random.key(0))
            pool = jax.eval_shape(functools.partial(
                hybrid.init_paged_cache, cfg, 9, 8, 2))
            pre = jax.make_jaxpr(
                lambda p, ids, pl, pg, ln, sl, c=cfg: hybrid.prefill_paged(
                    p, ids, c, pl, pg, ln, sl))(
                params, S((1, 32), i32), pool, S((1, 4), i32), S((1,), i32),
                S((1,), i32))
            key = jax.eval_shape(lambda: jax.random.key(0))
            step = jax.make_jaxpr(
                lambda p, t, pl, tb, qo, vl, k, g, c=cfg:
                hybrid.serve_step_paged(p, t, pl, tb, qo, vl, c, key=k,
                                        greedy=g))(
                params, S((2, 1), i32), pool, S((2, 4), i32), S((2,), i32),
                S((2,), i32), key, S((2,), jnp.bool_))
            for what, j in (("prefill", pre), ("step", step)):
                got[f"{name}.{what}"] = hashlib.sha256(
                    str(j).encode()).hexdigest()[:16]
        for name, cfg in (("latent", hybrid.latent_tiny(seq_len=4096)),
                          ("hybrid", hybrid.hybrid_tiny(seq_len=4096))):
            params = jax.eval_shape(functools.partial(hybrid.init_params, cfg),
                                    jax.random.key(0))
            pool = jax.eval_shape(functools.partial(
                hybrid.init_paged_cache, cfg, 513, 8, 2))
            pre = jax.make_jaxpr(
                lambda p, ids, pl, pg, ln, sl, c=cfg: hybrid.prefill_paged(
                    p, ids, c, pl, pg, ln, sl))(
                params, S((1, 4096), i32), pool, S((1, 512), i32),
                S((1,), i32), S((1,), i32))
            got[f"{name}.prefill4096"] = hashlib.sha256(
                str(pre).encode()).hexdigest()[:16]
        more = {}
        for name, cfg in (("gpt", gpt.gpt_tiny()),
                          ("llama", gpt.llama_tiny())):
            params = jax.eval_shape(functools.partial(gpt.init_params, cfg),
                                    jax.random.key(0))
            pool = jax.eval_shape(functools.partial(gpt.init_paged_cache, cfg,
                                                    9, 8))
            pre = jax.make_jaxpr(
                lambda p, ids, pl, pg, ln, c=cfg: gpt.prefill_paged(
                    p, ids, c, pl, pg, ln))(
                params, S((1, 32), i32), pool, S((1, 4), i32), S((1,), i32))
            key = jax.eval_shape(lambda: jax.random.key(0))
            step = jax.make_jaxpr(
                lambda p, t, pl, tb, qo, vl, k, g, c=cfg: gpt.serve_step_paged(
                    p, t, pl, tb, qo, vl, c, key=k, greedy=g))(
                params, S((2, 1), i32), pool, S((2, 4), i32), S((2,), i32),
                S((2,), i32), key, S((2,), jnp.bool_))
            more[f"{name}.prefill"] = _jaxpr_hash(pre)
            more[f"{name}.step"] = _jaxpr_hash(step)
        cfg = hybrid.latent_tiny(seq_len=2048)
        params = jax.eval_shape(functools.partial(hybrid.init_params, cfg),
                                jax.random.key(0))
        pool = jax.eval_shape(functools.partial(
            hybrid.init_paged_cache, cfg, 257, 8, 2))
        chunk = jax.make_jaxpr(
            lambda p, ids, pl, tb, qo, vl: hybrid.prefill_chunk_paged(
                p, ids, cfg, pl, tb, qo, vl))(
            params, S((1, 1024), i32), pool, S((1, 256), i32), S((1,), i32),
            S((1,), i32))
        more["latent.chunk1024"] = _jaxpr_hash(chunk)
    assert got == PARENT_JAXPRS
    assert more == PARENT_JAXPRS_MORE


def test_the_training_flash_programs_trace_as_they_did(monkeypatch):
    """The expanded serving form is a kernel of its own: the flash kernels'
    training programs (routed to the kernels as on a TPU) trace what they
    traced before it, forward and backward, and so does the patterned
    trainer's loss gradient that runs them at 192 / 128."""
    from paddle_tpu.incubate.kernels import flash_attention as FA
    monkeypatch.setattr(FA, "_on_tpu", lambda: True)
    S = jax.ShapeDtypeStruct
    bf16 = jnp.bfloat16
    got = {}
    with jax.default_matmul_precision(None):
        for D, Dv in ((128, 128), (192, 128)):
            def loss(q, k, v):
                return FA.flash_attention_fused(q, k, v, causal=True).astype(
                    F32).sum()
            j = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
                S((1, 1024, 2, D), bf16), S((1, 1024, 2, D), bf16),
                S((1, 1024, 2, Dv), bf16))
            got[f"flash{D}x{Dv}.train"] = _jaxpr_hash(j)
        cfg = hybrid.joyai_tiny(seq_len=256, qk_nope_head_dim=128,
                                qk_rope_head_dim=64, v_head_dim=128)
        params = jax.eval_shape(functools.partial(hybrid.init_params, cfg),
                                jax.random.key(0))
        j = jax.make_jaxpr(jax.grad(
            lambda p, t, l: hybrid.train_loss(p, t, l, cfg, remat=True)[0]))(
            params, S((1, 256), jnp.int32), S((1, 256), jnp.int32))
        got["joyai.train"] = _jaxpr_hash(j)
    assert got == PARENT_TRAINING_JAXPRS


def test_a_latent_configuration_without_dsa_serves_what_it_served():
    """The same weights with and without the indexer's leaves: index_topk 0
    ignores them, and its tokens are those of the tree without them."""
    cfg, params, _ = setup()
    plain = dataclasses.replace(cfg, index_topk=0)
    bare = dict(params, layers=[{k: v for k, v in lp.items()
                                 if not k.startswith("ix_")}
                                for lp in params["layers"]])
    prompts = [np.random.default_rng(s).integers(0, 256, 30, dtype=np.int32)
               for s in range(3)]

    def run(p):
        eng = LLMEngine(p, plain, num_slots=2, page_size=8, max_model_len=64)
        for x in prompts:
            eng.add_request(x, max_new_tokens=6)
        outs = eng.run()
        assert set(eng._pool) == {"c"}
        st = eng.stats()
        assert st["dsa_rows_searched"] == st["dsa_step_rows_searched"] == 0
        return [outs[r].token_ids for r in sorted(outs)]
    assert run(params) == run(bare)
