"""A patterned configuration TRAINED through `HybridParallelTrainer`
(JoyAI-LLM-Flash shaped: latent attention expanded, dense FFN, dropless
experts over a share of the router, the next-n module): the trainer against
the plain reference with gradients (`benchmarks/reference/joyai_flash.py`,
which imports nothing of the program), the one tree on its two paths (trained
expanded, served absorbed), the flash kernels at a score width of 192 against
a value width of 128, the expert layer's backward, the shares, the bias rule
and the next-n labels.  Tiny widths, seeded float32 weights, CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import joyai_flash as ref
from paddle_tpu.incubate.distributed.models.moe.dropless import moe_dropless
from paddle_tpu.incubate.kernels import flash_attention as FA
from paddle_tpu.incubate.kernels import grouped_matmul as GM
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models import gpt, hybrid
from paddle_tpu.parallel.hybrid import HybridParallelTrainer, MeshConfig

F32 = jnp.float32
OPT = dict(learning_rate=1e-3, weight_decay=0.01, beta1=0.9, beta2=0.95,
           eps=1e-8, grad_clip_norm=1.0)


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
        rope_theta=c.rope_theta, rope_scaling=c.rope_scaling,
        rms_norm_eps=c.rms_norm_eps,
        n_routed_experts=c.experts_here, router_experts=c.n_routed_experts,
        expert_offset=c.expert_offset,
        num_experts_per_tok=c.num_experts_per_tok,
        routed_scaling_factor=c.routed_scaling_factor,
        norm_topk_prob=c.norm_topk_prob,
        num_nextn_predict_layers=c.num_nextn_predict_layers,
        mtp_loss_weight=c.mtp_loss_weight,
        router_bias_update_rate=c.router_bias_update_rate)


def tiny(pattern="LFLELE", **kw):
    kw = dict(dict(dtype=F32, initializer_range=0.2, num_experts_per_tok=3),
              **kw)
    return hybrid.joyai_tiny(seq_len=64, pattern=pattern, **kw)


def batch(seed, B=2, S=32, vocab=256):
    ids = np.random.default_rng(seed).integers(0, vocab, (B, S + 1),
                                               dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def close(got, want, tol=2e-4):
    """Every leaf within `tol` of the reference's, relative to the leaf's
    largest entry."""
    flat_g, flat_w = ref.flat(got), ref.flat(want)
    assert flat_g.keys() == flat_w.keys()
    for k, w in flat_w.items():
        w = np.asarray(w, np.float64)
        np.testing.assert_allclose(np.asarray(flat_g[k], np.float64), w,
                                   atol=tol * max(np.abs(w).max(), 1e-6),
                                   rtol=0, err_msg=k)


# ---- the trainer against the plain reference ---------------------------------

@pytest.mark.parametrize("kw", [dict(), dict(expert_offset=12),
                                dict(num_nextn_predict_layers=0,
                                     pattern="LELF")],
                         ids=["share0", "share3", "no_next_n"])
def test_loss_and_every_leafs_gradient_are_the_references(kw):
    cfg = tiny(**kw)
    params = hybrid.init_params(cfg, jax.random.key(3))
    tok, lab = batch(1)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: hybrid.train_loss(p, tok, lab, cfg, remat=True),
        has_aux=True)(params)
    model = model_dict(cfg)
    main, mtp, loads = ref.loss_terms(params, tok, lab, model)
    np.testing.assert_allclose(aux["loss_main"], main, rtol=1e-5)
    np.testing.assert_allclose(aux["loss_mtp"], mtp, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(loss, main + cfg.mtp_loss_weight * mtp,
                               rtol=1e-5)
    np.testing.assert_array_equal(aux["load"], loads)
    want = jax.grad(lambda p: ref.loss(p, tok, lab, model))(params)
    close(grads, want)
    for lp in ref.flat(grads):
        if lp.endswith("router_bias"):
            assert not np.asarray(ref.flat(grads)[lp]).any()
    # the layered form the chip runs is the same function
    m2, t2, l2, g2 = ref.loss_and_grads(params, tok, lab, model)
    np.testing.assert_allclose([m2, t2], [main, mtp], rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(l2, loads)
    close(g2, want, 1e-4)


def test_three_adamw_steps_and_the_router_bias_follow_the_reference():
    cfg = tiny()
    tr = HybridParallelTrainer(cfg, MeshConfig(remat=True), seed=5,
                               **{k: v for k, v in OPT.items() if k != "eps"})
    p0 = jax.tree_util.tree_map(np.asarray, tr.params)
    model = model_dict(cfg)
    params = jax.tree_util.tree_map(jnp.asarray, p0)
    opt = {k: jax.tree_util.tree_map(jnp.zeros_like, params) for k in "mv"}
    for i in range(1, 4):
        tok, lab = batch(10 + i)
        got = float(tr.train_step(tok, lab))
        params, opt, r = ref.train_step(params, opt, tok, lab, i, model, OPT)
        want = r["loss_main"] + cfg.mtp_loss_weight * r["loss_mtp"]
        np.testing.assert_allclose(got, want, rtol=1e-5)
        np.testing.assert_allclose(float(tr.last_step["loss_mtp"]),
                                   r["loss_mtp"], rtol=1e-5)
    close(tr.params, params, 1e-3)      # against moves of 3e-3
    close(tr.opt_state["m"], opt["m"], 1e-3)
    got, want, was = (ref.flat(t) for t in (tr.params, params, p0))
    for name in [k for k in got if k.endswith("router_bias")]:
        # moved by whole steps of the rule, exactly as the reference's
        np.testing.assert_allclose(got[name], want[name], atol=1e-7)
        steps = (np.asarray(got[name]) - was[name]) / \
            cfg.router_bias_update_rate
        np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
        assert np.abs(steps).max() >= 1
    st = tr.stats()
    assert st["train_steps"] == 3 and st["tokens_trained"] == 3 * 2 * 32
    assert st["moe_layer_calls"] == 3 * 3 and st["moe_pairs_over_bound"] == 0
    assert st["moe_pairs_here"] + st["moe_pairs_away"] == 9 * 64 * 3
    assert st["router_bias_moves"] > 0
    np.testing.assert_allclose(st["loss_mtp"], r["loss_mtp"], rtol=1e-5)
    # eval_loss is the training loss, both terms kept
    tok, lab = batch(20)
    got = float(tr.eval_loss(tok, lab))
    main, mtp, _ = ref.loss_terms(params, tok, lab, model)
    np.testing.assert_allclose(got, main + cfg.mtp_loss_weight * mtp,
                               rtol=1e-4)
    np.testing.assert_allclose(tr.eval_terms["loss_mtp"], mtp, rtol=1e-4)


def test_the_bias_rule_moves_router_bias_and_adamw_does_not():
    """With the rule's rate at nought nothing moves the bias (no gradient
    reaches it, no decay applies); with it, every entry moves by whole
    steps towards the mean load."""
    tok, lab = batch(2)
    still = HybridParallelTrainer(tiny(router_bias_update_rate=0.0),
                                  MeshConfig(), weight_decay=0.5)
    b0 = np.asarray(still.params["layers"][3]["router_bias"]) + 0.25
    still.params["layers"][3]["router_bias"] = jnp.asarray(b0)
    still.train_step(tok, lab)
    np.testing.assert_array_equal(still.params["layers"][3]["router_bias"], b0)
    assert not np.asarray(still.opt_state["m"]["layers"][3]["router_bias"]
                          ).any()
    cfg = tiny()
    params = hybrid.init_params(cfg, jax.random.key(0))
    load = jnp.asarray([[9, 3, 6] + [6] * 13] * 3)
    moved, n = hybrid.router_bias_step(params, load, cfg)
    step = np.asarray(moved["layers"][3]["router_bias"])
    assert step[0] == -np.float32(0.001) and step[1] == np.float32(0.001)
    assert not step[2:].any() and int(n) == 6
    np.testing.assert_array_equal(
        moved["mtp"]["layers"][1]["router_bias"], step)


# ---- one tree, two paths -------------------------------------------------------

def test_the_trained_tree_is_the_served_tree():
    """The expanded training attention equals the absorbed paged path on the
    same weights: the bucketed prefill's logits are the training forward's
    at the last position, and the engine serves the tree (next-n module and
    all) to the tokens the training forward ranks first."""
    cfg = tiny(experts_here=16)
    params = hybrid.init_params(cfg, jax.random.key(7))
    prompt = batch(4, B=1, S=24)[0]
    x = gpt._embed(params, jnp.asarray(prompt), cfg)
    x, _ = hybrid._train_walk(cfg.layer_pattern, params["layers"], x, cfg,
                              remat=False)
    want = gpt.head_logits(hybrid._norm(x[:, -1], params["lnf_w"], cfg),
                           params, cfg)
    cache = hybrid.init_paged_cache(cfg, num_pages=5, page_size=8, num_slots=1)
    got, _, _ = hybrid.prefill_paged(
        params, jnp.asarray(prompt), cfg, cache, jnp.asarray([[1, 2, 3]]),
        jnp.asarray([24]), jnp.asarray([0]))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    eng = LLMEngine(params, cfg, num_slots=2, page_size=8, max_model_len=64)
    rid = eng.add_request(prompt[0], max_new_tokens=4)
    served = list(eng.run()[rid].token_ids)
    assert len(served) == 4
    seq = prompt[0].tolist()
    for t in served:
        x = gpt._embed(params, jnp.asarray([seq]), cfg)
        x, _ = hybrid._train_walk(cfg.layer_pattern, params["layers"], x, cfg,
                                  remat=False)
        logits = gpt.head_logits(hybrid._norm(x[:, -1], params["lnf_w"], cfg),
                                 params, cfg)
        assert int(jnp.argmax(logits[0])) == t
        seq.append(t)


# ---- flash at a score width of 192 against a value width of 128 ----------------

@pytest.mark.parametrize("widths", [(192, 128), (128, 128)],
                         ids=["mla_192_128", "dense_128"])
def test_flash_kernels_take_a_score_and_a_value_width(widths):
    """The forward and the backward kernel in interpret mode against the
    XLA oracle and its gradients."""
    from jax.experimental.pallas import tpu as pltpu
    D, Dv = widths
    B, S, H = 1, 256, 2
    r = np.random.default_rng(D)
    q, k = (jnp.asarray(r.normal(size=(B, S, H, D)), F32) for _ in range(2))
    v, g = (jnp.asarray(r.normal(size=(B, S, H, Dv)), F32) for _ in range(2))
    assert FA._shapes_ok_for_pallas(q, k, v)
    assert not FA._shapes_ok_for_pallas(q, k, v[..., :64])
    scale = D ** -0.5
    want, vjp = jax.vjp(lambda *a: FA.attention_xla(*a, causal=True,
                                                    scale=scale), q, k, v)
    with pltpu.force_tpu_interpret_mode():
        out, lse = FA._flash_fwd_impl(q, k, v, True, scale)
        dq, dk, dv = FA._flash_bwd_impl(q, k, v, out, lse, g, True, scale)
    assert out.shape == (B, S, H, Dv) and dk.shape == k.shape
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    for got, ref_g in zip((dq, dk, dv), vjp(g)):
        np.testing.assert_allclose(got, ref_g, rtol=2e-4, atol=2e-4)


def test_the_192_128_kernels_have_names_of_their_own():
    assert FA._kernel_name("fwd", 192, 128) == "flash_mla_fwd"
    assert FA._kernel_name("bwd", 192, 128) == "flash_mla_bwd"
    assert FA._kernel_name("fwd", 128, 128) == "flash_fwd"
    assert FA._kernel_name("bwd", 128, 128) == "flash_bwd"
    assert FA._kernel_name("bwd_dkv", 192, 128) == "flash_mla_bwd_dkv"
    assert FA._kernel_name("bwd_dq", 192, 128) == "flash_mla_bwd_dq"


@pytest.mark.parametrize("S,D,fits", [
    (8192, 192, True), (2048, 128, True), (65536, 256, True),
    (65536, 192, True), (131072, 128, True), (131072, 64, True),
    (131072, 192, False), (98304, 256, False), (262144, 128, False)])
def test_the_length_picks_the_backward_route(S, D, fits):
    """dq's accumulator for one (batch, head) stays in VMEM up to 64 MiB of
    whole 128-lane tiles; both training cells are far inside."""
    assert FA._fused_bwd_fits(S, D) is fits


@pytest.mark.parametrize("route", ["fused", "split"])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)],
                         ids=lambda b: f"bq{b[0]}_bk{b[1]}")
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("widths", [(192, 128), (128, 128)],
                         ids=["mla_192_128", "dense_128"])
def test_the_backward_gives_dq_dk_and_dv(widths, causal, blocks, route,
                                         monkeypatch):
    """The backward against `jax.vjp(attention_xla)` with S several blocks
    long both ways.  `fused`, the ONE kernel every cell runs: dq's resident
    accumulator is added to from more than one key block, dk and dv from
    more than one query block, and under the causal mask steps are skipped
    (their q-side blocks held, not fetched) on both sides of the diagonal,
    square blocks and not.  `split`: the dk/dv and dq kernels a length past
    `_fused_bwd_fits` takes."""
    from jax.experimental.pallas import tpu as pltpu
    D, Dv = widths
    B, S, H = 1, 512, 2
    monkeypatch.setattr(FA, "_bwd_blocks", lambda S, Sk: blocks)
    if route == "split":
        monkeypatch.setattr(FA, "_BWD_DQ_RESIDENT_MAX", 0)
    calls = []
    for name in ("_flash_bwd_call", "_flash_bwd_split_call"):
        monkeypatch.setattr(FA, name, lambda *a, _f=getattr(FA, name),
                            _n=name: calls.append(_n) or _f(*a))
    r = np.random.default_rng(D + causal)
    q, k = (jnp.asarray(r.normal(size=(B, S, H, D)), F32) for _ in range(2))
    v, g = (jnp.asarray(r.normal(size=(B, S, H, Dv)), F32) for _ in range(2))
    scale = D ** -0.5
    _, vjp = jax.vjp(lambda *a: FA.attention_xla(*a, causal=causal,
                                                 scale=scale), q, k, v)
    with pltpu.force_tpu_interpret_mode():
        out, lse = FA._flash_fwd_impl(q, k, v, causal, scale)
        got = FA._flash_bwd_impl(q, k, v, out, lse, g, causal, scale)
    assert calls == [{"fused": "_flash_bwd_call",
                      "split": "_flash_bwd_split_call"}[route]]
    for name, a, b in zip(("dq", "dk", "dv"), got, vjp(g)):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)


# ---- the dropless layer, differentiated -----------------------------------------

@pytest.mark.parametrize("held,offset,bound", [(16, 0, None), (4, 0, None),
                                               (4, 4, None), (2, 3, None),
                                               (4, 8, 40), (4, 0, 8)])
def test_expert_layer_gradients_match_the_reference(held, offset, bound):
    """The layer's output and its gradients in the input, the router, the
    held experts and the shared expert, against the reference's per-expert
    loop; `bound` gathers only so many held pairs (40 holds them all here;
    8 cannot, and says so)."""
    cfg = tiny("E", experts_here=held, expert_offset=offset,
               num_nextn_predict_layers=0)
    lp = hybrid.init_params(cfg, jax.random.key(held))["layers"][0]
    model = model_dict(cfg)
    h = jnp.asarray(np.random.default_rng(offset).normal(size=(24, 64)), F32)
    g = jnp.asarray(np.random.default_rng(1).normal(size=(24, 64)), F32)
    real = jnp.ones((24,), bool)

    def mine(lp, h):
        return moe_dropless(lp, h, cfg, real, pair_bound=bound)

    def theirs(lp, h):
        return ref.gated_experts(lp, h[None], model)[0][0]

    y, ctr = mine(lp, h)
    here = int(ctr["moe_pairs_here"])
    if bound is not None:
        assert int(ctr["moe_pairs_over_bound"]) == max(0, here - bound)
        np.testing.assert_array_equal(
            ctr["load"], ref.gated_experts(lp, h[None], model)[1])
        assert int(ctr["moe_load_min"]) <= int(ctr["moe_load_max"])
    if bound is not None and here > bound:
        # a bound that cuts pairs is a dropped token: it shows in the result
        assert float(jnp.abs(y - theirs(lp, h)).max()) > 1e-3
        return
    np.testing.assert_allclose(y, theirs(lp, h), rtol=1e-3, atol=1e-5)
    got = jax.grad(lambda lp, h: jnp.sum(mine(lp, h)[0] * g),
                   argnums=(0, 1))(lp, h)
    want = jax.grad(lambda lp, h: jnp.sum(theirs(lp, h) * g),
                    argnums=(0, 1))(lp, h)
    close(got[0], want[0], 2e-4)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-3, atol=1e-5)
    assert not np.asarray(got[0]["router_bias"]).any()
    assert np.asarray(got[0]["router_w"]).any()


def test_the_chips_grouped_product_backward_masks_rows_nobody_holds():
    """The TPU route's own rule (megablox `gmm` for the rows' gradient,
    `tgmm` for the matrices'), in interpret mode, against `ragged_dot`'s:
    rows outside the held groups take a gradient of nought, not whatever the
    kernel left there."""
    r = np.random.default_rng(0)
    sizes = jnp.asarray([100, 0, 140, 60, 84], jnp.int32)     # 3 of 5 held
    first, E, M, K, N = 1, 3, 512, 128, 256
    lhs = jnp.asarray(r.normal(size=(M, K)), F32)
    rhs = jnp.asarray(r.normal(size=(E, N, K)), F32)
    g = jnp.asarray(r.normal(size=(M, N)), F32)
    held = (np.arange(M) >= 100) & (np.arange(M) < 300)

    def tpu(lhs, rhs):
        out = GM._gmm_tpu(lhs, rhs, sizes, first, True, 128, True)
        return jnp.sum(jnp.where(held[:, None], out, 0.0) * g)

    def cpu(lhs, rhs):
        out = GM.grouped_matmul(lhs, rhs, sizes, first, transpose_rhs=True)
        return jnp.sum(jnp.where(held[:, None], out, 0.0) * g)

    got, want = (jax.grad(f, argnums=(0, 1))(lhs, rhs) for f in (tpu, cpu))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-3, atol=1e-3)
    assert not np.asarray(got[0])[~held].any()


def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """One expert a chip on sixteen chips at the router's width of 16: what
    the sixteen compute, the shared expert counted once, is the uncut
    reference layer."""
    cfg = tiny("E", experts_here=16, num_nextn_predict_layers=0)
    lp = hybrid.init_params(cfg, jax.random.key(2))["layers"][0]
    model = model_dict(cfg)
    h = jnp.asarray(np.random.default_rng(9).normal(size=(40, 64)), F32)
    real = jnp.ones((40,), bool)
    big = ("gate_w", "up_w", "down_w")
    parts = []
    for offset in range(16):
        share = tiny("E", experts_here=1, expert_offset=offset,
                     num_nextn_predict_layers=0)
        lp_share = dict(lp, **{n: lp[n][offset:offset + 1] for n in big})
        parts.append(moe_dropless(lp_share, h, share, real)[0])
    nobody = dict(lp, **{n: lp[n][:0] for n in big})
    shared = ref.gated_experts(nobody, h[None], model)[0][0]
    uncut = ref.gated_experts(lp, h[None], model)[0][0]
    np.testing.assert_allclose(sum(parts) - 15 * shared, uncut,
                               rtol=1e-3, atol=1e-5)
    assert float(jnp.abs(parts[0] - uncut).max()) > 1e-3


# ---- the next-n labels -------------------------------------------------------------

def test_next_n_labels_are_shifted_by_two_and_masked_at_the_end():
    tokens = np.arange(10, 18)[None]                   # t_0 .. t_7
    full = np.arange(11, 19)[None]                     # labels[i] = t_{i+1}
    np.testing.assert_array_equal(
        hybrid.mtp_labels(jnp.asarray(full))[0],
        [12, 13, 14, 15, 16, 17, 18, -100])            # t_{i+2}; the last none
    ended = full.copy()
    ended[0, -1] = -100                                # no t_8: labels from tokens
    np.testing.assert_array_equal(
        hybrid.mtp_labels(jnp.asarray(ended))[0],
        [12, 13, 14, 15, 16, 17, -100, -100])          # the last two masked
    np.testing.assert_array_equal(hybrid.mtp_labels(jnp.asarray(ended)),
                                  ref.mtp_labels(jnp.asarray(ended)))
    assert tokens[0, 2] + 2 == hybrid.mtp_labels(jnp.asarray(full))[0, 2]


# ---- what stays refused ---------------------------------------------------------------

@pytest.mark.parametrize("make,mesh,says", [
    (lambda: hybrid.hybrid_tiny(), MeshConfig(),
     "chunked scan .* has no backward"),
    (lambda: hybrid.latent_tiny(), MeshConfig(), "hc_mult 4"),
    (lambda: hybrid.hybrid_tiny(pattern="*E"), MeshConfig(),
     "position-free attention"),
    (lambda: tiny(), MeshConfig(dp=2), "a mesh of 2 devices"),
], ids=["M", "hc_mult", "star", "mesh"])
def test_the_trainer_refuses_what_it_cannot_train(make, mesh, says):
    with pytest.raises(ValueError, match=f"served, not trained: .*{says}"):
        HybridParallelTrainer(make(), mesh)
