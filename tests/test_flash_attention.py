"""Flash attention + loss chunking tests.

The Pallas kernels themselves only compile on real TPU (Mosaic); under the CPU
conftest these tests cover the XLA fallback path, the chunked-CE parity and, in
Pallas' TPU interpret mode, the gradient through the kernels.  The TPU-gated
test mirrors what /tmp-drive scripts exercise on hardware.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.incubate.kernels.flash_attention import (
    attention_xla, flash_attention_fused, _on_tpu)
from paddle_tpu.models.gpt import GPTConfig, init_params, loss_fn


def test_fused_entry_fallback_matches_xla_on_cpu():
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 128, 2, 64))
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    out = flash_attention_fused(q, k, v, causal=True)
    ref = attention_xla(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_loss_chunk_parity():
    # chunked CE must match the unchunked loss exactly (same f32 math)
    config = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                       max_seq_len=256)
    params = init_params(config, jax.random.key(0))
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, 512, (2, 256)), jnp.int32)
    lab = jnp.asarray(np.roll(np.asarray(tok), -1, 1), jnp.int32)
    lab = lab.at[:, -8:].set(-100)  # exercise ignore-index masking across chunks
    full = loss_fn(params, tok, lab, config, loss_chunk=None)
    chunked = loss_fn(params, tok, lab, config, loss_chunk=64)
    np.testing.assert_allclose(float(full), float(chunked), rtol=1e-5)
    # grads agree too
    gf = jax.grad(lambda p: loss_fn(p, tok, lab, config, loss_chunk=None))(params)
    gc = jax.grad(lambda p: loss_fn(p, tok, lab, config, loss_chunk=64))(params)
    for a, b in zip(jax.tree_util.tree_leaves(gf), jax.tree_util.tree_leaves(gc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_remat_policy_matches_plain_loss():
    config = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                       max_seq_len=256)
    params = init_params(config, jax.random.key(1))
    rng = np.random.RandomState(1)
    tok = jnp.asarray(rng.randint(0, 512, (2, 256)), jnp.int32)
    lab = jnp.asarray(np.roll(np.asarray(tok), -1, 1), jnp.int32)
    l0 = loss_fn(params, tok, lab, config, remat=False)
    l1 = loss_fn(params, tok, lab, config, remat=True)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    g0 = jax.grad(lambda p: loss_fn(p, tok, lab, config, remat=False))(params)
    g1 = jax.grad(lambda p: loss_fn(p, tok, lab, config, remat=True))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.skipif(not _on_tpu(), reason="Pallas kernels require TPU (Mosaic)")
def test_pallas_flash_fwd_bwd_vs_xla_on_tpu():
    from paddle_tpu.incubate.kernels.flash_attention import _flash_attention_core
    for causal in (True, False):
        ks = jax.random.split(jax.random.key(7), 4)
        q = jax.random.normal(ks[0], (2, 512, 4, 64), jnp.bfloat16)
        k = jax.random.normal(ks[1], (2, 512, 4, 64), jnp.bfloat16)
        v = jax.random.normal(ks[2], (2, 512, 4, 64), jnp.bfloat16)
        g = jax.random.normal(ks[3], (2, 512, 4, 64), jnp.bfloat16)
        scale = 1.0 / 8.0
        out_p, vjp_p = jax.vjp(lambda a, b, c: _flash_attention_core(a, b, c, causal, scale), q, k, v)
        out_x, vjp_x = jax.vjp(lambda a, b, c: attention_xla(a, b, c, None, causal, scale), q, k, v)
        np.testing.assert_allclose(np.asarray(out_p, np.float32),
                                   np.asarray(out_x, np.float32), atol=3e-2, rtol=3e-2)
        for a, b in zip(vjp_p(g), vjp_x(g)):
            a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
            err = np.abs(a32 - b32).max() / max(np.abs(b32).max(), 1e-6)
            assert err < 6e-2


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("widths", [(128, 128), (192, 128)],
                         ids=["dense_128", "mla_192_128"])
def test_grad_through_the_kernels_with_four_blocks_a_side(widths, causal,
                                                          monkeypatch):
    """`jax.grad` through the custom_vjp (forward kernel, residuals, the one
    backward kernel) in interpret mode, blocks of 128 at S = 512: dq's
    resident accumulator is written from four key blocks, and the causal
    skip is crossed in every row and column of blocks."""
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.incubate.kernels import flash_attention as FA
    monkeypatch.setattr(FA, "FWD_BLOCK", 128)
    monkeypatch.setattr(FA, "BWD_BLOCK", 128)
    D, Dv = widths
    r = np.random.default_rng(D + causal)
    q, k = (jnp.asarray(r.normal(size=(1, 512, 2, D)), jnp.float32)
            for _ in range(2))
    v, w = (jnp.asarray(r.normal(size=(1, 512, 2, Dv)), jnp.float32)
            for _ in range(2))
    scale = D ** -0.5

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v) * w).sum()

    want = jax.grad(loss(lambda *a: attention_xla(
        *a, causal=causal, scale=scale)), argnums=(0, 1, 2))(q, k, v)
    with pltpu.force_tpu_interpret_mode():
        got = jax.grad(loss(lambda *a: FA._flash_attention_core(
            *a, causal, scale)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)
