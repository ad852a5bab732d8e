"""Multi-chip (tensor-parallel) serving: mp-sharded LLMEngine vs single-chip.

The mp serving path (PR "Multi-chip serving") is a pure partitioning of the
same computation — Megatron-sharded serving params, page pool sharded on its
KVH axis, paged attention per-chip on the local head slice — so greedy
outputs must be TOKEN-IDENTICAL to single-chip serving on the same request
stream, with every scheduler feature (prefix cache, COW, chunked prefill,
speculative decoding, abort) unchanged.  Runs on 8 forced CPU host devices
(tests/conftest.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import gpt as G
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.incubate.kernels.paged_attention import (
    paged_prefill_attention_mp, paged_prefill_attention_xla)
from paddle_tpu.parallel.hybrid import serving_mesh

TINY = G.gpt_tiny(128)


@pytest.fixture(scope="module")
def params():
    return G.init_params(TINY, jax.random.key(0))


def _stream(seed=7, n=10):
    """Mixed stream: random prompts + a shared prefix (full-page shares, a
    bare-prefix donor, and non-aligned tails so COW fires)."""
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, TINY.vocab_size, (20,)).astype(np.int32)
    prompts = []
    for i in range(n):
        if i % 3 == 0:
            tail = int(rng.randint(0, 8))
            ext = rng.randint(0, TINY.vocab_size, (tail,)).astype(np.int32)
            prompts.append(np.concatenate([shared, ext]) if tail
                           else shared.copy())
        else:
            prompts.append(rng.randint(0, TINY.vocab_size,
                                       (rng.randint(1, 50),)).astype(np.int32))
    return prompts


def _run(params, config, mp, spec_len, prompts, chunk=16, abort_rid=None):
    eng = LLMEngine(params, config, num_slots=4, page_size=8,
                    max_model_len=64, prefill_chunk=chunk, prefix_cache=True,
                    spec_len=spec_len, mp=mp)
    rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    if abort_rid is not None:
        for _ in range(3):
            eng.step()
        eng.abort(rids[abort_rid])
    outs = eng.run()
    eng.cache.check_invariants()
    return {r: tuple(o.token_ids) for r, o in outs.items()}, eng.stats()


@pytest.fixture(scope="module")
def single_chip(params):
    out, _ = _run(params, TINY, 1, 0, _stream())
    return out


# ---------------------------------------------------------------------------
# engine token parity: mp vs single chip
# ---------------------------------------------------------------------------

def test_mp2_greedy_token_parity_chunked_prefix(params, single_chip):
    """mp=2, prefix cache on, chunked prefill on, spec off: byte-identical
    greedy tokens, one decode-side program, pool invariants clean."""
    out, st = _run(params, TINY, 2, 0, _stream())
    assert out == single_chip
    assert st["mp"] == 2
    assert st["decode_executables"] + st["verify_executables"] <= 2
    assert st["prefill_executables"] <= 2
    assert st["prefix_hit_requests"] > 0      # the mp run still shares pages


def test_mp2_spec_on_token_parity(params, single_chip):
    """mp=2 with speculative decoding: greedy acceptance stays lossless under
    tensor parallelism (verify + decode partitioned identically)."""
    out, st = _run(params, TINY, 2, 3, _stream())
    assert out == single_chip
    assert st["decode_executables"] + st["verify_executables"] <= 2
    assert st["spec_drafted_tokens"] >= 0     # lane exercised (stream-dep.)


@pytest.mark.slow
def test_mp4_spec_token_parity(params, single_chip):
    """mp=4 (1 kv head per chip): same stream, same tokens."""
    out, st = _run(params, TINY, 4, 3, _stream())
    assert out == single_chip
    assert st["mp"] == 4
    assert st["decode_executables"] + st["verify_executables"] <= 2


@pytest.mark.slow
def test_mp2_bucketed_prefill_parity(params):
    """Legacy bucketed one-shot prefill under mp (head-sharded dense flash
    via shard_map) matches single-chip bucketed serving."""
    base, _ = _run(params, TINY, 1, 0, _stream(seed=9, n=6), chunk=None)
    out, st = _run(params, TINY, 2, 0, _stream(seed=9, n=6), chunk=None)
    assert out == base


@pytest.mark.slow
def test_mp2_llama_gqa_parity():
    """GQA (llama preset, 2 kv heads -> 1 per chip) under mp=2."""
    config = G.llama_tiny(128)
    params = G.init_params(config, jax.random.key(1))
    prompts = [np.random.RandomState(i).randint(0, config.vocab_size,
                                                (1 + 5 * i,)).astype(np.int32)
               for i in range(5)]
    base, _ = _run(params, config, 1, 3, prompts)
    out, _ = _run(params, config, 2, 3, prompts)
    assert out == base


def test_mp2_abort_midrun_keeps_invariants(params):
    """abort() of an in-flight request under mp frees/derefs pages exactly as
    on a single chip (the cache manager is mp-oblivious); the survivors'
    outputs match the single-chip run of the same abort schedule."""
    base, _ = _run(params, TINY, 1, 0, _stream(seed=11, n=8), abort_rid=5)
    out, _ = _run(params, TINY, 2, 0, _stream(seed=11, n=8), abort_rid=5)
    assert out == base


def test_mp_rejects_indivisible_heads(params):
    with pytest.raises(ValueError, match="divide"):
        LLMEngine(params, TINY, num_slots=2, page_size=8, max_model_len=64,
                  mp=3)    # gpt_tiny has 4 heads


# ---------------------------------------------------------------------------
# head-sharded kernel vs oracle (q_len = 1 decode and q_len > 1 verify)
# ---------------------------------------------------------------------------

def _pool_case(rng, kvh):
    B, T, H, hd, page, P, maxp = 3, 5, 4, 64, 8, 9, 4
    q1 = jnp.asarray(rng.randn(B, H, hd), jnp.float32)
    qT = jnp.asarray(rng.randn(B, T, H, hd), jnp.float32)
    k = jnp.asarray(rng.randn(P, page, kvh, hd), jnp.float32)
    v = jnp.asarray(rng.randn(P, page, kvh, hd), jnp.float32)
    tbl = np.zeros((B, maxp), np.int32)
    tbl[0, :3] = [1, 2, 3]
    tbl[1, :2] = [4, 5]
    tbl[2, :4] = [6, 7, 8, 3]
    lengths = jnp.asarray([9, 4, 17], jnp.int32)
    valid = jnp.asarray([5, 1, 3], jnp.int32)
    return q1, qT, k, v, jnp.asarray(tbl), lengths, valid


@pytest.mark.parametrize("T", [5, 1], ids=["verify", "decode"])
@pytest.mark.parametrize("kvh", [4, 2], ids=["mha", "gqa"])
def test_sharded_verify_kernel_matches_oracle_qlen_gt1(kvh, T):
    """The head-sharded Pallas kernel (shard_map over mp=2, interpret mode
    on CPU) returns exactly the unsharded oracle's numbers, for q_len > 1
    (verify/chunk) and at the decode shape (one query a slot, valid 1) —
    attention never mixes heads, so per-chip slices compose."""
    rng = np.random.RandomState(3)
    q1, qT, k, v, tbl, lengths, valid = _pool_case(rng, kvh)
    if T == 1:
        qT, valid = q1[:, None], jnp.ones_like(valid)
    mesh = serving_mesh(2)
    ref = paged_prefill_attention_xla(qT, k, v, tbl, lengths, valid)
    got = paged_prefill_attention_mp(qT, k, v, tbl, lengths, valid, mesh,
                                     use_pallas=True, interpret=True)
    for b, n in enumerate(np.asarray(valid)):
        np.testing.assert_allclose(np.asarray(got)[b, :n],
                                   np.asarray(ref)[b, :n], atol=2e-5)
    # the sharding-constraint (oracle) route must agree too
    got_xla = jax.jit(lambda *a: paged_prefill_attention_mp(*a, mesh,
                                                            use_pallas=False))(
        qT, k, v, tbl, lengths, valid)
    for b, n in enumerate(np.asarray(valid)):
        np.testing.assert_allclose(np.asarray(got_xla)[b, :n],
                                   np.asarray(ref)[b, :n], atol=2e-5)


def test_sharded_kernel_rejects_indivisible_heads():
    rng = np.random.RandomState(5)
    _, qT, k, v, tbl, lengths, valid = _pool_case(rng, 4)
    with pytest.raises(ValueError, match="divisible"):
        paged_prefill_attention_mp(qT, k, v, tbl, lengths, valid,
                                   serving_mesh(8))
