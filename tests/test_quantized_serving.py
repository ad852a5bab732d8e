"""Quantized serving (ISSUE 11): weight-only int8 params + int8 KV page pool.

Covers the tentpole contracts:
- quant/dequant round-trip units (weights per-channel, KV per-token);
- kernel-vs-XLA-oracle parity on int8 pages (same dequant math on both
  routes, so the interpret-mode kernel matches the gather oracle to float
  tolerance);
- engine-level greedy top-1 agreement vs the fp engine across the serving
  modes (chunked+spec+prefix, bucketed, mp2, optimistic+preempt);
- `check_invariants` green on quantized pools, preempted-vs-undisturbed
  BYTE parity within the quantized mode (swap restores bit-exact int8
  pages; recompute re-quantizes deterministically);
- the fp default is byte-identical to a quantization-free engine;
- swap-pool intake admission (the PR-10 follow-on): a request whose worst
  case could never park in the host pool is rejected at `add_request`;
- the tpu_cost quantized account stays budget-clean with the declared
  >= 2x pool shrink.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models import gpt as gpt_mod
from paddle_tpu.quantization.serving import (
    dequantize_weight, kv_page_bytes, quantize_serving_params,
    quantize_weight)

AGREEMENT_BAR = 0.85    # greedy top-1 agreement floor vs fp (measured 1.0
                        # on the tiny audit model; the bar leaves room for
                        # near-tie argmax flips on other seeds)


@pytest.fixture(scope="module")
def cfg():
    return gpt_mod.gpt_tiny(64)


@pytest.fixture(scope="module")
def params(cfg):
    return gpt_mod.init_params(cfg, jax.random.key(0))


@pytest.fixture(scope="module")
def prompts(cfg):
    rng = np.random.RandomState(7)
    out = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
           for n in (3, 9, 17, 5)]
    # a shared-prefix pair (not page-aligned) so prefix sharing + COW run
    shared = rng.randint(0, cfg.vocab_size, (13,)).astype(np.int32)
    out.append(shared.copy())
    out.append(np.concatenate(
        [shared, rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)]))
    return out


def _run(params, cfg, prompts, max_new=8, **kw):
    eng = LLMEngine(params, cfg, page_size=8, max_model_len=64, **kw)
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    outs = eng.run()
    eng.cache.check_invariants()
    assert eng.cache.swapped_page_count == 0
    return [outs[r].token_ids for r in rids], eng


def _agreement(a, b):
    total = sum(max(len(x), len(y)) for x, y in zip(a, b))
    agree = sum(int(u == v) for x, y in zip(a, b) for u, v in zip(x, y))
    return agree / max(total, 1)


# ---------------------------------------------------------------------------
# quant/dequant units
# ---------------------------------------------------------------------------

def test_weight_quant_roundtrip_per_channel():
    rng = np.random.RandomState(0)
    w = (rng.randn(2, 64, 192) * rng.rand(1, 1, 192)).astype(np.float32)
    q, s = quantize_weight(w, channel_axis=(0, 2))
    assert q.dtype == np.int8 and s.dtype == np.float32
    assert s.shape == (2, 1, 192)
    assert np.abs(q).max() <= 127
    # symmetric rounding error is bounded by half a quantization step,
    # per (layer, channel)
    err = np.abs(dequantize_weight(q, s) - w)
    assert (err <= s / 2 + 1e-7).all()


def test_quantize_serving_params_structure(params, cfg):
    qp = quantize_serving_params(params, cfg)
    blocks = qp["blocks"]
    for k in ("qkv_w", "proj_w", "fc1_w", "fc2_w"):
        assert k not in blocks
        assert blocks[k + "_q"].dtype == np.int8
        assert blocks[k + "_scale"].shape == \
            (blocks[k + "_q"].shape[0], 1, blocks[k + "_q"].shape[2])
    assert "wte" not in qp and qp["wte_q"].dtype == np.int8
    assert qp["wte_scale"].shape == (cfg.vocab_size, 1)
    # biases/norms untouched
    assert blocks["ln1_w"] is params["blocks"]["ln1_w"]


def test_kv_quant_roundtrip():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(3, 4, 16).astype(np.float32) * 5.0)
    q, s = gpt_mod._quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (3, 4)
    deq = q.astype(jnp.float32) * s[..., None]
    assert float(jnp.max(jnp.abs(deq - x))) <= float(jnp.max(s)) / 2 + 1e-6


# ---------------------------------------------------------------------------
# kernel vs oracle on int8 pages (same dequant math -> float tolerance)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def int8_pool():
    rng = np.random.RandomState(2)
    P, page, KVH, hd = 10, 8, 2, 64
    kq = jnp.asarray(rng.randint(-127, 128, (P, page, KVH, hd)), jnp.int8)
    vq = jnp.asarray(rng.randint(-127, 128, (P, page, KVH, hd)), jnp.int8)
    ks = jnp.asarray(rng.rand(P, page, KVH).astype(np.float32) * 0.05)
    vs = jnp.asarray(rng.rand(P, page, KVH).astype(np.float32) * 0.05)
    tbl = jnp.asarray(rng.randint(1, P, (3, 4)), jnp.int32)
    return kq, vq, ks, vs, tbl


@pytest.mark.parametrize("T,qo,vl", [
    (4, [2, 9, 20], [1, 3, 4]),
    # the decode shape, its third slot inactive (null row, nothing valid)
    (1, [4, 16, 0], [1, 1, 0]),
], ids=["chunk", "decode-null-row"])
def test_kernel_oracle_parity_int8_prefill(int8_pool, T, qo, vl):
    from paddle_tpu.incubate.kernels.paged_attention import (
        paged_prefill_attention_pallas, paged_prefill_attention_xla)
    kq, vq, ks, vs, tbl = int8_pool
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(3, T, 4, 64).astype(np.float32))
    tbl = jnp.where(jnp.asarray(vl)[:, None] > 0, tbl, 0)
    qo, vl = jnp.asarray(qo, jnp.int32), jnp.asarray(vl, jnp.int32)
    got = np.asarray(paged_prefill_attention_pallas(
        q, kq, vq, tbl, qo, vl, interpret=True, kv_scales=(ks, vs)))
    want = np.asarray(paged_prefill_attention_xla(
        q, kq, vq, tbl, qo, vl, kv_scales=(ks, vs)))
    for b in range(3):      # rows past valid are padding garbage by contract
        np.testing.assert_allclose(got[b, :int(vl[b])], want[b, :int(vl[b])],
                                   rtol=1e-5, atol=1e-5)
        if not int(vl[b]):
            assert not got[b].any()     # an inactive slot comes back zeros


# ---------------------------------------------------------------------------
# fp default: quantization off changes nothing
# ---------------------------------------------------------------------------

def test_fp_default_byte_identity(params, cfg, prompts):
    default, d_eng = _run(params, cfg, prompts, num_slots=4, prefill_chunk=8,
                          spec_len=2)
    explicit, e_eng = _run(params, cfg, prompts, num_slots=4, prefill_chunk=8,
                           spec_len=2, weight_dtype="bf16", kv_dtype=None)
    assert default == explicit
    assert d_eng.weight_dtype is None and e_eng.kv_dtype is None
    # the fp pool tree is exactly the pre-quantization {k, v} pair
    pool = gpt_mod.init_paged_cache(cfg, 4, 8)
    assert set(pool) == {"k", "v"} and pool["k"].dtype == cfg.dtype
    assert d_eng.kv_pool_bytes() == e_eng.kv_pool_bytes()


def test_quant_dtype_validation(params, cfg):
    with pytest.raises(ValueError, match="kv_dtype"):
        LLMEngine(params, cfg, page_size=8, max_model_len=64,
                  kv_dtype="int4")


# ---------------------------------------------------------------------------
# engine-level greedy top-1 agreement vs fp, across serving modes
# ---------------------------------------------------------------------------

MODES = {
    "chunked_spec_prefix": dict(num_slots=4, prefill_chunk=8, spec_len=2),
    "bucketed": dict(num_slots=4, prefill_chunk=None),
    "mp2": dict(num_slots=4, prefill_chunk=8, spec_len=2, mp=2),
    "preempt": dict(num_slots=6, num_pages=9, prefill_chunk=8,
                    admission="optimistic", preempt="recompute"),
}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_engine_top1_agreement(params, cfg, prompts, mode):
    kw = MODES[mode]
    fp, _ = _run(params, cfg, prompts, max_new=12, **kw)
    q, eng = _run(params, cfg, prompts, max_new=12, weight_dtype="int8",
                  kv_dtype="int8", **kw)
    assert eng.stats()["kv_dtype"] == "int8"
    if mode == "preempt":
        assert eng.stats()["preemptions"] > 0
    assert _agreement(fp, q) >= AGREEMENT_BAR
    # every request still decodes its full budget (quantization must not
    # wedge a slot or truncate a stream)
    assert all(len(t) == 12 for t in q)


# ---------------------------------------------------------------------------
# quantized pools under preemption: byte parity + invariants + swap
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("preempt", ["recompute", "swap"])
def test_quantized_preempt_parity_and_no_leaks(params, cfg, prompts, preempt):
    base, _ = _run(params, cfg, prompts, max_new=12, num_slots=6,
                   prefill_chunk=8, weight_dtype="int8", kv_dtype="int8")
    got, eng = _run(params, cfg, prompts, max_new=12, num_slots=6,
                    num_pages=9, prefill_chunk=8, weight_dtype="int8",
                    kv_dtype="int8", admission="optimistic", preempt=preempt)
    st = eng.stats()
    assert st["preemptions"] > 0
    if preempt == "swap":
        # int8 pages swap as int8: the host pool bound shrinks with the pool
        assert st["preempt_swaps"] > 0
        assert eng.swap_pool_bytes() < \
            (eng.cache.num_pages - 1) * kv_page_bytes(cfg, 8)
    # preempted-vs-undisturbed parity holds WITHIN the quantized mode: swap
    # restores bit-exact int8 pages + scales, recompute re-quantizes the
    # same values deterministically
    assert got == base


def test_quantized_pool_bytes_ratio(params, cfg):
    fp_eng = LLMEngine(params, cfg, page_size=8, max_model_len=64)
    q_eng = LLMEngine(params, cfg, page_size=8, max_model_len=64,
                      kv_dtype="int8")
    ratio = fp_eng.kv_pool_bytes() / q_eng.kv_pool_bytes()
    assert ratio >= 2.0, ratio     # the "~2x smaller, same geometry" bar
    assert q_eng.cache.num_pages == fp_eng.cache.num_pages
    assert kv_page_bytes(cfg, 8) / kv_page_bytes(cfg, 8, "int8") == \
        pytest.approx(ratio)


# ---------------------------------------------------------------------------
# swap-pool intake admission (PR-10 follow-on)
# ---------------------------------------------------------------------------

def test_intake_swap_reject(params, cfg):
    eng = LLMEngine(params, cfg, page_size=8, max_model_len=64, num_slots=2,
                    admission="optimistic", preempt="swap", swap_pool_pages=2)
    # 8 + 32 tokens = 5 pages: fits the device pool, can NEVER park in a
    # 2-page host pool -> rejected at intake, not queued into a thrash loop
    rid = eng.add_request(np.arange(8, dtype=np.int32), max_new_tokens=32)
    out = eng._outputs[rid]
    assert out.finish_reason == "rejected"
    st = eng.stats()
    assert st["intake_swap_rejects"] == 1 and st["rejected_requests"] == 1
    # a parkable footprint is served normally
    rid2 = eng.add_request(np.arange(4, dtype=np.int32), max_new_tokens=8)
    eng.run()
    assert eng._outputs[rid2].finish_reason == "length"
    eng.cache.check_invariants()


def test_intake_gate_scoped_to_swap_mode(params, cfg):
    # recompute mode and zero-size host pools (parking disabled) must keep
    # serving footprints the device pool can hold — no intake gate
    for kw in (dict(admission="optimistic", preempt="recompute"),
               dict(admission="optimistic", preempt="swap",
                    swap_pool_pages=0),
               dict()):
        eng = LLMEngine(params, cfg, page_size=8, max_model_len=64,
                        num_slots=2, **kw)
        rid = eng.add_request(np.arange(8, dtype=np.int32), max_new_tokens=32)
        eng.run()
        assert eng._outputs[rid].finish_reason == "length"
        assert eng.stats()["intake_swap_rejects"] == 0


# ---------------------------------------------------------------------------
# mp layout + CI accounts
# ---------------------------------------------------------------------------

def test_serving_param_specs_quantized(params, cfg):
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.hybrid import serving_param_specs

    qp = quantize_serving_params(params, cfg)
    specs = serving_param_specs(cfg, qp)
    blocks = specs["blocks"]
    # int8 leaves keep the fp weight's Megatron spec...
    assert blocks["qkv_w_q"] == P(None, None, "mp")
    assert blocks["proj_w_q"] == P(None, "mp", None)
    # ...and scales shard with the weight's CHANNEL (last) dim: split for
    # column-parallel, replicated for row-parallel
    assert blocks["qkv_w_scale"] == P(None, None, "mp")
    assert blocks["fc1_w_scale"] == P(None, None, "mp")
    assert blocks["proj_w_scale"] == P()
    assert blocks["fc2_w_scale"] == P()
    # embedding pair vocab-sharded like the fp wte (scale rows ride the
    # vocab axis: one scale per vocab row)
    assert specs["wte_q"] == P("mp", None)
    assert specs["wte_scale"] == P("mp", None)


def test_cost_checks_quantized_clean():
    from paddle_tpu.analysis.cost_model import run_cost_checks

    reports, findings = run_cost_checks(include_mp=False)
    assert findings == []
    rep = reports[1]
    assert rep["quantized_pool_ratio"] >= 2.0
    assert rep["at_rest_quantized"]["pool_bytes"] < rep["at_rest"]["pool_bytes"]
    # int8 must shrink the TOTAL param account (the replicated remainder is
    # the norm/bias tail plus the row-parallel scales, which int8 slightly
    # grows — the win lives in the vocab-sharded + block columns; same
    # comparison JXP010 enforces)
    q, f = rep["at_rest_quantized"], rep["at_rest"]
    assert q["param_bytes_sharded"] + q["param_bytes_replicated"] < \
        f["param_bytes_sharded"] + f["param_bytes_replicated"]
    assert rep["host_pool_bytes_int8"] < rep["host_pool_bytes"]
    names = [p["name"] for p in rep["programs"]]
    assert "serve.fused_step_int8" in names


def test_bench_quantized_smoke():
    from bench_serve import run_serve_bench

    q = run_serve_bench(num_requests=6, num_slots=3, max_new_tokens=4,
                        prefill_chunk=8, spec_len=2, weight_dtype="int8",
                        kv_dtype="int8")
    fp = run_serve_bench(num_requests=6, num_slots=3, max_new_tokens=4,
                         prefill_chunk=8, spec_len=2)
    assert q["kv_dtype"] == "int8" and q["weight_dtype"] == "int8"
    assert q["kv_pool_bytes"] * 2 <= fp["kv_pool_bytes"]
    agree = sum(int(a == b) for qa, fa in zip(q["output_tokens"],
                                              fp["output_tokens"])
                for a, b in zip(qa, fa))
    total = sum(len(t) for t in fp["output_tokens"])
    assert agree / total >= AGREEMENT_BAR
    # dequant adds no executables: same program counts as the fp engine
    assert q["decode_executables"] == fp["decode_executables"] == 1
    assert q["prefill_executables"] == fp["prefill_executables"]
