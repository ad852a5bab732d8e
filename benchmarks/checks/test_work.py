"""The operation and byte counts against hand counts at one small shape."""
import pytest

from benchmarks.harness import reducers
from benchmarks.work import flash, paged, transformer

MODEL = dict(hidden_size=8, num_hidden_layers=2, intermediate_size=32,
             vocab_size=100, num_attention_heads=2, num_key_value_heads=1,
             head_dim=4, gated_mlp=False, bias=True, tie_word_embeddings=True)


def test_parameter_counts():
    # per layer: qkv 8 x (2 + 2) x 4 = 128, proj 64, ffn 2 x 8 x 32 = 512
    assert transformer.matmul_params(MODEL) == 2 * (128 + 64 + 512) + 800
    # + norms 4 x 8, biases 16 + 8 + 32 + 8 per layer; table 800, final norm 16
    assert transformer.all_params(MODEL) == \
        2 * (128 + 64 + 512 + 32 + 64) + 800 + 16


def test_train_and_forward_operations():
    n = transformer.all_params(MODEL)
    assert transformer.train_flops_per_token(MODEL, seq=16) == \
        6 * n + 6 * 2 * 16 * 8
    # 10 tokens reading 55 keys in all: 4 x layers x heads x hd per key
    assert transformer.forward_flops(MODEL, 10, 55) == \
        2 * transformer.matmul_params(MODEL) * 10 + 4 * 2 * 2 * 4 * 55


def test_flash_counts():
    # B=1, S=4, H=2: causal pairs 2 x (4 x 5 / 2) = 20; hd = 8
    assert flash.fwd(1, 4, 2, 8)["flops"] == 2 * 2 * 20 * 8
    assert flash.bwd_dkv(1, 4, 2, 8)["flops"] == 4 * 2 * 20 * 8
    assert flash.bwd_dq(1, 4, 2, 8)["flops"] == 3 * 2 * 20 * 8
    assert flash.fwd(1, 4, 2, 8)["bytes"] == 4 * (4 * 2 * 8) * 2


def test_paged_counts():
    w = paged.serve_attention(live_tokens=100, queries=4, H=8, KVH=2, hd=16)
    assert w["bytes"] == 2 * 100 * 2 * 16 * 2 + 2 * 4 * 8 * 16 * 2
    assert w["flops"] == 4 * 100 * 8 * 16


def test_kernel_roofline_reducer_and_silence():
    peaks = {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    spec = {"reducer": "kernel_roofline", "args": {"kernels": [
        {"pattern": "tpu_custom_call", "work": "paged.serve_attention",
         "args": {"live_tokens": "live", "queries": 4, "H": 8, "KVH": 2,
                  "hd": 16}}]}}
    ctx = {"peaks": peaks, "facts": {"live": 100},
           "ops": {"%k custom-call tpu_custom_call out=x in=6": [4e-5, 2]}}
    # bytes 12800 + 2048 = 14848 -> 14.848 us a call at 1 GB/s, two calls in 40 us
    assert reducers.reduce(spec, ctx) == pytest.approx(100 * 2 * 14.848e-6 / 4e-5)
    ctx["ops"] = {"%fusion.1 fusion": [1.0, 3]}
    assert reducers.reduce(spec, ctx) is None       # nothing to read: silent


def test_latency_statistics_by_name():
    from benchmarks.drivers.serve import _stat
    v = list(range(1, 101))
    assert _stat(v, "p95") == pytest.approx(95.05)
    assert _stat(v, "mean") == pytest.approx(50.5)
    assert _stat(v, "tail10") == pytest.approx(95.5)     # mean of 91..100
    with pytest.raises(SystemExit):
        _stat(v, "max")
