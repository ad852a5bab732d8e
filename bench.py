"""Benchmark: flagship GPT pretraining tokens/sec/chip on one real TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Config: GPT-3 1.3B architecture (hidden 2048, 24 layers, 16 heads, seq 2048),
bf16 params + bf16 Adam moments + remat — the single-chip projection of baseline
ladder #4.  vs_baseline is measured tokens/sec/chip divided by 3500 (a Megatron-LM
A100 per-chip figure for GPT-3 1.3B; the reference repo publishes no in-tree numbers
— see BASELINE.md), so vs_baseline >= 0.9 meets the ladder #4 bar.
"""
from __future__ import annotations

import json
import time

import numpy as np


A100_BASELINE_TOKENS_PER_SEC = 3500.0


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.analysis.cost_model import device_spec
    from paddle_tpu.models.gpt import GPTConfig, count_params
    from paddle_tpu.parallel import HybridParallelTrainer, MeshConfig
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        config = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                           num_heads=16, max_seq_len=2048, dtype=jnp.bfloat16)
        batch, seq, steps = 4, 2048, 8
    else:  # CI smoke: tiny
        config = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                           num_heads=4, max_seq_len=256)
        batch, seq, steps = 4, 256, 3

    trainer = HybridParallelTrainer(config, MeshConfig(remat=True),
                                    moment_dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)
    tok = rng.randint(0, config.vocab_size, (batch, seq)).astype(np.int32)
    lab = np.roll(tok, -1, axis=1).astype(np.int32)

    # warmup/compile; the host read of the loss waits for the step to execute
    loss = trainer.train_step(tok, lab)
    _ = float(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.train_step(tok, lab)
    final = float(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final)

    tokens_per_sec = batch * seq * steps / dt
    # model FLOPs/token (PaLM-appendix convention): 6*N + causal attention term
    n_params = count_params(trainer.params)
    gflop_per_tok = (6 * n_params
                     + 6 * config.num_layers * seq * config.hidden_size) / 1e9
    mfu = tokens_per_sec * gflop_per_tok * 1e9 / device_spec().flops_per_s
    print(json.dumps({
        "metric": "gpt3_1.3b_pretrain_tokens_per_sec_per_chip" if on_tpu
                  else "gpt_tiny_tokens_per_sec (cpu smoke)",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tokens_per_sec / A100_BASELINE_TOKENS_PER_SEC, 3)
                       if on_tpu else 0.0,
        "mfu_v5e": round(mfu, 3) if on_tpu else None,
    }))


if __name__ == "__main__":
    main()
