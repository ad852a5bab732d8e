"""Quantized serving: weight-only int8 params + int8 KV page pool helpers.

Reference parity: the reference ships a full QAT/PTQ layer
(`quantization/imperative/qat.py`, PTQ observers/quanters) whose deployment
form is int8 weights + scales dequantized into the matmul, and an int8
predictor path through `fluid/inference`.  This module is the SERVING face of
that layer for the paged engine (`inference.engine.LLMEngine`): the eager
`QAT`/`PTQ`/`Int8Linear` classes in `quantization/__init__.py` quantize
nn.Layer trees; here we quantize the functional `models.gpt` serving param
pytree and size the int8 KV page pool.

Two independent knobs (`LLMEngine(weight_dtype=, kv_dtype=)`):

- **Weight-only int8** (`quantize_serving_params`): symmetric per-channel PTQ
  of every serving matmul weight — `blocks.{qkv,proj,fc1,fc2,fcg}_w`, the
  tied embedding/head `wte` and an untied `lm_head`.  Channel = the
  NON-contracting dim of the serving matmul, so the scale vector shards with
  the weight's sharded dim under tensor parallelism (qkv/fc1/fcg: output
  columns, mp-sharded; proj/fc2: output columns, replicated like the
  row-parallel output; wte: vocab rows, replicated).  A quantized leaf `w`
  is stored as the PAIR `w_q` (int8) + `w_scale` (float32, broadcastable) —
  `models.gpt._w` dequantizes per BLOCK inside the layer scan, so the fp
  copy of a weight only ever exists one layer at a time (at-rest HBM drops
  ~4x vs fp32, ~2x vs bf16; the transient is one block's weights).
- **int8 KV pages** (`init_paged_cache(kv_dtype="int8")`, in `models.gpt`):
  the pool stores int8 k/v plus per-token-per-head float32 scales
  (`k_scale`/`v_scale`, `[L, P, page, KVH]` — the finest granularity of the
  ISSUE's "per-page (or per-page-per-head) scale" family).  Per-token scales
  are the one choice that keeps token-granular writes (decode, chunked
  prefill, verify rollback) exact and write-order independent: a per-page
  scale would need a lossy re-quantization of already-written tokens
  whenever a later token's absmax exceeded it.  Writes quantize in-program
  (`models.gpt._quantize_kv`); the paged-attention kernels and XLA oracles
  dequantize per page on read (`kv_scales=` lane).

Both knobs default OFF and the fp path is byte-identical to a
quantization-free engine (asserted by tests/test_quantized_serving.py).

Everything here is host-side numpy — no jit sites, no new compiled programs
(the dequant lives inside the existing serving executables; see
`tools/check_program_count.py`).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

INT8_QMAX = 127.0
# scale floor: keeps a zero channel/token from dividing by zero; quantized
# values of an all-zero vector are exactly 0 either way
SCALE_EPS = 1e-30

# serving matmul weights inside the stacked blocks tree and the channel
# (non-contracting) axis of each — all are [L, in, out] with channel = last
BLOCK_WEIGHT_KEYS = ("qkv_w", "proj_w", "fc1_w", "fc2_w", "fcg_w")

KV_SCALE_DTYPE = np.float32


def quantize_weight(w, channel_axis):
    """Symmetric per-channel int8 PTQ of one weight (host numpy).

    `channel_axis` (an int or tuple) names the dims whose entries each get
    their own scale — the non-contracting dim of the serving matmul, plus
    the leading layer dim for stacked block weights.  Returns (q int8,
    scale float32) with `scale` keeping `w`'s rank (size-1 on every reduced
    dim) so `q * scale` broadcasts back to the weight's shape."""
    w = np.asarray(w, np.float32)
    keep = (channel_axis,) if isinstance(channel_axis, int) else \
        tuple(channel_axis)
    axes = tuple(i for i in range(w.ndim) if i not in keep)
    absmax = np.max(np.abs(w), axis=axes, keepdims=True)
    scale = (np.maximum(absmax, SCALE_EPS) / INT8_QMAX).astype(np.float32)
    q = np.clip(np.round(w / scale), -INT8_QMAX, INT8_QMAX).astype(np.int8)
    return q, scale


def dequantize_weight(q, scale, dtype=np.float32):
    """Inverse of `quantize_weight` (the same math `models.gpt._w` traces)."""
    return (np.asarray(q, np.float32) * np.asarray(scale, np.float32)) \
        .astype(dtype)


def _block_scale(q, scale):
    """Normalize a stacked-block scale to [L, 1, out]: per-layer, per-output-
    channel (the keepdims reduction above already yields this shape)."""
    assert scale.shape == (q.shape[0], 1, q.shape[2]), scale.shape
    return scale


def quantize_serving_params(params: Dict[str, Any], config
                            ) -> Dict[str, Any]:
    """Weight-only int8 PTQ of a `models.gpt` serving param pytree.

    Every quantized weight `name` is REPLACED by the pair `name_q` (int8) +
    `name_scale` (float32); biases, norms and anything this function does
    not recognize (MoE expert banks, BERT-only leaves) pass through
    unquantized.  Stacked block weights `[L, in, out]` quantize per
    (layer, output-channel) — scale `[L, 1, out]`, which the layer scan
    slices to `[1, out]` per block so dequant broadcasts over the
    contraction dim.  `wte [V, D]` quantizes per vocab ROW (scale `[V, 1]`):
    the row is both the embedding-gather unit and the head matmul's
    non-contracting dim, so one scale serves both uses.  An untied
    `lm_head [D, V]` quantizes per vocab COLUMN (scale `[1, V]`).

    Host-side numpy in and out — the engine quantizes ONCE at init, before
    mp placement (`serving_param_specs` knows the `_q`/`_scale` layout)."""
    del config      # the key structure alone determines the treatment
    out: Dict[str, Any] = {}
    for name, leaf in params.items():
        if name == "blocks":
            blocks: Dict[str, Any] = {}
            for k, w in leaf.items():
                if k in BLOCK_WEIGHT_KEYS:
                    # per (layer, output channel): axes (0, 2) of [L, in, out]
                    q, s = quantize_weight(np.asarray(w), channel_axis=(0, 2))
                    blocks[k + "_q"] = q
                    blocks[k + "_scale"] = _block_scale(q, s)
                else:
                    blocks[k] = w
            out["blocks"] = blocks
        elif name == "wte":
            q, s = quantize_weight(np.asarray(leaf), channel_axis=0)
            out["wte_q"], out["wte_scale"] = q, s
        elif name == "lm_head":
            q, s = quantize_weight(np.asarray(leaf), channel_axis=1)
            out["lm_head_q"], out["lm_head_scale"] = q, s
        else:
            out[name] = leaf
    return out


def normalize_quant_dtype(value: Optional[str], knob: str) -> Optional[str]:
    """Engine/bench knob normalization: None / fp names mean OFF, "int8" is
    the one quantized form; anything else raises."""
    if value in (None, "fp", "fp32", "f32", "bf16", "bfloat16", "float32"):
        return None
    if value == "int8":
        return "int8"
    raise ValueError(f"{knob} must be None/'bf16' (off) or 'int8', "
                     f"got {value!r}")


def kv_page_bytes(config, page_size: int,
                  kv_dtype: Optional[str] = None) -> int:
    """At-rest bytes ONE page pool page occupies across all layers (k + v,
    plus the per-token scale lanes when quantized) — the formula the engine's
    `host_pool_bytes`, the tests' equal-byte pool sizing and the
    `tpu_cost` accounts all agree on.  A patterned configuration says what
    its paged lanes hold (`HybridConfig.page_bytes`: K/V of its attention
    layers, the one latent lane of its latent layers)."""
    if hasattr(config, "page_bytes"):
        if normalize_quant_dtype(kv_dtype, "kv_dtype") is not None:
            raise ValueError("a patterned configuration has no quantized "
                             "page pool")
        return config.page_bytes(page_size)
    L, KVH, hd = config.kv_layers, config.kv_heads, config.head_dim
    if normalize_quant_dtype(kv_dtype, "kv_dtype") == "int8":
        per_tok = hd * 1 + np.dtype(KV_SCALE_DTYPE).itemsize
    else:
        per_tok = hd * np.dtype(config.dtype).itemsize
    return 2 * L * page_size * KVH * per_tok


__all__ = [
    "BLOCK_WEIGHT_KEYS", "INT8_QMAX", "KV_SCALE_DTYPE",
    "quantize_weight", "dequantize_weight", "quantize_serving_params",
    "normalize_quant_dtype", "kv_page_bytes",
]
