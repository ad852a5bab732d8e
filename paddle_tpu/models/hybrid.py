"""Hybrid (Nemotron-H style) language models for serving: every layer is ONE
mixer chosen by a pattern letter — `M` a Mamba-2 state-space layer, `E` a
dropless mixture of experts with a shared expert, `*` attention with no
position term — in a pre-norm residual stack (x <- x + mixer(RMSNorm(x))),
final RMSNorm, untied head.

`inference.engine.LLMEngine` reaches this module the way it reaches
`models.gpt`: `init_paged_cache`, `prefill_paged` (the bucketed prompt pass)
and `serve_step_paged` (the fused step) with the same contracts, told apart
by `config.layer_pattern`.  What differs:

- The layers are of three kinds, so they are not one stacked tree under a
  `lax.scan`: `params["layers"]` is a list of per-layer trees and the pattern
  is walked statically.  No weight is ever sliced out of a stack.
- Two kinds of serving state live in ONE donated tree (`init_paged_cache`):
  the paged K/V pool of the attention layers ("k", "v": [L_attn, P, page, KVH,
  hd], read through page tables exactly as in `models.gpt`), and per Mamba
  layer i a recurrent state indexed by SLOT, not by page: "conv.i" [slots, 3,
  conv_dim] (the last three inputs of the causal convolution) and "ssm.i"
  [slots, H, P, N] float32.  Each lane is updated where it lies
  (`.at[].set` on the donated buffer); nothing pool-sized is copied.
- A slot's state is zeroed by the data, not by a dispatch: a row whose
  `q_offset` is 0 has no token behind it, so the step starts it from zeros
  (the bucketed prefill always does).  Rows that are padding (t >= valid) or
  inactive (null page-table row) get dt = 0 and leave both lanes untouched.
- Besides tokens the two passes return `aux`, a small int32 vector
  (`AUX_FIELDS`) of expert-routing and state counters that the engine fetches
  in the same `device_get` as the tokens.

Training a patterned configuration is not supported (the trainer refuses it).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..incubate.distributed.models.moe.serve import COUNTERS as MOE_COUNTERS
from ..incubate.distributed.models.moe.serve import moe_serve
from ..incubate.kernels.flash_attention import flash_attention_fused
from ..incubate.kernels.ssm import ssm_chunk_scan, ssm_update
from . import gpt as gpt_mod

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}
AUX_FIELDS = MOE_COUNTERS + ("ssm_slots_live", "ssm_state_resets")


@dataclasses.dataclass
class HybridConfig(gpt_mod.GPTConfig):
    """`GPTConfig` plus the pattern and the widths of the two new mixers.
    `num_layers` is len(layer_pattern); `num_heads`/`num_kv_heads`/`head_dim`
    describe the attention layers."""
    layer_pattern: str = "ME*"
    use_rope: bool = False
    use_rms_norm: bool = True
    use_bias: bool = False
    tie_word_embeddings: bool = False
    rms_norm_eps: float = 1e-5
    # Mamba-2
    mamba_num_heads: int = 8
    mamba_head_dim: int = 16
    ssm_state_size: int = 16
    mamba_n_groups: int = 2
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts: the router is n_routed_experts wide; this chip holds
    # experts_here of them from expert_offset on
    n_routed_experts: int = 8
    experts_here: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 64
    moe_shared_intermediate_size: int = 128
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True

    def __post_init__(self):
        super().__post_init__()
        bad = set(self.layer_pattern) - set(KINDS)
        if bad or not self.layer_pattern:
            raise ValueError(f"layer_pattern {self.layer_pattern!r}: letters "
                             f"are {sorted(KINDS)}")
        if len(self.layer_pattern) != self.num_layers:
            raise ValueError(f"num_layers {self.num_layers} != "
                             f"len(layer_pattern) {len(self.layer_pattern)}")
        if self.experts_here is None:
            self.experts_here = self.n_routed_experts
        if not 0 <= self.expert_offset <= \
                self.n_routed_experts - self.experts_here:
            raise ValueError("experts held must lie inside the router's range")
        if self.mamba_num_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_groups must divide mamba_num_heads")

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.ssm_state_size

    def count(self, letter: str) -> int:
        return self.layer_pattern.count(letter)

    @property
    def kv_layers(self) -> int:
        return self.count("*")

    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state one slot holds over all Mamba layers."""
        ssm = self.mamba_num_heads * self.mamba_head_dim * \
            self.ssm_state_size * 4
        conv = (self.conv_kernel - 1) * self.conv_dim * \
            jnp.dtype(self.dtype).itemsize
        return self.count("M") * (ssm + conv)


def hybrid_tiny(seq_len=128, pattern="MEM*E", **kw):
    return HybridConfig(vocab_size=256, hidden_size=64, num_layers=len(pattern),
                        num_heads=4, num_kv_heads=2, head_dim=32,
                        max_seq_len=seq_len, layer_pattern=pattern, **kw)


def init_params(config: HybridConfig, key) -> Dict[str, Any]:
    """Seeded weights in the laws the published initialiser uses: normal
    std `initializer_range`, residual out-projections std / sqrt(2 L),
    `A_log` = log U(1, 16), `dt_bias` the inverse softplus of a log-uniform
    time step in [time_step_min, time_step_max] floored at time_step_floor,
    `D` and norm weights one, router bias nought, the convolution's weight
    and bias U(+-1/sqrt(kernel))."""
    c = config
    D, L = c.hidden_size, c.num_layers
    std = c.initializer_range
    proj = std / math.sqrt(2 * L)
    keys = iter(jax.random.split(key, 8 * L + 4))

    def normal(shape, s, dtype=None):
        return (jax.random.normal(next(keys), shape, jnp.float32) * s
                ).astype(dtype or c.dtype)

    def uniform(shape, b):
        return jax.random.uniform(next(keys), shape, jnp.float32, -b, b
                                  ).astype(c.dtype)

    bound = 1.0 / math.sqrt(c.conv_kernel)      # a depthwise Conv1d's default
    layers = []
    for letter in c.layer_pattern:
        lp = {"norm_w": jnp.ones((D,), c.dtype)}
        if letter == "M":
            H = c.mamba_num_heads
            dt = jnp.exp(jax.random.uniform(next(keys), (H,)) * (
                math.log(c.time_step_max) - math.log(c.time_step_min))
                + math.log(c.time_step_min))
            dt = jnp.maximum(dt, c.time_step_floor)
            lp.update(
                in_w=normal((D, 2 * c.d_inner + 2 * c.mamba_n_groups *
                             c.ssm_state_size + H), std),
                conv_w=uniform((c.conv_kernel, c.conv_dim), bound),
                conv_b=uniform((c.conv_dim,), bound),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                A_log=jnp.log(jax.random.uniform(next(keys), (H,),
                                                 minval=1.0, maxval=16.0)),
                D=jnp.ones((H,), jnp.float32),
                gnorm_w=jnp.ones((c.d_inner,), c.dtype),
                out_w=normal((c.d_inner, D), proj))
        elif letter == "E":
            F, Fs = c.moe_intermediate_size, c.moe_shared_intermediate_size
            lp.update(
                router_w=normal((D, c.n_routed_experts), std, jnp.float32),
                router_bias=jnp.zeros((c.n_routed_experts,), jnp.float32),
                up_w=normal((c.experts_here, F, D), std),        # U^T
                down_w=normal((c.experts_here, F, D), proj),
                shared_up_w=normal((D, Fs), std),
                shared_down_w=normal((Fs, D), proj))
        else:
            lp.update(qkv_w=normal((D, c.qkv_dim), std),
                      proj_w=normal((c.num_heads * c.head_dim, D), proj))
        layers.append(lp)
    return {"wte": normal((c.vocab_size, D), std), "layers": layers,
            "lnf_w": jnp.ones((D,), c.dtype),
            "lm_head": normal((D, c.vocab_size), std)}


def init_paged_cache(config: HybridConfig, num_pages: int, page_size: int,
                     num_slots: int):
    """The one tree of serving state: the attention layers' paged pool and
    each Mamba layer's slot-indexed lanes (see the module docstring)."""
    c = config
    kv = (c.count("*"), num_pages, page_size, c.kv_heads, c.head_dim)
    cache = {"k": jnp.zeros(kv, c.dtype), "v": jnp.zeros(kv, c.dtype)}
    for i in range(c.count("M")):
        cache[f"conv.{i}"] = jnp.zeros(
            (num_slots, c.conv_kernel - 1, c.conv_dim), c.dtype)
        cache[f"ssm.{i}"] = jnp.zeros(
            (num_slots, c.mamba_num_heads, c.mamba_head_dim,
             c.ssm_state_size), jnp.float32)
    return cache


def _norm(x, w, c):
    return gpt_mod._norm(x, w, None, c)


def mamba_mixer(lp, h, conv, ssm, valid, c: HybridConfig):
    """The Mamba-2 mixer over T positions of B sequences, from and to state.

    h [B, T, D] (normed); conv [B, K-1, conv_dim] the last K-1 inputs of the
    convolution before position 0; ssm [B, H, P, N] float32; valid [B]: only
    positions t < valid[b] are real.  Returns (out [B, T, D], conv', ssm')
    where the primed state is that after position valid[b] - 1 (the state
    handed in when valid[b] is 0)."""
    B, T, _ = h.shape
    H, P, N, G = (c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size,
                  c.mamba_n_groups)
    K = c.conv_kernel
    f32 = jnp.float32
    zxbcdt = jnp.matmul(h, lp["in_w"])
    z, xbc, dt = jnp.split(zxbcdt, [c.d_inner, c.d_inner + c.conv_dim],
                           axis=-1)
    # causal depthwise convolution over [window | this call's inputs]
    full = jnp.concatenate([conv, xbc], axis=1)                  # [B, K-1+T, C]
    w = lp["conv_w"].astype(f32)
    acc = lp["conv_b"].astype(f32)
    for j in range(K):
        acc = acc + w[j] * full[:, j:j + T].astype(f32)
    xbc = jax.nn.silu(acc).astype(h.dtype)
    # the window after the last REAL input: inputs valid-K+1 .. valid-1
    take = valid[:, None] + jnp.arange(K - 1)[None, :]           # [B, K-1]
    conv = jnp.take_along_axis(full, take[:, :, None], axis=1)
    x, Bm, Cm = jnp.split(xbc, [c.d_inner, c.d_inner + G * N], axis=-1)
    x = x.reshape(B, T, H, P)
    Bm = Bm.reshape(B, T, G, N)
    Cm = Cm.reshape(B, T, G, N)
    dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    dt = jnp.where(jnp.arange(T)[None, :, None] < valid[:, None, None],
                   dt, 0.0)                                      # [B, T, H]
    A = -jnp.exp(lp["A_log"].astype(f32))
    if T == 1:
        y, ssm = ssm_update(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], ssm)
        y = y[:, None]
    else:
        y, ssm = ssm_chunk_scan(x, dt, A, Bm, Cm, ssm, chunk=c.chunk_size)
    y = y + lp["D"].astype(f32)[:, None] * x.astype(f32)
    # gate, then RMSNorm over each of the G groups of d_inner / G
    y = y.reshape(B, T, c.d_inner) * jax.nn.silu(z.astype(f32))
    yg = y.reshape(B, T, G, c.d_inner // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                            + c.rms_norm_eps)
    y = (yg.reshape(B, T, c.d_inner) * lp["gnorm_w"].astype(f32)
         ).astype(h.dtype)
    return jnp.matmul(y, lp["out_w"]), conv, ssm


def _qkv(lp, h, c: HybridConfig):
    B, T, _ = h.shape
    H, KVH, hd = c.num_heads, c.kv_heads, c.head_dim
    q, k, v = jnp.split(jnp.matmul(h, lp["qkv_w"]),
                        [H * hd, (H + KVH) * hd], axis=-1)
    return (q.reshape(B, T, H, hd), k.reshape(B, T, KVH, hd),
            v.reshape(B, T, KVH, hd))


def _sum_aux(parts, live, resets):
    """The `AUX_FIELDS` vector from the expert layers' counters."""
    def over(name, fn):
        vals = [p[name] for p in parts]
        return fn(jnp.stack(vals)) if vals else jnp.zeros((), jnp.int32)
    return jnp.stack([
        over("moe_pairs_here", jnp.sum), over("moe_pairs_away", jnp.sum),
        over("moe_experts_touched", jnp.sum), over("moe_load_max", jnp.max),
        live.astype(jnp.int32), resets.astype(jnp.int32)]).astype(jnp.int32)


def _walk(params, x, cache, c: HybridConfig, real, mamba, attention):
    """The layer loop of both passes: a static walk of the pattern.
    `mamba(lp, h, cache, i)` and `attention(lp, h, cache, i)` return (mixer
    output, cache); the expert layer needs no state.  Returns (x, cache,
    expert counters per E layer)."""
    B, T, D = x.shape
    seen = {"M": 0, "E": 0, "*": 0}
    counters = []
    for letter, lp in zip(c.layer_pattern, params["layers"]):
        i = seen[letter]
        seen[letter] += 1
        with jax.named_scope(KINDS[letter]):
            h = _norm(x, lp["norm_w"], c)
            if letter == "M":
                y, cache = mamba(lp, h, cache, i)
            elif letter == "E":
                y, ctr = moe_serve(lp, h.reshape(B * T, D), c,
                                   real.reshape(B * T))
                y = y.reshape(B, T, D)
                counters.append(ctr)
            else:
                y, cache = attention(lp, h, cache, i)
            x = x + y
    return x, cache, counters


def prefill_paged(params, input_ids, config: HybridConfig, cache, pages,
                  length, slots):
    """Bucketed prefill (`gpt.prefill_paged`'s contract, plus `slots` [B]:
    where each prompt's recurrent state is kept).  The state starts from
    zeros and is written as it stands after position length - 1: bucket
    padding moves neither lane.  Returns (logits [B, V] at the last real
    position, cache, aux)."""
    c = config
    B, Sb = input_ids.shape
    H, KVH, hd = c.num_heads, c.kv_heads, c.head_dim
    P, page = cache["k"].shape[1:3]
    real = jnp.arange(Sb)[None, :] < length[:, None]
    # keys and values are written token by token, padding to the null page,
    # as the fused step writes them: a whole-page write of [page, KVH, hd]
    # windows at KVH = 2 makes the compiler re-lay the pool out and back
    pidx = jnp.where(real, jnp.take(pages, jnp.arange(Sb) // page, axis=1), 0)
    off = jnp.arange(Sb) % page
    x = gpt_mod._embed(params, input_ids, c)

    def mamba(lp, h, cache, i):
        conv0 = jnp.zeros((B,) + cache[f"conv.{i}"].shape[1:], c.dtype)
        ssm0 = jnp.zeros((B,) + cache[f"ssm.{i}"].shape[1:], jnp.float32)
        y, conv, ssm = mamba_mixer(lp, h, conv0, ssm0, length, c)
        return y, dict(cache, **{
            f"conv.{i}": cache[f"conv.{i}"].at[slots].set(conv),
            f"ssm.{i}": cache[f"ssm.{i}"].at[slots].set(ssm)})

    def attention(lp, h, cache, i):
        q, k, v = _qkv(lp, h, c)
        rows = i * P + pidx
        new = {n: cache[n].reshape((-1,) + cache[n].shape[2:])
               .at[rows, off].set(a).reshape(cache[n].shape)
               for n, a in (("k", k), ("v", v))}
        k = jnp.repeat(k, H // KVH, axis=2)
        v = jnp.repeat(v, H // KVH, axis=2)
        attn = flash_attention_fused(q, k, v, causal=True)
        return jnp.matmul(attn.reshape(B, Sb, H * hd), lp["proj_w"]), \
            dict(cache, **new)

    x, cache, counters = _walk(params, x, cache, c, real, mamba, attention)
    x = _norm(x[jnp.arange(B), length - 1], params["lnf_w"], c)
    aux = _sum_aux(counters, jnp.asarray(B), jnp.asarray(B))
    return gpt_mod.head_logits(x, params, c), cache, aux


def serve_step_paged(params, tokens, cache, page_table, q_offset, valid,
                     config: HybridConfig, key=None, greedy=None, *,
                     sample: bool = False, temperature=1.0, top_k=None):
    """The fused serving step (`gpt.serve_step_paged`'s contract; one more
    result, `aux`).  Row b of the batch IS slot b of the state lanes.  A row
    with q_offset 0 starts from a zero state (nothing lies behind it); a row
    whose page-table row is null is inactive and leaves its state alone, as
    do positions t >= valid.  Returns (out_tokens [B, T], accept [B], cache,
    key, aux [len(AUX_FIELDS)] int32)."""
    from ..incubate.kernels.paged_attention import paged_prefill_attention
    c = config
    B, T = tokens.shape
    H, hd = c.num_heads, c.head_dim
    P, page = cache["k"].shape[1:3]
    active = page_table[:, 0] != 0
    fresh = q_offset == 0
    n_real = jnp.where(active, valid, 0)
    pos = q_offset[:, None] + jnp.arange(T)                      # [B, T]
    real = jnp.arange(T)[None, :] < n_real[:, None]
    pidx = jnp.where(real, jnp.take_along_axis(page_table, pos // page,
                                               axis=1), 0)       # pad -> null
    off = pos % page
    x = gpt_mod._embed(params, tokens, c)

    def mamba(lp, h, cache, i):
        conv_old, ssm_old = cache[f"conv.{i}"], cache[f"ssm.{i}"]
        conv0 = jnp.where(fresh[:, None, None], 0, conv_old)
        ssm0 = jnp.where(fresh[:, None, None, None], 0.0, ssm_old)
        y, conv, ssm = mamba_mixer(lp, h, conv0, ssm0, n_real, c)
        return y, dict(cache, **{
            f"conv.{i}": jnp.where(active[:, None, None], conv, conv_old),
            f"ssm.{i}": jnp.where(active[:, None, None, None], ssm, ssm_old)})

    def attention(lp, h, cache, i):
        q, k, v = _qkv(lp, h, c)
        base = i * P
        rows = base + pidx
        flat = {n: cache[n].reshape((-1,) + cache[n].shape[2:])
                for n in ("k", "v")}
        flat = {"k": flat["k"].at[rows, off].set(k),
                "v": flat["v"].at[rows, off].set(v)}
        attn = paged_prefill_attention(q, flat["k"], flat["v"],
                                       page_table + base, q_offset, n_real)
        return jnp.matmul(attn.reshape(B, T, H * hd), lp["proj_w"]), \
            dict(cache, **{n: a.reshape(cache[n].shape)
                           for n, a in flat.items()})

    x, cache, counters = _walk(params, x, cache, c, real, mamba, attention)
    with jax.named_scope("head"):
        x = _norm(x, params["lnf_w"], c)
        logits = gpt_mod.head_logits(x, params, c)
        out = gpt_mod.sharded_argmax(logits)
    rows = jnp.arange(B)
    if sample:
        ids, key = gpt_mod.sample_token(logits[rows, valid - 1], key,
                                        sample=True, temperature=temperature,
                                        top_k=top_k)
        out = out.at[rows, valid - 1].set(
            jnp.where(greedy, out[rows, valid - 1], ids))
    # no draft ever rides this step (speculation is refused for a recurrent
    # configuration: a rejected draft would need the state rolled back)
    accept = jnp.zeros((B,), jnp.int32)
    aux = _sum_aux(counters, jnp.sum(active), jnp.sum(active & fresh))
    return out, accept, cache, key, aux
